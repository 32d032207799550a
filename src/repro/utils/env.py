"""Computation-environment configuration for the jax-backed layers.

The compiled network backends (:mod:`repro.network.backend`), the
launchers and the kernel/roofline layers share two environment concerns:

* **Compile cache** — :func:`enable_compile_cache` keeps JAX's persistent
  compilation cache at a stable path, so a later process finds what an
  earlier one compiled.
* **Topology** — tests and benchmarks sometimes want a specific platform
  (``cpu``) or a multi-device host (``--xla_force_host_platform_device_count``)
  regardless of what hardware jax detects.

Precision is not set here: the network backends scope 64-bit types to
their own calls (``jax.enable_x64``), so model code in the same process
keeps its 32-bit defaults.

All helpers degrade gracefully: importing this module never imports jax,
and each setter raises ``RuntimeError`` with a clear message when jax is
missing rather than an opaque ``ImportError`` deep inside a backend.

>>> have_jax() in (True, False)
True
"""

from __future__ import annotations

import importlib.util
import os
from pathlib import Path

# src/repro/utils/env.py -> the checkout root
_REPO_ROOT = Path(__file__).resolve().parents[3]


def have_jax() -> bool:
    """Whether jax is importable in this environment (spec lookup only —
    does not import jax, so calling this is always cheap and safe)."""
    return importlib.util.find_spec("jax") is not None


def _require_jax():
    if not have_jax():
        raise RuntimeError(
            "jax is not installed; install jax[cpu] or use the numpy backend"
        )
    import jax

    return jax


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives for this checkout.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache
    sits at one fixed path inside the checkout (``<repo>/.jax_cache``).
    The path is part of every entry's key, so it never depends on a
    temporary directory, a process id or the time.
    """
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_REPO_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return the directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, so
    nothing is set here; otherwise only ``jax_compilation_cache_dir`` is
    updated.  Call before the first compile (the launchers and
    ``chip_smoke.py`` do, at start-up).
    """
    jax = _require_jax()
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def set_platform(platform: str = "cpu") -> None:
    """Pin jax to one platform (``cpu``, ``gpu`` or ``tpu``).

    Only takes effect before jax initialises its backends — call it at
    program start (benchmarks do, so timing never silently lands on an
    accelerator with different float semantics).
    """
    _require_jax().config.update("jax_platform_name", platform)


def set_host_device_count(n: int) -> None:
    """Force the host CPU platform to expose ``n`` devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count``.

    Must run before jax initialises; existing unrelated ``XLA_FLAGS``
    content is preserved.  Useful for exercising multi-device mesh code
    paths on a single machine.
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    flags = os.environ.get("XLA_FLAGS", "")
    parts = [
        f for f in flags.split() if not f.startswith("--xla_force_host_platform_device_count")
    ]
    parts.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(parts)
