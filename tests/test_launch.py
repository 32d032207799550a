"""The launch surface: compile-cache placement, the serve driver's options
and split, x64 scoping between the fabric backend and model steps, and
``chip_smoke.py`` refusing to run without a TPU."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch import serve
from repro.models import build_model
from repro.utils import env

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# Compile cache.
# ---------------------------------------------------------------------------
def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert env.compile_cache_dir() == str(tmp_path)


def test_compile_cache_dir_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert env.compile_cache_dir() == str(REPO / ".jax_cache")
    assert env.compile_cache_dir() == env.compile_cache_dir()


def test_enable_compile_cache_sets_only_the_missing_dir(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert env.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None  # jax reads the variable
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert env.enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


# ---------------------------------------------------------------------------
# Serve driver.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "argv,reduced",
    [([], True), (["--reduced"], True), (["--full"], False)],
    ids=["default", "reduced", "full"],
)
def test_serve_width_flags(argv, reduced):
    arch = serve.resolve_arch(serve.parse_args(["--arch", "zamba2-2.7b", *argv]))
    full = get_arch("zamba2-2.7b")
    assert arch == (full.reduced() if reduced else full)


def test_serve_prefill_logits_match_forward():
    args = serve.parse_args(
        ["--arch", "zamba2-2.7b", "--requests", "2", "--prompt-len", "8", "--gen-len", "3"]
    )
    out = serve.serve(args)
    V = out.model.cfg.vocab_size
    assert out.tokens.shape == (2, 3)
    assert 0 <= int(out.tokens.min()) and int(out.tokens.max()) < V
    full, _ = jax.jit(out.model.forward)(out.params, {"tokens": jnp.asarray(out.prompts)})
    a = np.asarray(out.prefill_logits[:, :V], np.float32)
    b = np.asarray(full[:, -1, :V], np.float32)
    # bf16 weights and activations, 4 reduced layers: rounding noise only
    assert np.linalg.norm(a - b) / np.linalg.norm(b) < 0.05


# ---------------------------------------------------------------------------
# x64 stays inside the fabric backend's calls.
# ---------------------------------------------------------------------------
def test_xla_backend_call_leaves_model_dtypes_alone():
    from repro.network import bisection_pairing, route_dor

    model = build_model(get_arch("zamba2-2.7b").reduced())
    params = model.init(jax.random.key(0))
    batch = {"tokens": jnp.zeros((2, 1), jnp.int32)}
    step = lambda c: model.decode_step(params, c, batch, jnp.array(0))
    before = jax.eval_shape(step, model.init_cache(2, 4))

    src, dst, vol = bisection_pairing((4, 4))
    loads = route_dor((4, 4), src, dst, vol, backend="xla")
    assert loads.dtype == np.float64  # the backend itself ran under x64
    assert not jax.config.jax_enable_x64

    after = jax.jit(step)(model.init_cache(2, 4))
    assert jax.tree.map(lambda x: x.dtype, after) == jax.tree.map(lambda x: x.dtype, before)
    assert jnp.zeros(1).dtype == jnp.float32 and jnp.arange(2).dtype == jnp.int32


# ---------------------------------------------------------------------------
# chip_smoke.py refuses to run off the chip.
# ---------------------------------------------------------------------------
def test_chip_smoke_refuses_cpu():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "platform=cpu" in proc.stdout
