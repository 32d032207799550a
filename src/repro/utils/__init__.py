"""repro.utils — small cross-cutting helpers.

``repro.utils.env`` configures the jax computation environment (compile
cache, platform, host device count) for the launchers, the compiled
network backends and the kernel layers; nothing here imports jax at module scope, so the
package stays importable on numpy-only installs.
"""

from .env import (
    compile_cache_dir,
    enable_compile_cache,
    have_jax,
    set_host_device_count,
    set_platform,
)

__all__ = [
    "compile_cache_dir",
    "enable_compile_cache",
    "have_jax",
    "set_host_device_count",
    "set_platform",
]
