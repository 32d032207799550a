"""Compiles for a described (not attached) TPU v5e chip.

The TPU compiler is installed even where no chip is, so the programs of
the main paths are compiled here at real widths: what the chip's compiler
refuses (an unaligned block, an op Mosaic cannot lower, a program larger
than HBM, a 64-bit op with no TPU lowering) fails here at no chip time.
Nothing runs, so nothing here says anything about results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around these compiles (an
entry compiled for a described chip cannot be read back without one).
"""


import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda x: _spec(sharding, x.shape, x.dtype), tree)


# ---------------------------------------------------------------------------
# Pallas kernels at model widths.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "S,H,K,hd",
    [(4096, 32, 32, 160), (4096, 32, 8, 128)],
    ids=["zamba2-2.7b", "granite-3-8b"],
)
def test_flash_attention_compiles(one_chip, S, H, K, hd):
    from repro.kernels.attention.ops import flash_attention

    q = _spec(one_chip, (1, S, H, hd), jnp.bfloat16)
    kv = _spec(one_chip, (1, S, K, hd), jnp.bfloat16)
    compiled = flash_attention.lower(q, kv, kv, causal=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_kernel_compiles_at_zamba2_widths(one_chip):
    from repro.configs import get_arch
    from repro.kernels.ssd.ops import ssd_scan
    from repro.models.mamba2 import ssm_dims

    cfg = get_arch("zamba2-2.7b")
    _, H, P, N = ssm_dims(cfg)
    G, B, S = cfg.ssm.n_groups, 1, 2048
    compiled = ssd_scan.lower(
        _spec(one_chip, (B, S, H, P)),
        _spec(one_chip, (B, S, H)),
        _spec(one_chip, (H,)),
        _spec(one_chip, (B, S, G, N)),
        _spec(one_chip, (B, S, G, N)),
        chunk=cfg.ssm.chunk,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rwkv6_kernel_compiles_at_rwkv6_3b_widths(one_chip):
    from repro.configs import get_arch
    from repro.kernels.rwkv6.ops import rwkv6_mix

    cfg = get_arch("rwkv6-3b")
    P = cfg.rwkv.head_dim
    H, B, S = cfg.d_model // P, 1, 2048
    x = _spec(one_chip, (B, S, H, P))
    compiled = rwkv6_mix.lower(x, x, x, x, _spec(one_chip, (H, P)), chunk=32).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# The full-width serving step.
# ---------------------------------------------------------------------------
def _compile_decode_step(one_chip, arch, B, max_len):
    """The decode step compiled with its cache donated."""
    from repro.models import build_model

    model = build_model(arch)
    params = _on(one_chip, model.init_shapes())
    cache = _on(one_chip, jax.eval_shape(lambda: model.init_cache(B, max_len)))
    batch = {"tokens": _spec(one_chip, (B, 1), jnp.int32)}
    return (
        jax.jit(model.decode_step, donate_argnums=1)
        .lower(params, cache, batch, _spec(one_chip, (), jnp.int32))
        .compile()
    ), cache


def _step_bytes(compiled):
    """Device bytes of a compiled decode step (arguments, outputs and
    temporaries, less what the donated cache aliases)."""
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0  # the cache is updated in place
    return (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        - mem.alias_size_in_bytes
        + mem.temp_size_in_bytes
    )


@pytest.fixture(scope="module")
def zamba2_7b_stage(one_chip):
    """``zamba2-7b`` cut to its first 24 layers (the ``zamba2-7b.chat``
    cell's program) at 64 requests and a 256-position cache, compiled once
    for the tests below."""
    import dataclasses

    from repro.configs import get_arch

    arch = dataclasses.replace(get_arch("zamba2-7b"), n_layers=24)
    return _compile_decode_step(one_chip, arch, 64, 256)


def test_zamba2_decode_step_fits_one_chip(one_chip):
    from repro.configs import get_arch

    compiled, _ = _compile_decode_step(one_chip, get_arch("zamba2-2.7b"), 8, 2048)
    total = _step_bytes(compiled)
    assert total < V5E_HBM_BYTES, f"{total / 1e9:.2f} GB"


def test_zamba2_7b_stage_fits_one_chip(zamba2_7b_stage):
    total = _step_bytes(zamba2_7b_stage[0])
    assert 10e9 < total < V5E_HBM_BYTES, f"{total / 1e9:.2f} GB"


def test_zamba2_7b_kv_write_touches_rows_not_lanes(zamba2_7b_stage):
    """Each application's k and v leaf, ``bf16[64,256,7168]``, keeps the
    position (axis 1) off the minor dimension, in the parameters and in
    the ``dynamic-update-slice`` that writes a token, so that write touches
    one row of tiles and not one lane of every tile; no cache-sized copy
    is made to feed the attention, and the whole cache stays aliased."""
    import re

    compiled, cache = zamba2_7b_stage
    text = compiled.as_text()
    kv = r"bf16\[64,256,7168\]\{(\d+)[,\d]*(?::[^}]*)?\}"
    minors = re.findall(kv, text)
    writes = re.findall(kv + r" dynamic-update-slice\(", text)
    assert len(writes) == 8  # k and v of four applications
    assert minors and "1" not in minors, sorted(set(minors))
    assert not re.search(kv + r" copy(?:-start)?\(", text)
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes


# ---------------------------------------------------------------------------
# The fabric engines' xla programs, under x64 as the backend runs them.
# ---------------------------------------------------------------------------
def test_route_loads_program_compiles(one_chip):
    from repro.network import backend

    with jax.enable_x64(True):
        idx = _spec(one_chip, (4096, 3), jnp.int32)
        backend._route_loads_fn((16, 16, 16), True).lower(
            idx, idx, _spec(one_chip, (4096,), jnp.float64)
        ).compile()


def test_drain_program_compiles(one_chip):
    from repro.network import backend, bisection_pairing, dor_paths

    plan = backend.prepare_drain(dor_paths((8, 8, 8), *bisection_pairing((4, 4, 4))))
    F = plan.n_flows
    with jax.enable_x64(True):
        backend._drain_fn().lower(
            _spec(one_chip, plan.lf.shape, jnp.int32),
            _spec(one_chip, plan.fl.shape, jnp.int32),
            _spec(one_chip, plan.cap.shape, jnp.float64),
            _spec(one_chip, (F,), jnp.float64),
            _spec(one_chip, (F,), jnp.bool_),
            max_iters=plan.max_iters,
            max_steps=100_000,
        ).compile()


def test_cut_scores_program_compiles(one_chip):
    from repro.network import backend

    with jax.enable_x64(True):
        backend._cut_fn().lower(
            _spec(one_chip, (64, 3), jnp.int64), _spec(one_chip, (64, 3), jnp.int64)
        ).compile()


def test_contention_field_program_compiles(one_chip):
    """The exact integer correlation that replaced the float64 FFT, which
    the chip's compiler refuses ("Unexpected operand type for FFT: f64")."""
    from repro.network import backend

    dims, n_chunks = (16, 16, 16), 3
    planes, a, n_rest = 2 * len(dims), dims[0], 16 * 16
    with jax.enable_x64(True):
        compiled = backend._contention_fn(dims, n_chunks).lower(
            _spec(one_chip, (planes, a, n_rest), jnp.int8),
            _spec(one_chip, (planes, n_chunks * a, n_rest), jnp.int8),
        ).compile()
    assert "fft" not in compiled.as_text().lower()
