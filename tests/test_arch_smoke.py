"""Per-architecture smoke tests: reduced configs, one forward + train step on
CPU, asserting output shapes and absence of NaNs (assignment requirement)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import all_archs, get_arch, SHAPES, cells
from repro.models import build_model, synthetic_batch

ARCH_NAMES = sorted(all_archs())


@pytest.fixture(scope="module")
def rng():
    return jax.random.key(0)


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_forward_shapes_and_no_nans(name, rng):
    cfg = get_arch(name).reduced()
    model = build_model(cfg)
    params = model.init(rng)
    B, S = 2, 16
    batch = synthetic_batch(cfg, B, S)
    logits, aux = jax.jit(model.forward)(params, batch)
    V = cfg.padded_vocab_size
    if cfg.n_codebooks > 1:
        assert logits.shape == (B, S, cfg.n_codebooks, V)
    elif cfg.frontend == "vlm":
        assert logits.shape == (B, S + cfg.num_patches, V)
    else:
        assert logits.shape == (B, S, V)
    assert not bool(jnp.isnan(logits).any())


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_one_train_step_no_nans(name, rng):
    cfg = get_arch(name).reduced()
    model = build_model(cfg)
    params = model.init(rng)
    batch = synthetic_batch(cfg, 2, 16)

    @jax.jit
    def step(p, b):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(p, b)
        p2 = jax.tree.map(lambda x, g: x - 1e-3 * g.astype(x.dtype), p, grads)
        return loss, p2

    loss, new_params = step(params, batch)
    assert jnp.isfinite(loss)
    for leaf in jax.tree.leaves(new_params):
        assert not bool(jnp.isnan(leaf).any())


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_decode_step_shapes(name, rng):
    cfg = get_arch(name).reduced()
    model = build_model(cfg)
    params = model.init(rng)
    B, S = 2, 8
    cache = model.init_cache(B, S)
    if cfg.frontend == "audio":
        batch = {"frame_embeds": jnp.zeros((B, 1, cfg.d_model), cfg.activation_dtype)}
    else:
        batch = {"tokens": jnp.zeros((B, 1), jnp.int32)}
        if cfg.frontend == "vlm":
            batch["patch_embeds"] = jnp.zeros((B, cfg.num_patches, cfg.d_model), cfg.activation_dtype)
    logits, new_cache = jax.jit(model.decode_step)(params, cache, batch, jnp.array(0))
    assert logits.shape[-1] == cfg.padded_vocab_size
    assert not bool(jnp.isnan(logits).any())
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


TOKEN_ARCHS = [
    n for n in ARCH_NAMES if get_arch(n).frontend == "none"
]


@pytest.mark.parametrize("name", TOKEN_ARCHS)
def test_decode_matches_prefill(name, rng):
    """Teacher-forced decode must reproduce the full-sequence logits."""
    cfg = get_arch(name).reduced()
    cfg = dataclasses.replace(cfg, param_dtype="float32", activation_dtype="float32")
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
        )
    model = build_model(cfg)
    params = model.init(rng)
    S = 12
    batch = synthetic_batch(cfg, 2, S)
    logits_full, _ = model.forward(params, batch)
    cache = model.init_cache(2, S)
    step = jax.jit(model.decode_step)
    for t in range(S):
        logits_t, cache = step(params, cache, {"tokens": batch["tokens"][:, t : t + 1]}, jnp.array(t))
        assert jnp.abs(logits_t[:, 0] - logits_full[:, t]).max() < 3e-4


def test_cells_assignment():
    """long_500k applies only to sub-quadratic archs; all archs have >= 3 cells."""
    long_archs = {n for n in ARCH_NAMES if "long_500k" in cells(get_arch(n))}
    assert long_archs == {"rwkv6-3b", "zamba2-2.7b", "zamba2-7b", "mixtral-8x7b"}
    for n in ARCH_NAMES:
        assert len(cells(get_arch(n))) >= 3


def test_param_counts_match_published_sizes():
    expect = {
        "nemotron-4-340b": (320e9, 360e9),
        "qwen1.5-110b": (100e9, 120e9),
        "command-r-35b": (28e9, 40e9),
        "granite-3-8b": (7e9, 9e9),
        "mixtral-8x7b": (44e9, 49e9),
        "phi3.5-moe-42b-a6.6b": (39e9, 44e9),
        "rwkv6-3b": (2.5e9, 5e9),
        "zamba2-2.7b": (2e9, 3.5e9),
        "zamba2-7b": (7.0e9, 7.8e9),
        "musicgen-large": (1.5e9, 3.5e9),
        "internvl2-1b": (0.3e9, 1.2e9),
    }
    for name, (lo, hi) in expect.items():
        n = get_arch(name).param_count()
        assert lo <= n <= hi, f"{name}: {n/1e9:.1f}B outside [{lo/1e9}, {hi/1e9}]"


def test_moe_capacity_drops_are_reported():
    cfg = get_arch("mixtral-8x7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = synthetic_batch(cfg, 2, 32)
    _, metrics = jax.jit(model.loss)(params, batch)
    assert "moe_aux_loss" in metrics and "moe_drop_rate" in metrics
    assert 0.0 <= float(metrics["moe_drop_rate"]) <= 1.0
    assert float(metrics["moe_aux_loss"]) >= 0.99  # ~1 for uniform routing


def test_mixtral_sliding_window_masks_distant_tokens():
    """A distant-past token must not influence logits beyond the window."""
    cfg = get_arch("mixtral-8x7b").reduced()
    cfg = dataclasses.replace(
        cfg,
        n_layers=1,
        param_dtype="float32",
        activation_dtype="float32",
        # capacity drops couple distant tokens through the router; remove
        # them so attention is the only cross-token channel
        moe=dataclasses.replace(cfg.moe, capacity_factor=16.0),
    )
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    S = 32  # window is 8 in the reduced config
    b1 = synthetic_batch(cfg, 1, S)
    tokens2 = b1["tokens"].at[0, 0].set((b1["tokens"][0, 0] + 1) % cfg.vocab_size)
    l1, _ = model.forward(params, b1)
    l2, _ = model.forward(params, {"tokens": tokens2})
    # last position is > window away from position 0: logits must match
    assert jnp.abs(l1[0, -1] - l2[0, -1]).max() < 1e-5


@pytest.mark.parametrize("name", ["granite-3-8b", "musicgen-large", "internvl2-1b", "rwkv6-3b"])
def test_chunked_loss_matches_full_loss(name):
    """The chunked-CE perf path must be numerically identical to full CE."""
    cfg = dataclasses.replace(
        get_arch(name).reduced(), param_dtype="float32", activation_dtype="float32"
    )
    m1 = build_model(cfg)
    m2 = dataclasses.replace(m1, loss_chunk=8)
    params = m1.init(jax.random.key(0))
    batch = synthetic_batch(cfg, 2, 20)  # 19 positions: 2 chunks + remainder 3
    l1, _ = m1.loss(params, batch)
    l2, _ = m2.loss(params, batch)
    assert abs(float(l1 - l2)) < 1e-5
    # gradients agree too
    g1 = jax.grad(lambda p: m1.loss(p, batch)[0])(params)
    g2 = jax.grad(lambda p: m2.loss(p, batch)[0])(params)
    diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), g1, g2)
    assert max(jax.tree.leaves(diffs)) < 1e-5
