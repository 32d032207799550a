"""The Zamba2 hybrid against the plain float32 reference (``reference_zamba2``)
at the reduced same-family configs: 7 layers with shared-block
applications before layers 1, 4 and 6 (unequal gaps, block 0 used twice
with two adapters), two shared blocks fed [hidden, embedding], adapter
rank 4, two B/C groups for ``zamba2-7b``.

The weights are drawn by the reference's ``draw_weights`` in the published
layout (float32, norm scales and ``D`` away from ones) and loaded into the
program by its own loader, ``zamba.from_published``; the program's init
plays no part.  Both sides compute in float32 (the reference at the
highest matmul precision), so what is left is the order of operations: the
chunked SSD scan and online-softmax attention against the sequential
recurrence and plain softmax.  Tolerance 1e-4 relative L2 over the
vocabulary (readings are about 1e-6).  The planted faults move the logits
by 1e-2 and more.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_zamba2 as ref
from repro.configs import get_arch
from repro.models import build_model, mamba2, zamba

RTOL = 1e-4
B, S = 2, 12


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)))


ARCHS = ["zamba2-7b", "zamba2-2.7b"]


@functools.lru_cache(maxsize=None)
def case(name):
    cfg = dataclasses.replace(get_arch(name).reduced(),
                              param_dtype="float32", activation_dtype="float32")
    model = build_model(cfg)
    keys = zamba.published_config(cfg)
    weights = ref.draw_weights(keys, 7, "float32")
    params = zamba.from_published(weights, cfg)
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    V = cfg.vocab_size
    expect = np.asarray(ref.forward(weights, keys, tokens))[..., :V]
    return cfg, model, params, tokens, expect


def program_forward(model, params, tokens):
    return np.asarray(model.forward(params, {"tokens": jnp.asarray(tokens)})[0])


def program_decode(model, params, tokens):
    """Prefill through the cache one token at a time, then decode: every
    position's logits, as ``serve`` computes them."""
    cache = model.init_cache(tokens.shape[0], tokens.shape[1])
    step = jax.jit(model.decode_step)
    out = []
    for t in range(tokens.shape[1]):
        logits, cache = step(params, cache, {"tokens": jnp.asarray(tokens[:, t:t + 1])}, jnp.array(t))
        out.append(np.asarray(logits[:, 0]))
    return np.stack(out, axis=1)


@pytest.mark.parametrize("name", ARCHS)
def test_reduced_config_has_the_published_structure(name):
    cfg = case(name)[0]
    apps = cfg.shared_applications
    assert apps == (1, 4, 6) and len({b - a for a, b in zip(apps, apps[1:])}) > 1
    assert cfg.n_shared_blocks == 2 and cfg.adapter_rank == 4
    assert cfg.resolved_attn_input_dim == 2 * cfg.d_model
    if cfg.name.startswith("zamba2-7b"):
        assert cfg.ssm.n_groups == 2


@pytest.mark.parametrize("name", ARCHS)
def test_forward_matches_reference(name):
    cfg, model, params, tokens, expect = case(name)
    assert rel_l2(program_forward(model, params, tokens)[..., :cfg.vocab_size], expect) < RTOL


@pytest.mark.parametrize("name", ARCHS)
def test_prefill_and_decode_through_cache_match_reference(name):
    cfg, model, params, tokens, expect = case(name)
    got = program_decode(model, params, tokens)[..., :cfg.vocab_size]
    assert rel_l2(got, expect) < RTOL


def _blocks_swapped(monkeypatch):
    monkeypatch.setattr(zamba, "block_of", lambda cfg, j: (j + 1) % cfg.n_shared_blocks)


def _embedding_dropped(monkeypatch):
    orig = zamba.shared_block
    monkeypatch.setattr(zamba, "shared_block", lambda bp, ap, h, e, cfg, attend: orig(
        bp, ap, h, jnp.zeros_like(e), cfg, attend))


def _norm_whole_width(monkeypatch):
    orig = mamba2.gated_rms_norm
    monkeypatch.setattr(mamba2, "gated_rms_norm",
                        lambda y, z, w, groups, eps: orig(y, z, w, 1, eps))


def _norm_scale_ignored(monkeypatch):
    """Every RMSNorm of the hybrid (layers, blocks, final) without its scale."""
    orig = zamba.apply_norm
    monkeypatch.setattr(zamba, "apply_norm", lambda p, x, cfg: orig(
        {**p, "scale": jnp.ones_like(p["scale"])}, x, cfg))


def _gated_norm_scale_ignored(monkeypatch):
    orig = mamba2.gated_rms_norm
    monkeypatch.setattr(mamba2, "gated_rms_norm", lambda y, z, w, groups, eps: orig(
        y, z, jnp.ones_like(w), groups, eps))


FAULTS = {"blocks_swapped": _blocks_swapped, "embedding_dropped": _embedding_dropped,
          "norm_whole_width": _norm_whole_width, "norm_scale_ignored": _norm_scale_ignored,
          "gated_norm_scale_ignored": _gated_norm_scale_ignored}


@pytest.mark.parametrize("fault", list(FAULTS.values()), ids=list(FAULTS))
@pytest.mark.parametrize("path", ["forward", "decode"])
def test_wiring_faults_fail_the_tolerance(monkeypatch, fault, path):
    """Controls, on ``zamba2-7b``'s reduced config (two groups)."""
    cfg, model, params, tokens, expect = case("zamba2-7b")
    fault(monkeypatch)
    run = program_forward if path == "forward" else program_decode
    assert rel_l2(run(model, params, tokens)[..., :cfg.vocab_size], expect) > 10 * RTOL


def test_drawn_weights_are_not_constants():
    """Norm scales, ``D``, ``A_log`` and ``dt_bias`` vary, so a program that
    drops one or applies it to the wrong head reads wrong."""
    cfg = case("zamba2-7b")[0]
    w = ref.draw_weights(zamba.published_config(cfg), 7, "float32")
    layer, block = w["layers"][0], w["blocks"][1]
    for x in (w["final_norm"], layer["input_layernorm"], layer["norm"], layer["D"],
              layer["A_log"], layer["dt_bias"], block["input_layernorm"],
              block["pre_ff_layernorm"]):
        assert float(jnp.std(x)) > 0.05
    # drawn when read, the same each time
    np.testing.assert_array_equal(w["layers"][3]["in_proj"], w["layers"][3]["in_proj"])
    assert not np.array_equal(w["layers"][3]["in_proj"], w["layers"][4]["in_proj"])


def test_loader_fills_the_models_tree():
    """``from_published`` gives every leaf the program's init gives, with
    its shape and dtype, and refuses a checkpoint of another depth."""
    cfg = get_arch("zamba2-7b").reduced()
    keys = zamba.published_config(cfg)
    got = jax.eval_shape(lambda: zamba.from_published(ref.draw_weights(keys, 0), cfg))
    want = jax.eval_shape(lambda: build_model(cfg).init(jax.random.key(0)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(got)] == [
        (x.shape, x.dtype) for x in jax.tree.leaves(want)]
    with pytest.raises(ValueError):
        zamba.from_published(ref.draw_weights({**keys, "num_hidden_layers": 6}, 0), cfg)


def test_attention_scale_is_the_configs():
    """Zamba2's (head_dim/2)^-1/2 is set by its presets; another config with
    a doubled attention input keeps 1/sqrt(head_dim)."""
    for name in ARCHS:
        cfg = get_arch(name)
        assert cfg.attn_scale == pytest.approx(1 / math.sqrt(cfg.head_dim / 2))
        assert cfg.reduced().attn_scale == pytest.approx(1 / math.sqrt(cfg.reduced().head_dim / 2))
    other = get_arch("granite-3-8b")
    wide = dataclasses.replace(other, attn_input_dim=2 * other.d_model)
    assert wide.attn_scale == 1 / math.sqrt(other.resolved_head_dim)


def test_published_param_count():
    """7.357e9 by the published widths (81 x 78.46e6 Mamba2, 2 x 333.97e6
    shared blocks, 13 x 16.97e6 adapters and linears, 114.7e6 embedding)."""
    full = get_arch("zamba2-7b")
    assert full.param_count() == 7_356_749_648
    cut = dataclasses.replace(full, n_layers=24)
    assert cut.shared_applications == (6, 11, 17, 23)
    assert cut.param_count() == 2_733_050_240


@pytest.mark.parametrize("name", ARCHS)
def test_param_count_is_the_models(name):
    """``param_count`` counts every leaf the model holds (the embedding's
    rows padded to a multiple of 256 aside)."""
    cfg = get_arch(name).reduced()
    params = jax.eval_shape(lambda: build_model(cfg).init(jax.random.key(0)))
    pad = (cfg.padded_vocab_size - cfg.vocab_size) * cfg.d_model
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.param_count() + pad


@pytest.mark.parametrize("reps", [1, 2])
@pytest.mark.parametrize("length", [1, 8, 16])
def test_flat_decode_attention_matches_4d(length, reps):
    """``attention_decode_flat`` over the flat ``(B, S, K*hd)`` cache against
    ``attention_decode`` over the same cache as ``(B, S, K, hd)`` in float32,
    at a head width off the 128 lanes (hd 24), with one, a middle and all of
    16 positions valid and the invalid ones holding noise, and with 2 query
    heads per kv head."""
    from repro.models.layers import attention_decode, attention_decode_flat

    Bq, S, K, hd = 3, 16, 4, 24
    cfg = get_arch("zamba2-7b").reduced()
    kq, kk, kv = jax.random.split(jax.random.key(length * reps), 3)
    q = jax.random.normal(kq, (Bq, 1, K * reps, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(kk, (Bq, S, K, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(kv, (Bq, S, K, hd)).astype(jnp.bfloat16)
    got = attention_decode_flat(q, k.reshape(Bq, S, K * hd), v.reshape(Bq, S, K * hd),
                                jnp.array(length), cfg)
    f32 = lambda x: x.astype(jnp.float32)
    want = attention_decode(f32(q), f32(k), f32(v), jnp.array(length), cfg)
    assert got.shape == want.shape and got.dtype == q.dtype
    assert rel_l2(got.reshape(Bq, -1), want.reshape(Bq, -1)) < 1e-2
