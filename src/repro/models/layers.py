"""Core neural layers: norms, rotary, GQA attention (XLA + Pallas paths), MLPs.

All layers are pure functions over explicit parameter pytrees (dicts), so
sharding rules (distributed/sharding.py) can address leaves by path, and
layer stacks can be scanned (params stacked on a leading layer axis).

Attention paths:
* ``xla``        — memory-efficient online-softmax attention, scanning over
                   KV blocks (O(S * block) memory).  Computes the full S^2
                   score matrix under the causal mask (XLA cannot skip
                   blocks); the causal over-count is corrected analytically
                   in the roofline (see EXPERIMENTS.md).
* ``banded``     — sliding-window attention: each query block attends a
                   static band of size (window + block); sub-quadratic.
* ``pallas``     — the flash kernel in repro.kernels (TPU target; validated
                   on CPU via interpret mode).
* decode         — single-token attention against a KV cache: ``(B, S, K,
                   hd)``, or flat ``(B, S, K*hd)`` (Zamba2's).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

PyTree = Any
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def dense_init(key, in_dim: int, shape, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms (computed in f32, cast back)
# ---------------------------------------------------------------------------
def init_norm(cfg: ArchConfig, dim: Optional[int] = None) -> Dict[str, jax.Array]:
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p: Dict[str, jax.Array], x: jax.Array, cfg: ArchConfig) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mean = xf.mean(-1, keepdims=True)
        var = ((xf - mean) ** 2).mean(-1, keepdims=True)
        out = (xf - mean) * jax.lax.rsqrt(var + cfg.resolved_norm_eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = (xf * xf).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + cfg.resolved_norm_eps) * p["scale"]
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd), positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP activations
# ---------------------------------------------------------------------------
def mlp_param_shapes(cfg: ArchConfig, d_ff: Optional[int] = None) -> Dict[str, Tuple[int, ...]]:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_act.endswith("_glu"):
        return {"wi": (d, ff), "wg": (d, ff), "wo": (ff, d)}
    return {"wi": (d, ff), "wo": (ff, d)}


def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None, dtype=None) -> PyTree:
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    shapes = mlp_param_shapes(cfg, d_ff)
    keys = jax.random.split(key, len(shapes))
    return {
        name: dense_init(k, shape[0], shape, dtype)
        for (name, shape), k in zip(sorted(shapes.items()), keys)
    }


def apply_mlp(
    p: PyTree, x: jax.Array, cfg: ArchConfig, lora: Optional[Tuple[jax.Array, jax.Array]] = None
) -> jax.Array:
    """``lora = (a, b)`` adds ``(x @ a) @ b`` to the gated MLP's projections:
    its first ``d_ff`` columns to the gate (``wi``), the rest to ``wg``."""
    h = x @ p["wi"]
    up = x @ p["wg"] if cfg.mlp_act.endswith("_glu") else None
    if lora is not None:
        lo_gate, lo_up = jnp.split((x @ lora[0]) @ lora[1], 2, axis=-1)
        h, up = h + lo_gate, up + lo_up
    if cfg.mlp_act == "silu_glu":
        h = jax.nn.silu(h) * up
    elif cfg.mlp_act == "gelu_glu":
        h = jax.nn.gelu(h, approximate=not cfg.gelu_exact) * up
    elif cfg.mlp_act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    elif cfg.mlp_act == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(f"unknown mlp_act {cfg.mlp_act}")
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ArchConfig, dtype=None) -> PyTree:
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    a = cfg.resolved_attn_input_dim  # input width; the output is d_model wide
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, a, (a, H, hd), dtype),
        "wk": dense_init(kk, a, (a, K, hd), dtype),
        "wv": dense_init(kv, a, (a, K, hd), dtype),
        "wo": dense_init(ko, H * hd, (H, hd, d), dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((K, hd), dtype)
        p["bv"] = jnp.zeros((K, hd), dtype)
    return p


def qkv_project(p: PyTree, x: jax.Array, cfg: ArchConfig, positions: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.attn_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _largest_divisor_at_most(n: int, cap: int) -> int:
    """Largest divisor of n that is <= cap (block sizes must tile exactly;
    e.g. the VLM's patch-extended sequence 4352 = 2^8 * 17 tiles at 544)."""
    for d in range(cap, 0, -1):
        if n % d == 0:
            return d
    return 1


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, K, hd) -> (B, S, H, hd) by repeating each KV head H/K times."""
    B, S, K, hd = k.shape
    reps = n_heads // K
    if reps == 1:
        return k
    return jnp.repeat(k, reps, axis=2)


def attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cfg: ArchConfig,
    kv_block: int = 1024,
) -> jax.Array:
    """Online-softmax causal attention scanning over KV blocks.

    Memory O(S * kv_block); computes masked full scores (see module note).
    q: (B, S, H, hd); k, v: (B, S, K, hd).  Returns (B, S, H, hd).
    """
    B, S, H, hd = q.shape
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    kv_block = _largest_divisor_at_most(S, min(kv_block, S))
    n_blocks = S // kv_block
    scale = cfg.attn_scale
    qf = q * scale
    kb = k.reshape(B, n_blocks, kv_block, H, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blocks, kv_block, H, hd).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(S)

    def step(carry, inputs):
        m, l, acc = carry  # (B,S,H), (B,S,H), (B,S,H,hd) running stats (f32)
        j, kj, vj = inputs  # block idx, (B,kv_block,H,hd) x2
        kv_pos = j * kv_block + jnp.arange(kv_block)
        s = jnp.einsum(
            "bqhk,bshk->bqsh", qf, kj, preferred_element_type=jnp.float32
        )  # scores, f32 accumulation
        mask = q_pos[:, None] >= kv_pos[None, :]
        if cfg.sliding_window is not None:
            mask &= q_pos[:, None] < kv_pos[None, :] + cfg.sliding_window
        s = jnp.where(mask[None, :, :, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=2))
        p = jnp.exp(s - m_new[:, :, None, :])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + p.sum(axis=2)
        pv = jnp.einsum("bqsh,bshk->bqhk", p.astype(kj.dtype), vj).astype(jnp.float32)
        acc_new = acc * correction[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, S, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, H), jnp.float32)
    acc0 = jnp.zeros((B, S, H, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (jnp.arange(n_blocks), kb, vb)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def attention_banded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cfg: ArchConfig,
    q_block: int = 1024,
) -> jax.Array:
    """Sliding-window attention with a static band per query block.

    Each query block of length Bq attends keys in
    [blk_start - window, blk_start + Bq): a slice of static length
    window + Bq (clamped at 0).  Sub-quadratic: O(S * (window + Bq)).
    """
    window = cfg.sliding_window
    assert window is not None
    B, S, H, hd = q.shape
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    q_block = _largest_divisor_at_most(S, min(q_block, S))
    n_blocks = S // q_block
    band = min(window + q_block, S)
    scale = cfg.attn_scale

    def block_fn(i, q_i):
        # q_i: (B, q_block, H, hd)
        start = i * q_block - window
        start_c = jnp.clip(start, 0, S - band)
        k_band = jax.lax.dynamic_slice_in_dim(k, start_c, band, axis=1)
        v_band = jax.lax.dynamic_slice_in_dim(v, start_c, band, axis=1)
        q_pos = i * q_block + jnp.arange(q_block)
        kv_pos = start_c + jnp.arange(band)
        s = jnp.einsum(
            "bqhk,bshk->bqsh", q_i * scale, k_band,
            preferred_element_type=jnp.float32,
        )
        mask = (q_pos[:, None] >= kv_pos[None, :]) & (
            q_pos[:, None] < kv_pos[None, :] + window
        )
        s = jnp.where(mask[None, :, :, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=2)
        return jnp.einsum("bqsh,bshk->bqhk", p.astype(v_band.dtype), v_band)

    qb = q.reshape(B, n_blocks, q_block, H, hd).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(lambda args: block_fn(*args), (jnp.arange(n_blocks), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd).astype(q.dtype)


def attention_decode(
    q: jax.Array,  # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, S, K, hd)
    v_cache: jax.Array,
    length: jax.Array,  # (B,) or scalar: number of valid cache entries
    cfg: ArchConfig,
) -> jax.Array:
    B, S, K, hd = k_cache.shape
    H = q.shape[2]
    reps = H // K
    scale = cfg.attn_scale
    qg = (q * scale).reshape(B, 1, K, reps, hd)
    s = jnp.einsum("bqkrh,bskh->bqksr", qg, k_cache).astype(jnp.float32)
    pos = jnp.arange(S)
    valid = pos[None, :] < jnp.broadcast_to(jnp.asarray(length), (B,))[:, None]
    if cfg.sliding_window is not None and S > cfg.sliding_window:
        # linear (non-ring) cache longer than the window: mask old entries
        lo = jnp.broadcast_to(jnp.asarray(length), (B,))[:, None] - cfg.sliding_window
        valid &= pos[None, :] >= lo
    s = jnp.where(valid[:, None, None, :, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=3)
    out = jnp.einsum("bqksr,bskh->bqkrh", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def attention_decode_flat(
    q: jax.Array,  # (B, 1, H, hd)
    k_flat: jax.Array,  # (B, S, K*hd): head k's values in columns k*hd .. (k+1)*hd
    v_flat: jax.Array,
    length: jax.Array,  # (B,) or scalar: number of valid cache entries
    cfg: ArchConfig,
) -> jax.Array:
    """``attention_decode`` over a cache whose heads share one flat axis.

    The cache is never reshaped to 4-D, so the compiler keeps the flat axis
    in lanes and the position a sublane row (a one-row write then touches
    one row of tiles, not one lane of every tile).  Both contractions run
    over the flat axis: the scores against q laid block-diagonally,
    ``Q[b, k*hd + i, k*reps + r] = q[b, k*reps + r, i]``, and the output as
    ``p . v`` for every head against every column, of which each head keeps
    its own kv head's columns (a 0/1 mask).  Accumulation is float32."""
    B, S, F = k_flat.shape
    H, hd = q.shape[2], q.shape[3]
    K = F // hd
    reps = H // K
    if cfg.sliding_window is not None:
        raise ValueError("attention_decode_flat has no sliding window")
    qg = (q[:, 0] * cfg.attn_scale).reshape(B, K, reps, hd)
    q_blk = jnp.einsum("bkri,kl->blikr", qg, jnp.eye(K, dtype=q.dtype)).reshape(B, F, H)
    s = jnp.einsum("bsj,bjh->bsh", k_flat, q_blk, preferred_element_type=jnp.float32)
    valid = jnp.arange(S)[None, :] < jnp.broadcast_to(jnp.asarray(length), (B,))[:, None]
    s = jnp.where(valid[:, :, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=1)
    o = jnp.einsum("bsh,bsj->bhj", p.astype(v_flat.dtype), v_flat,
                   preferred_element_type=jnp.float32)
    own = jnp.arange(F)[None, :] // hd == jnp.arange(K)[:, None]  # (K, F)
    o = jnp.where(own[None, :, None, :], o.reshape(B, K, reps, F), 0.0).sum(axis=1)
    out = o.reshape(B, reps, K, hd).transpose(0, 2, 1, 3)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def attention_output(p: PyTree, ctx: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])


def run_attention(
    p: PyTree,
    x: jax.Array,
    cfg: ArchConfig,
    positions: jax.Array,
    impl: str = "xla",
) -> jax.Array:
    """Full attention sublayer for train/prefill."""
    q, k, v = qkv_project(p, x, cfg, positions)
    if cfg.sliding_window is not None and x.shape[1] > cfg.sliding_window:
        ctx = attention_banded(q, k, v, cfg)
    elif impl == "pallas" or impl == "pallas_interpret":
        from repro.kernels.attention import ops as flash_ops

        ctx = flash_ops.flash_attention(
            q, k, v,
            causal=True,
            window=cfg.sliding_window,
            scale=cfg.attn_scale,
            interpret=(impl == "pallas_interpret"),
        )
    else:
        ctx = attention_xla(q, k, v, cfg)
    return attention_output(p, ctx)


def run_attention_decode(
    p: PyTree,
    x: jax.Array,  # (B, 1, d)
    cfg: ArchConfig,
    cache: Dict[str, jax.Array],
    position: jax.Array,  # scalar int: true sequence position (for rope)
    write_pos: Optional[jax.Array] = None,  # cache write index (ring buffers)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    write_pos = position if write_pos is None else write_pos
    q, k, v = qkv_project(p, x, cfg, position[None] if position.ndim == 0 else position)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), write_pos, axis=1
    )
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), write_pos, axis=1
    )
    length = jnp.minimum(position + 1, k_cache.shape[1])
    ctx = attention_decode(q, k_cache, v_cache, length, cfg)
    return attention_output(p, ctx), {"k": k_cache, "v": v_cache}
