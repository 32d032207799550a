"""Flash attention Pallas kernel (TPU target).

Tiling: grid = (batch, q_heads, n_q_blocks, n_kv_blocks); the last grid
dimension is the reduction ("arbitrary" semantics) — running max / sum /
accumulator live in VMEM scratch and persist across the kv iterations.
Block shapes are (blk_q, head_dim) / (blk_k, head_dim) tiles in VMEM, MXU
aligned (blk_* multiples of 128 at full scale; head_dim is the lane dim).

Causality is *structural*: fully-masked kv blocks are skipped with pl.when,
so the kernel does ~S^2/2 work (the XLA fallback cannot skip — this is the
kernel's roofline win, alongside fusion of the softmax pipeline).
GQA is handled in the BlockSpec index maps (q head h reads kv head
h // (H // K)) — no materialised KV repetition (HBM traffic win).
Sliding windows additionally skip blocks below the band.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,  # VMEM tiles
    o_ref,  # output tile (blk_q, hd)
    m_scr, l_scr, acc_scr,  # VMEM scratch
    *,
    scale: float,
    blk_q: int,
    blk_k: int,
    seq: int,
    causal: bool,
    window: Optional[int],
):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block
    n_k = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * blk_q
    k_start = j * blk_k

    # structural block skipping: above the diagonal / below the window band
    if causal or window is not None:
        live = jnp.bool_(True)
        if causal:
            live = jnp.logical_and(live, k_start <= q_start + blk_q - 1)
        if window is not None:
            live = jnp.logical_and(live, k_start + blk_k - 1 >= q_start - window + 1)
    else:
        live = jnp.bool_(True)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale  # (blk_q, hd), block dims squeezed
        k = k_ref[...].astype(jnp.float32)  # (blk_k, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (blk_q, blk_k)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        mask = q_pos >= k_pos if causal else jnp.full((blk_q, blk_k), True)
        if window is not None:
            mask = jnp.logical_and(mask, q_pos < k_pos + window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(j == n_k - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_hmajor(
    q: jax.Array,  # (B, H, S, hd)
    k: jax.Array,  # (B, K, S, hd)
    v: jax.Array,  # (B, K, S, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    blk_q: int = 128,
    blk_k: int = 128,
    scale: Optional[float] = None,  # default 1/sqrt(hd)
    interpret: bool = False,
) -> jax.Array:
    B, H, S, hd = q.shape
    K = k.shape[1]
    assert H % K == 0, "GQA requires n_heads % n_kv_heads == 0"
    group = H // K
    blk_q = min(blk_q, S)
    blk_k = min(blk_k, S)
    assert S % blk_q == 0 and S % blk_k == 0
    n_q, n_k = S // blk_q, S // blk_k
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        blk_q=blk_q,
        blk_k=blk_k,
        seq=S,
        causal=causal,
        window=window,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((None, None, blk_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, blk_k, hd), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((None, None, blk_k, hd), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, blk_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
