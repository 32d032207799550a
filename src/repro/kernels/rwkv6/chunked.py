"""RWKV6 chunked-scan Pallas kernel (TPU target).

Grid = (B, H, n_chunks); the chunk dimension is sequential ("arbitrary") and
carries the (P, P) per-head WKV state in VMEM scratch.  Within a chunk of
length Q the contribution of earlier tokens is

    o_t = r_t ⊙ e^{clw_{t-1}} · S_0
        + sum_{s<t} (r_t ⊙ e^{clw_{t-1}-clw_s}) · k_s v_s^T
        + (r_t ⊙ u ⊙ k_t) v_t

Numerics: all exponents are differences clw_{t-1} - clw_s <= 0 (clw is the
per-channel cumulative log decay, non-increasing), evaluated in the direct
(Q, Q, P) form — never the overflow-prone factorized e^{clw} · e^{-clw}
product.  The (Q, Q, P) intra tensor is VPU work; Q=32, P=64 keeps it at
256 KiB in VMEM.  (Production refinement: 16-token sub-chunk anchoring
turns the off-diagonal blocks into MXU matmuls — see DESIGN.md §Kernels.)

The state-in/state-out terms are (Q,P)x(P,P) matmuls on the MXU.  The
cumulative log decay is a lower-triangular matmul at full f32 precision
(Mosaic has no in-kernel ``cumsum``), and ``u`` arrives as an (H, 1, P)
array so its block is the (1, P) tail of the array, as the (8, 128) tiling
rule allows.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(
    r_ref, k_ref, v_ref, lw_ref, u_ref,  # (Q,P) tiles; u: (1,P)
    o_ref, sf_ref,  # outputs: (Q,P) tile; (P,P) final state
    state_scr,  # VMEM scratch (P,P)
    *,
    Q: int,
    P: int,
):
    c = pl.program_id(2)
    n_c = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    lw = lw_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)

    tril = (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    ).astype(jnp.float32)
    clw = jax.lax.dot_general(
        tril, lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # (Q,P) cumulative log decay (includes t)
    total = jnp.sum(lw, axis=0, keepdims=True)  # (1,P) the chunk's log decay
    dec_in = jnp.exp(clw - lw)  # e^{clw_{t-1}} <= 1
    state_t = state_scr[...]  # S^T: (value channel, key channel)
    # inter-chunk (MXU): (Q,P) @ S
    o_inter = jax.lax.dot_general(
        r * dec_in, state_t, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    # intra-chunk, direct bounded-exponent form (VPU)
    diff = (clw - lw)[:, None, :] - clw[None, :, :]  # (Q,Q,P), t x s
    mask = jax.lax.broadcasted_iota(jnp.int32, (Q, Q, P), 0) > jax.lax.broadcasted_iota(
        jnp.int32, (Q, Q, P), 1
    )  # s < t
    expdiff = jnp.where(mask, jnp.exp(diff), 0.0)
    scores = jnp.sum(r[:, None, :] * k[None, :, :] * expdiff, axis=2)  # (Q,Q)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # (Q,1)
    o_intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_intra = o_intra + diag * v
    o_ref[...] = (o_inter + o_intra).astype(o_ref.dtype)
    # state update (MXU), kept transposed so the key-channel decay scales
    # lanes: S'^T = S^T diag(e^{clw_Q}) + v^T (k ⊙ e^{clw_Q-clw})
    carry_k = k * jnp.exp(total - clw)  # (Q,P)
    state_new = state_t * jnp.exp(total) + jax.lax.dot_general(
        v, carry_k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    state_scr[...] = state_new

    @pl.when(c == n_c - 1)
    def _final():
        sf_ref[...] = state_new.astype(sf_ref.dtype)


def rwkv6_chunked_hmajor(
    r: jax.Array,  # (B, H, S, P)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,  # (B, H, S, P) log decay <= 0
    u: jax.Array,  # (H, P)
    *,
    chunk: int = 32,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, H, S, P = r.shape
    Q = min(chunk, S)
    assert S % Q == 0
    n_c = S // Q
    kernel = functools.partial(_rwkv6_kernel, Q=Q, P=P)
    out, state = pl.pallas_call(
        kernel,
        grid=(B, H, n_c),
        in_specs=[
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, 1, P), lambda b, h, c: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, P, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, logw, u.reshape(H, 1, P))
    return out, state.swapaxes(-1, -2)
