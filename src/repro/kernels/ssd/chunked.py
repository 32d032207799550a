"""Mamba2 SSD chunked-scan Pallas kernel (TPU target).

Grid = (B, H, n_chunks); sequential chunk dimension carries the (N, P)
per-head SSM state in VMEM scratch.  Unlike RWKV6, the SSD decay is a
*scalar* per head per step, so every intra-chunk term is an MXU matmul:

    L[t,s]   = exp(cla_t - cla_s)   (s <= t; (Q,Q), bounded: cla decreasing)
    scores   = (C B^T) ⊙ L          (Q,Q)   MXU + VPU mask
    y_intra  = scores @ (dt ⊙ x)    (Q,P)   MXU
    y_inter  = (C ⊙ e^{cla}) @ S    (Q,N)x(N,P) MXU
    S'       = e^{cla_Q} S + (B ⊙ e^{cla_Q-cla})^T (dt ⊙ x)   MXU

B/C group handling (n_groups < heads) is done in the BlockSpec index map
(head h reads group h // (H/G)) — no materialised repetition in HBM.

The cumulative log decay is a lower-triangular matmul at full f32
precision (Mosaic has no in-kernel ``cumsum``); its row form comes from the
transposed product, so no in-kernel transpose is needed either.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul(a, b, contract):
    """Full-f32 matmul: the prefix sums of log decays feed ``exp``."""
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )


def _ssd_kernel(
    xw_ref, la_ref, b_ref, c_ref,  # (Q,P), (Q,1), (Q,N), (Q,N) tiles
    y_ref, sf_ref,  # outputs: (Q,P), (N,P) final state
    state_scr,  # VMEM scratch (N,P)
    *,
    Q: int,
):
    c = pl.program_id(2)
    n_c = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xw = xw_ref[...].astype(jnp.float32)  # dt-weighted inputs (Q,P)
    la = la_ref[...].astype(jnp.float32)  # (Q,1) log decay per step
    bm = b_ref[...].astype(jnp.float32)  # (Q,N)
    cm = c_ref[...].astype(jnp.float32)  # (Q,N)

    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    mask = row >= col  # s <= t
    # cumulative log decay (includes t) as a column, the same as a row, and
    # the decay still to come after s (cla_Q - cla_s) as a column
    cla = _matmul(mask.astype(jnp.float32), la, ((1,), (0,)))  # (Q,1)
    cla_row = _matmul(la, (row <= col).astype(jnp.float32), ((0,), (0,)))  # (1,Q)
    rest = _matmul((row < col).astype(jnp.float32), la, ((1,), (0,)))  # (Q,1)
    state = state_scr[...]
    # inter-chunk
    y_inter = jax.lax.dot_general(
        cm * jnp.exp(cla), state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # intra-chunk
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q,Q) = C B^T
    L = jnp.where(mask, jnp.exp(cla - cla_row), 0.0)
    y_intra = jax.lax.dot_general(
        scores * L, xw, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y_ref[...] = (y_inter + y_intra).astype(y_ref.dtype)
    # state update
    carry_b = bm * jnp.exp(rest)  # (Q,N)
    state_new = state * jnp.exp(jnp.sum(la)) + jax.lax.dot_general(
        carry_b, xw, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    state_scr[...] = state_new

    @pl.when(c == n_c - 1)
    def _final():
        sf_ref[...] = state_new.astype(sf_ref.dtype)


def ssd_chunked_hmajor(
    xw: jax.Array,  # (B, H, S, P) dt-weighted inputs
    la: jax.Array,  # (B, H, S, 1) per-step log decay (dt * A)
    bm: jax.Array,  # (B, G, S, N)
    cm: jax.Array,  # (B, G, S, N)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, H, S, P = xw.shape
    G, N = bm.shape[1], bm.shape[3]
    assert H % G == 0
    hg = H // G
    Q = min(chunk, S)
    assert S % Q == 0
    n_c = S // Q
    kernel = functools.partial(_ssd_kernel, Q=Q)
    y, state = pl.pallas_call(
        kernel,
        grid=(B, H, n_c),
        in_specs=[
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, Q, N), lambda b, h, c: (b, h // hg, c, 0)),
            pl.BlockSpec((None, None, Q, N), lambda b, h, c: (b, h // hg, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xw, la, bm, cm)
    return y, state
