"""Mamba2 (SSD) blocks — the state-space backbone of Zamba2.

Implements the chunked SSD algorithm (Dao & Gu 2024): the selective SSM
    h_t = exp(A * dt_t) h_{t-1} + dt_t * B_t ⊗ x_t
    y_t = C_t · h_t + D * x_t
is evaluated chunk-parallel: within a chunk of length Q the causal decay
matrix L[t,s] = exp(sum_{j=s+1..t} A dt_j) turns the recurrence into two
matmuls (C B^T ⊙ L) x; across chunks a small (H, N, P) state is carried by a
scan.  This is also the blueprint of the Pallas kernel (repro/kernels/ssd).

Structure per block (Mamba2 paper / Zamba2 usage):
  in_proj -> [z | x | B | C | dt], causal depthwise conv with bias and
  SiLU on (x, B, C), SSD with the D skip, gated
  RMSNorm of y * silu(z) within each of the ``n_groups`` channel groups,
  out_proj.  Heads are assigned to B/C groups contiguously: heads
  [g*H/G, (g+1)*H/G) read group g.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from .layers import apply_norm, dense_init, init_norm

PyTree = Dict[str, jax.Array]


def ssm_dims(cfg: ArchConfig) -> Tuple[int, int, int, int]:
    """(d_inner, n_heads, head_dim, state_dim)."""
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    return d_in, H, s.head_dim, s.state_dim


def init_mamba2(key, cfg: ArchConfig, dtype) -> PyTree:
    s = cfg.ssm
    d = cfg.d_model
    d_in, H, P, N = ssm_dims(cfg)
    G = s.n_groups
    ks = jax.random.split(key, 5)
    proj_out = 2 * d_in + 2 * G * N + H  # z, x, B, C, dt
    return {
        "in_proj": dense_init(ks[0], d, (d, proj_out), dtype),
        "conv_w": (jax.random.normal(ks[1], (s.conv_width, d_in + 2 * G * N), jnp.float32) * 0.1),
        "conv_b": jax.random.normal(ks[3], (d_in + 2 * G * N,), jnp.float32) * 0.1,
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((H,), 1e-2, jnp.float32))),
        "norm": jnp.ones((d_in,), jnp.float32),  # gated RMSNorm scale
        "out_proj": dense_init(ks[2], d_in, (d_in, d), dtype),
    }


def causal_conv(
    x: jax.Array, w: jax.Array, b: jax.Array, state: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv1d with bias, then SiLU.  x: (B,S,C), w: (K,C),
    b: (C,), state: (B,K-1,C)."""
    K = w.shape[0]
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i : i + x.shape[1], :] * w[i] for i in range(K)) + b
    new_state = xp[:, -(K - 1) :, :] if K > 1 else state
    return jax.nn.silu(out), new_state


def ssd_chunked(
    xh: jax.Array,  # (B, S, H, P) inputs per head
    dt: jax.Array,  # (B, S, H) positive step sizes
    A: jax.Array,  # (H,) negative decay rates
    Bm: jax.Array,  # (B, S, G, N)
    Cm: jax.Array,  # (B, S, G, N)
    state0: jax.Array,  # (B, H, N, P)
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.  Heads are assigned to B/C groups contiguously
    (``jnp.repeat``): heads [g*H/G, (g+1)*H/G) read group g."""
    B, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, S)
    S_orig = S
    if S % Q:
        # pad with dt=0 steps: decay 1, zero input -> state unaffected
        pad = Q - S % Q
        padfn = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        xh, dt, Bm, Cm = padfn(xh), padfn(dt), padfn(Bm), padfn(Cm)
        S = S + pad
    n = S // Q
    h_per_g = H // G
    # expand groups to heads
    Bh = jnp.repeat(Bm, h_per_g, axis=2)  # (B,S,H,N)
    Ch = jnp.repeat(Cm, h_per_g, axis=2)

    la = dt * A[None, None, :]  # (B,S,H) log-decay per step (negative)
    xw = xh * dt[..., None]  # dt-weighted input

    def split(t, shape):
        return t.reshape((B, n, Q) + shape).transpose(1, 0, 2, *range(3, 3 + len(shape)))

    def chunk_step(state, inp):
        xc, lac, bc, cc = inp  # (B,Q,H,P), (B,Q,H), (B,Q,H,N), (B,Q,H,N)
        cla = jnp.cumsum(lac, axis=1)  # (B,Q,H) cumulative log decay (incl. t)
        # inter-chunk: y_inter[t] = exp(cla_t) * C_t . state0
        dec = jnp.exp(cla)  # <= 1
        y_inter = jnp.einsum("bqhn,bhnp->bqhp", cc * dec[..., None], state)
        # intra-chunk: L[t,s] = exp(cla_t - cla_s) for s <= t (scalar per head)
        diff = cla[:, :, None, :] - cla[:, None, :, :]  # (B,Q,Q,H)
        mask = jnp.tril(jnp.ones((Q, Q), bool))  # s <= t (includes diagonal)
        L = jnp.exp(jnp.where(mask[None, :, :, None], diff, -jnp.inf))
        scores = jnp.einsum("bqhn,bshn->bqsh", cc, bc) * L
        y_intra = jnp.einsum("bqsh,bshp->bqhp", scores, xc)
        # state: state' = exp(cla_Q) state + sum_s exp(cla_Q - cla_s) B_s x_s^T
        dec_all = jnp.exp(cla[:, -1])  # (B,H)
        carry = jnp.exp(cla[:, -1][:, None] - cla)  # (B,Q,H) <= 1
        state_new = state * dec_all[..., None, None] + jnp.einsum(
            "bqhn,bqhp->bhnp", bc * carry[..., None], xc
        )
        return state_new, y_inter + y_intra

    state, ys = jax.lax.scan(
        chunk_step,
        state0,
        (split(xw, (H, P)), split(la, (H,)), split(Bh, (H, N)), split(Ch, (H, N))),
    )
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S, H, P)
    return y[:, :S_orig], state


def gated_rms_norm(y: jax.Array, z: jax.Array, w: jax.Array, groups: int, eps: float) -> jax.Array:
    """Mamba2's output norm: ``y * silu(z)`` RMS-normalised within each of
    ``groups`` equal channel groups, times ``w``; float32."""
    *lead, C = y.shape
    g = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(*lead, groups, C // groups)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)
    return g.reshape(*lead, C) * w


def apply_mamba2(
    p: PyTree,
    x: jax.Array,  # (B, S, d)
    cfg: ArchConfig,
    state: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    s = cfg.ssm
    B, S, d = x.shape
    d_in, H, P, N = ssm_dims(cfg)
    G = s.n_groups
    proj = x @ p["in_proj"]
    z, xs, bm, cm, dt = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + G * N, 2 * d_in + 2 * G * N], axis=-1
    )
    conv_in = jnp.concatenate([xs, bm, cm], axis=-1)
    conv_out, conv_state = causal_conv(conv_in, p["conv_w"], p["conv_b"], state["conv"])
    xs, bm, cm = jnp.split(conv_out, [d_in, d_in + G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    A = -jnp.exp(p["A_log"])  # (H,)
    xh = xs.reshape(B, S, H, P).astype(jnp.float32)
    y, ssm_state = ssd_chunked(
        xh,
        dt,
        A,
        bm.reshape(B, S, G, N).astype(jnp.float32),
        cm.reshape(B, S, G, N).astype(jnp.float32),
        state["ssm"],
        s.chunk,
    )
    y = y + xh * p["D"][None, None, :, None]
    y = gated_rms_norm(y.reshape(B, S, d_in), z, p["norm"], G, cfg.resolved_norm_eps)
    y = y.astype(x.dtype)
    return y @ p["out_proj"], {"conv": conv_state, "ssm": ssm_state}


def init_mamba2_state(cfg: ArchConfig, batch: int) -> Dict[str, jax.Array]:
    s = cfg.ssm
    d_in, H, P, N = ssm_dims(cfg)
    G = s.n_groups
    return {
        "conv": jnp.zeros((batch, s.conv_width - 1, d_in + 2 * G * N), jnp.float32),
        "ssm": jnp.zeros((batch, H, N, P), jnp.float32),
    }


def reference_ssd(xh, dt, A, Bm, Cm, state0):
    """O(S) sequential oracle for tests."""
    B, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Bh = jnp.repeat(Bm, H // G, axis=2)
    Ch = jnp.repeat(Cm, H // G, axis=2)

    def step(h, t):
        a = jnp.exp(dt[:, t] * A[None, :])  # (B,H)
        xw = xh[:, t] * dt[:, t][..., None]  # (B,H,P)
        h = h * a[..., None, None] + Bh[:, t][..., None] * xw[:, :, None, :]
        y = jnp.einsum("bhn,bhnp->bhp", Ch[:, t], h)
        return h, y

    h, ys = jax.lax.scan(step, state0, jnp.arange(S))
    return ys.transpose(1, 0, 2, 3), h
