"""Jitted public wrapper for the flash attention kernel.

Accepts the model's (B, S, H, hd) layout, dispatches to the head-major
Pallas kernel (TPU target; ``interpret=True`` executes the same kernel body
on CPU for validation).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .flash import flash_attention_hmajor


@partial(jax.jit, static_argnames=("causal", "window", "blk_q", "blk_k", "scale", "interpret"))
def flash_attention(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, S, K, hd)
    v: jax.Array,  # (B, S, K, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    blk_q: int = 128,
    blk_k: int = 128,
    scale: Optional[float] = None,  # default 1/sqrt(hd)
    interpret: bool = False,
) -> jax.Array:
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out = flash_attention_hmajor(
        qh, kh, vh,
        causal=causal, window=window, blk_q=blk_q, blk_k=blk_k, scale=scale,
        interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3)
