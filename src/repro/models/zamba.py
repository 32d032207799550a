"""Zamba2 hybrid: a Mamba2 backbone with shared transformer blocks.

Every layer ``i`` has a Mamba2 of its own.  At the hybrid layers
(``cfg.shared_applications``) a shared transformer block is applied first:
application ``j`` uses block ``j % cfg.n_shared_blocks``, fed the hidden
stream ``h`` concatenated with the token embedding ``e``, and its output
goes through the application's own linear ``L_j``.  That sum is the Mamba2's
input only; the residual is taken from ``h``::

    x = h + L_j(block_b([h, e]))       (hybrid layers; otherwise x = h)
    h = h + Mamba2_i(RMSNorm_i(x))

A block is RMSNorm, attention from 2*d_model to d_model, RMSNorm, and a
gated MLP whose gate/up projection carries the application's own LoRA
adapter, with no residual inside.

The layers are unrolled: each layer's parameters and cache entries are
leaves of their own, so no slice of a stacked weight is taken.  The cache
is donated and its leaves aliased, which alone does not make a token's
write cheap: the TPU compiler lays a 4-D ``(B, S, K, hd)`` leaf out with
the position in lanes, so writing one position rewrites the whole leaf.
Each application's k and v are therefore flat, ``(B, S, K * hd)``, and
read by ``attention_decode_flat``: the position is a sublane row, and a
token's write touches one row of tiles.

``from_published`` loads weights held in the published checkpoint's module
layout, and ``published_config`` gives a config in the published
``config.json``'s keys.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from .layers import (
    apply_mlp,
    apply_norm,
    attention_decode_flat,
    attention_output,
    dense_init,
    embed_init,
    init_attention,
    init_mlp,
    init_norm,
    qkv_project,
    run_attention,
)
from .mamba2 import apply_mamba2, init_mamba2, init_mamba2_state, ssm_dims
from .transformer import logits_from_hidden

PyTree = Any


def init_params(key, cfg: ArchConfig) -> PyTree:
    dtype = jnp.dtype(cfg.param_dtype)
    d, r, ff = cfg.d_model, cfg.adapter_rank, cfg.d_ff
    ke, km, ks, ka, ko = jax.random.split(key, 5)

    def block(k):
        k_attn, k_mlp = jax.random.split(k)
        return {
            "norm_in": init_norm(cfg, cfg.resolved_attn_input_dim),
            "attn": init_attention(k_attn, cfg, dtype),
            "norm_ff": init_norm(cfg),
            "mlp": init_mlp(k_mlp, cfg, dtype=dtype),
        }

    def adapter(k):
        k_a, k_b, k_out = jax.random.split(k, 3)
        return {
            "lora_a": dense_init(k_a, d, (d, r), dtype),
            "lora_b": dense_init(k_b, r, (r, 2 * ff), dtype),
            "out": dense_init(k_out, d, (d, d), dtype),
        }

    n_apps = len(cfg.shared_applications)
    p = {
        "embed": embed_init(ke, (cfg.padded_vocab_size, d), dtype),
        "mamba_layers": [
            {"norm": init_norm(cfg), "mamba": init_mamba2(k, cfg, dtype)}
            for k in jax.random.split(km, cfg.n_layers)
        ],
        "shared_blocks": [block(k) for k in jax.random.split(ks, cfg.n_shared_blocks)],
        "adapters": [adapter(k) for k in jax.random.split(ka, n_apps)] if n_apps else [],
        "final_norm": init_norm(cfg),
    }
    if not cfg.tied_embeddings:
        p["lm_head"] = embed_init(ko, (d, cfg.padded_vocab_size), dtype)
    return p


def published_config(cfg: ArchConfig) -> Dict[str, Any]:
    """The config in the published ``config.json``'s keys."""
    s = cfg.ssm
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "attention_head_dim": cfg.resolved_head_dim,
        "attention_hidden_size": cfg.resolved_attn_input_dim, "ffn_hidden_size": cfg.d_ff,
        "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.n_layers,
        "hybrid_layer_ids": list(cfg.hybrid_layer_ids), "num_mem_blocks": cfg.n_shared_blocks,
        "adapter_rank": cfg.adapter_rank, "rms_norm_eps": cfg.resolved_norm_eps,
        "rope_theta": cfg.rope_theta, "n_mamba_heads": ssm_dims(cfg)[1],
        "mamba_headdim": s.head_dim, "mamba_d_state": s.state_dim, "mamba_ngroups": s.n_groups,
        "mamba_d_conv": s.conv_width, "mamba_expand": s.expand, "chunk_size": s.chunk,
    }


def from_published(w: Dict[str, Any], cfg: ArchConfig) -> PyTree:
    """Parameters from weights in the published checkpoint's module layout:
    ``embed`` (vocab, hidden) and ``final_norm``; per layer ``layers[i]``
    ``input_layernorm``, ``in_proj`` (hidden, z|x|B|C|dt), ``conv1d_weight``
    (d_conv, channels), ``conv1d_bias``, ``dt_bias``, ``A_log``, ``D``,
    ``norm`` and ``out_proj``; per shared block ``blocks[b]``
    ``input_layernorm``, ``q_proj``/``k_proj``/``v_proj`` (input,
    heads*head_dim), ``o_proj``, ``pre_ff_layernorm``, ``gate_up_proj``
    (hidden, gate|up) and ``down_proj``; per application ``adapters[j]``
    ``lora_A``, ``lora_B`` and ``linear``.  Matrices are ``(in, out)``; the
    embeddings are tied.

    ``layers``, ``blocks`` and ``adapters`` are read one entry at a time, so
    a checkpoint that yields its entries as they are read is never whole
    beside the parameters."""
    dtype = jnp.dtype(cfg.param_dtype)
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    a = cfg.resolved_attn_input_dim
    mat = lambda x, *shape: jnp.asarray(x, dtype).reshape(shape or x.shape)
    vec = lambda x: jnp.asarray(x, jnp.float32)
    want = {"layers": cfg.n_layers, "blocks": cfg.n_shared_blocks,
            "adapters": len(cfg.shared_applications)}
    got = {k: len(w[k]) for k in want}
    if got != want or not cfg.tied_embeddings:
        raise ValueError(f"checkpoint has {got} and tied embeddings; the config wants {want}, "
                         f"tied_embeddings={cfg.tied_embeddings}")

    def layer(lw):
        return {"norm": {"scale": vec(lw["input_layernorm"])}, "mamba": {
            "in_proj": mat(lw["in_proj"]), "conv_w": vec(lw["conv1d_weight"]),
            "conv_b": vec(lw["conv1d_bias"]), "A_log": vec(lw["A_log"]), "D": vec(lw["D"]),
            "dt_bias": vec(lw["dt_bias"]), "norm": vec(lw["norm"]),
            "out_proj": mat(lw["out_proj"])}}

    def block(bw):
        gate, up = jnp.split(mat(bw["gate_up_proj"]), 2, axis=1)
        return {
            "norm_in": {"scale": vec(bw["input_layernorm"])},
            "attn": {"wq": mat(bw["q_proj"], a, H, hd), "wk": mat(bw["k_proj"], a, K, hd),
                     "wv": mat(bw["v_proj"], a, K, hd), "wo": mat(bw["o_proj"], H, hd, d)},
            "norm_ff": {"scale": vec(bw["pre_ff_layernorm"])},
            "mlp": {"wi": gate, "wg": up, "wo": mat(bw["down_proj"])},
        }

    embed = mat(w["embed"])
    p = {
        "embed": jnp.pad(embed, ((0, cfg.padded_vocab_size - embed.shape[0]), (0, 0))),
        "mamba_layers": [layer(lw) for lw in w["layers"]],
        "shared_blocks": [block(bw) for bw in w["blocks"]],
        "adapters": [{"lora_a": mat(aw["lora_A"]), "lora_b": mat(aw["lora_B"]),
                      "out": mat(aw["linear"])} for aw in w["adapters"]],
        "final_norm": {"scale": vec(w["final_norm"])},
    }
    return p


def block_of(cfg: ArchConfig, j: int) -> int:
    """The shared block the j-th application uses: they take turns."""
    return j % cfg.n_shared_blocks


def shared_block(
    block_p: PyTree, adapter_p: PyTree, h: jax.Array, e: jax.Array, cfg: ArchConfig,
    attend: Callable[[PyTree, jax.Array], Tuple[jax.Array, Any]],
) -> Tuple[jax.Array, Any]:
    """One application: ``L_j(block_b([h, e]))`` and what ``attend`` returns
    beside the attention output (the updated KV cache when decoding)."""
    a = apply_norm(block_p["norm_in"], jnp.concatenate([h, e], axis=-1), cfg)
    o, kv = attend(block_p["attn"], a)
    m = apply_norm(block_p["norm_ff"], o, cfg)
    f = apply_mlp(block_p["mlp"], m, cfg, lora=(adapter_p["lora_a"], adapter_p["lora_b"]))
    return f @ adapter_p["out"], kv


def _layers(cfg: ArchConfig, e: jax.Array, block: Callable, mamba: Callable) -> jax.Array:
    """The hidden stream through every layer: ``block(j, h)`` at the j-th
    hybrid layer, then ``mamba(i, x)`` at every layer ``i``."""
    apps = {i: j for j, i in enumerate(cfg.shared_applications)}
    h = e
    for i in range(cfg.n_layers):
        x = h
        if i in apps:
            with jax.named_scope("zamba2.shared_block"):
                x = h + block(apps[i], h)
        with jax.named_scope("zamba2.mamba"):
            h = h + mamba(i, x)
    return h


def _head(p: PyTree, cfg: ArchConfig, h: jax.Array, return_hidden: bool = False) -> jax.Array:
    with jax.named_scope("zamba2.head"):
        h = apply_norm(p["final_norm"], h, cfg)
        return h if return_hidden else logits_from_hidden(p, cfg, h)


def forward(
    p: PyTree,
    cfg: ArchConfig,
    batch: Dict[str, jax.Array],
    attn_impl: str = "xla",
    remat: str = "block",
    unroll: bool = False,
    return_hidden: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The whole sequence at once (``unroll`` is accepted for the common
    interface: the layers are always unrolled)."""
    dtype = jnp.dtype(cfg.activation_dtype)
    e = jnp.take(p["embed"], batch["tokens"], axis=0).astype(dtype)
    B, S = e.shape[:2]
    positions = jnp.arange(S)

    def block_fn(block_p, adapter_p, h, e):
        attend = lambda ap, a: (run_attention(ap, a, cfg, positions, attn_impl), None)
        return shared_block(block_p, adapter_p, h, e, cfg, attend)[0]

    def mamba_fn(layer_p, x):
        # fresh zero state per layer: the full sequence is processed at once
        out, _ = apply_mamba2(
            layer_p["mamba"], apply_norm(layer_p["norm"], x, cfg), cfg, init_mamba2_state(cfg, B)
        )
        return out

    if remat == "block":
        block_fn, mamba_fn = jax.checkpoint(block_fn), jax.checkpoint(mamba_fn)
    h = _layers(
        cfg, e,
        lambda j, h: block_fn(p["shared_blocks"][block_of(cfg, j)], p["adapters"][j], h, e),
        lambda i, x: mamba_fn(p["mamba_layers"][i], x),
    )
    return _head(p, cfg, h, return_hidden), {}


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> PyTree:
    """Per layer the Mamba2's conv window and SSM state (float32); per
    shared-block application one KV pair, each ``(batch, max_len,
    n_kv_heads * head_dim)`` with head k in columns ``k * head_dim ..``."""
    s = cfg.ssm
    d_in, H, P, N = ssm_dims(cfg)
    dtype = jnp.dtype(cfg.activation_dtype)
    kv = (batch, max_len, cfg.n_kv_heads * cfg.resolved_head_dim)
    n_apps = len(cfg.shared_applications)
    return {
        "conv": [
            jnp.zeros((batch, s.conv_width - 1, d_in + 2 * s.n_groups * N), jnp.float32)
            for _ in range(cfg.n_layers)
        ],
        "ssm": [jnp.zeros((batch, H, N, P), jnp.float32) for _ in range(cfg.n_layers)],
        "k": [jnp.zeros(kv, dtype) for _ in range(n_apps)],
        "v": [jnp.zeros(kv, dtype) for _ in range(n_apps)],
    }


def decode_step(
    p: PyTree,
    cfg: ArchConfig,
    cache: PyTree,
    batch: Dict[str, jax.Array],
    position: jax.Array,
    unroll: bool = False,
) -> Tuple[jax.Array, PyTree]:
    dtype = jnp.dtype(cfg.activation_dtype)
    e = jnp.take(p["embed"], batch["tokens"], axis=0).astype(dtype)
    new = {name: list(leaves) for name, leaves in cache.items()}

    def attend(j, ap, a):
        """Write the token's k and v as one row of the flat leaves, then attend."""
        q, k, v = qkv_project(ap, a, cfg, position[None])
        B = a.shape[0]
        kv = [jax.lax.dynamic_update_slice_in_dim(
            cache[n][j], x.reshape(B, 1, -1).astype(cache[n][j].dtype), position, axis=1)
            for n, x in (("k", k), ("v", v))]
        ctx = attention_decode_flat(q, *kv, position + 1, cfg)
        return attention_output(ap, ctx), kv

    def block(j, h):
        block_p = p["shared_blocks"][block_of(cfg, j)]
        s, kv = shared_block(block_p, p["adapters"][j], h, e, cfg,
                             lambda ap, a: attend(j, ap, a))
        new["k"][j], new["v"][j] = kv
        return s

    def mamba(i, x):
        layer_p = p["mamba_layers"][i]
        out, state = apply_mamba2(
            layer_p["mamba"], apply_norm(layer_p["norm"], x, cfg), cfg,
            {"conv": cache["conv"][i], "ssm": cache["ssm"][i]},
        )
        new["conv"][i], new["ssm"][i] = state["conv"], state["ssm"]
        return out

    h = _layers(cfg, e, block, mamba)
    return _head(p, cfg, h), new
