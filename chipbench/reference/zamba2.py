"""Plain float32 reference of Zamba2's forward pass, for the model cells' check.

It imports nothing of the program.  The configuration is the published
``config.json``'s keys (``hidden_size``, ``hybrid_layer_ids``,
``num_mem_blocks``, ...), of which the first ``num_hidden_layers`` layers
are run; the weights are a dict in the published module layout (below),
in any float dtype: each layer's are upcast to
float32 as the layer runs, so bf16 weights of a model that fills a chip
leave room for this beside them.  Matrix products run at
``jax.default_matmul_precision("highest")``.  There is no cache and no
kernel; the only loop beyond the layers is the plain sequential SSM
recurrence.

With ``e`` the token embedding, ``h = e`` to start, and ``j`` counting the
hybrid layers from 0, each layer ``i``:

* hybrid: ``x = h + (block_{j mod num_mem_blocks}([h, e])) @ linear_j``,
  where the block is RMSNorm, attention (2*hidden -> heads x head_dim ->
  hidden, RoPE on q and k over all head dims), RMSNorm, and the gated MLP
  ``(gelu(g) * up) @ down`` with ``[g|up] = m @ gate_up + (m @ A_j) @ B_j``,
  with no residual inside; otherwise ``x = h``;
* ``h = h + Mamba2_i(RMSNorm_i(x))``.

Mamba2: in_proj to z, x, B, C, dt; causal depthwise conv with bias and
SiLU over (x, B, C); heads read their group's B and C (groups contiguous);
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence
``s_t = exp(dt_t A) s_{t-1} + dt_t B_t x_t``, ``y_t = C_t s_t + D x_t``;
``y * silu(z)`` normalised within each group; out_proj.  Then the final
RMSNorm and the logits against the embedding.

The weights: ``embed`` (vocab, hidden) and ``final_norm``; per layer
``layers[i]``: ``input_layernorm``, ``in_proj`` (hidden, 2*d_in +
2*groups*state + heads), ``conv1d_weight`` (d_conv, d_in + 2*groups*state),
``conv1d_bias``, ``dt_bias``, ``A_log``, ``D``, ``norm`` (d_in) and
``out_proj`` (d_in, hidden); per shared block ``blocks[b]``:
``input_layernorm`` (2*hidden), ``q_proj``/``k_proj``/``v_proj`` (2*hidden,
heads*head_dim), ``o_proj`` (heads*head_dim, hidden), ``pre_ff_layernorm``,
``gate_up_proj`` (hidden, 2*ffn) and ``down_proj``; per application
``adapters[j]``: ``lora_A`` (hidden, rank), ``lora_B`` (rank, 2*ffn) and
``linear`` (hidden, hidden).  ``draw_weights`` draws them from a seed, in
that layout, independently of the program: the benchmark loads them into
the program through its own loader and gives the reference the same draw.
"""

from __future__ import annotations

import math
from collections.abc import Sequence
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (batch, T, heads, dim), positions 0..T-1; the two halves of the
    head dims are rotated together (``rotate_half``)."""
    T, dim = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs  # (T, dim/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _block(cfg: Dict, w: Dict, ad: Dict, h, e):
    w, ad = _f32(w), _f32(ad)
    n, T = h.shape[:2]
    H, K, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["attention_head_dim"]
    eps = cfg["rms_norm_eps"]
    a = rms_norm(jnp.concatenate([h, e], axis=-1), w["input_layernorm"], eps)
    q = rope((a @ w["q_proj"]).reshape(n, T, H, hd), cfg["rope_theta"])
    k = rope((a @ w["k_proj"]).reshape(n, T, K, hd), cfg["rope_theta"])
    v = (a @ w["v_proj"]).reshape(n, T, K, hd)
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    # Departure from the plain 1/sqrt(head_dim): the published modelling code
    # scales by (head_dim / 2)^-1/2, its handling of the input being
    # [hidden, embedding], twice the hidden width.
    scale = 1.0 / math.sqrt(hd / 2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    ctx = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    o = ctx.reshape(n, T, H * hd) @ w["o_proj"]
    m = rms_norm(o, w["pre_ff_layernorm"], eps)
    g, up = jnp.split(m @ w["gate_up_proj"] + (m @ ad["lora_A"]) @ ad["lora_B"], 2, axis=-1)
    f = (jax.nn.gelu(g, approximate=False) * up) @ w["down_proj"]
    return f @ ad["linear"]


def _mamba(cfg: Dict, w: Dict, h, x):
    w = _f32(w)
    n, T = x.shape[:2]
    H, P, N = cfg["n_mamba_heads"], cfg["mamba_headdim"], cfg["mamba_d_state"]
    G, Kc = cfg["mamba_ngroups"], cfg["mamba_d_conv"]
    d_in = H * P
    u = rms_norm(x, w["input_layernorm"], cfg["rms_norm_eps"]) @ w["in_proj"]
    z, xbc, dt = jnp.split(u, [d_in, 2 * d_in + 2 * G * N], axis=-1)
    pad = jnp.pad(xbc, ((0, 0), (Kc - 1, 0), (0, 0)))
    conv = sum(pad[:, i : i + T] * w["conv1d_weight"][i] for i in range(Kc)) + w["conv1d_bias"]
    xs, bm, cm = jnp.split(jax.nn.silu(conv), [d_in, d_in + G * N], axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # (n, T, H)
    A = -jnp.exp(w["A_log"])
    xh = xs.reshape(n, T, H, P)
    group = jnp.arange(H) // (H // G)  # heads of a group are contiguous
    bh = bm.reshape(n, T, G, N)[:, :, group]  # (n, T, H, N)
    ch = cm.reshape(n, T, G, N)[:, :, group]

    def step(s, t):  # the state is kept in float32, as the published kernels keep it
        s = (s * jnp.exp(dt[:, t] * A)[..., None, None]
             + (dt[:, t, :, None] * bh[:, t])[..., None] * xh[:, t, :, None, :])
        return s, jnp.einsum("bhn,bhnp->bhp", ch[:, t], s)

    _, ys = jax.lax.scan(step, jnp.zeros((n, H, N, P), F32), jnp.arange(T))
    y = ys.transpose(1, 0, 2, 3) + w["D"][:, None] * xh
    y = (y.reshape(n, T, d_in) * jax.nn.silu(z)).reshape(n, T, G, d_in // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    return h + (y.reshape(n, T, d_in) * w["norm"]) @ w["out_proj"]


def _head(cfg: Dict, norm, embed, h):
    h = rms_norm(h, norm.astype(F32), cfg["rms_norm_eps"])
    # Tied embeddings (assumed: the key is not in the published config; it is
    # the Zamba2 default): the logits are taken against the input embedding.
    return h @ embed.astype(F32).T


def hybrid_layers(cfg: Dict) -> List[int]:
    return [i for i in cfg["hybrid_layer_ids"] if i < cfg["num_hidden_layers"]]


class Drawn(Sequence):
    """``n`` groups of weights (layers, blocks or adapters), each drawn from
    the seed when it is read, so that a model that fills a chip is never
    held twice there: the same index gives the same weights every time."""

    def __init__(self, n: int, draw: Callable[[int], Dict]):
        self._n, self._draw = n, draw

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i: int) -> Dict:
        if not 0 <= i < self._n:
            raise IndexError(i)
        return self._draw(i)


def draw_weights(cfg: Dict, seed: int, dtype="bfloat16",
                 leaf: Optional[Callable] = None) -> Dict:
    """Seeded random weights in the layout above, stored in ``dtype`` as a
    checkpoint holds them (``leaf``, if given, is applied to each), with
    ``layers``, ``blocks`` and ``adapters`` drawn when read (``Drawn``).

    Matrices are N(0, 1/fan_in), the embedding N(0, 0.02^2).  Every norm
    scale and ``D`` is U(0.5, 1.5), not ones, so a program that drops a
    scale or mixes up ``D`` across heads reads wrong.  ``A_log`` is
    log U(1, 16) and ``dt_bias`` the inverse softplus of a step drawn
    log-uniform in [``time_step_min``, ``time_step_max``] and floored at
    ``time_step_floor`` (the published initialisation; its defaults 1e-3,
    0.1, 1e-4); the conv weight and bias are U(+-1/sqrt(d_conv)), a
    depthwise Conv1d's initialisation."""
    d, ff, r = cfg["hidden_size"], cfg["ffn_hidden_size"], cfg["adapter_rank"]
    H, K, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["attention_head_dim"]
    Hm, P, N = cfg["n_mamba_heads"], cfg["mamba_headdim"], cfg["mamba_d_state"]
    G, Kc = cfg["mamba_ngroups"], cfg["mamba_d_conv"]
    d_in, a = Hm * P, 2 * d
    conv_ch = d_in + 2 * G * N
    root = jax.random.key(seed)
    out = (lambda x: x.astype(dtype)) if leaf is None else (lambda x: leaf(x.astype(dtype)))

    def keys(group: int, i: int, n: int):
        return iter(jax.random.split(jax.random.fold_in(jax.random.fold_in(root, group), i), n))

    def normal(k, shape, fan_in):
        return out(jax.random.normal(k, shape, F32) / math.sqrt(fan_in))

    def uniform(k, shape, lo, hi):
        return out(jax.random.uniform(k, shape, F32, lo, hi))

    def scale(k, n):
        return uniform(k, (n,), 0.5, 1.5)

    def layer(i):
        k = keys(1, i, 9)
        lo, hi = math.log(cfg.get("time_step_min", 1e-3)), math.log(cfg.get("time_step_max", 0.1))
        dt = jnp.maximum(jnp.exp(jax.random.uniform(next(k), (Hm,), F32, lo, hi)),
                         cfg.get("time_step_floor", 1e-4))
        b = 1.0 / math.sqrt(Kc)
        return {"input_layernorm": scale(next(k), d),
                "in_proj": normal(next(k), (d, 2 * d_in + 2 * G * N + Hm), d),
                "conv1d_weight": uniform(next(k), (Kc, conv_ch), -b, b),
                "conv1d_bias": uniform(next(k), (conv_ch,), -b, b),
                "dt_bias": out(dt + jnp.log(-jnp.expm1(-dt))),
                "A_log": out(jnp.log(jax.random.uniform(next(k), (Hm,), F32, 1.0, 16.0))),
                "D": scale(next(k), Hm), "norm": scale(next(k), d_in),
                "out_proj": normal(next(k), (d_in, d), d_in)}

    def block(i):
        k = keys(2, i, 8)
        return {"input_layernorm": scale(next(k), a),
                "q_proj": normal(next(k), (a, H * hd), a),
                "k_proj": normal(next(k), (a, K * hd), a),
                "v_proj": normal(next(k), (a, K * hd), a),
                "o_proj": normal(next(k), (H * hd, d), H * hd),
                "pre_ff_layernorm": scale(next(k), d),
                "gate_up_proj": normal(next(k), (d, 2 * ff), d),
                "down_proj": normal(next(k), (ff, d), ff)}

    def adapter(i):
        k = keys(3, i, 3)
        return {"lora_A": normal(next(k), (d, r), d), "lora_B": normal(next(k), (r, 2 * ff), r),
                "linear": normal(next(k), (d, d), d)}

    k = keys(0, 0, 2)
    return {"embed": out(jax.random.normal(next(k), (cfg["vocab_size"], d), F32) * 0.02),
            "final_norm": scale(next(k), d),
            "layers": Drawn(cfg["num_hidden_layers"], layer),
            "blocks": Drawn(cfg["num_mem_blocks"], block),
            "adapters": Drawn(len(hybrid_layers(cfg)), adapter)}


def forward(w: Dict, cfg: Dict, tokens) -> jax.Array:
    """Logits (batch, T, vocab), float32, for ``tokens`` (batch, T)."""
    hybrid = {i: j for j, i in enumerate(hybrid_layers(cfg))}
    with jax.default_matmul_precision("highest"):
        e = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(F32)
        h = e
        for i in range(cfg["num_hidden_layers"]):
            x = h
            if i in hybrid:
                j = hybrid[i]
                x = h + _block(cfg, w["blocks"][j % cfg["num_mem_blocks"]], w["adapters"][j], h, e)
            h = _mamba(cfg, w["layers"][i], h, x)
        return _head(cfg, w["final_norm"], w["embed"], h)

