"""Operations and bytes of a Zamba2 decode step, from the configuration's
published keys and the traffic's shapes alone (never from the program).

A decode step feeds one token of each of ``batch`` requests through
every layer: each layer's Mamba2, and at each hybrid layer one
application of a shared block with that application's adapter and output
linear, then the final norm and the logits against the embedding.

* FLOPs: 2 per multiply-add of every matrix product, a shared block's
  counted once per application; attention's scores and PV against
  ``length`` cached positions; the SSM update (``5 * state * headdim`` per
  head: decay, input outer product, add, output contraction) and the
  depthwise conv.  Norms and activations are left out (under 1%).
* Least bytes: every weight the step multiplies by, in bf16, read once
  per use (a shared block is read at each of its applications: between
  two of them lie Mamba2 layers, and a block is far larger than the chip's
  on-chip memory); the SSM and conv state, float32, read and written;
  ``length`` cached key/value positions of each application, bf16, read.
"""

from __future__ import annotations

from typing import Dict

BF16 = 2
F32 = 4


def shapes(c: Dict) -> Dict[str, int]:
    d = c["hidden_size"]
    H, P, N, G = c["n_mamba_heads"], c["mamba_headdim"], c["mamba_d_state"], c["mamba_ngroups"]
    d_in = H * P
    return {
        "d": d, "d_in": d_in, "H": H, "P": P, "N": N, "G": G,
        "conv_ch": d_in + 2 * G * N, "Kc": c["mamba_d_conv"],
        "a": c["attention_hidden_size"], "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "hd": c["attention_head_dim"],
        "ff": c["ffn_hidden_size"], "r": c["adapter_rank"], "V": c["vocab_size"],
        "layers": c["num_hidden_layers"],
        "apps": sum(1 for i in c["hybrid_layer_ids"] if i < c["num_hidden_layers"]),
    }


def mamba_matmul_params(c: Dict) -> int:
    """in_proj and out_proj of one Mamba2 layer."""
    s = shapes(c)
    return s["d"] * (2 * s["d_in"] + 2 * s["G"] * s["N"] + s["H"]) + s["d_in"] * s["d"]


def mamba_layer_params(c: Dict) -> int:
    """Every parameter of one Mamba2 layer with its input norm."""
    s = shapes(c)
    conv = (s["Kc"] + 1) * s["conv_ch"]  # weight and bias
    return mamba_matmul_params(c) + conv + 3 * s["H"] + s["d_in"] + s["d"]


def block_matmul_params(c: Dict) -> int:
    """q, k, v, o, gate_up and down of one shared block."""
    s = shapes(c)
    attn = s["a"] * s["hd"] * (s["heads"] + 2 * s["kv_heads"]) + s["heads"] * s["hd"] * s["d"]
    return attn + 3 * s["d"] * s["ff"]


def block_params(c: Dict) -> int:
    s = shapes(c)
    return block_matmul_params(c) + s["a"] + s["d"]  # and its two norms


def adapter_params(c: Dict) -> int:
    """One application's LoRA (A, B) and output linear."""
    s = shapes(c)
    return s["d"] * s["r"] + s["r"] * 2 * s["ff"] + s["d"] * s["d"]


def params(c: Dict) -> int:
    """Every parameter of the configuration as served (tied embedding)."""
    s = shapes(c)
    return (s["layers"] * mamba_layer_params(c) + c["num_mem_blocks"] * block_params(c)
            + s["apps"] * adapter_params(c) + s["V"] * s["d"] + s["d"])


def state_bytes(c: Dict) -> int:
    """SSM and conv state of one request, float32."""
    s = shapes(c)
    return s["layers"] * F32 * (s["H"] * s["N"] * s["P"] + (s["Kc"] - 1) * s["conv_ch"])


def kv_bytes_per_position(c: Dict) -> int:
    """Keys and values of one position of one request, every application."""
    s = shapes(c)
    return s["apps"] * 2 * s["kv_heads"] * s["hd"] * BF16


def step_matmul_weights(c: Dict) -> int:
    """Weights a step multiplies by, shared blocks once per application."""
    s = shapes(c)
    return (s["layers"] * mamba_matmul_params(c)
            + s["apps"] * (block_matmul_params(c) + adapter_params(c)) + s["V"] * s["d"])


def decode_step_flops(c: Dict, batch: int, length: float) -> float:
    s = shapes(c)
    attn = s["apps"] * 4 * length * s["heads"] * s["hd"]
    ssm = s["layers"] * (5 * s["H"] * s["N"] * s["P"] + 2 * s["Kc"] * s["conv_ch"])
    return batch * (2 * step_matmul_weights(c) + attn + ssm)


def decode_step_bytes(c: Dict, batch: int, length: float) -> float:
    return (BF16 * step_matmul_weights(c) + 2 * batch * state_bytes(c)
            + batch * length * kv_bytes_per_position(c))


def unit_steps(mix: Dict) -> int:
    return mix["prompt_len"] + mix["gen_len"]


def unit_flops(c: Dict, mix: Dict) -> float:
    """Every step of one unit: step t attends to t + 1 positions."""
    return sum(decode_step_flops(c, mix["requests"], t + 1) for t in range(unit_steps(mix)))


def mean_step_bytes(c: Dict, mix: Dict) -> float:
    """Least bytes of a step, averaged over the steps of a unit."""
    T = unit_steps(mix)
    return decode_step_bytes(c, mix["requests"], (T + 1) / 2)
