"""Operations and bytes the served work needs, from a model's sizes.

These count what the algorithm requires, not what an implementation
happens to do: attention over the live context only (causal pairs in a
prefill, the keys written so far in a decode step), matrix products of
the real tokens only (no padding rows or columns), and the output
projection only where a token's logits are used (the last prompt token of
a prefill, every decode step). A multiply-add is two operations.

``m`` is a model's sizes as the configuration file holds them.
"""
from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    """Weights of one decoder layer's matrix products."""
    d, h, k, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    return d * h * hd + 2 * d * k * hd + h * hd * d + d * 2 * ff + ff * d


def attn_flops_per_pair(m: dict) -> int:
    """QK^T and PV for one (query, key) pair, summed over layers."""
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"]


def logits_flops(m: dict) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def prefill_flops(m: dict, n_text: int, n_patches: int) -> int:
    """One prompt of ``n_text`` tokens after ``n_patches`` image rows."""
    s = n_text + n_patches
    dense = 2 * m["num_layers"] * layer_matmul_params(m) * s
    attn = attn_flops_per_pair(m) * s * (s + 1) // 2
    proj = 2 * n_patches * m.get("frontend_dim", 0) * m["d_model"]
    return dense + attn + proj + logits_flops(m)


def decode_flops(m: dict, ctx: int) -> int:
    """One decode token that attends ``ctx`` keys, itself included."""
    return (2 * m["num_layers"] * layer_matmul_params(m)
            + attn_flops_per_pair(m) * ctx + logits_flops(m))


def kv_bytes(m: dict, ctx: int, itemsize: int = 2) -> int:
    """Keys and values one decode token's attention reads at ``ctx``."""
    return (m["num_layers"] * ctx * 2 * m["num_kv_heads"] * m["head_dim"]
            * itemsize)


def decode_attn_flops(m: dict, ctx: int) -> int:
    return attn_flops_per_pair(m) * ctx


def image_bytes(h: int, w: int) -> int:
    """Pixels the scorer's kernel reads for one float32 image."""
    return 4 * h * w


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Roofline bound: the larger of compute time and HBM time."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
