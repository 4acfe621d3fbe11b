"""Operations and bytes the algorithm needs, from shapes alone.

One function per kernel and one per whole step. Operations count a
multiply-add as two. Bytes count what must cross HBM at least once:
weights at their packed width with their per-channel affine terms, the
live part of a KV pool, activations in and out at their stored width.
Nothing recomputed is counted. Sizes come from a configuration file's
``config`` (Hugging Face key names).
"""

from __future__ import annotations

import math

BF16 = 2


def gemm(m: int, k: int, n: int, bits: int, *, act_bytes: int = BF16,
         out_bytes: int = BF16) -> tuple[float, float]:
    """``y[m, n] = x[m, k] @ dequant(codes[k, n])``: codes packed at
    ``bits`` per weight, fp32 scale and bias per output channel."""
    ops = 2.0 * m * k * n
    codes = math.ceil(k * bits / 8) * n
    return ops, float(codes + 8 * n + m * k * act_bytes + m * n * out_bytes)


def paged_attention(ctx: list, *, heads: int, kv_heads: int, head_dim: int,
                    block: int, kv_bits: int = 8, group: int = 32,
                    scale_bytes: int = 2) -> tuple[float, float]:
    """One decode query per sequence against its cached keys and values:
    ``ctx`` holds each sequence's context length. Only live blocks are
    read; each cached vector is ``head_dim`` codes plus one fp16 scale per
    ``group``."""
    live = sum(math.ceil(c / block) * block for c in ctx)
    per_vec = head_dim * kv_bits / 8 + (head_dim // group) * scale_bytes
    kv = 2.0 * live * kv_heads * per_vec
    qo = 2.0 * len(ctx) * heads * head_dim * BF16
    return 4.0 * heads * head_dim * float(sum(ctx)), kv + qo


def layer_sites(c: dict) -> dict:
    """``site -> (k, n)`` of one decoder layer's weight GEMMs."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    return {"attn_q": (d, h * hd), "attn_k": (d, kv * hd),
            "attn_v": (d, kv * hd), "attn_o": (h * hd, d),
            "mlp_gate": (d, f), "mlp_up": (d, f), "mlp_down": (f, d)}


def matmul_params(c: dict) -> float:
    """Weights multiplied per token: every layer's GEMMs and the head."""
    per_layer = sum(k * n for k, n in layer_sites(c).values())
    return float(per_layer * c["num_hidden_layers"]
                 + c["hidden_size"] * c["vocab_size"])


def attention_flops(c: dict, ctx: float) -> float:
    """Scores and weighted values of one query over ``ctx`` keys, every
    layer."""
    return (4.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * ctx)


def decode_token(c: dict, ctx: int) -> float:
    """Forward FLOPs of one decoded token at context ``ctx``."""
    return 2.0 * matmul_params(c) + attention_flops(c, ctx)


def prefill(c: dict, start: int, end: int) -> float:
    """Forward FLOPs of prompt positions ``start .. end-1`` (earlier ones
    come from the prefix cache), causal. Only the last position needs the
    head."""
    n = end - start
    if n <= 0:
        return 0.0
    body = matmul_params(c) - c["hidden_size"] * c["vocab_size"]
    ctx = (end * (end + 1) - start * (start + 1)) / 2.0
    return (2.0 * body * n + 2.0 * c["hidden_size"] * c["vocab_size"]
            + attention_flops(c, ctx))


def train_token(c: dict, seq: int) -> float:
    """Forward and backward FLOPs per trained token at sequence length
    ``seq``: 6 per weight multiplied, plus causal attention (scores and
    values, forward and backward)."""
    return 6.0 * matmul_params(c) + 3.0 * attention_flops(c, (seq + 1) / 2)
