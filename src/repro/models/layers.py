"""Shared model layers: norms, rotary embeddings, MLP variants.

All layers are pure functions taking explicit params; quantization flows
through the ``QuantContext`` (``qc``) handle. Matmul compute dtype is bf16
(TPU-native) with fp32 accumulation via ``preferred_element_type``; master
params stay fp32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sites import QuantContext

COMPUTE_DTYPE = jnp.bfloat16


def rms_norm(x, gain, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gain.astype(jnp.float32))).astype(x.dtype)


def qmatmul(qc: QuantContext, name: str, x, w, *, positions: int = 1,
            act_quantized: bool = True, act_name: str | None = None,
            register: bool = True):
    """Quantized matmul over the last axis of ``x``: (..., in) @ (in, out).

    Registers the site, fake-quantizes the weight, performs the contraction in
    bf16 with fp32 accumulation. The *output activation* quantization is the
    caller's job (after the nonlinearity, paper Fig. 1) via ``qc.act``.

    In serve mode, sites with an int-code export dispatch the bit-width-
    matched fused-dequant GEMM instead (Pallas on TPU, jnp reference
    elsewhere — DESIGN.md §8/§11): the fp weight is never materialized,
    ``y = x @ (codes * scale + bias)`` comes straight off the int8 codes —
    unpacked in-kernel for 2/4-bit packed storage.
    """
    if register:
        qc.register_matmul(
            name, w.shape, fan_in=int(w.shape[0]), out_features=int(w.shape[-1]),
            positions=positions, act_quantized=act_quantized,
        )
    qw = qc.serving_weight(name)
    if qw is not None:
        from repro.kernels.quant_matmul.ops import quant_matmul_qt

        # With a calibrated ``.in`` spec the GEMM goes fully integer: the
        # kernel quantizes the activation tile on the fly and accumulates
        # int8×int8 in int32 (DESIGN.md §16). Without one, the int-weight ×
        # fp32-act fused-dequant path runs — the asserted oracle.
        y = quant_matmul_qt(
            x, qw, act_spec=qc.input_spec(name), impl=qc.matmul_impl)
        return y.astype(COMPUTE_DTYPE)
    x = qc.act_in(name, x)
    wq = qc.weight(name, w)
    y = jax.lax.dot_general(
        x.astype(COMPUTE_DTYPE), wq.astype(COMPUTE_DTYPE),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return y.astype(COMPUTE_DTYPE)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)  # (half,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections: tuple[int, ...]):
    """Qwen2-VL M-RoPE: rotary halves split into (t, h, w) sections.

    x: (B, S, H, hd); positions3: (3, B, S) int positions per component;
    ``sections`` sums to hd/2.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)  # (half,)
    # per-frequency position source: section i uses positions3[i]
    sec_ids = jnp.repeat(
        jnp.arange(len(sections)), jnp.asarray(sections), total_repeat_length=half
    )  # (half,)
    pos = jnp.take(positions3, sec_ids, axis=0)  # (half, B, S)
    ang = jnp.moveaxis(pos, 0, -1).astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def glu_mlp(qc: QuantContext, p, x, kind: str):
    """SwiGLU / GeGLU / plain-GELU MLP with quantization sites."""
    if kind in ("swiglu", "geglu"):
        g = qmatmul(qc, "mlp_gate", x, p["w_gate"])
        u = qmatmul(qc, "mlp_up", x, p["w_up"])
        act = jax.nn.silu if kind == "swiglu" else (
            lambda v: jax.nn.gelu(v, approximate=True)
        )
        h = act(g.astype(jnp.float32)).astype(COMPUTE_DTYPE) * u
        h = qc.act("mlp_up", h)
        y = qmatmul(qc, "mlp_down", h, p["w_down"])
        y = qc.act("mlp_down", y)
        return y
    # plain gelu (musicgen / t5-style)
    h = qmatmul(qc, "mlp_in", x, p["w_in"])
    h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(COMPUTE_DTYPE)
    h = qc.act("mlp_in", h)
    y = qmatmul(qc, "mlp_out", h, p["w_out"])
    y = qc.act("mlp_out", y)
    return y


def init_glu_mlp(key, d_model: int, d_ff: int, kind: str):
    k = jax.random.split(key, 3)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape) / jnp.sqrt(fan_in)).astype(jnp.float32)

    if kind in ("swiglu", "geglu"):
        return {
            "w_gate": w(k[0], (d_model, d_ff), d_model),
            "w_up": w(k[1], (d_model, d_ff), d_model),
            "w_down": w(k[2], (d_ff, d_model), d_ff),
        }
    return {
        "w_in": w(k[0], (d_model, d_ff), d_model),
        "w_out": w(k[1], (d_ff, d_model), d_ff),
    }


def softcap(x, cap: float | None):
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap
