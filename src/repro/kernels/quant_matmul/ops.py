"""Jit'd wrappers for the bit-width-dispatched fused dequant GEMM.

The kernels compute the complete affine dequant
``y = scale * (x @ codes) + bias * rowsum(x)`` (== ``x @ (codes*scale+bias)``
exactly) in their epilogue; these wrappers flatten the leading activation
dims, compute ``rowsum(x)`` (one VPU reduction, fused into the x load by
XLA) and pick the Pallas kernel or the pure-jnp oracle by ``impl``
(``repro.kernels.pallas_flags``). See quant_matmul.py
for the kernel contracts.

``quant_matmul_op`` is the raw int8 entry point (unchanged: the oracle path
every packed configuration is gated against). ``quant_matmul_qt`` is the
serving dispatcher: it takes a ``quant.QuantizedTensor`` and selects the
int8 or packed-sub-byte kernel from its static storage class — the one
place bit-width dispatch happens, for every model layer. With an
``act_spec`` (a ``quant.ActQuantSpec``, DESIGN.md §16) it instead
quantizes the incoming activation tile on the fly to int8 codes and
dispatches the INTEGER kernels: the weight grid's per-channel
``(scale, bias)`` and the activation grid's per-tensor ``(sx, bx)`` fold
into ``eff_scale``/``eff_bias``/``const`` exactly (see quant_matmul.py),
so the integer path equals ``fake_quant(x) @ dequant(qt)`` up to fp32
epilogue rounding — the requantization tolerance the serving oracle gate
documents.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantizer import quantize_to_int
from repro.kernels import pallas_flags

from .quant_matmul import (int_matmul_packed_pallas, int_matmul_pallas,
                           quant_matmul_packed_pallas, quant_matmul_pallas)
from .ref import (int_matmul_packed_ref, int_matmul_ref,
                  quant_matmul_packed_ref, quant_matmul_ref)


@functools.partial(jax.jit, static_argnames=("impl",))
def quant_matmul_op(
    x: jnp.ndarray,
    codes: jnp.ndarray,
    scale: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    impl: str,
) -> jnp.ndarray:
    """y = x @ (codes*scale + bias); x: (..., K), codes: (K, N) int8."""
    orig = x.shape
    x2 = x.reshape(-1, orig[-1]).astype(jnp.float32)
    use_pallas, interpret = pallas_flags(impl)
    if use_pallas:
        rowsum = jnp.sum(x2, axis=1)
        y = quant_matmul_pallas(x2, codes, scale, bias, rowsum,
                                interpret=interpret)
    else:
        y = quant_matmul_ref(x2, codes, scale, bias)
    return y.reshape(orig[:-1] + (codes.shape[1],))


@functools.partial(jax.jit,
                   static_argnames=("bits", "k", "impl"))
def quant_matmul_packed_op(
    x: jnp.ndarray,
    packed: jnp.ndarray,
    scale: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    bits: int,
    k: int,
    impl: str,
) -> jnp.ndarray:
    """Packed twin of ``quant_matmul_op``: packed (ceil(K/per), N) uint8."""
    orig = x.shape
    x2 = x.reshape(-1, orig[-1]).astype(jnp.float32)
    use_pallas, interpret = pallas_flags(impl)
    if use_pallas:
        rowsum = jnp.sum(x2, axis=1)
        y = quant_matmul_packed_pallas(x2, packed, scale, bias, rowsum,
                                       bits=bits, k=k, interpret=interpret)
    else:
        y = quant_matmul_packed_ref(x2, packed, scale, bias, bits=bits, k=k)
    return y.reshape(orig[:-1] + (packed.shape[-1],))


@functools.partial(jax.jit,
                   static_argnames=("act_bits", "act_signed", "impl"))
def int_matmul_op(
    x: jnp.ndarray,
    codes: jnp.ndarray,
    scale: jnp.ndarray,
    bias: jnp.ndarray,
    colsum: jnp.ndarray,
    act_beta: jnp.ndarray,
    *,
    act_bits: int,
    act_signed: bool = True,
    impl: str,
) -> jnp.ndarray:
    """Integer entry point: quantize ``x`` per-tensor, int8×int8 GEMM.

    ``x``: (..., K) float; ``codes``: (K, N) int8 weight codes with their
    per-channel affine ``scale``/``bias`` (N,) and precomputed K-sum
    ``colsum`` (N,) int32. Returns (..., N) fp32 equal (exactly, in exact
    arithmetic) to ``fake_quant(x) @ (codes*scale + bias)``.
    """
    orig = x.shape
    k = orig[-1]
    x2 = x.reshape(-1, k)
    qx, sx, bx = quantize_to_int(x2, act_bits, act_beta, act_signed)
    rowsum = jnp.sum(qx.astype(jnp.int32), axis=1).astype(jnp.float32)
    eff_scale = sx * scale
    eff_bias = sx * bias
    const = bx * (scale * colsum.astype(jnp.float32) + k * bias)
    use_pallas, interpret = pallas_flags(impl)
    if use_pallas:
        y = int_matmul_pallas(qx, codes, eff_scale, eff_bias, rowsum, const,
                              interpret=interpret)
    else:
        y = int_matmul_ref(qx, codes, eff_scale, eff_bias, rowsum, const)
    return y.reshape(orig[:-1] + (codes.shape[-1],))


@functools.partial(jax.jit,
                   static_argnames=("bits", "k", "act_bits", "act_signed",
                                    "impl"))
def int_matmul_packed_op(
    x: jnp.ndarray,
    packed: jnp.ndarray,
    scale: jnp.ndarray,
    bias: jnp.ndarray,
    colsum: jnp.ndarray,
    act_beta: jnp.ndarray,
    *,
    bits: int,
    k: int,
    act_bits: int,
    act_signed: bool = True,
    impl: str,
) -> jnp.ndarray:
    """Packed twin of ``int_matmul_op``: sub-byte weight codes decoded to
    int8 in-kernel, same on-the-fly activation quantization."""
    orig = x.shape
    x2 = x.reshape(-1, orig[-1])
    qx, sx, bx = quantize_to_int(x2, act_bits, act_beta, act_signed)
    rowsum = jnp.sum(qx.astype(jnp.int32), axis=1).astype(jnp.float32)
    eff_scale = sx * scale
    eff_bias = sx * bias
    const = bx * (scale * colsum.astype(jnp.float32) + k * bias)
    use_pallas, interpret = pallas_flags(impl)
    if use_pallas:
        y = int_matmul_packed_pallas(qx, packed, eff_scale, eff_bias, rowsum,
                                     const, bits=bits, k=k,
                                     interpret=interpret)
    else:
        y = int_matmul_packed_ref(qx, packed, eff_scale, eff_bias, rowsum,
                                  const, bits=bits, k=k)
    return y.reshape(orig[:-1] + (packed.shape[-1],))


def quant_matmul_qt(x, qt, *, impl: str, act_spec=None) -> jnp.ndarray:
    """Serving dispatcher: ``y = x @ dequant(qt)`` off a QuantizedTensor.

    Static dispatch on ``qt.storage_bits`` (pytree aux data, so each jit /
    scan specialization compiles exactly one kernel per site): 8-bit codes
    take the int8 kernel unchanged; 2/4-bit packed codes take the fused
    unpack+dequant kernel. ``scale``/``bias`` arrive per-tensor (scalar-ish)
    or per-channel; the kernel contract is per-output-channel (N,) vectors.

    With ``act_spec`` (per-tensor ``quant.ActQuantSpec``) the activation is
    quantized on the fly and the int8×int8 integer-accumulation kernels run
    instead — fully-integer MACs for both storage classes (DESIGN.md §16).
    """
    n = qt.codes.shape[-1]
    scale = jnp.broadcast_to(qt.scale.reshape(-1), (n,))
    bias = jnp.broadcast_to(qt.bias.reshape(-1), (n,))
    if act_spec is not None:
        colsum = jnp.broadcast_to(qt.code_colsum().reshape(-1), (n,))
        act_beta = jnp.asarray(act_spec.beta, jnp.float32).reshape(())
        if qt.storage_bits == 8:
            return int_matmul_op(
                x, qt.codes, scale, bias, colsum, act_beta,
                act_bits=act_spec.bits, act_signed=act_spec.signed, impl=impl)
        return int_matmul_packed_op(
            x, qt.codes, scale, bias, colsum, act_beta,
            bits=qt.storage_bits, k=qt.k, act_bits=act_spec.bits,
            act_signed=act_spec.signed, impl=impl)
    if qt.storage_bits == 8:
        return quant_matmul_op(x, qt.codes, scale, bias, impl=impl)
    return quant_matmul_packed_op(
        x, qt.codes, scale, bias, bits=qt.storage_bits, k=qt.k, impl=impl)
