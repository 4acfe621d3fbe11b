"""Jit'd public wrapper for the fused fake-quant kernel.

Normalizes arbitrary tensor shapes / gate granularities onto the kernel's
(M, N) x (N,) layout, and runs the Pallas kernel or the pure-jnp oracle as
``impl`` says (``repro.kernels.pallas_flags``; kernels are validated in
interpret mode on CPU — DESIGN.md §3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import pallas_flags

from .fake_quant import fake_quant_pallas
from .ref import fake_quant_ref


@functools.partial(jax.jit, static_argnames=("signed", "impl"))
def fake_quant_op(
    x: jnp.ndarray,
    gate: jnp.ndarray,
    beta: jnp.ndarray,
    *,
    signed: bool = True,
    impl: str,
) -> jnp.ndarray:
    """Fake-quantize ``x`` at bit-width T(gate) with range beta.

    gate/beta may be scalar (per-tensor) or (x.shape[-1],) (per-channel).
    """
    orig_shape = x.shape
    n = orig_shape[-1]
    x2 = x.reshape(-1, n).astype(jnp.float32)
    g = jnp.broadcast_to(jnp.asarray(gate, jnp.float32), (n,))
    b = jnp.broadcast_to(jnp.asarray(beta, jnp.float32), (n,))
    use_pallas, interpret = pallas_flags(impl)
    if use_pallas:
        out = fake_quant_pallas(x2, g, b, signed, interpret=interpret)
    else:
        out = fake_quant_ref(x2, g, b, signed)
    return out.reshape(orig_shape).astype(x.dtype)
