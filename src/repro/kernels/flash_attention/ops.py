"""Jit'd wrapper for flash attention with GQA layout handling."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import pallas_flags

from .flash_attention import flash_attention_pallas
from .ref import attention_ref


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "impl"),
)
def flash_attention_op(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    impl: str,
) -> jnp.ndarray:
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D) with Hq % Hkv == 0."""
    hq, hkv = q.shape[1], k.shape[1]
    if hkv != hq:
        reps = hq // hkv
        k = jnp.repeat(k, reps, axis=1)
        v = jnp.repeat(v, reps, axis=1)
    use_pallas, interpret = pallas_flags(impl)
    if use_pallas:
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, softcap=softcap,
            interpret=interpret,
        )
    return attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=causal, window=window, softcap=softcap,
    ).astype(q.dtype)
