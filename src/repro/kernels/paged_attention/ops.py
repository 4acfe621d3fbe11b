"""Jit'd dispatch wrapper for paged decode attention (ref / Pallas)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import pallas_flags

from .paged_attention import paged_attention_pallas
from .ref import paged_attention_ref


@functools.partial(
    jax.jit,
    static_argnames=("window", "sinks", "softcap", "impl"))
def paged_attention_op(q, k_pool, v_pool, block_table, pos, *,
                       window: int | None = None,
                       sinks: int = 0,
                       softcap: float | None = None,
                       impl: str,
                       k_scale=None, v_scale=None):
    """q: (B, KV, G, hd); pools: (num_blocks, bs, KV, hd) float — or integer
    codes with ``k_scale``/``v_scale`` (num_blocks, bs, KV, num_groups) fp16
    group scales for the fused-dequant path (DESIGN.md §14);
    block_table: (B, max_blocks) int32; pos: (B,) int32 → (B, KV, G, hd) f32.

    ``k_scale``/``v_scale`` are traced operands: their presence changes the
    argument pytree, so float and quantized pools get separate jit
    specializations without a static flag.
    """
    pos = jnp.asarray(pos, jnp.int32)
    use_pallas, interpret = pallas_flags(impl)
    if use_pallas:
        return paged_attention_pallas(
            q, k_pool, v_pool, block_table, pos,
            window=window, sinks=sinks, softcap=softcap, interpret=interpret,
            k_scale=k_scale, v_scale=v_scale)
    return paged_attention_ref(
        q, k_pool, v_pool, block_table, pos, window=window, sinks=sinks,
        softcap=softcap, k_scale=k_scale, v_scale=v_scale)
