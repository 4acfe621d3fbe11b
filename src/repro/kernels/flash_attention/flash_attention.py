"""Pallas TPU kernel: blockwise causal flash attention (online softmax).

FlashAttention (arXiv:2205.14135) re-thought for the TPU memory hierarchy:
Q/K/V tiles stream HBM->VMEM; the (block_q x block_k) score tile lives
entirely in VMEM/VREG; softmax statistics (running max m, denominator l) and
the output accumulator are VMEM scratch carried across the kv grid dimension.
MXU does both GEMMs; the causal structure prunes upper-triangular kv blocks
via ``pl.when`` (skipping ~half the FLOPs without dynamic shapes).

Grid: (batch*heads, q_blocks, kv_blocks) — kv innermost so scratch carries
are local; 128-aligned block sizes for the MXU.

Supports the model zoo's needs: causal, sliding-window (mixtral/gemma2
local layers), and gemma2's attention softcap.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_q: int, block_k: int, kv_steps: int,
            causal: bool, window: int | None, softcap: float | None,
            scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # causal block pruning: skip blocks entirely above the diagonal
    run = True
    if causal:
        run = (k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, q_start - (k_start + block_k - 1) < window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= rows >= cols
        if window is not None:
            mask &= (rows - cols) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)[:, None]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                    # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)           # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    causal: bool = True, window: int | None = None,
    softcap: float | None = None,
    block_q: int = 128, block_k: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """q/k/v: (B, H, S, D) -> (B, H, S, D). S % block sizes handled by cdiv."""
    b, h, s, d = q.shape
    bq, bk = min(block_q, s), min(block_k, s)
    kv_steps = pl.cdiv(s, bk)
    grid = (b * h, pl.cdiv(s, bq), kv_steps)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, s, d)
    vf = v.reshape(b * h, s, d)
    out = pl.pallas_call(
        functools.partial(
            _kernel, block_q=bq, block_k=bk, kv_steps=kv_steps,
            causal=causal, window=window, softcap=softcap, scale=d**-0.5,
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom l
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)
