"""Pallas TPU kernel: fused gated fake quantization (CGMQ hot path).

The paper's Eq. 3 residual decomposition naively costs 5 elementwise
quantization passes (b = 2,4,8,16,32) per tensor per training step — five
HBM round-trips of VPU work. Exploiting the telescoping identity
``x_q = Q(x, T(g))`` (property-tested against the residual form in
tests/test_gates.py), this kernel fuses the gate->bit-width map, range clip,
scale, round and pass-through select into ONE HBM->VMEM->HBM pass.

Tiling: 2D grid over (row, col) blocks; (block_m x block_n) fp32 tiles in
VMEM (default 256x512 = 512 KiB in + 512 KiB out, well under the ~16 MiB
v5e VMEM); gate/beta are per-column (bn,) slices. All arithmetic is VPU
elementwise — the kernel is HBM-bandwidth bound by construction, which is
exactly why the fusion matters (5x fewer bytes moved than the unfused chain).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# T(g) thresholds (paper Eq. 4), encoded branchlessly in-kernel.
_EDGES = (0.0, 1.0, 2.0, 3.0, 4.0)
_BITS = (0.0, 2.0, 4.0, 8.0, 16.0, 32.0)
GATE_MIN = 0.5


def _kernel(x_ref, g_ref, b_ref, o_ref, *, signed: bool):
    x = x_ref[...]
    g = jnp.maximum(g_ref[...], GATE_MIN)  # no pruning (paper)
    beta = jnp.maximum(b_ref[...], 1e-8)

    # bits = T(g), branchless
    bits = jnp.full_like(g, _BITS[0])
    for edge, b in zip(_EDGES, _BITS[1:]):
        bits = jnp.where(g > edge, b, bits)

    alpha = -beta if signed else jnp.zeros_like(beta)
    span = beta - alpha
    b_eff = jnp.clip(bits, 2.0, 31.0)
    n = jnp.exp2(b_eff) - 1.0
    s = span / n
    xc = jnp.clip(x, alpha[None, :], beta[None, :])
    q = alpha[None, :] + s[None, :] * jnp.round((xc - alpha[None, :]) / s[None, :])
    o_ref[...] = jnp.where(bits[None, :] >= 32.0, x, q)


def fake_quant_pallas(
    x: jnp.ndarray,
    gate: jnp.ndarray,
    beta: jnp.ndarray,
    signed: bool,
    *,
    block_m: int = 256,
    block_n: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """x: (M, N) fp32; gate/beta: (N,). Returns fake-quantized x.

    ``interpret`` runs the kernel body under the Pallas interpreter (CPU
    tests only); on TPU it is always False.
    """
    m, n = x.shape
    bm, bn = min(block_m, m), min(block_n, n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    return pl.pallas_call(
        functools.partial(_kernel, signed=signed),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bn,), lambda i, j: (j,)),
            pl.BlockSpec((bn,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
    )(x, gate, beta)
