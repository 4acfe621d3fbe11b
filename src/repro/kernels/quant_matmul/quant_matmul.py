"""Pallas TPU kernels: bit-width-dispatched fused dequant GEMM (CGMQ
serving path).

Weights exported by CGMQ (``quant.QuantizedTensor``) are stored as integer
codes with per-output-channel affine terms ``w = codes * scale + bias`` —
int8 words for the 8-bit storage class, bit-PACKED uint8 words for the 2/4-
bit classes (``quant.pack``: ``8 // bits`` codes per byte along K). Serving
wants ``y = x @ w`` without materializing the fp16/fp32 weight in HBM — the
Marlin/AWQ idiom (taxonomy B.12) adapted to the MXU:

    y[m, n] = scale[n] * (x @ codes)[m, n] + bias[n] * rowsum(x)[m]

The first term comes from MXU matmuls over tiles resident in VMEM; the
rank-1 bias term reuses ``rowsum(x)``, a single cheap VPU reduction over the
activations, which the wrapper (ops.py) computes once and feeds in as a
fourth operand. Both terms are applied in the epilogue on the final K step,
while the fp32 output tile is still in VMEM — the full affine dequant costs
zero extra passes over the (M, N) output in HBM.

The PACKED variant additionally unpacks the sub-byte codes in-register
(shift/mask on the int32-widened tile, interleave, ONE dot) before the same
epilogue — the weight bytes streamed from HBM are ``K * bits / 8`` per
column, i.e. 16x fewer than fp32 at 2 bits. Decode is weight-bandwidth-
bound, so roofline decode time drops proportionally to the certified
bit-width, not to a uniform int8 floor.

Tiling: grid (M/bm, N/bn, K/bk); accumulation in the fp32 output tile across
the K grid dimension (output revisiting), 128-aligned tiles for the MXU
picked by the shared ``layout`` helper. For the packed kernels the K block
is counted in UNPACKED columns (``bk`` is a whole number of packed rows),
so all kernels share one grid/masking scheme, and the in-register sub-byte
decode is ``layout.unpack_tile`` — repeat + shift/mask, no sublane
interleave.

Kernel contract (DESIGN.md §8/§11):
    x:      (M, K)  fp32/bf16 activations
    codes:  (K, N) int8 centered codes, or (ceil(K/per), N) uint8 packed
    scale:  (N,)    fp32 per-output-channel scale
    bias:   (N,)    fp32 per-output-channel offset (asymmetric / unsigned
                    grids; exactly zero only for symmetric signed grids)
    rowsum: (M,)    fp32 ``sum_k x[m, k]``
    (the wrappers hand the vectors to Mosaic as (1, N) rows and an (M, 1)
    column, so every operand block is 2-D and lane-dense)
    out:    (M, N)  fp32 ``x @ (codes * scale + bias)``, exact in fp32

The INTEGER variants (`int_matmul_pallas` / `int_matmul_packed_pallas`,
DESIGN.md §16) take int8 activation CODES instead of float activations and
accumulate on the MXU in **int32** (an int32 VMEM scratch tile persists
across the sequential K grid steps). The affine epilogue is the same rank-1
structure with the activation's per-tensor affine folded in: with
``x = qx*sx + bx`` and ``w[k, n] = codes[k, n]*scale[n] + bias[n]``,

    y[m, n] = (sx*scale[n]) * acc[m, n]            # int32 MXU accumulator
            + (sx*bias[n])  * rowsum(qx)[m]        # rank-1, like the fp path
            + bx * (scale[n]*colsum(codes)[n] + K*bias[n])   # constant (N,)

so the wrapper passes ``eff_scale = sx*scale``, ``eff_bias = sx*bias``,
integer ``rowsum(qx)`` and the precomputed ``const`` vector (``colsum`` is
exported once with the weights — recomputing it per decode tick would cost
a second GEMM-sized reduction).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import gemm_blocks, packed_blocks, unpack_tile


# Per-channel vectors ride as lane-dense 2-D blocks: (1, N) rows for the
# per-output-channel terms and an (M, 1) column for rowsum. Mosaic refuses
# 1-D (bn,) blocks of an f32[N] operand whose XLA tiling is T(1024).
def _row_spec(bn: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))


def _col_spec(bm: int) -> pl.BlockSpec:
    return pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0))


def _k_rows(shape, bk: int) -> jnp.ndarray:
    """Global K index of each row of a (bk, bn) weight tile at this step."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) + pl.program_id(2) * bk


def _row(v: jnp.ndarray) -> jnp.ndarray:
    return v.reshape(1, -1)


def _col(v: jnp.ndarray) -> jnp.ndarray:
    return v.reshape(-1, 1)


def _kernel(x_ref, c_ref, s_ref, b_ref, r_ref, o_ref, *, k_steps: int,
            k_total: int, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)           # (bm, bk)
    codes = c_ref[...].astype(jnp.float32)       # (bk, bn)
    if k_total % bk:
        # Ragged K: the final block reads past K; zero the out-of-bounds
        # tail so it contributes nothing. (Ragged M/N only pollute cropped
        # output padding; ragged K would corrupt real accumulations.)
        # 2-D iota: Pallas-TPU rejects 1-D jnp.arange at lowering time.
        k0 = pl.program_id(2) * bk
        kx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + k0
        kc = jax.lax.broadcasted_iota(jnp.int32, codes.shape, 0) + k0
        x = jnp.where(kx < k_total, x, 0.0)
        codes = jnp.where(kc < k_total, codes, 0.0)
    o_ref[...] += jax.lax.dot(x, codes, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        # Affine dequant on the resident output tile:
        #   y = scale * (x @ codes) + bias * rowsum(x)
        o_ref[...] = (
            o_ref[...] * s_ref[...] + r_ref[...] * b_ref[...]
        )


def quant_matmul_pallas(
    x: jnp.ndarray,
    codes: jnp.ndarray,
    scale: jnp.ndarray,
    bias: jnp.ndarray,
    rowsum: jnp.ndarray,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """x: (M, K); codes: (K, N) int8; scale/bias: (N,); rowsum: (M,).

    Returns (M, N) fp32 ``x @ (codes * scale + bias)`` — the complete affine
    epilogue runs inside the kernel (see module docstring for the contract).
    """
    m, k = x.shape
    _, n = codes.shape
    bm, bn, bk = gemm_blocks(m, n, k, block_m=block_m, block_n=block_n,
                             block_k=block_k)
    k_steps = pl.cdiv(k, bk)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), k_steps)
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, k_total=k, bk=bk),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            _row_spec(bn),
            _row_spec(bn),
            _col_spec(bm),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        interpret=interpret,
    )(x, codes, _row(scale), _row(bias), _col(rowsum))


# ---------------------------------------------------------------------------
# Packed sub-byte variant: fused unpack + dequant GEMM
# ---------------------------------------------------------------------------


def _packed_kernel(x_ref, p_ref, s_ref, b_ref, r_ref, o_ref, *, bits: int,
                   k_steps: int, k_total: int, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)                # (bm, bk)
    if k_steps * bk != k_total:
        # Mask x columns past K: pack-padding words and ragged-K block
        # tails then multiply a zeroed activation, so garbage codes (and a
        # non-finite out-of-bounds activation) contribute nothing.
        k0 = pl.program_id(2) * bk
        kx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + k0
        x = jnp.where(kx < k_total, x, 0.0)
    p = p_ref[...].astype(jnp.int32)                  # (bk // per, bn)
    codes = unpack_tile(p, bits).astype(jnp.float32)  # (bk, bn)
    o_ref[...] += jax.lax.dot(x, codes, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        o_ref[...] = (
            o_ref[...] * s_ref[...] + r_ref[...] * b_ref[...]
        )


def quant_matmul_packed_pallas(
    x: jnp.ndarray,
    packed: jnp.ndarray,
    scale: jnp.ndarray,
    bias: jnp.ndarray,
    rowsum: jnp.ndarray,
    *,
    bits: int,
    k: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """x: (M, K); packed: (ceil(K/per), N) uint8 sub-byte codes.

    Returns (M, N) fp32 ``x @ (unpack(packed) * scale + bias)`` with the
    unpack fused into the K loop (see module docstring). ``bits`` in {2, 4};
    ``k`` is the logical (unpacked) fan-in.
    """
    assert bits in (2, 4), bits
    per = 8 // bits
    m = x.shape[0]
    kp, n = packed.shape
    # K block in unpacked columns, forced to a whole number of packed rows.
    bm, bn, bkp, bk = packed_blocks(m, n, kp, per, block_m=block_m,
                                    block_n=block_n, block_k=block_k)
    k_steps = pl.cdiv(kp, bkp)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), k_steps)
    return pl.pallas_call(
        functools.partial(_packed_kernel, bits=bits, k_steps=k_steps,
                          k_total=k, bk=bk),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkp, bn), lambda i, j, kk: (kk, j)),
            _row_spec(bn),
            _row_spec(bn),
            _col_spec(bm),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        interpret=interpret,
    )(x, packed, _row(scale), _row(bias), _col(rowsum))


# ---------------------------------------------------------------------------
# Integer variants: int8 x int8 GEMM with int32 MXU accumulation (§16)
# ---------------------------------------------------------------------------


def _int_kernel(x_ref, c_ref, s_ref, b_ref, r_ref, cst_ref, o_ref, acc_ref,
                *, k_steps: int, k_total: int, bk: int):
    # acc_ref: int32 VMEM scratch — TPU grids execute sequentially per core,
    # so the accumulator persists across the K grid steps of one (i, j) tile.
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                    # (bm, bk) int8 codes
    codes = c_ref[...]                                # (bk, bn) int8 codes
    if k_total % bk:
        # Ragged K: zero the weight rows past K; a zeroed int8 operand makes
        # the out-of-bounds products exact zeros in the int32 accumulator
        # (integer garbage in the activation tail times 0 is 0). Masking
        # the (bk, bn) weight tile, not the (bm, bk) activation tile, keeps
        # the select off int8 tiles shorter than a packed sublane group,
        # which Mosaic cannot broadcast into at decode M.
        codes = jnp.where(_k_rows(codes.shape, bk) < k_total, codes,
                          jnp.zeros_like(codes))
    acc_ref[...] += jax.lax.dot(x, codes, preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        # One cast of the int32 accumulator, then the zero-point-corrected
        # affine: y = eff_scale*acc + eff_bias*rowsum(qx) + const.
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * s_ref[...]
            + r_ref[...] * b_ref[...] + cst_ref[...]
        )


def int_matmul_pallas(
    qx: jnp.ndarray,
    codes: jnp.ndarray,
    eff_scale: jnp.ndarray,
    eff_bias: jnp.ndarray,
    rowsum: jnp.ndarray,
    const: jnp.ndarray,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """qx: (M, K) int8 activation codes; codes: (K, N) int8 weight codes.

    Returns (M, N) fp32
    ``eff_scale * (qx @ codes) + eff_bias * rowsum + const`` with the GEMM
    accumulated in int32 (see module docstring for how the wrapper folds
    the two affine grids into these vectors). ``rowsum``: (M,) fp32
    ``sum_k qx[m, k]``; ``const``: (N,) fp32.
    """
    m, k = qx.shape
    _, n = codes.shape
    bm, bn, bk = gemm_blocks(m, n, k, block_m=block_m, block_n=block_n,
                             block_k=block_k)
    k_steps = pl.cdiv(k, bk)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), k_steps)
    return pl.pallas_call(
        functools.partial(_int_kernel, k_steps=k_steps, k_total=k, bk=bk),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            _row_spec(bn),
            _row_spec(bn),
            _col_spec(bm),
            _row_spec(bn),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(qx, codes, _row(eff_scale), _row(eff_bias), _col(rowsum),
      _row(const))


def _int_packed_kernel(x_ref, p_ref, s_ref, b_ref, r_ref, cst_ref, o_ref,
                       acc_ref, *, bits: int, k_steps: int, k_total: int,
                       bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                    # (bm, bk) int8 codes
    p = p_ref[...].astype(jnp.int32)                  # (bk // per, bn)
    codes = unpack_tile(p, bits)                      # (bk, bn) int32
    if k_steps * bk != k_total:
        # Zero the unpacked weight rows past K (pack-padding codes and
        # ragged tails), in int32 before the int8 cast — the same scheme
        # as the unpacked integer kernel.
        codes = jnp.where(_k_rows(codes.shape, bk) < k_total, codes, 0)
    codes = codes.astype(jnp.int8)
    acc_ref[...] += jax.lax.dot(x, codes, preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * s_ref[...]
            + r_ref[...] * b_ref[...] + cst_ref[...]
        )


def int_matmul_packed_pallas(
    qx: jnp.ndarray,
    packed: jnp.ndarray,
    eff_scale: jnp.ndarray,
    eff_bias: jnp.ndarray,
    rowsum: jnp.ndarray,
    const: jnp.ndarray,
    *,
    bits: int,
    k: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """Packed twin of ``int_matmul_pallas``: 2/4-bit weight codes are
    decoded to int8 in-register (``layout.unpack_tile``) and fed to the
    same int32-accumulating dot — sub-byte weight bandwidth AND integer
    MACs in one kernel."""
    assert bits in (2, 4), bits
    per = 8 // bits
    m = qx.shape[0]
    kp, n = packed.shape
    bm, bn, bkp, bk = packed_blocks(m, n, kp, per, block_m=block_m,
                                    block_n=block_n, block_k=block_k)
    k_steps = pl.cdiv(kp, bkp)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), k_steps)
    return pl.pallas_call(
        functools.partial(_int_packed_kernel, bits=bits, k_steps=k_steps,
                          k_total=k, bk=bk),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkp, bn), lambda i, j, kk: (kk, j)),
            _row_spec(bn),
            _row_spec(bn),
            _col_spec(bm),
            _row_spec(bn),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(qx, packed, _row(eff_scale), _row(eff_bias), _col(rowsum),
      _row(const))
