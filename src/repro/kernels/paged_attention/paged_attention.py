"""Pallas TPU kernel: paged single-token decode attention (vLLM-style).

The decode-time half of the paged KV cache (DESIGN.md §10): each serving
slot's K/V lives in fixed-size token blocks scattered through a physical
pool, addressed by a per-slot block table. The kernel walks one slot's table
entries and runs an online-softmax accumulation over its blocks — the paged
analogue of FlashDecoding — without ever materializing the gathered
(B, L, KV, hd) K/V that the jnp oracle builds.

Layout and TPU mapping:

  * grid ``(B, max_blocks)`` with the block dimension innermost, so the
    softmax statistics (m, l) and the output accumulator stay resident in
    VMEM scratch across a slot's blocks — same carry discipline as the
    flash_attention kernel.
  * the block table and per-slot positions ride in as **scalar prefetch**
    (``PrefetchScalarGridSpec``): the K/V BlockSpec index_map reads
    ``table[b, j]`` to DMA exactly the physical block the slot's j-th
    logical block lives in. Unallocated entries (-1) clip to the reserved
    garbage block 0 and are masked out by the position test.
  * GQA: q arrives as (B, KV*G, hd); scores run as a KV-batched dot_general
    so every query group hits the MXU against its own KV head.
  * blocks wholly past the row's position (and, for sliding-window layers,
    wholly fallen out of the window) are pruned with ``pl.when`` before any
    compute.
  * long-context windows (DESIGN.md §17) add a third scalar-prefetch
    operand: the per-slot **first-live-block index** ``fl``. The K/V
    index_map routes every dead block (``j < fl[b]`` and not a pinned sink
    block) to the garbage block 0, so out-of-window blocks are never DMA'd
    at all — the window walk touches O(window/bs + sinks) blocks per slot
    regardless of prompt length, on all KV dtypes (the quantized scale
    operands share the same routed index_map). ``sinks`` (leading token
    count, block-aligned by the engine) re-admits the pinned prefix in both
    the block prune and the in-block mask: the §17 rule is
    ``kp <= p and (p - kp < window or kp < sinks)``.

Quantized pools (DESIGN.md §14) add a **fused dequant-on-block-load**: the
per-group fp16 scales ride in as two extra block-mapped operands whose
BlockSpec index_map reads the SAME ``table[b, j]`` entry as the code
blocks, so scale DMA is paged exactly like the codes; the affine is applied
in-register before the scores dot, and the online-softmax carry is
untouched. The group scales are broadcast to lanes by select, never by a
lane-splitting reshape, and int4 blocks are consumed as two nibble planes
(even and odd head elements) whose partial dots sum to the full score, so
the kernel never interleaves lanes.

The kernel runs natively on TPU (``interpret=False``) and in interpret
mode only in CPU tests (DESIGN.md §3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _f16_bits_to_f32(h):
    """fp16 bit patterns (int16) -> fp32 values, exact for normals, zero,
    inf and NaN (subnormals flush to zero; KV scales are floored at an
    fp16-normal value, so none occur). Mosaic has no f16 vector loads on
    v5e, so the scale pools enter the kernel bitcast to int16."""
    h = h.astype(jnp.int32) & 0xFFFF
    sign = (h & 0x8000) << 16
    exp = (h >> 10) & 0x1F
    mant = (h & 0x3FF) << 13
    bits = jnp.where(exp == 0x1F, 0x7F800000 | mant, ((exp + 112) << 23) | mant)
    bits = jnp.where(exp == 0, 0, bits)
    return jax.lax.bitcast_convert_type(sign | bits, jnp.float32)


def _expand_scale(scale, width: int, group_width: int):
    """(bs, KV, ng) int16-coded fp16 group scales -> (bs, KV, width)
    per-lane fp32 scales.

    Lane ``d`` takes group ``d // group_width``: a select per group over a
    lane broadcast of that group's 1-wide slice. The obvious
    ``(..., hd) -> (..., ng, group)`` reshape splits the lane axis, which
    Mosaic refuses (``infer-vector-layout: unsupported shape cast``).
    """
    s = _f16_bits_to_f32(scale)
    shape = s.shape[:-1] + (width,)
    group = jax.lax.broadcasted_iota(jnp.int32, shape, 2) // group_width
    out = jnp.broadcast_to(s[..., 0:1], shape)
    for gi in range(1, s.shape[-1]):
        out = jnp.where(group == gi, jnp.broadcast_to(s[..., gi:gi + 1], shape),
                        out)
    return out


def _dequant_planes(codes, scale, planes: int, group_size: int):
    """Fused dequant of one block's codes into ``planes`` fp32 lane planes.

    int8 (``planes == 1``): the codes times the lane-expanded scales.
    int4 (``planes == 2``): byte ``i`` holds head elements ``2i`` (low
    nibble) and ``2i+1`` (high nibble), both in group ``i // (gs/2)``; the
    two nibble planes are the even and odd head elements, each
    ``hd/2`` lanes wide, so no lane interleave is ever formed in-kernel.
    """
    width = codes.shape[-1]
    s = _expand_scale(scale, width, group_size // planes)
    if planes == 1:
        return [codes.astype(jnp.float32) * s]
    p = codes.astype(jnp.int32)
    return [((p & 0xF) - 8).astype(jnp.float32) * s,
            (((p >> 4) & 0xF) - 8).astype(jnp.float32) * s]


def _kernel(table_ref, pos_ref, fl_ref, q_ref, k_ref, v_ref, *rest,
            block_size: int, blocks: int,
            kv_heads: int, groups: int, window: int | None,
            sinks: int, softcap: float | None, scale: float,
            planes: int, group_size: int = 0):
    if group_size:  # quantized: two scale operands precede the output
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    p = pos_ref[b]
    start = j * block_size
    run = (start <= p) & (table_ref[b, j] >= 0)
    if window is not None:
        in_win = p - (start + block_size - 1) < window
        if sinks:
            in_win = jnp.logical_or(in_win, start < sinks)
        run = jnp.logical_and(run, in_win)
        # mirror the index_map's dead-block routing: j < fl[b] never ran DMA
        run = jnp.logical_and(
            run, jnp.logical_or(j >= fl_ref[b], start < sinks))

    @pl.when(run)
    def _compute():
        if group_size:
            # fused dequant in-register, one fp32 plane per lane group
            ks = _dequant_planes(k_ref[0], ks_ref[0], planes, group_size)
            vs = _dequant_planes(v_ref[0], vs_ref[0], planes, group_size)
        else:
            ks = [k_ref[0].astype(jnp.float32)]   # (bs, KV, hd)
            vs = [v_ref[0].astype(jnp.float32)]
        s = None
        for pi in range(planes):
            qr = q_ref[0, pi].astype(jnp.float32).reshape(kv_heads, groups, -1)
            # batched over the KV head axis: (KV, G, w) x (bs, KV, w)
            sp = jax.lax.dot_general(
                qr, ks[pi], (((2,), (2,)), ((0,), (1,))),
                preferred_element_type=jnp.float32)  # (KV, G, bs)
            s = sp if s is None else s + sp
        s = s * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        cols = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = cols <= p
        if window is not None:
            in_win = (p - cols) < window
            if sinks:
                in_win |= cols < sinks
            mask &= in_win
        s = jnp.where(mask, s, NEG_INF).reshape(kv_heads * groups, -1)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        pexp = jnp.exp(s - m_new)                  # (KV*G, bs)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(pexp, axis=1)[:, None]
        pr = pexp.reshape(kv_heads, groups, -1)
        for pi in range(planes):
            pv = jax.lax.dot_general(
                pr, vs[pi], (((2,), (0,)), ((0,), (1,))),
                preferred_element_type=jnp.float32)  # (KV, G, w)
            acc_scr[pi] = acc_scr[pi] * alpha + pv.reshape(acc_scr.shape[1:])
        m_scr[...] = m_new

    @pl.when(j == blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        for pi in range(planes):
            o_ref[0, pi] = (acc_scr[pi] / denom).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pool, v_pool, block_table, pos, *,
                           window: int | None = None,
                           sinks: int = 0,
                           softcap: float | None = None,
                           interpret: bool,
                           k_scale=None, v_scale=None):
    """q: (B, KV, G, hd); pools: (num_blocks, bs, KV, hd) float or
    (num_blocks, bs, KV, packed_head) codes + ``k_scale``/``v_scale``
    (num_blocks, bs, KV, num_groups) fp16 per-group scales;
    block_table: (B, max_blocks); pos: (B,). Returns (B, KV, G, hd).

    ``window``/``sinks`` (both static) enable the §17 block-sparse walk:
    the per-slot first-live-block index is derived from ``pos`` here and
    scalar-prefetched so dead blocks are never loaded (module docstring).

    int4 pools run as two lane planes (even / odd head elements): q is
    de-interleaved into the planes here and the output re-interleaved
    after the call, outside the kernel."""
    b, kvh, g, hd = q.shape
    bs = k_pool.shape[1]
    mb = block_table.shape[1]
    hdp = k_pool.shape[-1]
    quant = k_scale is not None
    ng = k_scale.shape[-1] if quant else 0
    group_size = hd // ng if quant else 0
    # int4 codes (packed uint8) split into even/odd lane planes
    planes = 2 if quant and k_pool.dtype == jnp.uint8 else 1
    if quant:
        assert ng * group_size == hd and group_size % planes == 0, (hd, ng)
    w = hd // planes
    # (B, P, KV*G, w): plane p holds head elements d with d % P == p
    qp = q.reshape(b, kvh * g, w, planes).transpose(0, 3, 1, 2)
    sink_blocks = -(-sinks // bs)
    if window is not None:
        # first block the sliding window still reaches; sink blocks pinned
        fl = jnp.maximum((pos - window + 1) // bs,
                         sink_blocks).astype(jnp.int32)
    else:
        fl = jnp.zeros_like(pos, dtype=jnp.int32)

    def table_map(bi, j, tbl, ps, fl):
        live = (j >= fl[bi]) | (j < sink_blocks)
        return (jnp.where(live, jnp.maximum(tbl[bi, j], 0), 0), 0, 0, 0)

    def row_map(bi, j, tbl, ps, fl):
        return (bi, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, planes, kvh * g, w), row_map),
        pl.BlockSpec((1, bs, kvh, hdp), table_map),
        pl.BlockSpec((1, bs, kvh, hdp), table_map),
    ]
    operands = [qp, k_pool, v_pool]
    if quant:
        # scale blocks page through the SAME table entry as the codes
        in_specs += [pl.BlockSpec((1, bs, kvh, ng), table_map)] * 2
        operands += [jax.lax.bitcast_convert_type(a, jnp.int16)
                     for a in (k_scale, v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, planes, kvh * g, w), row_map),
        scratch_shapes=[
            pltpu.VMEM((kvh * g, 1), jnp.float32),   # running max m
            pltpu.VMEM((kvh * g, 1), jnp.float32),   # running denom l
            pltpu.VMEM((planes, kvh * g, w), jnp.float32),  # output acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, block_size=bs, blocks=mb, kv_heads=kvh, groups=g,
            window=window, sinks=sinks, softcap=softcap, scale=hd ** -0.5,
            planes=planes, group_size=group_size,
        ),
        out_shape=jax.ShapeDtypeStruct((b, planes, kvh * g, w), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(block_table, pos, fl, *operands)
    return out.transpose(0, 2, 3, 1).reshape(b, kvh, g, hd)
