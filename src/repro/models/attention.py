"""Attention: GQA with global / sliding-window kinds, softcap, qk-norm, M-RoPE.

Two code paths (DESIGN.md §4):

  * train/prefill — dense masked attention with KV heads materialized to the
    full head count (keeps the head axis uniformly shardable over `model`).
  * decode — grouped-query attention against a KV cache; the cache sequence
    axis is sharded (FlashDecoding-style split-KV falls out of GSPMD's
    partial-reduction handling), heads stay replicated.

Local attention uses a ring-buffer cache of ``window`` slots at decode time so
sliding-window archs (mixtral, gemma2 local layers, recurrentgemma) stay O(w)
memory at 500k context.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.sites import QuantContext
from repro.quant import kv as kv_codec

from .layers import COMPUTE_DTYPE, apply_mrope, apply_rope, qmatmul, rms_norm, softcap

NEG_INF = -1e30


def init_attn(key, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    k = jax.random.split(key, 4)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape) / jnp.sqrt(fan_in)).astype(jnp.float32)

    p = {
        "wq": w(k[0], (d, h * hd), d),
        "wk": w(k[1], (d, kv * hd), d),
        "wv": w(k[2], (d, kv * hd), d),
        "wo": w(k[3], (h * hd, d), h * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), jnp.float32)
        p["bk"] = jnp.zeros((kv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((kv * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def _project_qkv(qc: QuantContext, p, x, cfg: ModelConfig, positions, mrope_pos):
    """Shared q/k/v projection + norm + rope. x: (B, S, d)."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = qmatmul(qc, "attn_q", x, p["wq"])
    k = qmatmul(qc, "attn_k", x, p["wk"])
    v = qmatmul(qc, "attn_v", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.mrope_sections is not None:
        q = apply_mrope(q, mrope_pos, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mrope_pos, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _repeat_kv(t, groups: int):
    """(B, S, KV, hd) -> (B, S, KV*groups, hd)."""
    b, s, kv, hd = t.shape
    t = jnp.broadcast_to(t[:, :, :, None, :], (b, s, kv, groups, hd))
    return t.reshape(b, s, kv * groups, hd)


def _resolve_window(window, kind: str, cfg: ModelConfig):
    """Resolve the engine's static ``(window, sink_tokens)`` mask tuple
    (DESIGN.md §17) for one layer. Local layers tighten their architectural
    window and drop sinks — the ring layout physically overwrites positions
    older than ``cfg.window``, so a sink there would be unservable; the
    sink contract covers full-history layers only. Global layers take the
    tuple verbatim. Returns ``(effective_window | None, sink_tokens)``;
    ``None`` means causal-only. The tuple (not a WindowSpec) keeps
    ``repro.models`` free of serving imports."""
    if window is None:
        return (cfg.window if kind == "local" else None, 0)
    w, sinks = window
    if kind == "local":
        return (min(cfg.window, w), 0)
    return (w, sinks)


def attention_train(
    qc: QuantContext,
    p,
    x,
    cfg: ModelConfig,
    kind: str,
    *,
    positions=None,
    mrope_pos=None,
    plan=None,
    window=None,
):
    """Causal (optionally sliding-window) attention. Returns (y, (k, v)).

    ``window``: optional engine ``(window, sink_tokens)`` tuple (§17) layered
    on top of the architectural mask via ``_resolve_window``."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(qc, p, x, cfg, positions, mrope_pos)
    groups = cfg.n_heads // cfg.n_kv_heads
    k_r, v_r = _repeat_kv(k, groups), _repeat_kv(v, groups)
    if plan is not None:
        q, k_r, v_r = plan.shard_attn_qkv(q, k_r, v_r)

    scale = cfg.head_dim ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(COMPUTE_DTYPE), k_r.astype(COMPUTE_DTYPE),
        preferred_element_type=jnp.float32,
    ) * scale
    logits = softcap(logits, cfg.attn_softcap)
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    mask = qi >= ki
    eff, sinks = _resolve_window(window, kind, cfg)
    if eff is not None:
        in_win = (qi - ki) < eff
        if sinks:
            in_win |= ki < sinks
        mask &= in_win
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(COMPUTE_DTYPE)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_r,
                     preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    # NOTE: the QK^T / PV products are activation-activation matmuls with no
    # weight operand — not BOP-constrained sites (DESIGN.md §3).
    y = qmatmul(qc, "attn_o", out, p["wo"])
    y = qc.act("attn_o", y)
    return y, (k, v)


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------


def init_attn_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                    dtype=jnp.bfloat16,
                    spec: kv_codec.KVQuantSpec | None = None):
    """Ring/contiguous decode cache; with ``spec`` set, quantized storage
    (packed codes + fp16 group scales — same flat layout as the paged pool,
    DESIGN.md §14)."""
    slots = min(cfg.window, max_seq) if kind == "local" else max_seq
    if spec is not None:
        assert spec.head_dim == cfg.head_dim, (spec, cfg.head_dim)
        cshape = (batch, slots, cfg.n_kv_heads, spec.packed_head)
        sshape = (batch, slots, cfg.n_kv_heads, spec.num_groups)
        return {"k": jnp.zeros(cshape, spec.code_dtype),
                "v": jnp.zeros(cshape, spec.code_dtype),
                "k_scale": jnp.zeros(sshape, spec.scale_dtype),
                "v_scale": jnp.zeros(sshape, spec.scale_dtype)}
    shape = (batch, slots, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def attention_decode(
    qc: QuantContext,
    p,
    x,
    cache: dict,
    pos,
    cfg: ModelConfig,
    kind: str,
    *,
    mrope_pos=None,
    plan=None,
    window=None,
):
    """One-token decode. x: (B, 1, d); pos: (B,) int32 per-row positions
    (tokens so far) — scalars broadcast, so single-sequence callers can pass
    a plain int. Rows decode independently: each row's K/V lands at its own
    position and its mask admits only its own history, which is what lets a
    continuous-batching engine keep slots at unrelated positions in one
    batched step.

    Local layers treat the cache as a ring buffer of ``window`` slots.
    ``window``: optional engine ``(window, sink_tokens)`` tuple (§17) — the
    contiguous global cache masks to it by absolute position (rows past the
    window stay resident here; only the paged layout evicts them).
    Returns (y, new_cache).
    """
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None]
    mp = None
    if cfg.mrope_sections is not None:
        mp = (
            mrope_pos
            if mrope_pos is not None
            else jnp.broadcast_to(positions[None], (3, b, 1))
        )
    q, k, v = _project_qkv(qc, p, x, cfg, positions, mp)

    slots = cache["k"].shape[1]
    slot = pos % slots if kind == "local" else jnp.minimum(pos, slots - 1)
    rows = jnp.arange(b)
    spec = kv_codec.spec_from_cache(cache, cfg.head_dim)
    if spec is not None:
        # write-site quantization (§14): floats never land in the cache
        kc, ksc = kv_codec.quantize_kv(k[:, 0], spec)
        vc, vsc = kv_codec.quantize_kv(v[:, 0], spec)
        new_cache = {
            "k": cache["k"].at[rows, slot].set(kc),
            "v": cache["v"].at[rows, slot].set(vc),
            "k_scale": cache["k_scale"].at[rows, slot].set(ksc),
            "v_scale": cache["v_scale"].at[rows, slot].set(vsc),
        }
        ck = kv_codec.dequantize_kv(new_cache["k"], new_cache["k_scale"], spec)
        cv = kv_codec.dequantize_kv(new_cache["v"], new_cache["v_scale"], spec)
        if plan is not None:
            ck = plan.shard_cache(ck)
            cv = plan.shard_cache(cv)
    else:
        ck = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))
        if plan is not None:
            ck = plan.shard_cache(ck)
            cv = plan.shard_cache(cv)
        new_cache = {"k": ck, "v": cv}

    groups = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, cfg.n_kv_heads, groups, cfg.head_dim)
    scale = cfg.head_dim ** -0.5
    logits = jnp.einsum(
        "bkgd,bskd->bkgs", qg.astype(COMPUTE_DTYPE), ck.astype(COMPUTE_DTYPE),
        preferred_element_type=jnp.float32,
    ) * scale
    logits = softcap(logits, cfg.attn_softcap)
    sids = jnp.arange(slots)[None, :]
    posb = pos[:, None]
    eff, sinks = _resolve_window(window, kind, cfg)
    if kind == "local":
        # ring buffer: slot s holds absolute position ap with ap % slots == s
        # and ap <= pos; valid iff pos - ap < window and ap <= pos.
        ap = posb - ((posb - sids) % slots)
        valid = (ap >= 0) & (ap <= posb) & ((posb - ap) < eff)
    else:
        valid = sids <= posb
        if eff is not None:
            in_win = (posb - sids) < eff
            if sinks:
                in_win |= sids < sinks
            valid &= in_win
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(COMPUTE_DTYPE)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, cv,
                     preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    y = qmatmul(qc, "attn_o", out, p["wo"])
    y = qc.act("attn_o", y)
    return y, new_cache


def attention_decode_paged(
    qc: QuantContext,
    p,
    x,
    pool: dict,
    block_table,
    pos,
    cfg: ModelConfig,
    kind: str,
    *,
    mrope_pos=None,
    plan=None,
    write_mask=None,
    window=None,
):
    """One-token decode through a paged KV pool (DESIGN.md §10).

    ``pool``: {"k", "v"} of (num_blocks, bs, KV, hd) — one layer's physical
    block pool; ``block_table``: (B, max_blocks) int32 mapping each row's
    logical blocks to physical ids (-1 = unallocated); ``pos``: (B,) int32.

    The new K/V lands at physical block ``table[b, pos // bs]`` offset
    ``pos % bs``; rows outside ``write_mask`` (idle slots, teacher steps for
    another slot) are routed to the reserved garbage block 0 so they can
    never corrupt pool blocks they don't own. The attend then gathers
    through the table (``kernels/paged_attention``: jnp oracle, or the
    Pallas kernel per ``qc.matmul_impl``). Local layers keep full history in
    blocks and mask to the window — the ring buffer's O(window) residency is
    traded for block-granular allocation.

    ``window``: optional engine ``(window, sink_tokens)`` tuple (§17),
    resolved per layer kind and forwarded as static args so the kernel's
    first-live-block walk skips dead blocks entirely.

    Returns (y, new_pool).
    """
    from repro.kernels.paged_attention.ops import paged_attention_op

    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    mp = None
    if cfg.mrope_sections is not None:
        mp = (
            mrope_pos
            if mrope_pos is not None
            else jnp.broadcast_to(pos[None, :, None], (3, b, 1))
        )
    q, k, v = _project_qkv(qc, p, x, cfg, pos[:, None], mp)

    bs = pool["k"].shape[1]
    mb = block_table.shape[1]
    lp = jnp.clip(pos, 0, mb * bs - 1)
    rows = jnp.arange(b)
    phys = block_table[rows, lp // bs]
    ok = phys >= 0
    if write_mask is not None:
        ok &= write_mask.astype(bool)
    tgt = jnp.where(ok, phys, 0)
    off = lp % bs
    spec = kv_codec.spec_from_cache(pool, cfg.head_dim)
    if spec is not None:
        # write-site quantization (§14): codes + group scales land together
        kc, ksc = kv_codec.quantize_kv(k[:, 0], spec)
        vc, vsc = kv_codec.quantize_kv(v[:, 0], spec)
        new_pool = {
            "k": pool["k"].at[tgt, off].set(kc),
            "v": pool["v"].at[tgt, off].set(vc),
            "k_scale": pool["k_scale"].at[tgt, off].set(ksc),
            "v_scale": pool["v_scale"].at[tgt, off].set(vsc),
        }
        scales = {"k_scale": new_pool["k_scale"],
                  "v_scale": new_pool["v_scale"]}
    else:
        new_pool = {
            "k": pool["k"].at[tgt, off].set(k[:, 0].astype(pool["k"].dtype)),
            "v": pool["v"].at[tgt, off].set(v[:, 0].astype(pool["v"].dtype)),
        }
        scales = {"k_scale": None, "v_scale": None}
    if plan is not None:
        new_pool = {name: plan.shard_pool(a) for name, a in new_pool.items()}
        scales = {"k_scale": new_pool.get("k_scale"),
                  "v_scale": new_pool.get("v_scale")}

    groups = cfg.n_heads // cfg.n_kv_heads
    qg = q[:, 0].reshape(b, cfg.n_kv_heads, groups, cfg.head_dim)
    eff, sinks = _resolve_window(window, kind, cfg)
    out = paged_attention_op(
        qg.astype(COMPUTE_DTYPE), new_pool["k"], new_pool["v"],
        block_table, pos,
        window=eff, sinks=sinks,
        softcap=cfg.attn_softcap,
        impl=qc.matmul_impl, **scales,
    )
    out = out.astype(COMPUTE_DTYPE).reshape(b, 1, cfg.n_heads * cfg.head_dim)
    y = qmatmul(qc, "attn_o", out, p["wo"])
    y = qc.act("attn_o", y)
    return y, new_pool


def attention_prefill_chunk(
    qc: QuantContext,
    p,
    x,
    cache: dict,
    pos0,
    clen,
    cfg: ModelConfig,
    kind: str,
    *,
    slot=0,
    block_table=None,
    positions=None,
    mrope_pos=None,
    plan=None,
    window=None,
):
    """Chunk-resumable prefill attention for ONE serving slot (DESIGN.md §15).

    ``x``: (1, C, d) hidden states for the prompt positions
    ``pos0 .. pos0+clen-1`` (lanes past ``clen`` are padding). The chunk's
    K/V is written into the slot's cache AT ITS OFFSET first, then the
    queries attend THROUGH the cache — the multi-token generalization of
    ``attention_decode``'s write-then-attend. Every query position therefore
    reads identical cache content over a static key axis no matter where the
    chunk boundaries fall, which is what makes chunked streams bit-identical
    across any split (quantized KV included: each position's codes are a
    pure function of that position's K/V).

    Ring caches: ``cache`` is one layer's (slots, S, KV, ·) entry. Local
    layers require ``clen <= ring`` (the engine clamps chunk sizes to the
    window) or earlier in-chunk queries would lose their ring slots to later
    writes. Paged: ``block_table`` is the slot's (max_blocks,) physical row;
    unallocated/padding lanes route to the reserved garbage block 0.

    ``pos0``/``clen``/``slot`` may be traced scalars. ``window``: optional
    engine ``(window, sink_tokens)`` tuple (§17). On the paged path a
    binding window switches the key gather from the whole table to a
    bounded O(sinks + window + C) two-segment gather — the sink prefix
    blocks plus the blocks the sliding window can reach from this chunk —
    so long-context chunked prefill never materializes dead blocks. When
    the window cannot bind (small tables, or ``window=None``) the gather
    stays whole-table so logits remain bit-identical to the unwindowed
    path. Returns (y (1, C, d), new_cache_entry).
    """
    b, c, _ = x.shape
    pos0 = jnp.asarray(pos0, jnp.int32)
    clen = jnp.asarray(clen, jnp.int32)
    lanes = jnp.arange(c)
    if positions is None:
        positions = (pos0 + lanes)[None, :]
    mp = None
    if cfg.mrope_sections is not None:
        mp = (
            mrope_pos
            if mrope_pos is not None
            else jnp.broadcast_to(positions[None], (3, b, c))
        )
    q, k, v = _project_qkv(qc, p, x, cfg, positions, mp)
    kc, vc = k[0], v[0]  # (C, KV, hd)
    qpos = positions[0]  # (C,) absolute query positions (garbage past clen)
    eff, sinks = _resolve_window(window, kind, cfg)
    spec = kv_codec.spec_from_cache(cache, cfg.head_dim)
    if spec is not None:
        # write-site quantization (§14): the whole chunk quantizes before it
        # lands, so cache content matches the decode write path per position
        kq, ksc = kv_codec.quantize_kv(kc, spec)
        vq, vsc = kv_codec.quantize_kv(vc, spec)
        entries = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
    else:
        entries = {"k": kc, "v": vc}

    if block_table is None:
        ring = cache["k"].shape[1]
        if kind == "local":
            # Ring buffers hold only the last `ring` positions, so writing
            # the chunk first would evict positions the chunk's EARLIER
            # queries still need. Instead: per-query gather over a canonical
            # key axis of exactly `window` lanes ordered by absolute
            # position, sourcing each position from the chunk (storage
            # dtype round-tripped, so its value matches what a later chunk
            # would read back) or from the pre-chunk ring. The reduction
            # layout is position-indexed and static, hence bit-identical
            # under any chunk split.
            old = {name: cache[name][slot] for name in entries}
            if spec is not None:
                ck = kv_codec.dequantize_kv(entries["k"], entries["k_scale"],
                                            spec)
                cv = kv_codec.dequantize_kv(entries["v"], entries["v_scale"],
                                            spec)
                rk = kv_codec.dequantize_kv(old["k"], old["k_scale"], spec)
                rv = kv_codec.dequantize_kv(old["v"], old["v_scale"], spec)
            else:
                ck = entries["k"].astype(cache["k"].dtype)
                cv = entries["v"].astype(cache["v"].dtype)
                rk, rv = old["k"], old["v"]
            allk = jnp.concatenate([ck.astype(COMPUTE_DTYPE),
                                    rk.astype(COMPUTE_DTYPE)], axis=0)
            allv = jnp.concatenate([cv.astype(COMPUTE_DTYPE),
                                    rv.astype(COMPUTE_DTYPE)], axis=0)
            w = cfg.window
            kp = qpos[:, None] - w + 1 + jnp.arange(w)[None, :]  # (C, W)
            src = jnp.where(kp >= pos0, jnp.clip(kp - pos0, 0, c - 1),
                            c + (kp % ring))
            valid = kp >= 0
            if eff != cfg.window:  # engine window tightens the local layer
                valid &= (qpos[:, None] - kp) < eff
            keys_k = allk[src]  # (C, W, KV, hd)
            keys_v = allv[src]
            # now land the chunk: ring slot r ends holding absolute position
            # hold(r) = f - ((f - r) mod ring), f the chunk's final position;
            # only slots the chunk reached are replaced.
            f = pos0 + clen - 1
            r = jnp.arange(ring)
            hold = f - ((f - r) % ring)
            write = hold >= pos0
            ci = jnp.clip(hold - pos0, 0, c - 1)
            new_cache = {}
            for name, xv in entries.items():
                upd = jnp.where(
                    write.reshape((ring,) + (1,) * (old[name].ndim - 1)),
                    jnp.take(xv, ci, axis=0).astype(cache[name].dtype),
                    old[name])
                new_cache[name] = cache[name].at[slot].set(upd)
            groups = cfg.n_heads // cfg.n_kv_heads
            qg = q[0].reshape(c, cfg.n_kv_heads, groups, cfg.head_dim)
            scale = cfg.head_dim ** -0.5
            logits = jnp.einsum(
                "ckgd,cwkd->ckgw", qg.astype(COMPUTE_DTYPE), keys_k,
                preferred_element_type=jnp.float32,
            ) * scale
            logits = softcap(logits, cfg.attn_softcap)
            logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
            probs = jax.nn.softmax(logits, axis=-1).astype(COMPUTE_DTYPE)
            out = jnp.einsum("ckgw,cwkd->ckgd", probs, keys_v,
                             preferred_element_type=jnp.float32)
            out = out.astype(COMPUTE_DTYPE).reshape(
                1, c, cfg.n_heads * cfg.head_dim)
            y = qmatmul(qc, "attn_o", out, p["wo"])
            y = qc.act("attn_o", y)
            return y, new_cache
        else:
            # scatter with padding lanes pushed out of bounds — JAX drops
            # out-of-bounds scatter updates, so pad lanes never land
            idx = jnp.where(lanes < clen, pos0 + lanes, ring)
            new_cache = {}
            for name, xv in entries.items():
                new_cache[name] = cache[name].at[slot, idx].set(
                    xv.astype(cache[name].dtype))
            sids = jnp.arange(ring)[None, :]
            valid = sids <= qpos[:, None]
            if eff is not None:
                in_win = (qpos[:, None] - sids) < eff
                if sinks:
                    in_win |= sids < sinks
                valid &= in_win
        if spec is not None:
            keys_k = kv_codec.dequantize_kv(
                new_cache["k"][slot], new_cache["k_scale"][slot], spec)
            keys_v = kv_codec.dequantize_kv(
                new_cache["v"][slot], new_cache["v_scale"][slot], spec)
        else:
            keys_k = new_cache["k"][slot]
            keys_v = new_cache["v"][slot]
    else:
        bs = cache["k"].shape[1]
        mb = block_table.shape[0]
        nb = cache["k"].shape[0]
        p_abs = pos0 + lanes
        phys = block_table[jnp.clip(p_abs // bs, 0, mb - 1)]
        ok = (lanes < clen) & (phys >= 0)
        tgt = jnp.where(ok, phys, 0)  # garbage block for invalid lanes
        off = p_abs % bs
        new_cache = {}
        for name, xv in entries.items():
            new_cache[name] = cache[name].at[tgt, off].set(
                xv.astype(cache[name].dtype))
        sb = -(-sinks // bs)
        nw = min(mb, -(-(eff + c) // bs) + 1) if eff is not None else mb
        if window is not None and eff is not None and sb + nw < mb:
            # Bounded two-segment gather (docstring): the pinned sink blocks
            # plus the `nw` blocks the sliding window can reach from any
            # query in this chunk — O(sinks + window + C) keys however long
            # the prompt. `fl0` is the first window-reachable block, clamped
            # so the segment stays inside the table; when it clamps low the
            # segments overlap, and the window lanes' `kp >= sinks` term
            # de-duplicates them (sink positions count exactly once).
            fl0 = jnp.clip((pos0 - eff + 1) // bs, 0, mb - nw)
            blks = jnp.concatenate(
                [jnp.arange(sb), fl0 + jnp.arange(nw)])  # (sb + nw,)
            rowt = block_table[blks]
            rowb = jnp.clip(rowt, 0, nb - 1)
            kpos = (blks[:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
            alloc_ok = jnp.repeat(rowt >= 0, bs)
            win_lane = jnp.repeat(jnp.arange(sb + nw) >= sb, bs)
            valid = alloc_ok[None, :] & (kpos[None, :] <= qpos[:, None])
            win_ok = (((qpos[:, None] - kpos[None, :]) < eff)
                      & (kpos[None, :] >= sinks))
            valid &= win_ok | ~win_lane[None, :]
        else:
            rowb = jnp.clip(block_table, 0, nb - 1)
            kpos = jnp.arange(mb * bs)
            alloc_ok = (block_table >= 0)[kpos // bs]
            valid = alloc_ok[None, :] & (kpos[None, :] <= qpos[:, None])
            if eff is not None:
                in_win = (qpos[:, None] - kpos[None, :]) < eff
                if sinks:
                    in_win |= kpos[None, :] < sinks
                valid &= in_win
        if spec is not None:
            gk = kv_codec.dequantize_kv(
                new_cache["k"][rowb], new_cache["k_scale"][rowb], spec)
            gv = kv_codec.dequantize_kv(
                new_cache["v"][rowb], new_cache["v_scale"][rowb], spec)
        else:
            gk = new_cache["k"][rowb]
            gv = new_cache["v"][rowb]
        keys_k = gk.reshape(-1, cfg.n_kv_heads, cfg.head_dim)
        keys_v = gv.reshape(-1, cfg.n_kv_heads, cfg.head_dim)

    groups = cfg.n_heads // cfg.n_kv_heads
    qg = q[0].reshape(c, cfg.n_kv_heads, groups, cfg.head_dim)
    scale = cfg.head_dim ** -0.5
    logits = jnp.einsum(
        "ckgd,skd->ckgs", qg.astype(COMPUTE_DTYPE),
        keys_k.astype(COMPUTE_DTYPE),
        preferred_element_type=jnp.float32,
    ) * scale
    logits = softcap(logits, cfg.attn_softcap)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(COMPUTE_DTYPE)
    out = jnp.einsum("ckgs,skd->ckgd", probs, keys_v.astype(COMPUTE_DTYPE),
                     preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)
    out = out.reshape(1, c, cfg.n_heads * cfg.head_dim)
    y = qmatmul(qc, "attn_o", out, p["wo"])
    y = qc.act("attn_o", y)
    return y, new_cache


def write_prefill_slot(cfg: ModelConfig, kind: str, cache: dict, k, v, slot,
                       plen):
    """Write one serving slot's prefill K/V range in one shot.

    ``cache``: {"k", "v"} of shape (B, slots, KV, hd), with an optional
    leading scan axis (R, B, slots, KV, hd); ``k``/``v``: the batched-prefill
    K/V for the slot's right-padded prompt, shaped like the cache with B=1
    and the sequence axis S_pad in place of ``slots``. ``slot``/``plen`` may
    be traced scalars.

    Global caches take positions [0, S_pad) verbatim. Ring (local) caches
    gather, for each ring slot r, the unique prompt position p ≡ r (mod ring)
    in (plen - ring, plen]. Right-padding beyond ``plen`` (and ring slots a
    short prompt never reached) is written but never attended: the decode
    mask only admits positions <= pos, and decode overwrites each position in
    the same step that first exposes it.

    Quantized caches quantize here — after the ring gather, before the
    slice write — so codes and scales land through the identical update.
    """
    if kind == "local":
        ring = cache["k"].shape[-3]
        r = jnp.arange(ring)
        p = plen - 1 - ((plen - 1 - r) % ring)
        p = jnp.clip(p, 0, k.shape[-3] - 1)
        k = jnp.take(k, p, axis=-3)
        v = jnp.take(v, p, axis=-3)
    spec = kv_codec.spec_from_cache(cache, cfg.head_dim)
    if spec is not None:
        kc, ksc = kv_codec.quantize_kv(k, spec)
        vc, vsc = kv_codec.quantize_kv(v, spec)
        entries = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
    else:
        entries = {"k": k, "v": v}
    out = {}
    for name, x in entries.items():
        tgt = cache[name]
        start = [0] * tgt.ndim
        start[-4] = slot  # the batch (slot) axis, stacked or not
        out[name] = jax.lax.dynamic_update_slice(
            tgt, x.astype(tgt.dtype), tuple(start))
    return out


def fill_cache_from_prefill(cfg: ModelConfig, kind: str, k, v, max_seq: int):
    """Build a decode cache from full prefill K/V ((B, S, KV, hd))."""
    b, s, kv, hd = k.shape
    cache = init_attn_cache(cfg, kind, b, max_seq, dtype=COMPUTE_DTYPE)
    slots = cache["k"].shape[1]
    if kind == "local":
        # place the last `min(s, slots)` tokens at their ring positions
        take = min(s, slots)
        idx = (jnp.arange(s - take, s)) % slots
        cache["k"] = cache["k"].at[:, idx].set(k[:, s - take:].astype(COMPUTE_DTYPE))
        cache["v"] = cache["v"].at[:, idx].set(v[:, s - take:].astype(COMPUTE_DTYPE))
    else:
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(COMPUTE_DTYPE), (0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(COMPUTE_DTYPE), (0, 0, 0, 0))
    return cache
