"""Paged KV cache tests (DESIGN.md §10).

Four layers of coverage:
  * the hard equivalence gate — paged decode logits match the ring-cache
    path to bf16 tolerance on EVERY transformer config with attention;
  * kernel validation — the Pallas paged-attention kernel (interpret mode)
    against the pure-jnp oracle;
  * allocator state machine — alloc / share / tick-alloc / CoW / free
    round-trips on the device-resident free list;
  * scheduler behavior — prefix sharing admits N same-prefix requests with
    ONE prefill, copy-on-write isolates divergent continuations, and
    retirement returns every block to the pool.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_smoke_config
from repro.core.sites import QuantContext
from repro.kernels.paged_attention.ops import paged_attention_op
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.models import transformer as tfm
from repro.serving import kv_pool
from repro.serving.engine import Request, SamplingParams, ServingEngine

ATTN_ARCHS = [
    a for a in ALL_ARCHS
    if any(k in ("global", "local")
           for k in (list(get_smoke_config(a).block_pattern)
                     + list(get_smoke_config(a).remainder_kinds)))
]

BS = 8          # block size
MAX_SEQ = 32


def _model(arch, seed=0):
    cfg = get_smoke_config(arch)
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, params


def _inputs(cfg, plen, key=0):
    k = jax.random.PRNGKey(key)
    if cfg.embed_input:
        return jax.random.randint(k, (1, plen), 0, cfg.vocab_size)
    return jax.random.normal(k, (1, plen, cfg.d_model), jnp.float32) * 0.3


def _mrope(cfg, s):
    if cfg.mrope_sections is None:
        return None
    return jnp.broadcast_to(jnp.arange(s)[None, None, :], (3, 1, s))


# ---------------------------------------------------------------------------
# Equivalence gate: paged decode == ring decode on every transformer config
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ATTN_ARCHS)
def test_paged_decode_matches_ring_every_config(arch):
    """The acceptance gate: after an identical prefill, the paged block-pool
    decode path must reproduce the ring-cache decode logits to bf16
    tolerance, step after step, for every attention-bearing architecture
    (global, local/ring-window, GQA/MQA/MHA, softcap, qk-norm, M-RoPE, MoE,
    hybrid recurrent). Attention-free archs have no KV to page."""
    cfg, params = _model(arch)
    qc = QuantContext(mode="off")
    plen = 9
    x = _inputs(cfg, plen, key=1)

    cache_r = tfm.init_cache(cfg, 1, MAX_SEQ)
    logits_r, cache_r = tfm.prefill_slot(
        qc, params, x, plen, cache_r, 0, cfg, mrope_pos=_mrope(cfg, plen))

    mb = MAX_SEQ // BS
    nb = mb + 1
    cache_p = tfm.init_paged_cache(cfg, 1, nb, BS)
    alloc = kv_pool.init_alloc(nb, 1, mb)
    alloc = kv_pool.alloc_range(alloc, 0, 0, -(-plen // BS))
    logits_p, cache_p = tfm.prefill_slot(
        qc, params, x, plen, cache_p, 0, cfg, mrope_pos=_mrope(cfg, plen),
        block_table=alloc["table"])

    np.testing.assert_allclose(
        np.asarray(logits_p[0, plen - 1, : cfg.vocab_size]),
        np.asarray(logits_r[0, plen - 1, : cfg.vocab_size]),
        rtol=2e-2, atol=2e-2)

    rng = np.random.default_rng(2)
    adv = jnp.ones((1,), jnp.int32)
    for t in range(4):
        if cfg.embed_input:
            tok = jnp.asarray([int(rng.integers(0, cfg.vocab_size))],
                              jnp.int32)
        else:
            tok = jax.random.normal(jax.random.PRNGKey(10 + t),
                                    (1, 1, cfg.d_model), jnp.float32) * 0.3
        lr, cache_r = tfm.decode_step(qc, params, cache_r, tok, cfg,
                                      advance=adv)
        alloc = kv_pool.tick_alloc(alloc, cache_p["pos"], adv, BS)
        lp, cache_p = tfm.decode_step(qc, params, cache_p, tok, cfg,
                                      advance=adv,
                                      block_table=alloc["table"])
        np.testing.assert_allclose(
            np.asarray(lp[..., : cfg.vocab_size]),
            np.asarray(lr[..., : cfg.vocab_size]),
            rtol=2e-2, atol=2e-2, err_msg=f"{arch} step {t}")
        assert int(cache_p["pos"][0]) == int(cache_r["pos"][0]) == plen + t + 1


# ---------------------------------------------------------------------------
# Kernel: Pallas (interpret) vs jnp oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window,softcap", [(None, None), (8, None),
                                            (None, 30.0), (8, 50.0)])
def test_paged_attention_pallas_matches_ref(window, softcap):
    rng = np.random.default_rng(0)
    b, kvh, g, hd, bs, mb, nb = 3, 2, 4, 16, 8, 4, 16
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    # distinct physical blocks per row, with unallocated (-1) tails
    table = np.full((b, mb), -1, np.int32)
    phys = rng.permutation(np.arange(1, nb))
    pos = np.asarray([5, 12, 25], np.int32)
    k = 0
    for r in range(b):
        for j in range(int(pos[r]) // bs + 1):
            table[r, j] = phys[k]
            k += 1
    table = jnp.asarray(table)
    posj = jnp.asarray(pos)
    want = paged_attention_ref(q, kp, vp, table, posj, window=window,
                               softcap=softcap)
    got = paged_attention_op(q, kp, vp, table, posj, window=window,
                             softcap=softcap, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Allocator state machine
# ---------------------------------------------------------------------------


def _snap(alloc):
    return {k: np.asarray(jax.device_get(v)) for k, v in alloc.items()}


def test_alloc_free_roundtrip_preserves_free_list():
    alloc = kv_pool.init_alloc(9, 2, 4)
    a0 = _snap(alloc)
    assert a0["n_free"] == 8 and a0["ref"][0] == 1
    alloc = kv_pool.alloc_range(alloc, 0, 0, 3)
    alloc = kv_pool.alloc_range(alloc, 1, 0, 2)
    a = _snap(alloc)
    assert a["n_free"] == 3
    row0, row1 = a["table"][0], a["table"][1]
    assert (row0[:3] > 0).all() and (row1[:2] > 0).all()
    used = set(row0[:3]) | set(row1[:2])
    assert len(used) == 5, "blocks must be distinct"
    assert all(a["ref"][i] == 1 for i in used)
    alloc = kv_pool.free_slot(alloc, 0)
    alloc = kv_pool.free_slot(alloc, 1)
    a = _snap(alloc)
    assert a["n_free"] == 8
    assert (a["table"] == -1).all()
    assert set(a["free"][:8]) == set(range(1, 9)), "free list lost blocks"
    assert (a["ref"][1:] == 0).all()


def test_share_prefix_refcounts_block_until_last_user_frees():
    alloc = kv_pool.init_alloc(9, 2, 4)
    alloc = kv_pool.alloc_range(alloc, 0, 0, 2)
    row0 = np.asarray(jax.device_get(alloc["table"][0]))
    alloc = kv_pool.share_prefix(alloc, 1, jnp.asarray(row0), 2)
    a = _snap(alloc)
    assert (a["table"][1][:2] == row0[:2]).all()
    assert all(a["ref"][i] == 2 for i in row0[:2])
    alloc = kv_pool.free_slot(alloc, 0)
    a = _snap(alloc)
    assert a["n_free"] == 6, "shared blocks must survive the first free"
    assert all(a["ref"][i] == 1 for i in row0[:2])
    alloc = kv_pool.free_slot(alloc, 1)
    a = _snap(alloc)
    assert a["n_free"] == 8
    assert set(a["free"][:8]) == set(range(1, 9))


def test_tick_alloc_pops_only_for_rows_entering_new_blocks():
    alloc = kv_pool.init_alloc(17, 4, 4)
    alloc = kv_pool.alloc_range(alloc, 0, 0, 1)
    alloc = kv_pool.alloc_range(alloc, 1, 0, 1)
    pos = jnp.asarray([8, 3, 0, 0], jnp.int32)   # row 0 crosses into block 1
    mask = jnp.asarray([1, 1, 0, 0], jnp.int32)  # rows 2/3 idle
    before = _snap(alloc)["n_free"]
    alloc = kv_pool.tick_alloc(alloc, pos, mask, 8)
    a = _snap(alloc)
    assert a["n_free"] == before - 1
    assert a["table"][0, 1] > 0 and a["ref"][a["table"][0, 1]] == 1
    assert a["table"][1, 1] == -1           # row 1 still inside block 0
    assert (a["table"][2:] == -1).all()     # idle rows untouched


def test_cow_block_gives_private_copy():
    cfg = get_smoke_config("tinyllama-1.1b")
    alloc = kv_pool.init_alloc(9, 2, 2)
    pool = kv_pool.init_pool(cfg, 9, BS)
    alloc = kv_pool.alloc_range(alloc, 0, 0, 1)
    old = int(jax.device_get(alloc["table"][0, 0]))
    pool["k"] = pool["k"].at[old].set(1.5)
    row0 = np.asarray(jax.device_get(alloc["table"][0]))
    alloc = kv_pool.share_prefix(alloc, 1, jnp.asarray(row0), 1)
    alloc, layers = kv_pool.cow_block(alloc, [pool], 1, 0)
    a = _snap(alloc)
    new = int(a["table"][1, 0])
    assert new != old and a["ref"][old] == 1 and a["ref"][new] == 1
    np.testing.assert_array_equal(
        np.asarray(layers[0]["k"][new]), np.asarray(layers[0]["k"][old]))


# ---------------------------------------------------------------------------
# Allocator invariants under random op storms (property test, §13)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # hypothesis not installed: deterministic shim
    from _hyp_fallback import given, settings
    from _hyp_fallback import strategies as st

_NB, _SLOTS, _MB = 17, 4, 4     # 16 usable blocks, 4 slots, 4 blocks/slot


def _check_alloc_invariants(alloc, nb=_NB):
    """The §13 pool-safety contract, checked after EVERY operation:
    refcounts never negative, the free stack never double-pops, and no
    block is ever lost or aliased — in_use + free == num_blocks - 1
    (block 0 is the pinned garbage lane). ``in_use`` counts device refs,
    so LRU-style retained blocks (ref without a table entry) are covered
    too."""
    a = _snap(alloc)
    n_free = int(a["n_free"])
    assert 0 <= n_free <= nb - 1
    assert (a["ref"] >= 0).all(), "negative refcount"
    assert a["ref"][0] >= 1, "garbage block must stay pinned"
    head = a["free"][:n_free].tolist()
    assert len(set(head)) == n_free, "free stack double-pop"
    assert 0 not in head, "garbage block on the free stack"
    assert (a["ref"][a["free"][:n_free]] == 0).all(), \
        "free block still referenced"
    in_use = int((a["ref"][1:] > 0).sum())
    assert in_use + n_free == nb - 1, "blocks leaked or aliased"
    live = a["table"][a["table"] >= 0]
    assert (a["ref"][live] > 0).all(), "table points at a dead block"


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_allocator_invariants_under_random_op_storms(seed):
    """Random alloc / share / tick-alloc / CoW-free / preempt / steal
    sequences against the device-resident allocator: every §13 invariant
    holds after every single op, and after draining, the pool is whole."""
    rng = np.random.default_rng(seed)
    alloc = kv_pool.init_alloc(_NB, _SLOTS, _MB)
    stolen = None
    for _ in range(25):
        a = _snap(alloc)
        n_free = int(a["n_free"])
        occ = [s for s in range(_SLOTS) if (a["table"][s] >= 0).any()]
        empty = [s for s in range(_SLOTS) if s not in occ]
        op = rng.choice(["alloc", "share", "free", "tick", "preempt",
                         "steal"])
        if op == "alloc" and empty:
            n = int(rng.integers(1, _MB + 1))
            if n <= n_free:
                alloc = kv_pool.alloc_range(alloc, int(rng.choice(empty)),
                                            0, n)
        elif op == "share" and occ and empty:
            src = int(rng.choice(occ))
            k = int((a["table"][src] >= 0).sum())
            alloc = kv_pool.share_prefix(
                alloc, int(rng.choice(empty)),
                jnp.asarray(a["table"][src]), int(rng.integers(1, k + 1)))
        elif op == "free" and occ:
            alloc = kv_pool.free_slot(alloc, int(rng.choice(occ)))
        elif op == "tick" and occ:
            # rows crossing into their next (unallocated) block; honor the
            # no-preemption precondition demand <= n_free
            pos = np.zeros(_SLOTS, np.int32)
            mask = np.zeros(_SLOTS, np.int32)
            budget = n_free
            for s in occ:
                k = int((a["table"][s] >= 0).sum())
                if k < _MB and budget > 0 and rng.random() < 0.7:
                    pos[s], mask[s] = k * BS, 1
                    budget -= 1
            alloc = kv_pool.tick_alloc(alloc, jnp.asarray(pos),
                                       jnp.asarray(mask), BS)
        elif op == "preempt" and occ:
            # every growable row demands a block; victims are freed
            # in-devices until the demand fits the free stack
            pos = np.zeros(_SLOTS, np.int32)
            active = np.zeros(_SLOTS, bool)
            for s in occ:
                k = int((a["table"][s] >= 0).sum())
                if k < _MB:
                    pos[s], active[s] = k * BS, True
            alloc, pre = kv_pool.preempt_for_free(
                alloc, jnp.asarray(pos), jnp.asarray(active),
                jnp.asarray(rng.integers(1, 20, _SLOTS), jnp.int32),
                jnp.asarray(rng.permutation(_SLOTS) + 1, jnp.int32), BS)
            pre = np.asarray(jax.device_get(pre))
            a2 = _snap(alloc)
            assert (a2["table"][pre] == -1).all(), \
                "preempted row kept blocks"
        elif op == "steal":
            if stolen is None and n_free > 0:
                alloc, stolen = kv_pool.steal_blocks(
                    alloc, int(rng.integers(1, n_free + 1)))
            elif stolen is not None:
                alloc = kv_pool.unsteal_blocks(alloc, stolen)
                stolen = None
        _check_alloc_invariants(alloc)
    # drain: give back steals, free every slot -> the pool is whole again
    if stolen is not None:
        alloc = kv_pool.unsteal_blocks(alloc, stolen)
    a = _snap(alloc)
    for s in range(_SLOTS):
        if (a["table"][s] >= 0).any():
            alloc = kv_pool.free_slot(alloc, s)
    a = _snap(alloc)
    assert int(a["n_free"]) == _NB - 1
    assert set(a["free"][: _NB - 1].tolist()) == set(range(1, _NB))
    assert (a["ref"][1:] == 0).all()


# ---------------------------------------------------------------------------
# Scheduler: prefix sharing, CoW, retirement
# ---------------------------------------------------------------------------


def _solo_output(cfg, params, prompt, max_new, **kw):
    eng = ServingEngine(cfg, params, slots=1, max_seq=64, **kw)
    eng.submit(Request(rid=0, prompt=prompt, max_new=max_new))
    return eng.run_to_completion()[0].output


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "gemma2-2b",
                                  "recurrentgemma-2b"])
def test_engine_ring_and_paged_layouts_agree(arch):
    """End-to-end: the engine emits identical token streams under both KV
    layouts, with requests admitted mid-flight at staggered lengths."""
    cfg, params = _model(arch)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, (int(p),))
               for p in (5, 9, 4, 12)]
    outs = {}
    for layout in ("ring", "paged"):
        eng = ServingEngine(cfg, params, slots=2, max_seq=64,
                            kv_layout=layout)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new=6))
        outs[layout] = {r.rid: r.output for r in eng.run_to_completion()}
    assert outs["ring"] == outs["paged"]


def test_unquantized_engine_serves_through_the_pallas_paged_kernel(
        monkeypatch):
    """An fp32/bf16 engine hands its ``matmul_impl`` to the model: with a
    Pallas backend its decode tick attends through the Pallas paged kernel
    (on a TPU, ``"pallas"``), not the jnp oracle, and streams the same
    tokens as a ``ref`` engine."""
    import repro.kernels.paged_attention.ops as pa_ops

    traced = []

    def counting(*a, **kw):
        traced.append(kw["interpret"])
        return real(*a, **kw)

    real = pa_ops.paged_attention_pallas
    monkeypatch.setattr(pa_ops, "paged_attention_pallas", counting)
    cfg, params = _model("tinyllama-1.1b")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, (int(p),)) for p in (5, 9)]
    outs = {}
    for impl in ("ref", "pallas_interpret"):
        traced.clear()
        eng = ServingEngine(cfg, params, slots=2, max_seq=32,
                            matmul_impl=impl)
        outs[impl] = [r.tokens for r in
                      eng.generate(prompts, SamplingParams(max_new=4))]
        assert bool(traced) == (impl != "ref"), (impl, traced)
    assert outs["ref"] == outs["pallas_interpret"]


@pytest.mark.parametrize("plen", [11, 16])
def test_prefix_sharing_admits_n_requests_with_one_prefill(plen):
    """The headline paged-KV property: N same-prompt admissions run ONE
    prefill forward (plus sub-block teacher steps), and every request's
    output matches a solo run. plen=16 is block-aligned, exercising the
    copy-on-write of the final shared block."""
    cfg, params = _model("tinyllama-1.1b")
    rng = np.random.default_rng(plen)
    prompt = rng.integers(0, cfg.vocab_size, (plen,))
    n = 4
    eng = ServingEngine(cfg, params, slots=n, max_seq=64)
    for i in range(n):
        eng.submit(Request(rid=i, prompt=prompt, max_new=5))
    fin = {r.rid: r.output for r in eng.run_to_completion()}
    st = eng.stats
    assert st["prefill_forwards"] == 1, "N same-prefix admissions != 1 prefill"
    assert st["shared_admissions"] == n - 1
    assert st["teacher_steps"] <= (n - 1) * eng.block_size
    if plen % eng.block_size == 0:
        assert st["cow_copies"] == n - 1
    want = _solo_output(cfg, params, prompt, 5)
    for i in range(n):
        assert fin[i] == want, f"shared request {i} diverged from solo"


def test_divergent_prompts_share_leading_blocks_only():
    """Two prompts equal through the first block but divergent INSIDE a
    later full block map only their leading table entries to the same
    physical blocks; the second request still runs its own prefill (from the
    divergent block on) and both outputs match their solo runs."""
    cfg, params = _model("tinyllama-1.1b")
    rng = np.random.default_rng(9)
    head = rng.integers(0, cfg.vocab_size, (8,))
    pa = np.concatenate([head, rng.integers(0, cfg.vocab_size, (9,))])
    pb = np.concatenate([head, rng.integers(0, cfg.vocab_size, (9,))])
    eng = ServingEngine(cfg, params, slots=2, max_seq=64)
    eng.submit(Request(rid=0, prompt=pa, max_new=4))
    eng.submit(Request(rid=1, prompt=pb, max_new=4))
    fin = {r.rid: r.output for r in eng.run_to_completion()}
    st = eng.stats
    assert st["prefix_hit_blocks"] == 1 and st["prompt_blocks"] == 4
    assert st["prefill_forwards"] == 2   # divergence in block 1: both prefill
    assert st["shared_admissions"] == 0
    assert fin[0] == _solo_output(cfg, params, pa, 4)
    assert fin[1] == _solo_output(cfg, params, pb, 4)


def test_divergent_tail_takes_fast_path_with_private_block():
    """Prompts sharing every FULL block but divergent in the sub-block tail
    admit without a second prefill: the tail is teacher-forced into a
    private block, so no CoW is needed and outputs match the solo runs."""
    cfg, params = _model("tinyllama-1.1b")
    rng = np.random.default_rng(10)
    head = rng.integers(0, cfg.vocab_size, (8,))
    pa = np.concatenate([head, rng.integers(0, cfg.vocab_size, (3,))])
    pb = np.concatenate([head, rng.integers(0, cfg.vocab_size, (3,))])
    eng = ServingEngine(cfg, params, slots=2, max_seq=64)
    eng.submit(Request(rid=0, prompt=pa, max_new=4))
    eng.submit(Request(rid=1, prompt=pb, max_new=4))
    fin = {r.rid: r.output for r in eng.run_to_completion()}
    st = eng.stats
    assert st["prefill_forwards"] == 1 and st["shared_admissions"] == 1
    assert st["cow_copies"] == 0 and st["teacher_steps"] == 3
    assert fin[0] == _solo_output(cfg, params, pa, 4)
    assert fin[1] == _solo_output(cfg, params, pb, 4)


def test_cow_sharer_does_not_keep_stale_prefix_entry():
    """Regression: a CoW'd sharer must drop the CoW'd block's prefix-cache
    key. If it kept the key, the map entry would outlive the registrant's
    retirement (which frees the physical block), and a later same-prefix
    admission would map a freed — possibly recycled — block. Interleaving:
    registrant A retires while CoW sharer B still runs, an unrelated
    request D recycles A's freed blocks, then C re-admits the shared
    prompt; C's output must match a solo run."""
    cfg, params = _model("tinyllama-1.1b")
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab_size, (16,))   # block-aligned -> CoW
    other = rng.integers(0, cfg.vocab_size, (16,))
    eng = ServingEngine(cfg, params, slots=3, max_seq=64)
    eng.submit(Request(rid=0, prompt=shared, max_new=2))    # registrant
    eng.submit(Request(rid=1, prompt=shared, max_new=20))   # CoW sharer
    while not any(r.rid == 0 for r in eng.finished):
        eng.step()
    assert eng.stats["cow_copies"] == 1
    eng.submit(Request(rid=2, prompt=other, max_new=2))     # recycles blocks
    eng.submit(Request(rid=3, prompt=shared, max_new=4))
    fin = {r.rid: r.output for r in eng.run_to_completion()}
    assert fin[3] == _solo_output(cfg, params, shared, 4), \
        "late same-prefix admission mapped a freed/recycled block"


def test_retirement_returns_all_blocks_and_evicts_prefix_cache():
    cfg, params = _model("tinyllama-1.1b")
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, (16,))
    eng = ServingEngine(cfg, params, slots=3, max_seq=64)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=prompt, max_new=3))
    mid_blocks = None
    eng.step()
    mid_blocks = eng.pool_stats()["blocks_in_use"]
    assert mid_blocks > 0
    eng.run_to_completion()
    ps = eng.pool_stats()
    assert ps["blocks_in_use"] == 0, "retirement leaked pool blocks"
    assert not eng._prefix_map and not eng._key_refs
    assert ps["prefix_hit_rate"] > 0


def test_prefix_lru_retains_blocks_past_zero_refs():
    """ROADMAP item: with ``prefix_lru_blocks`` the prefix cache holds a
    device ref on registered blocks, so a popular prompt survives ALL its
    requests retiring — the next same-prefix admission still skips the
    prefill (the default capacity-0 engine re-prefills here)."""
    cfg, params = _model("tinyllama-1.1b")
    rng = np.random.default_rng(21)
    prompt = rng.integers(0, cfg.vocab_size, (16,))
    want = _solo_output(cfg, params, prompt, 4)

    eng = ServingEngine(cfg, params, slots=1, max_seq=64,
                        prefix_lru_blocks=2)
    eng.submit(Request(rid=0, prompt=prompt, max_new=3))
    eng.run_to_completion()
    ps = eng.pool_stats()
    assert ps["retained_blocks"] == 2 and ps["blocks_in_use"] == 2
    assert eng._prefix_map, "retirement evicted retained prefix keys"

    eng.submit(Request(rid=1, prompt=prompt, max_new=4))
    fin = eng.run_to_completion()
    assert fin[-1].output == want, "retained blocks served stale KV"
    assert eng.stats["prefill_forwards"] == 1, \
        "re-admission after retirement should hit the retained prefix"
    assert eng.stats["shared_admissions"] == 1

    # capacity-0 baseline: same workload pays a second prefill
    base = ServingEngine(cfg, params, slots=1, max_seq=64)
    for rid in (0, 1):
        base.submit(Request(rid=rid, prompt=prompt, max_new=3))
        base.run_to_completion()
    assert base.stats["prefill_forwards"] == 2
    assert base.pool_stats()["retained_blocks"] == 0


def test_prefix_lru_capacity_pressure_evicts_oldest():
    """Capacity pressure: retained blocks are bounded by the LRU capacity —
    the oldest key is evicted (and its block released back to the pool)
    when a newer prefix needs the headroom; outputs stay correct through
    recycling."""
    cfg, params = _model("tinyllama-1.1b")
    rng = np.random.default_rng(22)
    pa = rng.integers(0, cfg.vocab_size, (16,))
    pb = rng.integers(0, cfg.vocab_size, (16,))
    want_a = _solo_output(cfg, params, pa, 4)
    want_b = _solo_output(cfg, params, pb, 4)

    eng = ServingEngine(cfg, params, slots=1, max_seq=64,
                        prefix_lru_blocks=2)  # room for ONE 2-block prefix
    eng.submit(Request(rid=0, prompt=pa, max_new=3))
    eng.run_to_completion()
    assert eng.pool_stats()["retained_blocks"] == 2
    eng.submit(Request(rid=1, prompt=pb, max_new=3))
    eng.run_to_completion()
    # A's keys were evicted for B's; retained stays at capacity
    ps = eng.pool_stats()
    assert ps["retained_blocks"] == 2
    assert len(eng._prefix_map) == 2, "evicted keys must leave the map"

    eng.submit(Request(rid=2, prompt=pa, max_new=4))  # A: evicted -> prefill
    fin = eng.run_to_completion()
    assert fin[-1].output == want_a
    assert eng.stats["prefill_forwards"] == 3
    eng.submit(Request(rid=3, prompt=pb, max_new=4))  # B: evicted by A's readmit
    fin = eng.run_to_completion()
    assert fin[-1].output == want_b, "recycled block leaked into B's KV"
    # every non-retained block is back on the free stack
    ps = eng.pool_stats()
    assert ps["blocks_in_use"] == ps["retained_blocks"] == 2


def test_prefix_lru_never_starves_generation():
    """The pool is sized up by exactly the LRU capacity, so a full slot
    complement can still generate to max_seq with the cache at capacity."""
    cfg, params = _model("tinyllama-1.1b")
    rng = np.random.default_rng(23)
    filler = rng.integers(0, cfg.vocab_size, (16,))
    eng = ServingEngine(cfg, params, slots=2, max_seq=32,
                        prefix_lru_blocks=2)
    eng.submit(Request(rid=0, prompt=filler, max_new=2))
    eng.run_to_completion()
    assert eng.pool_stats()["retained_blocks"] == 2
    # both slots now generate deep into their rows with the cache full
    for i in range(2):
        eng.submit(Request(rid=10 + i,
                           prompt=rng.integers(0, cfg.vocab_size, (4,)),
                           max_new=24))
    fin = eng.run_to_completion()
    assert all(len(r.output) == 24 for r in fin[-2:])


def test_stop_token_releases_paged_blocks_in_same_tick():
    """Regression (DESIGN.md §12): a request that hits a stop token before
    ``max_new`` must release its paged KV blocks at retirement, in the SAME
    tick that emitted the stop — not hold them until ``max_new`` ticks
    elapse. The pool free-count must recover immediately."""
    cfg, params = _model("tinyllama-1.1b")
    rng = np.random.default_rng(31)
    prompt = rng.integers(0, cfg.vocab_size, (12,))
    probe = ServingEngine(cfg, params, slots=1, max_seq=64)
    stream = probe.generate([prompt], SamplingParams(max_new=8))[0].tokens

    stop = stream[3]
    k = stream.index(stop)
    eng = ServingEngine(cfg, params, slots=1, max_seq=64)
    free0 = int(jax.device_get(eng.alloc["n_free"]))
    eng.submit(Request(rid=0, prompt=prompt,
                       params=SamplingParams(max_new=64, stop=(stop,))))
    ticks = 0
    while eng.waiting or any(r is not None for r in eng.slot_req):
        eng.step()
        ticks += 1
    req = eng.finished[0]
    assert req.finish_reason == "stop"
    assert req.output == stream[: k + 1], "stop token must end the stream"
    # retirement freed the row the moment the stop tick retired it: the
    # loop exited on the stop tick, nowhere near the 64-token budget
    assert ticks == max(k, 1) and eng.stats["decode_ticks"] == k
    assert int(jax.device_get(eng.alloc["n_free"])) == free0, \
        "stop-token retirement leaked pool blocks past the stop tick"
    assert eng.pool_stats()["blocks_in_use"] == 0

    # first-token stop: the armed slot must be shut down before its blocks
    # free, or the still-active row would pop fresh blocks every tick
    eng = ServingEngine(cfg, params, slots=1, max_seq=64)
    res = eng.generate([prompt], SamplingParams(max_new=64,
                                                stop=(stream[0],)))[0]
    assert res.finish_reason == "stop" and res.tokens == [stream[0]]
    assert eng.stats["decode_ticks"] == 0
    assert int(jax.device_get(eng.alloc["n_free"])) == free0
    # and the pool stays intact while another request runs to completion
    out = eng.generate([prompt], SamplingParams(max_new=8))[0]
    assert out.tokens == stream
    assert eng.pool_stats()["blocks_in_use"] == 0


def test_undersized_pool_policies_at_construction():
    """An undersized pool (can't back every slot at max_seq) is legal WITH
    victim preemption (§13) — "auto" turns it on — but is refused when
    preemption is explicitly off (an exhausted free stack would silently
    alias one physical block into two slots), and a pool too small to back
    even ONE slot is always refused."""
    cfg, params = _model("tinyllama-1.1b")
    eng = ServingEngine(cfg, params, slots=4, max_seq=64, num_blocks=16)
    assert eng.preemption, "undersized pool must auto-enable preemption"
    with pytest.raises(ValueError, match="preemption"):
        ServingEngine(cfg, params, slots=4, max_seq=64, num_blocks=16,
                      preemption=False)
    # floor: max_blocks + 1 garbage block = 9 for max_seq=64/bs=8
    with pytest.raises(ValueError, match="one slot"):
        ServingEngine(cfg, params, slots=4, max_seq=64, num_blocks=8)
    # exactly the full provisioning minimum: preemption stays off
    eng = ServingEngine(cfg, params, slots=2, max_seq=16,
                        num_blocks=2 * 2 + 1)
    assert not eng.preemption


def test_hybrid_ssm_attention_arch_serves_in_both_layouts():
    """A jamba-style config mixing SSM and attention blocks has a sub-chunk
    prefill tail but can't take the state-threaded tail forward (attention
    has no carried state to resume) — both layouts must fall back to
    teacher-forced tail steps and match the scan-of-decode-steps oracle."""
    import dataclasses

    base = get_smoke_config("tinyllama-1.1b")
    cfg = dataclasses.replace(
        base, name="hybrid-smoke", block_pattern=("ssm", "global"),
        n_layers=4, ssm_state=16, ssm_head_dim=16, ssm_expand=2,
        ssm_chunk=8, conv_kernel=4)
    params = tfm.init_params(cfg, jax.random.PRNGKey(7))
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, (11,))  # chunk 8 -> 3-token tail

    qc = QuantContext(mode="off")
    cache = tfm.init_cache(cfg, 1, 32)
    for t in prompt:
        logits, cache = tfm.decode_step(qc, params, cache,
                                        jnp.asarray([int(t)], jnp.int32), cfg)
    want = [int(jnp.argmax(logits[0, 0, : cfg.vocab_size]))]
    for _ in range(3):
        logits, cache = tfm.decode_step(
            qc, params, cache, jnp.asarray([want[-1]], jnp.int32), cfg)
        want.append(int(jnp.argmax(logits[0, 0, : cfg.vocab_size])))

    for layout in ("ring", "paged"):
        eng = ServingEngine(cfg, params, slots=2, max_seq=32,
                            kv_layout=layout)
        eng.submit(Request(rid=0, prompt=prompt, max_new=4))
        out = eng.run_to_completion()[0].output
        assert out == want, f"{layout} hybrid tail diverged from oracle"
        assert eng.stats["teacher_steps"] == 3


def test_paged_int8_serve_mode():
    """Paged layout composes with the int8 fused-dequant decode path."""
    from repro.serving.engine import make_uniform_quant_state

    cfg, params = _model("tinyllama-1.1b")
    qs = make_uniform_quant_state(cfg, params)
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, (9,))
    outs = {}
    for layout in ("ring", "paged"):
        eng = ServingEngine(cfg, params, slots=2, max_seq=64, quant_state=qs,
                            matmul_impl="ref", kv_layout=layout)
        assert len(eng.qweights) >= 8
        eng.submit(Request(rid=0, prompt=prompt, max_new=4))
        outs[layout] = eng.run_to_completion()[0].output
    assert outs["ring"] == outs["paged"]


# ---------------------------------------------------------------------------
# Quantized pools: fused dequant kernel + CoW bit-identity (DESIGN.md §14)
# ---------------------------------------------------------------------------

from repro.quant import KVQuantSpec, quantize_kv  # noqa: E402


def _quant_kernel_fixture(bits, seed=0):
    rng = np.random.default_rng(seed)
    b, kvh, g, hd, bs, mb, nb = 3, 2, 4, 16, 8, 4, 16
    spec = KVQuantSpec(bits=bits, group_size=8, head_dim=hd)
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    table = np.full((b, mb), -1, np.int32)
    phys = rng.permutation(np.arange(1, nb))
    pos = np.asarray([5, 12, 25], np.int32)
    k = 0
    for r in range(b):
        for j in range(int(pos[r]) // bs + 1):
            table[r, j] = phys[k]
            k += 1
    return spec, q, kf, vf, jnp.asarray(table), jnp.asarray(pos)


@pytest.mark.parametrize("bits,window,softcap",
                         [(8, None, None), (8, 8, None), (8, None, 30.0),
                          (4, None, None), (4, 8, 50.0)])
def test_paged_attention_pallas_matches_ref_quantized(bits, window, softcap):
    """Fused dequant-on-block-load: the Pallas kernel (scales paged through
    the same block-table index_map as the codes, affine applied in-register)
    must reproduce the jnp oracle's gather-then-dequant semantics — and the
    quantized oracle itself must stay within codec tolerance of the float
    pool."""
    spec, q, kf, vf, table, pos = _quant_kernel_fixture(bits)
    kp, ks = quantize_kv(kf, spec)
    vp, vs = quantize_kv(vf, spec)
    want = paged_attention_ref(q, kp, vp, table, pos, window=window,
                               softcap=softcap, k_scale=ks, v_scale=vs)
    got = paged_attention_op(q, kp, vp, table, pos, window=window,
                             softcap=softcap, impl="pallas_interpret",
                             k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    want_f = paged_attention_ref(q, kf, vf, table, pos, window=window,
                                 softcap=softcap)
    np.testing.assert_allclose(np.asarray(want), np.asarray(want_f),
                               atol=0.05 if bits == 8 else 0.35)


def test_cow_block_quantized_copies_codes_and_aux_bit_identical():
    """§14 CoW contract: the private copy of a shared quantized block is
    bit-identical in BOTH codes and per-group scales — the generic
    per-entry copy never round-trips through floats."""
    cfg = get_smoke_config("tinyllama-1.1b")
    hd = cfg.head_dim
    spec = KVQuantSpec(bits=8, group_size=hd, head_dim=hd)
    alloc = kv_pool.init_alloc(9, 2, 2)
    pool = kv_pool.init_pool(cfg, 9, BS, spec=spec)
    assert pool["k"].dtype == jnp.int8
    assert pool["k_scale"].dtype == jnp.float16
    alloc = kv_pool.alloc_range(alloc, 0, 0, 1)
    old = int(jax.device_get(alloc["table"][0, 0]))
    rng = np.random.default_rng(3)
    for name in ("k", "v"):
        block = jnp.asarray(
            rng.normal(size=(BS, cfg.n_kv_heads, hd)), jnp.float32)
        codes, scale = quantize_kv(block, spec)
        pool[name] = pool[name].at[old].set(codes)
        pool[name + "_scale"] = pool[name + "_scale"].at[old].set(scale)
    row0 = np.asarray(jax.device_get(alloc["table"][0]))
    alloc = kv_pool.share_prefix(alloc, 1, jnp.asarray(row0), 1)
    alloc, layers = kv_pool.cow_block(alloc, [pool], 1, 0)
    a = {k: np.asarray(jax.device_get(v)) for k, v in alloc.items()}
    new = int(a["table"][1, 0])
    assert new != old and a["ref"][old] == 1 and a["ref"][new] == 1
    for name in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(layers[0][name][new]), np.asarray(layers[0][name][old]))


def test_quantized_prefix_sharing_cow_streams_unaffected():
    """§14 regression, extending the stale-key test to int8 pools: a
    same-prefix admission shares blocks, the sharer CoWs on its first
    divergent write, and BOTH the registrant's and the sharer's streams
    equal their solo int8 runs."""
    cfg, params = _model("tinyllama-1.1b")
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab_size, (16,))   # block-aligned -> CoW
    eng = ServingEngine(cfg, params, slots=2, max_seq=64, kv_dtype="int8")
    eng.submit(Request(rid=0, prompt=shared, max_new=6))
    eng.submit(Request(rid=1, prompt=shared, max_new=12))
    fin = {r.rid: r.output for r in eng.run_to_completion()}
    assert eng.stats["shared_admissions"] == 1
    assert eng.stats["cow_copies"] >= 1
    assert fin[0] == _solo_output(cfg, params, shared, 6, kv_dtype="int8"), \
        "registrant stream perturbed by a sharer's CoW"
    assert fin[1] == _solo_output(cfg, params, shared, 12, kv_dtype="int8")


# ---------------------------------------------------------------------------
# §17 long-context: windowed kernel vs dense masked oracle
# ---------------------------------------------------------------------------

from repro.serving.window import (WindowSpec, as_window_spec,
                                  first_live_block, max_live_blocks,
                                  window_demand_blocks)


def _kernel_fixture(seed=0):
    rng = np.random.default_rng(seed)
    b, kvh, g, hd, bs, mb, nb = 3, 2, 4, 16, 8, 4, 16
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    table = np.full((b, mb), -1, np.int32)
    phys = rng.permutation(np.arange(1, nb))
    pos = np.asarray([5, 12, 25], np.int32)
    k = 0
    for r in range(b):
        for j in range(int(pos[r]) // bs + 1):
            table[r, j] = phys[k]
            k += 1
    return q, kp, vp, table, jnp.asarray(pos)


def _dense_window_oracle(q, kp, vp, table, pos, window, sinks):
    """Gather-to-dense + masked softmax in numpy: the §17 acceptance
    oracle (``kp <= p and (p - kp < window or kp < sinks)``). Only
    positions the mask admits are gathered, so evicted (-1) out-of-window
    table entries never need to exist."""
    qn, kpn, vpn = map(np.asarray, (q, kp, vp))
    tn, pn = np.asarray(table), np.asarray(pos)
    b, kvh, g, hd = qn.shape
    bs = kpn.shape[1]
    out = np.zeros((b, kvh, g, hd), np.float32)
    scale = hd ** -0.5
    for r in range(b):
        p = int(pn[r])
        sel = []
        for kpos in range(p + 1):
            if window is not None and not ((p - kpos) < window
                                           or kpos < sinks):
                continue
            blk = int(tn[r, kpos // bs])
            assert blk >= 0, "mask admits an unbacked position"
            sel.append((kpos, blk))
        ks = np.stack([kpn[blk, kpos % bs] for kpos, blk in sel])
        vs = np.stack([vpn[blk, kpos % bs] for kpos, blk in sel])
        for h in range(kvh):
            s = qn[r, h] @ ks[:, h].T * scale
            e = np.exp(s - s.max(axis=1, keepdims=True))
            out[r, h] = (e / e.sum(axis=1, keepdims=True)) @ vs[:, h]
    return out


# ragged window x sink x block-size interactions: windows below / straddling
# / beyond one block, sinks covering none / one / two blocks, a window so
# large it never binds, and a one-token window pinned entirely to sinks
WINDOW_CASES = [(8, 0), (3, 0), (5, 8), (8, 8), (3, 16), (1, 16), (100, 0)]


@pytest.mark.parametrize("window,sinks", WINDOW_CASES)
def test_windowed_paged_attention_matches_dense_masked_oracle(window, sinks):
    """§17 acceptance oracle: the windowed jnp path AND the Pallas
    first-live-block walk both reproduce a dense gather with the causal
    window+sink mask, across ragged window/sink/block-size combos."""
    q, kp, vp, table, pos = _kernel_fixture()
    want = _dense_window_oracle(q, kp, vp, table, pos, window, sinks)
    got_ref = paged_attention_ref(q, kp, vp, jnp.asarray(table), pos,
                                  window=window, sinks=sinks)
    np.testing.assert_allclose(np.asarray(got_ref), want,
                               rtol=1e-5, atol=1e-5)
    got_pl = paged_attention_op(q, kp, vp, jnp.asarray(table), pos,
                                window=window, sinks=sinks,
                                impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got_pl), want,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window,sinks", [(8, 0), (5, 8), (8, 8), (1, 16)])
def test_windowed_kernel_bit_identical_on_evicted_tables(window, sinks):
    """Out-of-window eviction is invisible to attention: clearing every
    table entry below the first live block (the exact set the engine's
    eviction pass frees) leaves windowed logits BIT-identical on both the
    jnp oracle and the Pallas kernel — proof no evicted block is read."""
    q, kp, vp, table, pos = _kernel_fixture()
    bs = kp.shape[1]
    sb = -(-sinks // bs)
    ev = table.copy()
    for r in range(table.shape[0]):
        fl = max((int(pos[r]) - window + 1) // bs, sb)
        ev[r, sb:fl] = -1
    for impl in ("ref", "pallas_interpret"):
        full = paged_attention_op(q, kp, vp, jnp.asarray(table), pos,
                                  window=window, sinks=sinks,
                                  impl=impl)
        evd = paged_attention_op(q, kp, vp, jnp.asarray(ev), pos,
                                 window=window, sinks=sinks,
                                 impl=impl)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(evd))


@pytest.mark.parametrize("bits,window,sinks",
                         [(8, 5, 8), (8, 8, 0), (4, 5, 8), (4, 1, 16)])
def test_windowed_pallas_matches_ref_quantized(bits, window, sinks):
    """§17 x §14: the first-live-block walk routes the quantized scale
    operands through the same dead-block index_map as the codes — windowed
    int8/int4 Pallas must match the windowed quantized jnp oracle."""
    spec, q, kf, vf, table, pos = _quant_kernel_fixture(bits)
    kp, ks = quantize_kv(kf, spec)
    vp, vs = quantize_kv(vf, spec)
    want = paged_attention_ref(q, kp, vp, table, pos, window=window,
                               sinks=sinks, k_scale=ks, v_scale=vs)
    got = paged_attention_op(q, kp, vp, table, pos, window=window,
                             sinks=sinks, impl="pallas_interpret",
                             k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# §17 eviction ops: release_range / evict_out_of_window state machine
# ---------------------------------------------------------------------------


def test_release_range_frees_only_unshared_blocks():
    alloc = kv_pool.init_alloc(9, 2, 4)
    alloc = kv_pool.alloc_range(alloc, 0, 0, 4)
    row0 = np.asarray(jax.device_get(alloc["table"][0]))
    alloc = kv_pool.share_prefix(alloc, 1, jnp.asarray(row0), 2)
    alloc = kv_pool.release_range(alloc, 0, 0, 3)
    a = _snap(alloc)
    assert (a["table"][0, :3] == -1).all()
    assert a["table"][0, 3] == row0[3], "untouched tail cleared"
    assert (a["table"][1, :2] == row0[:2]).all(), "sharer's row perturbed"
    assert a["ref"][row0[0]] == 1 and a["ref"][row0[1]] == 1, \
        "shared block freed under the sharer"
    assert a["ref"][row0[2]] == 0
    assert row0[2] in a["free"][: int(a["n_free"])].tolist()
    _check_alloc_invariants(alloc, nb=9)


def test_evict_out_of_window_respects_refcounts_sinks_and_retention():
    """The §17 eviction contract: sink blocks are pinned, a block shared
    with another slot (ref > 1) or retained by the LRU cache is decremented
    but NEVER freed, rows with live=False are untouched, and a second
    eviction at the same first-live index is a no-op."""
    alloc = kv_pool.init_alloc(17, 3, 4)
    alloc = kv_pool.alloc_range(alloc, 0, 0, 4)
    alloc = kv_pool.alloc_range(alloc, 2, 0, 3)
    row0 = np.asarray(jax.device_get(alloc["table"][0]))
    row2 = np.asarray(jax.device_get(alloc["table"][2]))
    alloc = kv_pool.share_prefix(alloc, 1, jnp.asarray(row0), 2)
    alloc = kv_pool.retain_block(alloc, int(row0[1]))  # LRU-held
    free0 = int(_snap(alloc)["n_free"])
    fl = jnp.asarray([3, 0, 0], jnp.int32)
    live = jnp.asarray([True, False, False])
    alloc = kv_pool.evict_out_of_window(alloc, fl, live, 1)
    a = _snap(alloc)
    # col 0 is a sink block: pinned, still mapped
    assert a["table"][0, 0] == row0[0] and a["ref"][row0[0]] == 2
    # col 1 was shared + retained: unmapped here, but never freed
    assert a["table"][0, 1] == -1
    assert a["ref"][row0[1]] == 2, "shared/retained block lost refs"
    assert row0[1] not in a["free"][: int(a["n_free"])].tolist()
    # col 2 was exclusive: freed
    assert a["table"][0, 2] == -1 and a["ref"][row0[2]] == 0
    assert row0[2] in a["free"][: int(a["n_free"])].tolist()
    # col 3 is at/above first-live: untouched
    assert a["table"][0, 3] == row0[3] and a["ref"][row0[3]] == 1
    # live=False rows untouched even though fl would evict nothing anyway
    assert (a["table"][1, :2] == row0[:2]).all()
    assert (a["table"][2] == row2).all()
    assert int(a["n_free"]) == free0 + 1
    _check_alloc_invariants(alloc)
    # idempotence: same first-live again evicts nothing
    again = _snap(kv_pool.evict_out_of_window(alloc, fl, live, 1))
    for k in ("table", "ref", "n_free"):
        np.testing.assert_array_equal(again[k], a[k])


def test_evict_out_of_window_dedups_a_block_shared_within_one_row():
    """A physical block mapped at TWO evicted columns of the same row (the
    self-share degenerate case) must lose both refs in one pass without
    being pushed to the free stack twice."""
    alloc = kv_pool.init_alloc(9, 2, 4)
    alloc = kv_pool.alloc_range(alloc, 0, 0, 1)
    row0 = np.asarray(jax.device_get(alloc["table"][0]))
    phys = np.full((4,), -1, np.int32)
    phys[0] = phys[1] = row0[0]
    alloc = kv_pool.share_prefix(alloc, 1, jnp.asarray(phys), 2)
    assert int(_snap(alloc)["ref"][row0[0]]) == 3
    alloc = kv_pool.evict_out_of_window(
        alloc, jnp.asarray([0, 2], jnp.int32),
        jnp.asarray([False, True]), 0)
    a = _snap(alloc)
    assert (a["table"][1, :2] == -1).all()
    assert a["ref"][row0[0]] == 1, "row-internal double-count"
    head = a["free"][: int(a["n_free"])].tolist()
    assert head.count(int(row0[0])) == 0
    _check_alloc_invariants(alloc, nb=9)
    alloc = kv_pool.free_slot(alloc, 0)
    a = _snap(alloc)
    assert int(a["n_free"]) == 8 and (a["ref"][1:] == 0).all()


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_window_eviction_invariants_under_random_op_storms(seed):
    """§17 storm: random WINDOW SIZES crossed with evict / release-range /
    retain / CoW-share / preempt / steal sequences. After every op the §13
    pool contract holds (``in_use + free == num_blocks - 1``), eviction
    never frees a block another slot still references (pre-op ref > 1) or
    a prefix-LRU-retained block, and after draining, the pool is whole."""
    rng = np.random.default_rng(seed)
    alloc = kv_pool.init_alloc(_NB, _SLOTS, _MB)
    stolen = None
    retained: list[int] = []        # host mirror of LRU-held device refs
    for _ in range(30):
        a = _snap(alloc)
        n_free = int(a["n_free"])
        occ = [s for s in range(_SLOTS) if (a["table"][s] >= 0).any()]
        empty = [s for s in range(_SLOTS) if s not in occ]
        op = rng.choice(["alloc", "share", "evict", "release", "retain",
                         "free", "preempt", "steal"])
        if op == "alloc" and empty:
            n = int(rng.integers(1, _MB + 1))
            if n <= n_free:
                alloc = kv_pool.alloc_range(alloc, int(rng.choice(empty)),
                                            0, n)
        elif op == "share" and occ and empty:
            # share_prefix's contract is a CONTIGUOUS valid prefix (the
            # engine only shares at admission, before any eviction can
            # punch holes in a row) — mirror that here
            src = int(rng.choice(occ))
            row = a["table"][src]
            lead = int(np.argmax(row < 0)) if (row < 0).any() else _MB
            if lead >= 1:
                alloc = kv_pool.share_prefix(
                    alloc, int(rng.choice(empty)), jnp.asarray(row),
                    int(rng.integers(1, lead + 1)))
        elif op == "evict" and occ:
            # random window geometry: the engine's eviction shape is
            # fl = max((pos - W + 1) // BS, sink_blocks); here fl and
            # sink_blocks are drawn directly to cover every ragged case
            sb = int(rng.integers(0, _MB))
            fl = np.zeros(_SLOTS, np.int32)
            live = np.zeros(_SLOTS, bool)
            for s in occ:
                if rng.random() < 0.8:
                    fl[s] = int(rng.integers(sb, _MB + 1))
                    live[s] = True
            ref_before = a["ref"].copy()
            alloc = kv_pool.evict_out_of_window(
                alloc, jnp.asarray(fl), jnp.asarray(live), sb)
            a2 = _snap(alloc)
            head = set(a2["free"][: int(a2["n_free"])].tolist())
            for s in np.where(live)[0]:
                for j in range(sb, fl[s]):
                    blk = int(a["table"][s, j])
                    if blk < 0:
                        continue
                    assert a2["table"][s, j] == -1
                    if ref_before[blk] > 1 or blk in retained:
                        assert blk not in head or a2["ref"][blk] == 0, \
                            "freed a block with live references"
                        if blk in retained:
                            assert a2["ref"][blk] >= 1, \
                                "freed an LRU-retained block"
                            assert blk not in head
        elif op == "release" and occ:
            s = int(rng.choice(occ))
            k = int((a["table"][s] >= 0).sum())
            start = int(rng.integers(0, k))
            alloc = kv_pool.release_range(
                alloc, s, start, int(rng.integers(1, k - start + 1)))
        elif op == "retain":
            mapped = np.unique(a["table"][a["table"] >= 0])
            cand = [int(b) for b in mapped if b not in retained]
            if cand and rng.random() < 0.7:
                blk = int(rng.choice(cand))
                alloc = kv_pool.retain_block(alloc, blk)
                retained.append(blk)
            elif retained:
                blk = retained.pop(int(rng.integers(0, len(retained))))
                alloc = kv_pool.release_block(alloc, blk)
        elif op == "free" and occ:
            alloc = kv_pool.free_slot(alloc, int(rng.choice(occ)))
        elif op == "preempt" and occ:
            pos = np.zeros(_SLOTS, np.int32)
            active = np.zeros(_SLOTS, bool)
            for s in occ:
                k = int((a["table"][s] >= 0).sum())
                if k < _MB:
                    pos[s], active[s] = k * BS, True
            alloc, _pre = kv_pool.preempt_for_free(
                alloc, jnp.asarray(pos), jnp.asarray(active),
                jnp.asarray(rng.integers(1, 20, _SLOTS), jnp.int32),
                jnp.asarray(rng.permutation(_SLOTS) + 1, jnp.int32), BS)
        elif op == "steal":
            if stolen is None and n_free > 0:
                alloc, stolen = kv_pool.steal_blocks(
                    alloc, int(rng.integers(1, n_free + 1)))
            elif stolen is not None:
                alloc = kv_pool.unsteal_blocks(alloc, stolen)
                stolen = None
        _check_alloc_invariants(alloc)
        a3 = _snap(alloc)
        head = set(a3["free"][: int(a3["n_free"])].tolist())
        assert not (head & set(retained)), "retained block on free stack"
    # drain: give back steals and retention, free every slot -> pool whole
    if stolen is not None:
        alloc = kv_pool.unsteal_blocks(alloc, stolen)
    for blk in retained:
        alloc = kv_pool.release_block(alloc, blk)
    a = _snap(alloc)
    for s in range(_SLOTS):
        if (a["table"][s] >= 0).any():
            alloc = kv_pool.free_slot(alloc, s)
    a = _snap(alloc)
    assert int(a["n_free"]) == _NB - 1
    assert set(a["free"][: _NB - 1].tolist()) == set(range(1, _NB))
    assert (a["ref"][1:] == 0).all()
