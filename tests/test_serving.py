"""Serving fast-path tests: batched prefill, scheduler, quantized decode.

Covers the serving hot path (DESIGN.md §8/§11) and the request-lifecycle
API (§12):
  * batched prefill ≡ the seed's scan-of-decode-steps (logits equivalence),
  * continuous-batching scheduler invariants (slot isolation, FIFO
    admission, retirement/reuse),
  * the mixed-precision integer decode path: fused-dequant GEMMs vs the
    fake-quant train-mode reference, and the packed sub-byte storage path
    vs the unpacked int8 oracle — bit-for-bit, on every transformer config,
  * SamplingParams + in-tick sampling: the temperature=0 facade is
    bit-identical to the argmax oracle on every servable arch and layout,
    seeded streams are invariant to slot placement / admission order / KV
    layout, stop tokens retire in-tick, and the host-sync ledger stays at
    one sync per tick with sampling enabled.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_smoke_config
from repro.core.sites import QuantContext
from repro.models import transformer as tfm
from repro.quant import specs_from_state
from repro.serving import (Request, SamplingParams, ServingEngine,
                           TokenEvent, export_int_model,
                           make_mixed_quant_state, make_uniform_quant_state)
from repro.serving.sampling import mask_logits, sample_tokens

ARCH = "tinyllama-1.1b"


def _model(seed=0, arch=ARCH):
    cfg = get_smoke_config(arch)
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, params


def _quant_state(cfg, params, gate_init=2.2, granularity="per_channel"):
    return make_uniform_quant_state(cfg, params, gate_init=gate_init,
                                    granularity=granularity)


def _serve_qc(qs, qw, matmul_impl="ref"):
    return QuantContext(
        mode="serve", cfg=qs["qcfg"],
        specs=specs_from_state(qs["gates"], qs["betas"], qs["signed"]),
        qweights=qw, matmul_impl=matmul_impl)


# ---------------------------------------------------------------------------
# Batched prefill ≡ scan of decode steps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("plen", [3, 7])
def test_prefill_slot_matches_scan_of_decode_steps(plen):
    """One causal forward per slot == the seed's token-by-token prefill."""
    cfg, params = _model()
    prompt = np.arange(1, plen + 1, dtype=np.int32)
    qc = QuantContext(mode="off")

    # seed path: scan decode_step over the prompt on a fresh cache
    cache_ref = tfm.init_cache(cfg, 1, 32)
    for t in prompt:
        logits_ref, cache_ref = tfm.decode_step(
            qc, params, cache_ref, jnp.asarray([t], jnp.int32), cfg)

    # new path: right-padded single forward into slot 0
    spad = 16
    toks = np.zeros((1, spad), np.int32)
    toks[0, :plen] = prompt
    cache_new = tfm.init_cache(cfg, 1, 32)
    logits_new, cache_new = tfm.prefill_slot(
        qc, params, jnp.asarray(toks), plen, cache_new, 0, cfg)

    np.testing.assert_allclose(
        np.asarray(logits_new[0, plen - 1, : cfg.vocab_size]),
        np.asarray(logits_ref[0, 0, : cfg.vocab_size]),
        rtol=2e-2, atol=2e-2)
    assert int(cache_new["pos"][0]) == plen == int(cache_ref["pos"][0])

    # and the caches are interchangeable: decode diverges by bf16 noise only
    nxt = jnp.asarray([5], jnp.int32)
    l1, _ = tfm.decode_step(qc, params, cache_ref, nxt, cfg)
    l2, _ = tfm.decode_step(qc, params, cache_new, nxt, cfg)
    np.testing.assert_allclose(
        np.asarray(l1[..., : cfg.vocab_size]),
        np.asarray(l2[..., : cfg.vocab_size]), rtol=2e-2, atol=2e-2)


def test_prefill_slot_counts_one_forward(capsys):
    """Engine accounting: one batched forward per admission, vs plen
    decode-step forwards (each ``slots`` wide) in the seed path."""
    cfg, params = _model()
    eng = ServingEngine(cfg, params, slots=4, max_seq=64)
    rng = np.random.default_rng(0)
    for i in range(4):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab_size, (9,)),
                           max_new=2))
    eng.run_to_completion()
    st = eng.stats
    assert st["prefill_forwards"] == 4
    assert st["seed_equiv_forwards"] == 4 * 9
    # slot-forward ratio: (plen * slots) seed slot-forwards vs 1 per admission
    ratio = st["seed_equiv_forwards"] * eng.slots / st["prefill_forwards"]
    assert ratio >= eng.slots


@pytest.mark.parametrize("arch,plen", [
    ("mamba2-1.3b", 11),        # ssm_chunk=8: chunk-aligned prefix + 3-token
    ("mamba2-1.3b", 6),         #   teacher-forced tail / pure exact length
    ("recurrentgemma-2b", 9),   # rglru + local ring: exact-length prefill
])
def test_recurrent_arch_prefill_matches_scan_of_decode(arch, plen):
    """Recurrent-state archs must not bake padding into the slot state:
    engine output == manual scan-of-decode-steps greedy, even with another
    request mid-generation in the neighboring slot (teacher-forced tail
    steps must not touch other slots' recurrent state)."""
    cfg, params = _model(arch=arch)
    rng = np.random.default_rng(plen)
    prompt = rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32)

    eng = ServingEngine(cfg, params, slots=2, max_seq=32)
    # occupy slot 0 first so the probed request admits mid-flight
    eng.submit(Request(rid=9, prompt=rng.integers(0, cfg.vocab_size, (5,)),
                       max_new=8))
    eng.step()
    eng.submit(Request(rid=0, prompt=prompt, max_new=4))
    fin = {r.rid: r.output for r in eng.run_to_completion()}

    qc = QuantContext(mode="off")
    cache = tfm.init_cache(cfg, 1, 32)
    for t in prompt:
        logits, cache = tfm.decode_step(qc, params, cache,
                                        jnp.asarray([t], jnp.int32), cfg)
    outs = [int(jnp.argmax(logits[0, 0, : cfg.vocab_size]))]
    for _ in range(3):
        logits, cache = tfm.decode_step(
            qc, params, cache, jnp.asarray([outs[-1]], jnp.int32), cfg)
        outs.append(int(jnp.argmax(logits[0, 0, : cfg.vocab_size])))
    assert fin[0] == outs


# ---------------------------------------------------------------------------
# Scheduler invariants
# ---------------------------------------------------------------------------


def test_slot_isolation_prefill_does_not_corrupt_neighbors():
    """A request's output is identical whether it shares the engine with
    other requests (admitted mid-flight, forcing interleaved prefills) or
    runs alone — i.e. one slot's prefill never corrupts another slot's KV."""
    cfg, params = _model()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, (int(p),))
               for p in (5, 9, 4, 11, 6)]

    eng = ServingEngine(cfg, params, slots=2, max_seq=64)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=6))
    shared = {r.rid: r.output for r in eng.run_to_completion()}

    for i, p in enumerate(prompts):
        solo = ServingEngine(cfg, params, slots=1, max_seq=64)
        solo.submit(Request(rid=i, prompt=p, max_new=6))
        out = solo.run_to_completion()[0].output
        assert shared[i] == out, f"slot sharing changed request {i}"


def test_admission_and_retirement_ordering():
    """FIFO admission; retired slots immediately rehost the next waiter."""
    cfg, params = _model()
    rng = np.random.default_rng(2)
    eng = ServingEngine(cfg, params, slots=2, max_seq=64)
    # staggered lengths force slot 0 to retire before slot 1
    lens = [2, 5, 3, 4]
    for i, n in enumerate(lens):
        eng.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, (4,)),
                           max_new=n))

    eng._admit()
    assert [r.rid for r in eng.slot_req] == [0, 1]  # FIFO admission
    assert [r.rid for r in eng.waiting] == [2, 3]

    fin = eng.run_to_completion()
    rids = [r.rid for r in fin]
    assert sorted(rids) == [0, 1, 2, 3]
    assert rids.index(0) < rids.index(1)  # fewer tokens -> retires first
    assert rids.index(0) < rids.index(2)  # 2 rehosts 0's slot after it frees
    assert all(len(r.output) == n for r, n in
               zip(sorted(fin, key=lambda r: r.rid), lens))
    assert eng.slot_req == [None, None] and not eng.waiting


def test_max_new_one_retires_at_admission():
    cfg, params = _model()
    eng = ServingEngine(cfg, params, slots=1, max_seq=32)
    eng.submit(Request(rid=0, prompt=np.asarray([1, 2, 3], np.int32),
                       max_new=1))
    fin = eng.run_to_completion()
    assert len(fin) == 1 and len(fin[0].output) == 1 and fin[0].done


def test_device_resident_state_one_sync_shapes():
    """The tick's host transfer is three (slots,)-vectors; outputs accrue
    only for slots that were active when the tick ran."""
    cfg, params = _model()
    eng = ServingEngine(cfg, params, slots=3, max_seq=32)
    eng.submit(Request(rid=0, prompt=np.asarray([1, 2], np.int32), max_new=3))
    eng.step()
    # slots 1/2 idle: state must keep them inactive with no output
    active = np.asarray(jax.device_get(eng.state["active"]))
    assert active.tolist() == [True, False, False]
    assert len(eng.slot_req[0].output) == 2  # prefill token + one tick


# ---------------------------------------------------------------------------
# Int8 decode path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("granularity", ["per_tensor", "per_channel"])
def test_int8_decode_matches_fake_quant_reference(granularity):
    """Serve-mode logits (fused-dequant GEMM off int codes) match the
    train-mode fake-quant fp32 reference within bf16 matmul tolerance."""
    cfg, params = _model()
    qs = _quant_state(cfg, params, granularity=granularity)
    qw, ledger = export_int_model(params, cfg, qs)
    assert qw, "no sites exported"
    assert all(b <= 8 for b in ledger.max_bits().values())

    toks = jnp.asarray([3, 7], jnp.int32)
    cache = tfm.init_cache(cfg, 2, 16)
    from repro.core.sites import merge_ranges
    qc_train = QuantContext(mode="train", cfg=qs["qcfg"], gates=qs["gates"],
                            ranges=merge_ranges(qs["betas"], qs["signed"]),
                            probes={})
    lt, _ = tfm.decode_step(qc_train, params, cache, toks, cfg)
    ls, _ = tfm.decode_step(_serve_qc(qs, qw), params, cache, toks, cfg)
    lt = np.asarray(lt[..., : cfg.vocab_size])
    ls = np.asarray(ls[..., : cfg.vocab_size])
    np.testing.assert_allclose(ls, lt, rtol=5e-2, atol=2e-2)


def test_int8_pallas_interpret_matches_ref_path():
    """The Pallas kernel (interpret) and the jnp reference produce the same
    serve-mode logits — kernel validation at the model level."""
    cfg, params = _model()
    qs = _quant_state(cfg, params)
    qw, _ = export_int_model(params, cfg, qs)
    toks = jnp.asarray([11], jnp.int32)
    cache = tfm.init_cache(cfg, 1, 16)
    outs = []
    for impl in ("ref", "pallas_interpret"):
        l, _ = tfm.decode_step(_serve_qc(qs, qw, impl), params, cache, toks,
                               cfg)
        outs.append(np.asarray(l[..., : cfg.vocab_size]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-5)


def test_int8_engine_serves_end_to_end():
    """Full engine pass in serve mode: tokens come off the int8 hot path."""
    cfg, params = _model()
    qs = _quant_state(cfg, params)
    eng = ServingEngine(cfg, params, slots=2, max_seq=64, quant_state=qs,
                        matmul_impl="ref")
    assert len(eng.qweights) >= 8  # attn q/k/v/o + mlp gate/up/down + head
    rng = np.random.default_rng(4)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, (5,)),
                           max_new=4))
    fin = eng.run_to_completion()
    assert len(fin) == 3
    for r in fin:
        assert len(r.output) == 4
        assert all(0 <= t < cfg.vocab_size for t in r.output)


def test_export_skips_high_bit_sites_and_ledgers_them():
    """Sites whose gate maps above 8 bits are not exported (they'd lose
    their grid in int8) and serve via the fake-quant fallback — and that
    fallback is no longer silent: every rejected site lands in the export
    ledger with its reason, and the export warns once."""
    cfg, params = _model()
    qs = _quant_state(cfg, params, gate_init=4.5)  # T(4.5) = 32 bits
    with pytest.warns(UserWarning, match="NOT fully integer-quantized"):
        qw, ledger = export_int_model(params, cfg, qs)
    assert qw == {} and ledger.max_bits() == {}
    fb = ledger.fallbacks()
    assert fb and all(e["reason"] == "bits>8" for e in fb.values())
    assert all(e["bits"] == 32 for e in fb.values())
    # engine still runs on the fallback path
    with pytest.warns(UserWarning, match="NOT fully integer-quantized"):
        eng = ServingEngine(cfg, params, slots=1, max_seq=32, quant_state=qs)
    eng.submit(Request(rid=0, prompt=np.asarray([1, 2, 3], np.int32),
                       max_new=2))
    fin = eng.run_to_completion()
    assert len(fin) == 1 and len(fin[0].output) == 2


# ---------------------------------------------------------------------------
# Packed sub-byte decode: bit-for-bit against the int8 oracle, every config
# ---------------------------------------------------------------------------


def _decode_inputs(cfg, rng):
    if cfg.embed_input:
        return jnp.asarray(rng.integers(0, cfg.vocab_size, (2,)), jnp.int32)
    return jnp.asarray(rng.normal(size=(2, 1, cfg.d_model)), jnp.float32) * 0.3


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_packed_decode_matches_int8_oracle_every_config(arch):
    """The §11 acceptance gate: a mixed 2/4/8-bit export served from PACKED
    sub-byte storage produces decode logits bit-for-bit identical to the
    same export in the unpacked int8 oracle layout, for every architecture.
    Packing must be pure storage — zero numerics."""
    cfg, params = _model(arch=arch)
    qs = make_mixed_quant_state(cfg, params)
    qw_packed, ledger = export_int_model(params, cfg, qs)
    qw_oracle, _ = export_int_model(params, cfg, qs, pack=False)
    assert qw_packed, f"{arch}: no sites exported"
    assert any(qt.storage_bits < 8 for qt in qw_packed.values()), \
        f"{arch}: mixed state exported no sub-byte site"
    # packed device bytes follow the ceil(bits/8) accounting exactly
    for key, qt in qw_packed.items():
        per = 8 // qt.storage_bits
        want_rows = -(-qt.k // per)
        assert qt.codes.shape[-2] == want_rows, key
        assert qt.codes_bytes() < qw_oracle[key].codes_bytes() \
            or qt.storage_bits == 8, key

    rng = np.random.default_rng(7)
    toks = _decode_inputs(cfg, rng)
    cache = tfm.init_cache(cfg, 2, 16)
    lp, _ = tfm.decode_step(_serve_qc(qs, qw_packed), params, cache, toks,
                            cfg)
    lo, _ = tfm.decode_step(_serve_qc(qs, qw_oracle), params, cache, toks,
                            cfg)
    np.testing.assert_array_equal(np.asarray(lp), np.asarray(lo),
                                  err_msg=f"{arch}: packed != int8 oracle")


def test_engine_serves_packed_sub_byte_end_to_end():
    """Engine pass on a mixed 2/4/8-bit export: tokens come off the packed
    kernels, and the quant_report ledger shows sub-byte device bytes."""
    cfg, params = _model()
    qs = make_mixed_quant_state(cfg, params)
    eng = ServingEngine(cfg, params, slots=2, max_seq=64, quant_state=qs,
                        matmul_impl="ref")
    assert any(qt.storage_bits < 8 for qt in eng.qweights.values())
    rng = np.random.default_rng(11)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, (5,)),
                           max_new=4))
    fin = eng.run_to_completion()
    assert len(fin) == 3 and all(len(r.output) == 4 for r in fin)
    rep = eng.quant_report()
    t = rep["totals"]
    assert t["bytes_per_weight"] < t["uniform_int8_bytes_per_weight"]
    assert t["bytes_device"] < t["bytes_uniform_int8"] < t["bytes_fp32"]
    assert t["fallback_sites"] == 0
    assert rep["bops"]["model"] < rep["bops"]["uniform_int8"]


# ---------------------------------------------------------------------------
# Request lifecycle: SamplingParams + in-tick sampling (DESIGN.md §12)
# ---------------------------------------------------------------------------

# every arch the engine can serve from token prompts (the two modality
# stubs take embeddings, not tokens, and have no request-level entry)
TOKEN_ARCHS = [a for a in ALL_ARCHS if get_smoke_config(a).embed_input]


def test_sampling_params_validation():
    p = SamplingParams(temperature=0.7, top_k=5, top_p=0.9, seed=1,
                      stop=(3, 7), max_new=4)
    assert not p.greedy and p.stop == (3, 7)
    assert SamplingParams().greedy
    for bad in (dict(temperature=-1.0), dict(top_k=-1), dict(top_p=0.0),
                dict(top_p=1.5), dict(max_new=0), dict(stop=(-2,))):
        with pytest.raises(ValueError):
            SamplingParams(**bad)


def test_mask_logits_top_k_top_p_support():
    """top-k bounds the kept set by rank; top-p keeps the smallest head of
    the sorted distribution whose mass reaches p (first token always kept);
    disabled knobs (0 / 1.0) keep everything."""
    rng = np.random.default_rng(0)
    l = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    off = mask_logits(l, jnp.zeros((4,), jnp.int32), jnp.ones((4,)))
    np.testing.assert_array_equal(np.asarray(off), np.asarray(l))

    k = jnp.asarray([1, 3, 8, 0], jnp.int32)
    kept = (np.asarray(mask_logits(l, k, jnp.ones((4,)))) > -1e30).sum(-1)
    assert kept.tolist() == [1, 3, 8, 64]
    # the kept lanes are exactly the top-k by value
    m = np.asarray(mask_logits(l, k, jnp.ones((4,))))
    for r in range(3):
        top = set(np.argsort(-np.asarray(l[r]))[: int(k[r])])
        assert set(np.nonzero(m[r] > -1e30)[0]) == top

    tiny = mask_logits(l, jnp.zeros((4,), jnp.int32),
                       jnp.full((4,), 1e-6, jnp.float32))
    kept = (np.asarray(tiny) > -1e30).sum(-1)
    assert kept.tolist() == [1, 1, 1, 1]  # first sorted token always kept
    p = jnp.asarray([0.5, 0.9, 1.0, 0.99], jnp.float32)
    m = np.asarray(mask_logits(l, jnp.zeros((4,), jnp.int32), p))
    for r in range(4):
        probs = np.exp(np.asarray(l[r])) / np.exp(np.asarray(l[r])).sum()
        order = np.argsort(-probs)
        csum = np.cumsum(probs[order])
        want = order[: int(np.searchsorted(csum, float(p[r])) + 1)]
        assert set(np.nonzero(m[r] > -1e30)[0]) == set(want), r


def test_sample_tokens_greedy_rows_bit_exact_and_support():
    """temperature<=0 rows return exactly argmax; sampled rows only ever
    draw from their top-k support."""
    rng = np.random.default_rng(1)
    l = jnp.asarray(rng.normal(size=(3, 32)) * 3, jnp.float32)
    greedy = np.asarray(jnp.argmax(l, -1))
    for trial in range(20):
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(3) + 3 * trial)
        toks = np.asarray(sample_tokens(
            l, keys, jnp.asarray([0.0, 1.5, 0.0]),
            jnp.asarray([0, 3, 0], jnp.int32), jnp.ones((3,))))
        assert toks[0] == greedy[0] and toks[2] == greedy[2]
        assert toks[1] in set(np.argsort(-np.asarray(l[1]))[:3])


_F32_MIN = float(np.finfo(np.float32).min)


def _mask_logits_by_argsort(logits, top_k, top_p):
    """The nucleus mask as first written (argsort, gather, cumulative
    mass, inverse argsort, gather): the oracle ``mask_logits`` must equal
    bit for bit."""
    v = logits.shape[-1]
    order = jnp.argsort(-logits, axis=-1, stable=True)
    ranked = jnp.take_along_axis(logits, order, axis=-1)
    rank = jnp.arange(v)[None, :]
    k = jnp.where(top_k > 0, top_k, v)[:, None]
    in_k = rank < k
    probs = jax.nn.softmax(jnp.where(in_k, ranked, _F32_MIN), axis=-1)
    mass_before = jnp.cumsum(probs, axis=-1) - probs
    keep = in_k & (mass_before < top_p[:, None])
    keep = keep | (rank == 0)
    ranked = jnp.where(keep, ranked, _F32_MIN)
    inverse = jnp.argsort(order, axis=-1)
    return jnp.take_along_axis(ranked, inverse, axis=-1)


@pytest.mark.parametrize("rows", ["normal", "rounded", "tied"])
@pytest.mark.parametrize("v", [64, 1000, 151936])
def test_mask_logits_equals_argsort_oracle_bit_for_bit(v, rows,
                                                       monkeypatch):
    """The per-row threshold cut keeps exactly the argsort oracle's lanes,
    ties included, for every top-k in {0, 1, 3, 8} x top-p in {1e-6, 0.5,
    0.9, 0.99, 1.0} on flat and peaked rows; so ``sample_tokens`` draws
    the same tokens under either mask."""
    from repro.serving import sampling

    grid = [(k, p) for k in (0, 1, 3, 8) for p in (1e-6, 0.5, 0.9, 0.99,
                                                   1.0)]
    b = 2 * len(grid)
    rng = np.random.default_rng(v)
    scale = np.repeat([1.0, 8.0], len(grid))[:, None]  # flat, peaked
    l = rng.normal(size=(b, v)) * scale
    if rows == "rounded":
        l = np.round(l, 1)
    elif rows == "tied":
        l = np.broadcast_to(np.round(l[:, :1], 1), l.shape)
    l = jnp.asarray(l, jnp.float32)
    k = jnp.asarray([g[0] for g in grid] * 2, jnp.int32)
    p = jnp.asarray([g[1] for g in grid] * 2, jnp.float32)

    got = np.asarray(mask_logits(l, k, p))
    want = np.asarray(_mask_logits_by_argsort(l, k, p))
    assert np.array_equal(got, want)

    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(b) + v)
    temp = jnp.asarray(np.where(np.arange(b) % 7 == 0, 0.0, 0.7),
                       jnp.float32)
    toks = np.asarray(sample_tokens(l, keys, temp, k, p))
    monkeypatch.setattr(sampling, "mask_logits", _mask_logits_by_argsort)
    np.testing.assert_array_equal(
        toks, np.asarray(sample_tokens(l, keys, temp, k, p)))


@pytest.mark.parametrize("arch", TOKEN_ARCHS)
def test_generate_argmax_matches_manual_greedy_every_arch(arch):
    """The §12 acceptance gate: temperature=0 generation through the
    ``generate()`` facade is identical to the manual scan-of-decode-steps
    argmax oracle — the pre-redesign greedy path — on every servable arch,
    in the ring layout AND (where the arch has attention) the paged one."""
    cfg, params = _model(arch=arch)
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)

    qc = QuantContext(mode="off")
    cache = tfm.init_cache(cfg, 1, 32)
    for t in prompt:
        logits, cache = tfm.decode_step(qc, params, cache,
                                        jnp.asarray([int(t)], jnp.int32), cfg)
    want = [int(jnp.argmax(logits[0, 0, : cfg.vocab_size]))]
    for _ in range(2):
        logits, cache = tfm.decode_step(
            qc, params, cache, jnp.asarray([want[-1]], jnp.int32), cfg)
        want.append(int(jnp.argmax(logits[0, 0, : cfg.vocab_size])))

    kinds = list(cfg.block_pattern) + list(cfg.remainder_kinds)
    layouts = ["ring"]
    if any(k in ("global", "local") for k in kinds):
        layouts.append("paged")
    for layout in layouts:
        eng = ServingEngine(cfg, params, slots=2, max_seq=32,
                            kv_layout=layout)
        res = eng.generate([prompt], SamplingParams(max_new=3))
        assert res[0].tokens == want, (arch, layout)
        assert res[0].finish_reason == "length"


def test_seed_determinism_across_placement_order_and_layout():
    """Identical ``SamplingParams(seed=...)`` produce identical token
    streams no matter which slot hosts the request, what was admitted
    before it, or which KV layout backs the cache — the token stream is a
    pure function of (prompt, params)."""
    cfg, params = _model()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (9,))
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95, seed=123,
                        max_new=6)
    streams = []
    for layout in ("ring", "paged"):
        solo = ServingEngine(cfg, params, slots=3, max_seq=64,
                             kv_layout=layout)
        streams.append((layout, "solo", solo.generate([prompt], sp)[0].tokens))
        # crowded: two sampled decoys admitted first push the probe into
        # slot 2, and it admits mid-flight
        eng = ServingEngine(cfg, params, slots=3, max_seq=64,
                            kv_layout=layout)
        for i in (50, 51):
            eng.submit(Request(
                rid=i, prompt=rng.integers(0, cfg.vocab_size, (5,)),
                params=SamplingParams(temperature=0.5, seed=i, max_new=9)))
        eng.step()
        streams.append((layout, "crowded", eng.generate([prompt], sp)[0].tokens))
    want = streams[0][2]
    assert len(set(want)) > 1
    for layout, mode, got in streams[1:]:
        assert got == want, f"{layout}/{mode} diverged: {got} vs {want}"


def test_seeded_stream_survives_prefix_shared_admission():
    """A fully prefix-shared (teacher-forced, zero-prefill) admission of the
    same prompt+params reproduces the registrant's sampled stream: the key
    chain is positioned by tokens emitted, not by admission path."""
    cfg, params = _model()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, (11,))
    sp = SamplingParams(temperature=0.9, top_p=0.9, seed=7, max_new=5)
    eng = ServingEngine(cfg, params, slots=2, max_seq=64)
    a, b = eng.generate([prompt, prompt], [sp, sp])
    assert eng.stats["shared_admissions"] == 1
    assert a.tokens == b.tokens
    assert len(set(a.tokens)) > 1


def test_host_sync_ledger_one_sync_per_tick_with_sampling():
    """§8's one-host-sync-per-tick contract survives in-tick sampling: the
    ledger shows exactly one transfer per decode tick, and none from the
    sampling math itself."""
    cfg, params = _model()
    eng = ServingEngine(cfg, params, slots=2, max_seq=64)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, (6,)) for _ in range(3)]
    eng.generate(prompts, SamplingParams(temperature=1.0, top_p=0.9, seed=3,
                                         max_new=5))
    st = eng.stats
    assert st["decode_ticks"] > 0
    assert st["tick_syncs"] == st["decode_ticks"]
    # admission first-tokens are fetched ONE batched transfer per wave:
    # 3 requests through 2 slots = 2 waves (no prefix-registration reads,
    # the 6-token prompts hold no full block)
    assert st["admit_syncs"] == 2
    assert st["stat_syncs"] == 0


def test_generate_stream_yields_per_tick_deltas():
    cfg, params = _model()
    eng = ServingEngine(cfg, params, slots=2, max_seq=64)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, (4,)),
               rng.integers(0, cfg.vocab_size, (7,))]
    events = list(eng.generate_stream(prompts, SamplingParams(max_new=4)))
    assert all(isinstance(ev, TokenEvent) for ev in events)
    by_rid = {}
    for ev in events:
        by_rid.setdefault(ev.rid, []).append(ev)
    assert len(by_rid) == 2
    for evs in by_rid.values():
        assert [e.index for e in evs] == [0, 1, 2, 3]
        assert [e.done for e in evs] == [False, False, False, True]
        assert evs[-1].finish_reason == "length"
        assert all(e.finish_reason is None for e in evs[:-1])


def test_generate_on_token_callback_matches_results():
    cfg, params = _model()
    eng = ServingEngine(cfg, params, slots=2, max_seq=64)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, (5,)) for _ in range(2)]
    seen = []
    res = eng.generate(prompts, SamplingParams(max_new=3),
                       on_token=lambda ev: seen.append(ev))
    streamed = {}
    for ev in seen:
        streamed.setdefault(ev.rid, []).append(ev.token)
    assert {r.rid: r.tokens for r in res} == streamed


def test_stop_token_truncates_stream_and_sets_reason():
    """Stop tokens end the request in the tick that emits them — including
    a stop hit on the very first (prefill-sampled) token — and the slot
    rehosts the next request cleanly."""
    cfg, params = _model()
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, cfg.vocab_size, (7,))
    eng = ServingEngine(cfg, params, slots=1, max_seq=64)
    base = eng.generate([prompt], SamplingParams(max_new=8))[0].tokens

    mid = base[3]
    k = base.index(mid)
    eng = ServingEngine(cfg, params, slots=1, max_seq=64)
    res = eng.generate([prompt], SamplingParams(max_new=8, stop=(mid,)))[0]
    assert res.tokens == base[: k + 1]
    assert res.finish_reason == "stop"

    # first-token stop: retires at admission, zero decode ticks for it,
    # and the deactivated slot serves the next request unperturbed
    eng = ServingEngine(cfg, params, slots=1, max_seq=64)
    r0, r1 = eng.generate([prompt, prompt],
                          [SamplingParams(max_new=8, stop=(base[0],)),
                           SamplingParams(max_new=8)])
    assert r0.tokens == [base[0]] and r0.finish_reason == "stop"
    assert r1.tokens == base


def test_request_legacy_max_new_folds_into_params():
    req = Request(rid=0, prompt=np.asarray([1, 2], np.int32), max_new=9)
    assert req.params.max_new == 9 and req.params.greedy
    req = Request(rid=1, prompt=np.asarray([1], np.int32),
                  params=SamplingParams(max_new=3))
    assert req.max_new == 3


def test_too_many_stop_tokens_rejected_at_submit():
    cfg, params = _model()
    eng = ServingEngine(cfg, params, slots=1, max_seq=32, max_stop=2)
    with pytest.raises(ValueError, match="stop tokens"):
        eng.submit(Request(rid=0, prompt=np.asarray([1], np.int32),
                           params=SamplingParams(stop=(1, 2, 3))))
    # a bad batch member must not orphan earlier members in the queue
    with pytest.raises(ValueError, match="stop tokens"):
        eng.generate([np.asarray([1], np.int32), np.asarray([2], np.int32)],
                     [SamplingParams(), SamplingParams(stop=(1, 2, 3))])
    assert not eng.waiting


def test_generate_finishing_on_final_permitted_tick_returns():
    """max_ticks boundary: a batch that completes on the last allowed tick
    must return its results, not raise."""
    cfg, params = _model()
    eng = ServingEngine(cfg, params, slots=1, max_seq=32)
    res = eng.generate([np.asarray([1, 2], np.int32)],
                       SamplingParams(max_new=3), max_ticks=2)
    assert res[0].tokens and res[0].finish_reason == "length"
    evs = list(eng.generate_stream([np.asarray([3, 4], np.int32)],
                                   SamplingParams(max_new=3), max_ticks=2))
    assert len(evs) == 3 and evs[-1].done
    with pytest.raises(RuntimeError, match="still running"):
        eng.generate([np.asarray([5], np.int32)],
                     SamplingParams(max_new=8), max_ticks=2)


def test_generate_stream_submits_eagerly():
    """The batch must be in the queue before the stream is first advanced,
    so other engine traffic can pick it up either way."""
    cfg, params = _model()
    eng = ServingEngine(cfg, params, slots=1, max_seq=32)
    stream = eng.generate_stream([np.asarray([1, 2, 3], np.int32)],
                                 SamplingParams(max_new=2))
    assert len(eng.waiting) == 1
    assert len(list(stream)) == 2


# ---------------------------------------------------------------------------
# Quantized KV cache: int8 blocks vs the float-pool oracle (DESIGN.md §14)
# ---------------------------------------------------------------------------

import math  # noqa: E402

from repro.quant import KVQuantSpec  # noqa: E402
from repro.serving import kv_pool  # noqa: E402

KV_BS = 8
KV_MAX_SEQ = 32


def _kv_spec(cfg, bits=8):
    # same alignment rule as the engine: largest power-of-two group <= 32
    # that divides head_dim, so the fused kernel never sees a ragged group
    return KVQuantSpec(bits=bits, group_size=math.gcd(cfg.head_dim, 32),
                       head_dim=cfg.head_dim)


def _kv_inputs(cfg, plen, key=1):
    k = jax.random.PRNGKey(key)
    if cfg.embed_input:
        return jax.random.randint(k, (1, plen), 0, cfg.vocab_size)
    return jax.random.normal(k, (1, plen, cfg.d_model), jnp.float32) * 0.3


def _kv_mrope(cfg, s):
    if cfg.mrope_sections is None:
        return None
    return jnp.broadcast_to(jnp.arange(s)[None, None, :], (3, 1, s))


def _kv_decode_logits(cfg, params, layout, kv_spec):
    """Teacher-forced prefill + 4 decode steps; per-step logit rows."""
    qc = QuantContext(mode="off")
    plen = 9
    x = _kv_inputs(cfg, plen)
    kv_dtype = jnp.float32 if kv_spec is None else jnp.bfloat16
    if layout == "ring":
        cache = tfm.init_cache(cfg, 1, KV_MAX_SEQ, kv_dtype=kv_dtype,
                               kv_spec=kv_spec)
        alloc = None
    else:
        mb = KV_MAX_SEQ // KV_BS
        cache = tfm.init_paged_cache(cfg, 1, mb + 1, KV_BS,
                                     kv_dtype=kv_dtype, kv_spec=kv_spec)
        alloc = kv_pool.init_alloc(mb + 1, 1, mb)
        alloc = kv_pool.alloc_range(alloc, 0, 0, -(-plen // KV_BS))
    table = None if alloc is None else alloc["table"]
    lg, cache = tfm.prefill_slot(qc, params, x, plen, cache, 0, cfg,
                                 mrope_pos=_kv_mrope(cfg, plen),
                                 block_table=table)
    rows = [np.asarray(lg[0, plen - 1, : cfg.vocab_size])]
    adv = jnp.ones((1,), jnp.int32)
    rng = np.random.default_rng(2)
    for t in range(4):
        if cfg.embed_input:
            tok = jnp.asarray([int(rng.integers(0, cfg.vocab_size))],
                              jnp.int32)
        else:
            tok = jax.random.normal(jax.random.PRNGKey(10 + t),
                                    (1, 1, cfg.d_model), jnp.float32) * 0.3
        if alloc is not None:
            alloc = kv_pool.tick_alloc(alloc, cache["pos"], adv, KV_BS)
        lg, cache = tfm.decode_step(
            qc, params, cache, tok, cfg, advance=adv,
            block_table=None if alloc is None else alloc["table"])
        rows.append(np.asarray(lg[0, 0, : cfg.vocab_size]))
    return rows


# Measured headroom: every arch stays under 0.013 max-abs-err except
# arctic-480b, whose expert router sits on a near-tie at one step of this
# seed — KV rounding flips the expert pick and shifts ~40% of that step's
# logits by ~0.13. That's router sensitivity, not codec error, so it gets
# its own documented bound instead of loosening the gate for everyone.
KV_INT8_ATOL = {"arctic-480b": 0.2}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_int8_kv_decode_logits_near_float_pool_oracle(arch):
    """§14 acceptance gate: int8 group-wise KV decode logits stay within a
    tested tolerance of the fp32 float-pool oracle on every attention arch,
    in BOTH the ring and paged layouts, step after step."""
    cfg = get_smoke_config(arch)
    kinds = list(cfg.block_pattern) + list(cfg.remainder_kinds)
    if not any(k in ("global", "local") for k in kinds):
        pytest.skip("attention-free arch: no KV cache to quantize")
    cfg, params = _model(arch=arch)
    spec = _kv_spec(cfg)
    atol = KV_INT8_ATOL.get(arch, 2e-2)
    for layout in ("ring", "paged"):
        oracle = _kv_decode_logits(cfg, params, layout, None)
        quant = _kv_decode_logits(cfg, params, layout, spec)
        for t, (o, q) in enumerate(zip(oracle, quant)):
            np.testing.assert_allclose(
                q, o, rtol=2e-2, atol=atol,
                err_msg=f"{arch} {layout} step {t}")


def test_int8_kv_preempted_streams_identical_to_solo():
    """Preemption + resume over quantized blocks: steal/requantize-free
    restore must leave every stream identical to an unpressured solo run
    with the same int8 KV storage."""
    cfg, params = _model()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, (12,)) for _ in range(4)]
    sps = [SamplingParams(temperature=0.8, top_p=0.9, seed=100 + i,
                          max_new=24) for i in range(4)]
    solo = []
    for p, sp in zip(prompts, sps):
        e = ServingEngine(cfg, params, slots=1, max_seq=64, kv_dtype="int8")
        solo.append(e.generate([p], [sp])[0].tokens)
    eng = ServingEngine(cfg, params, slots=4, max_seq=64, num_blocks=14,
                        kv_dtype="int8")
    assert eng.preemption  # the pool is undersized on purpose
    outs = eng.generate(prompts, sps)
    st = eng.stats
    assert st["preemptions"] > 0
    assert st["resumed_admissions"] > 0
    for o, s in zip(outs, solo):
        assert o.tokens == s
    assert eng.pool_stats()["blocks_in_use"] == 0


def test_int8_kv_prefix_shared_admission_streams_identical():
    """Prefix sharing + CoW over quantized blocks: a fully shared admission
    reproduces both the registrant's stream and an unshared solo run."""
    cfg, params = _model()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, (16,))
    sp = SamplingParams(temperature=0.9, top_p=0.9, seed=7, max_new=6)
    solo_eng = ServingEngine(cfg, params, slots=1, max_seq=64,
                             kv_dtype="int8")
    solo = solo_eng.generate([prompt], [sp])[0].tokens
    eng = ServingEngine(cfg, params, slots=2, max_seq=64, kv_dtype="int8")
    a, b = eng.generate([prompt, prompt], [sp, sp])
    assert eng.stats["shared_admissions"] == 1
    assert a.tokens == b.tokens == solo
    assert len(set(a.tokens)) > 1


# ---------------------------------------------------------------------------
# Chunked prefill ≡ whole-prompt prefill (DESIGN.md §15)
# ---------------------------------------------------------------------------


def _has_attention(cfg):
    kinds = list(cfg.block_pattern) + list(cfg.remainder_kinds)
    return any(k in ("global", "local") for k in kinds)


def _chunk_run(cfg, params, layout, kv_dtype, chunk):
    """One greedy + one seeded-sampled request through an engine with the
    given ``prefill_chunk_tokens``; returns comparable terminal streams."""
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, cfg.vocab_size, (13,)),
               rng.integers(0, cfg.vocab_size, (21,))]
    sps = [SamplingParams(max_new=4),
           SamplingParams(max_new=4, temperature=0.8, top_k=13, seed=5)]
    eng = ServingEngine(cfg, params, slots=2, max_seq=32, kv_layout=layout,
                        kv_dtype=kv_dtype, prefill_chunk_tokens=chunk)
    res = eng.generate(prompts, sps)
    st = eng.stats
    # the one-host-sync-per-tick ledger survives chunked admission
    assert st["tick_syncs"] == st["decode_ticks"]
    if chunk is not None and chunk < 13:
        assert st["prefill_chunks"] > len(prompts)  # prompts really split
    return [(r.tokens, r.finish_reason) for r in res]


@pytest.mark.parametrize("arch", TOKEN_ARCHS)
def test_chunked_prefill_streams_bit_identical_every_arch(arch):
    """The §15 acceptance gate: token streams are BIT-IDENTICAL under
    ``prefill_chunk_tokens`` ∈ {one KV block, ragged, ∞} on every
    token-servable arch, in the ring layout AND (where the arch has
    attention) the paged one, greedy and seeded-sampled alike.

    bf16 KV compares every chunk setting against the legacy whole-prompt
    engine (``prefill_chunk_tokens=None``): the bf16 round-trip is the
    identity, so chunked and legacy attends see the same key bits. int8 KV
    compares chunk settings against the ∞-chunk run instead — the legacy
    prefill attends over fresh (non-round-tripped) K/V, while every chunked
    attend reads storage-dtype codes, which is its own (chunk-invariant)
    numeric contract."""
    cfg, params = _model(arch=arch)
    layouts = ["ring"] + (["paged"] if _has_attention(cfg) else [])
    for layout in layouts:
        want = _chunk_run(cfg, params, layout, "bf16", None)
        for chunk in (8, 3, 1000):
            got = _chunk_run(cfg, params, layout, "bf16", chunk)
            assert got == want, (arch, layout, "bf16", chunk)
        if not _has_attention(cfg):
            continue  # attention-free arch: no KV codes to quantize
        want = _chunk_run(cfg, params, layout, "int8", 1000)
        for chunk in (8, 3):
            got = _chunk_run(cfg, params, layout, "int8", chunk)
            assert got == want, (arch, layout, "int8", chunk)
