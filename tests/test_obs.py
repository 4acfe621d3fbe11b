"""Observability (DESIGN.md §18): engine spans on the profiler's host
plane, named scopes in the device programs' metadata, the compile counter,
and the per-request admission stamp and prefix-hit count."""

import functools
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compile_cache import compile_counts
from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.launch import steps as steps_lib
from repro.models import transformer as tfm
from repro.serving import Request, SamplingParams, ServingEngine

ARCH = "tinyllama-1.1b"


@functools.lru_cache(maxsize=None)
def _model():
    cfg = get_smoke_config(ARCH)
    return cfg, tfm.init_params(cfg, jax.random.PRNGKey(0))


def _engine(**kw):
    cfg, params = _model()
    kw.setdefault("prefill_chunk_tokens", 8)
    return ServingEngine(cfg, params, slots=2, max_seq=64, **kw)


def _requests(n, *, shared=16, rest=12, max_new=4, rid0=0):
    """``n`` requests whose prompts open with the same ``shared`` tokens,
    half of them sampled."""
    cfg, _ = _model()
    rng = np.random.default_rng(7)
    head = rng.integers(0, cfg.vocab_size, shared)
    return [Request(rid=rid0 + i, prompt=np.concatenate(
        [head, rng.integers(0, cfg.vocab_size, rest + i)]).astype(np.int32),
        params=SamplingParams(max_new=max_new, temperature=0.7 * (i % 2),
                              seed=i))
        for i in range(n)]


def _host_spans(trace_dir) -> list:
    """``(name, start_ns, end_ns, stats, line)`` of every ``engine.*``
    event on the trace's host plane."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for pl in ProfileData.from_file(path).planes:
        if pl.name != "/host:CPU":
            continue
        for li, ln in enumerate(pl.lines):
            for e in ln.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats),
                                li))
    return out


def test_engine_spans_on_the_host_plane(tmp_path):
    eng = _engine()
    warm = _requests(1, rid0=100)
    eng.generate([warm[0].prompt], SamplingParams(max_new=2))
    ticks0 = eng.stats["decode_ticks"]
    chunks0 = eng.stats["prefill_chunks"]
    reqs = _requests(3)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
    ticks = eng.stats["decode_ticks"] - ticks0
    spans = _host_spans(tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)

    rids = {r.rid for r in reqs}
    assert sorted(s[3]["rid"] for s in by["engine.submit"]) == sorted(rids)
    chunks = by["engine.prefill_chunk"]
    assert len(chunks) == eng.stats["prefill_chunks"] - chunks0
    admits = by["engine.admit"]
    for _, a, b, st, line in chunks:
        assert st["rid"] in rids and 0 <= st["slot"] < eng.slots
        assert 0 < st["tokens"] <= eng.prefill_chunk_tokens
        assert any(a0 <= a and b <= b0 and ln == line
                   for _, a0, b0, _, ln in admits)
    tick_syncs = [s for s in by["engine.sync"] if s[3]["kind"] == "tick"]
    assert ticks > 0 and len(tick_syncs) == ticks
    assert len(by["engine.tick"]) == len(by["engine.emit"]) == ticks
    for _, a, b, _, line in by["engine.tick"] + admits:
        assert any(a0 <= a and b <= b0 and ln == line
                   for _, a0, b0, _, ln in by["engine.step"])
    assert len(by["engine.expire"]) == len(admits)


def test_tick_lowering_carries_sample_and_kv_alloc_scopes():
    eng = _engine()
    text = eng._tick.lower(eng.params, eng.qweights, eng.cache, eng.state,
                           eng.alloc).as_text(debug_info=True)
    assert "/sample/" in text
    assert "/kv_alloc/" in text


def test_sampled_tick_lowering_has_one_sort_and_no_gather():
    """``sample_tokens`` at the Qwen3-4B vocabulary and 32 slots lowers to
    a single sort and no gather: the nucleus cut is applied in vocabulary
    order, not through a permutation."""
    from repro.serving.sampling import sample_tokens

    b, v = 32, 151936
    f32 = jnp.float32
    text = jax.jit(sample_tokens).lower(
        jax.ShapeDtypeStruct((b, v), f32),
        jax.ShapeDtypeStruct((b, 2), jnp.uint32),
        jax.ShapeDtypeStruct((b,), f32), jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.ShapeDtypeStruct((b,), f32)).as_text()
    assert text.count("stablehlo.sort") == 1
    assert "gather" not in text


def test_train_step_lowering_carries_cgmq_scopes():
    cfg = get_smoke_config(ARCH)
    shape = ShapeConfig("t", seq_len=16, global_batch=2, kind="train")
    recipe = steps_lib.make_recipe(cfg, shape)
    state = steps_lib.init_train_state(recipe, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 16), jnp.int32)
    text = jax.jit(steps_lib.make_train_step(recipe, None)).lower(
        state, {"tokens": toks, "targets": toks}).as_text(debug_info=True)
    for scope in ("cgmq_stats", "cgmq_controller", "fake_quant", "adam",
                  "attn", "ffn"):
        assert f"/{scope}/" in text, scope


def test_compile_counter_names_a_new_chunk_shape():
    eng = _engine(prefill_chunk_tokens=16)
    cfg, _ = _model()
    rng = np.random.default_rng(3)

    def serve(plen):
        eng.generate([rng.integers(0, cfg.vocab_size, plen)],
                     SamplingParams(max_new=2))
        return compile_counts().get("jit(_prefill_chunk)", [0, 0.0])

    compile_counts()
    first = serve(5)     # chunk shape 8
    again = serve(6)     # shape 8 again: nothing new
    wider = serve(12)    # shape 16
    assert first[0] >= 1 and first[1] > 0.0
    assert again == first
    assert wider[0] == first[0] + 1 and wider[1] > first[1]


@pytest.mark.parametrize("mode", ["wave", "chunked", "pressured"])
def test_admission_stamp_and_own_prefix_hits(mode):
    if mode == "wave":
        eng = _engine(prefill_chunk_tokens=None)
    elif mode == "chunked":
        eng = _engine()
    else:  # an undersized pool: requests are preempted and resumed
        eng = _engine(num_blocks=10)
    reqs = _requests(5, max_new=20)
    # the first request registers its prompt blocks before the others
    # bind, so they can share them (two requests prefilling at once both
    # miss the cache)
    eng.submit(reqs[0])
    while not reqs[0].output:
        eng.step()
    for r in reqs[1:]:
        eng.submit(r)
    eng.run_to_completion()
    assert all(r.done and r.finish_reason in ("length", "stop")
               for r in reqs)
    for r in reqs:
        assert r.submit_s <= r.admit_s <= r.first_token_s <= r.finish_s
    assert eng.stats["admissions"] == len(reqs)
    assert eng.stats["queue_wait_s"] == pytest.approx(
        sum(r.admit_s - r.submit_s for r in reqs))
    assert sum(r.prefix_hit_blocks for r in reqs) == \
        eng.stats["prefix_hit_blocks"] > 0
    if mode == "pressured":
        assert eng.stats["preemptions"] > 0
