"""``trace_scopes.py`` on hand-made traces with known idle intervals,
spans and scope paths; the existing reduction and readers pinned on the
recorded slice; ``queue_wait_ms`` on the engine's counters."""

import copy
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import trace_scopes  # noqa: E402

FIXTURE = Path(__file__).with_name("trace_fixture.json")


def _trace(scoped=True, engine=True):
    """Chip 0 busy over [0, 100], [150, 180], [190, 200] inside one tick
    run and [300, 350] inside a prefill run: gaps of 50, 10 and 100 ns."""
    def op(name, start, dur, path):
        return [name, start, dur, {"tf_op": path} if scoped else {}]

    ops = [op("%while.1 = ...", 0, 100, "jit(_tick)/p0_global/attn/while"),
           op("%fusion.3 = ...", 10, 10, "jit(_tick)/sample/sort"),
           op("%quant_matmul_op.7 = ...", 30, 20,
              "jit(_tick)/p0_global/ffn/quant_matmul"),
           op("%sort.2 = ...", 150, 30, "jit(_tick)/sample/sort"),
           op("%copy.4 = ...", 190, 10, "jit(_tick)/kv_alloc/copy"),
           op("%fusion.9 = ...", 300, 50, "jit(_prefill_chunk)/sample/x")]
    host = [["step", 0, 320, {}], ["wait_arrival", 330, 100, {}]]
    if engine:
        host += [["engine.admit", 90, 70, {}],
                 ["engine.prefill_chunk", 100, 40, {}],
                 ["engine.sync", 170, 15, {"kind": "tick"}],
                 ["engine.tick", 200, 100, {}],
                 ["backend_compile_and_load", 240, 40, {}]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ["jit__tick(1)", 0, 200, {}],
                ["jit__prefill_chunk(2)", 300, 100, {}]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}


def test_gap_labels_and_engine_idle_on_a_hand_made_trace():
    data = _trace()
    gaps = trace_scopes.idle_gaps(data)
    assert gaps == [(100, 150), (180, 190), (200, 300)]
    assert trace_scopes.label_gaps(gaps, trace_scopes.spans(data)) == [
        ["backend_compile_and_load", pytest.approx(100e-9)],
        ["engine.prefill_chunk", pytest.approx(50e-9)],
        ["engine.sync kind=tick", pytest.approx(10e-9)]]
    # the union of program spans covers 50 + 5 + 100 ns of the gaps
    idle, by_span = trace_scopes.engine_idle(
        gaps, trace_scopes.spans(data, program_only=True))
    assert idle == pytest.approx(155e-9)
    assert dict(by_span) == {
        "backend_compile_and_load": pytest.approx(100e-9),
        "engine.prefill_chunk": pytest.approx(50e-9),
        "engine.sync kind=tick": pytest.approx(5e-9)}
    assert trace_scopes.engine_idle_share(data, 1e-6) == pytest.approx(15.5)
    # the benchmark's reduction of the same trace keeps its labels
    plain = trace_reduce.reduce(_trace(), 1)
    assert {lab for lab, _ in plain["gaps"]} == {"step"}
    assert trace_scopes.idle_per_tick_ms(plain) == pytest.approx(160e-6)


def test_explain_reads_a_hand_made_trace():
    import explain

    out = explain.explain(_trace(), 1, {"seconds": 1e-6}, "serve")
    assert out["busy_s"] == pytest.approx(190e-9)
    assert out["gaps"][0] == ["backend_compile_and_load",
                              pytest.approx(100e-9)]
    assert out["gaps_1ms"] == []
    assert out["longest_gap"] == [200, 300, [
        ["step", 0, 320], ["engine.tick", 200, 100],
        ["backend_compile_and_load", 240, 40]]]
    assert out["compile_spans"] == [["backend_compile_and_load", 240, 40]]
    assert out["sampling_share"] == pytest.approx(100.0 * 40 / 140)
    assert out["engine_idle_share"] == pytest.approx(15.5)
    assert out["tick_runs"] == 1
    train = explain.explain(_trace(), 1, {"seconds": 1e-6}, "train")
    assert train["cgmq_share"] is None and train["scope_shares"] == {
        "_total_s": 0.0}


def test_scope_shares_inside_tick_runs():
    data = _trace()
    tick = trace_scopes.TICK_MODULE
    # tick self time 140 ns: while 70 (attn), sorts 10 + 30 (sample),
    # GEMM 20 (ffn), copy 10 (kv_alloc); the prefill's op is outside
    assert trace_scopes.share(data, tick, trace_scopes.SAMPLING) == \
        pytest.approx(100.0 * 40 / 140)
    assert trace_scopes.share(data, tick, ("kv_alloc",)) == \
        pytest.approx(100.0 * 10 / 140)
    shares = trace_scopes.scope_shares(data, tick)
    assert shares["p0_global"] == pytest.approx(100.0 * 90 / 140)
    assert shares["attn"] == pytest.approx(50.0)
    assert shares["_total_s"] == pytest.approx(140e-9)
    assert trace_scopes.under("jit(_tick)/sample/sort", ("sample",))
    assert not trace_scopes.under("jit(_tick)/resample/sort", ("sample",))
    assert trace_scopes.under(
        "jit(train_step)/transpose(jvp(cgmq_stats))/add_any:", ("cgmq_stats",))


def test_cgmq_share_inside_train_step_runs():
    paths = [("cgmq_stats", 20), ("transpose(jvp(cgmq_stats))", 10),
             ("fake_quant", 20), ("p0_global/attn", 100),
             ("cgmq_controller", 10), ("adam", 40)]
    ops, t = [], 0
    for path, dur in paths:
        ops.append([f"%fusion.{t} = ...", t, dur,
                    {"tf_op": f"jit(train_step)/{path}/x"}])
        t += dur
    data = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_train_step(4)", 0, t]]}]}]}
    train = trace_scopes.TRAIN_MODULE
    assert trace_scopes.share(data, train, trace_scopes.CGMQ) == \
        pytest.approx(100.0 * 40 / 200)
    assert trace_scopes.share(data, train, ("fake_quant",)) == \
        pytest.approx(10.0)
    assert trace_scopes.share(data, train, ("adam",)) == pytest.approx(20.0)


def test_scope_stat_is_read_from_event_metadata(tmp_path):
    """A device plane whose ops keep ``tf_op`` on their metadata, as a TPU
    trace does: once as a string, once as a reference to a stat name; two
    ops of the same text in two programs keep their own paths, since an
    event finds its metadata by id. A host span keeps its ids."""
    space = trace_scopes._xspace()()
    pl = space.planes.add(name="/device:TPU:0")
    for key, name in ((7, "tf_op"), (8, "hlo_category"),
                      (9, "jit(_tick)/kv_alloc/add:")):
        pl.stat_metadata.add(key=key).value.name = name
    same = "%add.1 = s32[32] add(...)"
    metas = {1: ("%sort.6 = (f32[32,151936]) sort(...)",
                 [dict(metadata_id=8, str_value="sort"),
                  dict(metadata_id=7, str_value="jit(_tick)/sample/sort:")]),
             2: (same, [dict(metadata_id=7, ref_value=9)]),
             3: (same, [dict(metadata_id=7,
                             str_value="jit(_prefill_chunk)/x/add:")]),
             4: ("%copy.2 = f32[8] copy(...)", [])}
    for key, (name, stats) in metas.items():
        m = pl.event_metadata.add(key=key).value
        m.id, m.name = key, name
        for st in stats:
            m.stats.add(**st)
    ln = pl.lines.add(name="XLA Ops", timestamp_ns=1000)
    for mid, off in ((1, 0), (2, 5000), (3, 9000), (4, 12000)):
        ln.events.add(metadata_id=mid, offset_ps=off, duration_ps=2500)
    host = space.planes.add(name="/host:CPU")
    host.stat_metadata.add(key=1).value.name = "rid"
    host.event_metadata.add(key=1).value.name = "engine.admit"
    hl = host.lines.add(name="python3", timestamp_ns=2000)
    hl.events.add(metadata_id=1, offset_ps=1000, duration_ps=4000).stats.add(
        metadata_id=1, int64_value=0)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    data = trace_scopes.load(path)
    evs = data["planes"][0]["lines"][0]["events"]
    assert [e[:3] for e in evs] == [
        [metas[1][0], 1000, 2], [same, 1005, 2], [same, 1009, 2],
        [metas[4][0], 1012, 2]]
    assert [trace_scopes.scope_path(e) for e in evs] == [
        "jit(_tick)/sample/sort:", "jit(_tick)/kv_alloc/add:",
        "jit(_prefill_chunk)/x/add:", ""]
    assert trace_scopes.host_events(data) == [
        ["engine.admit", 2001, 4, {"rid": 0}]]


def test_a_program_without_scopes_or_spans_reads_nothing():
    data = _trace(scoped=False, engine=False)
    assert trace_scopes.share(data, trace_scopes.TICK_MODULE,
                              trace_scopes.SAMPLING) is None
    # paths without the program's scope names read nothing either
    assert trace_scopes.share(_trace(), trace_scopes.TICK_MODULE,
                              ("adam",)) is None
    assert trace_scopes.engine_idle_share(data, 1e-6) is None
    program = trace_scopes.spans(data, program_only=True)
    assert trace_scopes.engine_idle(trace_scopes.idle_gaps(data),
                                    program) == (0.0, [])
    assert [lab for lab, _ in trace_scopes.label_gaps(
        trace_scopes.idle_gaps(data), trace_scopes.spans(data))] == \
        ["step"] * 3


def _fixture_rec():
    red = trace_reduce.reduce(json.loads(FIXTURE.read_text()), 1)
    return {"trace": red, "trace_window": {"seconds": 0.5},
            "conf": harness.config_file("qwen3-4b"),
            "cell": harness.cell("qwen3-4b.chat.saturated"),
            "peaks": harness.peaks("TPU v5 lite"), "slots": 32,
            "block_size": 8,
            "trace_steps": [{"hit_blocks": 64, "prefills": [1500, 700],
                             "decode_ctx": [600 + 17 * i
                                            for i in range(30)]}]}


def test_recorded_slice_reduction_is_pinned():
    red = _fixture_rec()["trace"]
    assert red["busy_s"] == pytest.approx(0.09137397, rel=1e-12)
    assert len(red["ops"]) == 569 and len(red["op_totals"]) == 42
    assert red["op_totals"][:3] == [
        ("while", pytest.approx(0.060222517, rel=1e-12)),
        ("paged_attention_op", pytest.approx(0.016736105, rel=1e-12)),
        ("copy", pytest.approx(0.006152175, rel=1e-12))]
    assert [m[0] for m in red["modules"]] == [
        "jit__prefill_chunk(8922496065130866865)",
        "jit__tick(11049070978689590788)"]
    assert len(red["gaps"]) == 10
    assert red["gaps"][0] == ["step", pytest.approx(3.134e-06, rel=1e-9)]
    assert red["idle_by_span"] == [("step",
                                    pytest.approx(4.604e-06, rel=1e-9))]


@pytest.mark.parametrize("name,value", [
    ("decode_tick_ms", 205.533552),
    ("device_idle_share", 81.725206),
    ("quant_gemm_roofline", 35.72952728897197),
    ("paged_attn_roofline", 3.67621503803429),
    ("serve_mfu", 3.383216209088325),
])
def test_recorded_slice_readers_are_pinned(name, value):
    assert harness.metric_reader(name).read(_fixture_rec()) == \
        pytest.approx(value, rel=1e-9)


def test_queue_wait_reads_the_engine_counters_or_nothing():
    reader = harness.metric_reader("queue_wait_ms.tput")
    rec = {"window": {"admissions": 4, "queue_wait_s": 0.5}}
    assert reader.read(rec) == pytest.approx(125.0)
    parent = copy.deepcopy(rec)
    del parent["window"]["admissions"], parent["window"]["queue_wait_s"]
    assert reader.read(parent) is None
    rec["window"]["admissions"] = 0
    assert reader.read(rec) is None


def test_queue_wait_on_a_tiny_serve_run():
    from test_chip_serve_cpu import run_tiny

    rec = run_tiny(seed=11)
    w = rec["window"]
    assert w["admissions"] > 0 and w["queue_wait_s"] >= 0.0
    assert harness.metric_reader("queue_wait_ms.tput").read(rec) >= 0.0


def test_span_ids_come_back_from_a_profiled_engine(tmp_path):
    """The engine's spans keep the ids they were opened with through
    ``load``, on a CPU profile of a few engine steps, and ``load`` reads
    every event as ``trace_reduce.load`` does."""
    import glob

    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.models import transformer as tfm
    from repro.serving import Request, SamplingParams, ServingEngine

    cfg = get_smoke_config("tinyllama-1.1b")
    eng = ServingEngine(cfg, tfm.init_params(cfg, jax.random.PRNGKey(0)),
                        slots=2, max_seq=64, prefill_chunk_tokens=8)
    prompt = np.arange(1, 13, dtype=np.int32)
    eng.generate([prompt], SamplingParams(max_new=2))
    harness.start_trace(tmp_path)
    eng.submit(Request(rid=7, prompt=prompt,
                       params=SamplingParams(max_new=3)))
    eng.run_to_completion()
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    data = trace_scopes.load(path)
    # every event as ``trace_reduce.load`` reads it, with no stat changed
    plain = trace_reduce.load(path)
    for pa, pb in zip(plain["planes"], data["planes"], strict=True):
        for la, lb in zip(pa["lines"], pb["lines"], strict=True):
            for ea, eb in zip(la["events"], lb["events"], strict=True):
                assert ea[:3] == eb[:3] and ea[3].items() <= eb[3].items()
    spans = [e for e in trace_scopes.host_events(data)
             if trace_scopes.is_program_span(e[0])]
    kinds = {trace_scopes.span_label(e) for e in spans
             if e[0] == "engine.sync"}
    assert "engine.sync kind=tick" in kinds
    chunks = [e[3] for e in spans if e[0] == "engine.prefill_chunk"]
    assert chunks and all(c["rid"] == 7 and c["tokens"] > 0 for c in chunks)
