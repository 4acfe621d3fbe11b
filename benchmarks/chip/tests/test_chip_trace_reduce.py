"""``trace_reduce.py`` on a recorded trace slice and on a hand-made one."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import trace_reduce  # noqa: E402

FIXTURE = Path(__file__).with_name("trace_fixture.json")


def _busy_by_sweep(events):
    """Covered length by an endpoint sweep (independent of ``merge``)."""
    pts = sorted([(e[1], 1) for e in events]
                 + [(e[1] + e[2], -1) for e in events],
                 key=lambda p: (p[0], -p[1]))
    depth, last, total = 0, None, 0.0
    for t, d in pts:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_recorded_slice():
    data = json.loads(FIXTURE.read_text())
    ops = data["planes"][0]["lines"][0]["events"]
    red = trace_reduce.reduce(data, 1)
    assert red["busy_s"] == pytest.approx(_busy_by_sweep(ops) * 1e-9)
    # ops on the line nest (a while around its body), so self times
    # partition the busy time
    assert sum(v for _, v in red["op_totals"]) == pytest.approx(
        red["busy_s"])
    gemm = sum(e[2] for e in ops if e[0].startswith("%quant_matmul"))
    got = sum(v for k, v in red["op_totals"] if k.startswith("quant_matmul"))
    assert gemm > 0 and got == pytest.approx(gemm * 1e-9)
    ticks = [m for m in red["modules"] if m[0].startswith("jit__tick")]
    assert len(ticks) == 1
    inside = trace_reduce.within(red["ops"], ticks)
    assert inside and all(
        ticks[0][1] <= o[1] and o[1] + o[2] <= ticks[0][1] + ticks[0][2]
        for o in inside)
    assert {lab for lab, _ in red["gaps"]} <= {"step",
                                               trace_reduce.UNLABELLED}


def test_hand_made_trace():
    ops = [["%while.1 = ...", 0, 100], ["%fusion.3 = ...", 10, 10],
           ["%quant_matmul_op.7 = ...", 30, 20], ["%copy.2 = ...", 150, 10],
           ["%paged_attention_op.1 = ...", 300, 10]]
    host = [["step", 0, 200], ["submit", 90, 50],
            ["wait_arrival", 200, 200], ["other", 0, 1000]]
    data = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit__tick(1)", 0, 160]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}
    red = trace_reduce.reduce(data, 1)
    assert red["busy_s"] == pytest.approx(120e-9)
    totals = dict(red["op_totals"])
    assert totals["while"] == pytest.approx(70e-9)
    assert totals["quant_matmul_op"] == pytest.approx(20e-9)
    assert red["gaps"] == [["wait_arrival", pytest.approx(140e-9)],
                           ["submit", pytest.approx(50e-9)]]
    inside = trace_reduce.within(red["ops"], red["modules"])
    assert [o[0] for o in inside] == ["while", "fusion", "quant_matmul_op",
                                      "copy"]
