"""The traffic generator and the latency stamps of a serve run."""

import copy
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import traffic  # noqa: E402

MIX = harness.traffic_mix("chat.saturated")


def test_same_seed_same_schedule():
    a = traffic.generate(MIX, 7, 151936, 20.0)
    b = traffic.generate(MIX, 7, 151936, 20.0)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [(x.max_new, x.temperature, x.seed) for x in a] == \
        [(y.max_new, y.temperature, y.seed) for y in b]


def test_seeds_share_one_schedule_with_other_tokens():
    """Every seed offers the same multiset of gaps and requests, in
    another order and with other tokens."""
    a = traffic.generate(MIX, 1, 151936, 20.0)
    b = traffic.generate(MIX, 2**33 + 5, 151936, 20.0)

    def gaps(arr):
        return sorted(np.round(np.diff([x.due_s for x in arr]), 9))

    def work(arr):
        return sorted((x.max_new, x.temperature, x.group,
                       len(x.prompt) - (MIX["shared_prefix"]["tokens"]
                                        if x.group >= 0 else 0))
                      for x in arr)

    assert gaps(a) == gaps(b) and work(a) == work(b)
    assert [x.due_s for x in a] != [x.due_s for x in b]
    assert [x.max_new for x in a] != [x.max_new for x in b]
    assert [x.seed for x in a] != [x.seed for x in b]


def test_warm_burst_is_due_when_the_warm_load_starts():
    arr = traffic.generate(MIX, 9, 151936, 20.0)
    burst = MIX["warm_burst"]
    assert burst > 0
    assert [x.due_s for x in arr[:burst]] == [-MIX["warm_s"]] * burst
    assert arr[burst].due_s > -MIX["warm_s"]


def _big(mix, **arrivals):
    m = copy.deepcopy(mix)
    m["arrivals"].update(arrivals)
    m["warm_s"] = 0.0
    return m


def test_rate_and_bursts_follow_the_mix():
    for process, cv in (("poisson", 1.0), ("gamma", 3.0)):
        m = _big(MIX, process=process, rate_per_s=50.0, cv=cv)
        s = traffic.sizes(m, 400.0)
        gaps = s["gaps"]
        assert len(gaps) == pytest.approx(50 * 400, rel=0.05)
        assert gaps.std() / gaps.mean() == pytest.approx(cv, rel=0.15)


def test_lengths_shares_and_prefixes_follow_the_mix():
    m = _big(MIX, rate_per_s=50.0)
    arr = traffic.generate(m, 3, 1000, 200.0)
    p, o = m["prompt"], m["output"]
    out = np.array([a.max_new for a in arr])
    assert out.min() >= o["min"] and out.max() <= o["max"]
    assert np.median(out) == pytest.approx(o["median"], rel=0.1)
    sp = m["shared_prefix"]
    shared = [a for a in arr if a.group >= 0]
    assert len(shared) / len(arr) == pytest.approx(sp["share"], abs=0.03)
    heads = {}
    for a in shared:
        head = a.prompt[:sp["tokens"]].tobytes()
        assert heads.setdefault(a.group, head) == head
    assert len(heads) == sp["groups"]
    plain = np.array([len(a.prompt) for a in arr if a.group < 0])
    assert plain.min() >= p["min"] and plain.max() <= p["max"]
    assert np.median(plain) == pytest.approx(p["median"], rel=0.1)
    assert max(len(a.prompt) for a in shared) <= p["max"]
    sampled = [a for a in arr if a.temperature > 0]
    assert len(sampled) / len(arr) == pytest.approx(
        m["sampled"]["share"], abs=0.03)
    assert all(a.top_p == m["sampled"]["top_p"] for a in sampled)
    dues = [a.due_s for a in arr]
    assert dues == sorted(dues)


def test_latency_is_timed_from_the_due_time():
    import serve_cell

    def served(due, submit, first, finish, n, reason="length"):
        req = SimpleNamespace(done=True, finish_reason=reason,
                              first_token_s=first, finish_s=finish,
                              output=[1] * n)
        return SimpleNamespace(due=due, submit=submit, req=req,
                               arrival=None)

    w = SimpleNamespace(t_open=10.0, t_close=20.0, snap={"end": (50.0,)},
                        drain=True,
                        served=[served(12.0, 12.5, 13.0, 14.0, 11),
                                served(9.0, 9.0, 9.5, 9.6, 2),
                                served(15.0, 15.0, None, None, 0,
                                       reason="error")])
    lat = serve_cell.latencies(w)
    assert lat["ttft_s"] == [1.0, 35.0]     # from due, not from submit
    assert lat["tpot_s"] == [pytest.approx(0.1), 35.0]
    assert lat["failed"] == 1 and lat["finished"] == 1
    assert lat["lateness_s"] == [0.5, 0.0]


def test_a_cell_that_stops_at_the_close_counts_the_unfinished_apart():
    import serve_cell

    def served(due, req):
        return SimpleNamespace(due=due, submit=due, req=req, arrival=None)

    busy = SimpleNamespace(done=False, finish_reason=None,
                           first_token_s=15.0, finish_s=None, output=[1])
    ok = SimpleNamespace(done=True, finish_reason="stop", first_token_s=12.0,
                         finish_s=13.0, output=[1, 2, 3])
    w = SimpleNamespace(t_open=10.0, t_close=20.0, snap={"end": (20.0,)},
                        drain=False, served=[served(11.0, ok),
                                             served(14.0, busy)])
    lat = serve_cell.latencies(w)
    assert lat["finished"] == 1 and lat["unfinished"] == 1
    assert lat["failed"] == 0 and lat["ttft_s"] == [1.0]
