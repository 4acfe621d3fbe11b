"""The four-chip train cell driven end to end on a 2x2 CPU mesh at the
tiny size of ``test_chip_train_cpu.tiny()``: the harness's look for a
chip skipped, the program's sharded step over four devices and the
reference on one, as in a chip run. Each case runs in a subprocess that sees four
host devices, so this process keeps its one-device view."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import harness  # noqa: E402

CELL = "qwen3-4b.cgmq.train.mesh4"

RUN = """
import json, sys
sys.path[:0] = [{chip!r}, {tests!r}]
import faults, harness, train_cell
from test_chip_train_cpu import tiny

o = tiny({cell!r})
o["mix"] = {{"batch": 8, "seq": 16}}
case = {case!r}
out = {{}}
if case == "sound":
    rec = train_cell.run({cell!r}, 5, 1.0, False, overrides={{**o, "keep": True}})
    lim = rec["cell"]["correct"]
    low = train_cell.reference_readings(
        harness.reference("qwen3-4b"), rec["conf"], rec["make"],
        rec["make_batch"], rec["key"], rec["adam"], rec["recipe"],
        compute="float8_e4m3fn")
    got = train_cell.compare(low, rec["reference"], lim["leaf_rule"])
    got["state_share_max"] = rec["timings"]["gaps"]["state_share_max"]
    out["control"] = train_cell.judge(got, lim)
    out["devices"] = len(rec["devs"])
    out["compiles"] = rec["window"]["compiles"]
else:
    with faults.TRAIN[case]():
        rec = train_cell.run({cell!r}, 5, 1.0, False, overrides=o)
out.update(correct=rec["correct"], checks=rec["checks"],
           attempted=rec["attempted"])
print(json.dumps(out))
"""


def run_case(case: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(harness.REPO / "src"))
    code = RUN.format(chip=str(HERE.parent), tests=str(HERE), cell=CELL,
                      case=case)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_mesh_run_is_correct_and_its_control_is_not():
    got = run_case("sound")
    assert got["devices"] == 4 and got["attempted"] > 0
    assert got["compiles"] == 0
    assert got["correct"], got["checks"]
    assert got["checks"]["state_share_max"]["value"] < 0.5
    checks, ok = got["control"]
    assert not ok, checks


@pytest.mark.parametrize("fault, number", [
    ("half_batch", "grad_gap"),
    ("exchange_skipped", "grad_gap"),
    ("state_replicated", "state_share_max"),
])
def test_mesh_fault_is_not_correct(fault, number):
    got = run_case(fault)
    c = got["checks"][number]
    assert c["value"] > c["limit"], got["checks"]
    assert not got["correct"]
    if fault == "state_replicated":
        assert c["value"] == 1.0


def test_collective_share_reads_a_hand_made_mesh_trace():
    """Self time of collectives over busy time, inside train-step runs
    only, on op names from a chip trace of the mesh step: a ``while``
    keeps the time its body leaves, a fusion that calls a reduce-scatter
    counts, a matmul fusion that streams an all-gather does not."""
    import trace_reduce

    reader = harness.metric_reader("collective_share.train")
    fx = json.loads((HERE / "mesh_ops_fixture.json").read_text())
    rec = {"trace": {
        "ops": [[trace_reduce.op_name(o)] + o[1:] + [o[0]]
                for o in fx["ops"]],
        "modules": fx["modules"]}}
    # collectives (async start 5, async done 10, psum 5, all-gather 10,
    # reduce-scatter 20, all-to-all 10 ns) of the 190 ns busy (idle at
    # 100-110); the all-reduce at 300 runs in another program
    assert reader.read(rec) == pytest.approx(100.0 * 60 / 190)
    assert reader.read({"no": "trace"}) is None
    rec["trace"]["modules"] = [["jit_other(3)", 0, 400]]
    assert reader.read(rec) is None


def test_train_mfu_divides_by_the_cells_chips():
    reader = harness.metric_reader("train_mfu")
    rec = {"window": {"steps": 177, "partial": 0.0, "seconds": 51.0},
           "tokens_per_step": 2048, "seq": 1024,
           "conf": harness.config_file("qwen3-4b"),
           "cell": harness.cell("qwen3-4b.cgmq.train"),
           "peaks": harness.peaks("TPU v5 lite")}
    import flops

    # the one-chip cell's reading, as the reader wrote it before it knew
    # of chips
    tok_s = 177 * 2048 / 51.0
    before = 100.0 * flops.train_token(rec["conf"]["config"], 1024) \
        * tok_s / rec["peaks"]["bf16_flops"]
    assert reader.read(rec) == before
    rec["cell"] = harness.cell(CELL)
    assert reader.read(rec) == pytest.approx(before / 4)


class Queued:
    """A step's result on a device that runs one 40-ms step after another,
    each as soon as the one before it is done and it has been sent."""

    def __init__(self, after):
        import time

        self.done_at = max(time.monotonic(), after) + 0.04

    def block_until_ready(self):
        import time

        time.sleep(max(0.0, self.done_at - time.monotonic()))
        return self

    def __float__(self):
        return 0.0


def queued_step(state, batch):
    out = Queued(state.done_at if state else 0.0)
    return out, {"loss": out}


def test_window_counts_the_share_of_the_step_in_flight_at_the_close():
    import train_cell

    _, win = train_cell.Window(queued_step, lambda key, i: None, None, 0.5,
                               None, harness.CompileClock()).run(None, None,
                                                                 0)
    assert 0.0 <= win["partial"] < 1.0
    # 500 ms of 40-ms steps
    assert win["steps"] + win["partial"] == pytest.approx(12.5, abs=0.3)
    rec = {"window": win, "tokens_per_step": 10}
    assert harness.metric_reader("train_tok_s").read(rec) == pytest.approx(
        (win["steps"] + win["partial"]) * 10 / win["seconds"])


def test_traced_window_leaves_out_the_profilers_start_and_stop(
        monkeypatch, tmp_path):
    """Starting and stopping the profiler stalls the host for 0.3 s each,
    and no step runs meanwhile; the window moves its close out by that
    time, so it holds as many steps as an untraced one."""
    import time

    import jax

    import train_cell

    monkeypatch.setattr(harness, "start_trace",
                        lambda d: time.sleep(0.3))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: time.sleep(0.3))
    _, win = train_cell.Window(queued_step, lambda key, i: None, None, 3.5,
                               tmp_path, harness.CompileClock()).run(
                                   None, None, 0)
    assert win["profiler_s"] == pytest.approx(0.6, abs=0.05)
    assert win["seconds"] == pytest.approx(3.5)
    # 3.5 s of 40-ms steps, not the 2.9 s the stalls leave
    assert win["steps"] + win["partial"] == pytest.approx(87.5, abs=1.5)
