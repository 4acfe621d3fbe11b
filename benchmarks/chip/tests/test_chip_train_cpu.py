"""A train cell driven end to end on the CPU at a tiny size: the harness's
look for a chip skipped, everything else as in a chip run. A sound run is
correct; a run whose timed step is broken underneath is not."""

import copy
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402

CELL = "qwen3-4b.cgmq.train"


def tiny():
    """The cell at d_model 64, two layers, 2 x 16 tokens. Its limits are
    the tiny model's own, set between what sound runs read there (loss
    under 1.3e-4, gradient under 4.5e-3, change under 2.1e-3 on three
    seeds) and what half a batch reads (loss 2.9e-3, gradient 0.082,
    change 0.023 at the least) or the reference in float8 (gradient 0.18,
    change 0.49 at the least); the worst gate's gap under 0.052 on six
    seeds, so the mean gate's lower still, where a controller that keeps
    its state reads 1 at the worst gate."""
    conf = copy.deepcopy(harness.config_file("qwen3-4b"))
    conf["config"].update(hidden_size=64, intermediate_size=128,
                          num_attention_heads=4, num_key_value_heads=2,
                          head_dim=16, num_hidden_layers=2, vocab_size=277)
    cell = copy.deepcopy(harness.cell(CELL))
    cell["correct"].update(loss_gap=1e-3, grad_gap=0.03, change_gap=0.01,
                           gate_gap_mean=0.1)
    return dict(cell=cell, conf=conf, mix={"batch": 2, "seq": 16},
                chips=False, cache=False)


def run_tiny(seed=3, **extra):
    import train_cell

    return train_cell.run(CELL, seed, 1.0, False,
                          overrides={**tiny(), **extra})


def test_sound_run_is_correct_and_its_control_is_not():
    import train_cell

    rec = run_tiny(keep=True)
    assert rec["correct"], rec["checks"]
    assert rec["attempted"] > 0 and rec["window"]["compiles"] == 0
    rec["peaks"] = harness.peaks("TPU v5 lite")
    for name in ("train_tok_s", "train_mfu", "setup_s", "compile_s"):
        v = harness.metric_reader(name).read(rec)
        assert v is not None and v > 0, name
    # the control: the reference's two steps in float8, held to float32
    lim = rec["cell"]["correct"]
    low = train_cell.reference_readings(
        harness.reference(rec["cell"]["config"]), rec["conf"], rec["make"],
        rec["make_batch"], rec["key"], rec["adam"], rec["recipe"],
        compute="float8_e4m3fn")
    got = train_cell.compare(low, rec["reference"], lim["leaf_rule"])
    assert not train_cell.judge(got, lim)[1], got


def test_step_returning_its_state_unchanged_is_not_correct():
    import faults

    with faults.state_unchanged():
        rec = run_tiny()
    assert rec["checks"]["change_gap"]["value"] == 1.0
    assert not rec["correct"], rec["checks"]


def test_controller_returning_its_state_unchanged_is_not_correct():
    import faults

    with faults.cgmq_unchanged():
        rec = run_tiny()
    assert rec["timings"]["gaps"]["gate_gap_worst"] == 1.0
    assert rec["checks"]["gate_gap_mean"]["value"] > 0.5
    assert rec["checks"]["range_change"]["value"] == 0.0
    assert not rec["correct"], rec["checks"]


def test_half_the_batch_left_out_is_not_correct():
    import faults

    with faults.half_batch():
        rec = run_tiny()
    assert not rec["correct"], rec["checks"]
