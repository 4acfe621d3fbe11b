"""A serve cell driven end to end on the CPU at a tiny size: the harness's
look for a chip skipped, the program's jnp kernels, everything else as in
a chip run. A sound run is correct; a run whose timed path is broken
underneath is not."""

import copy
import json
import os
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402

CELL = "qwen3-4b.chat.saturated"
NUCLEUS_LIMIT = 3.0


def tiny():
    conf = copy.deepcopy(harness.config_file("qwen3-4b"))
    conf["config"].update(hidden_size=64, intermediate_size=128,
                          num_attention_heads=4, num_key_value_heads=2,
                          head_dim=16, num_hidden_layers=2, vocab_size=277)
    cell = copy.deepcopy(harness.cell(CELL))
    cell["engine"].update(slots=4, prefill_chunk_tokens=32)
    # the tiny model's logits spread far less than the real one's, so its
    # limits are its own: sound runs read a widest gap under 0.02 there,
    # the reference in float8 0.074 at the least (four seeds); and a
    # nucleus ratio under 0.9, a sampler without the top-p cut 19 (seed 5)
    cell["correct"].update(requests=4, min_tokens=10, max_logit_gap=0.05,
                           sampled_requests=4, min_sampled_tokens=10,
                           nucleus_ratio=NUCLEUS_LIMIT)
    mix = copy.deepcopy(harness.traffic_mix(cell["traffic"]))
    mix.update(warm_s=0.5, warm_burst=4)
    mix["arrivals"]["rate_per_s"] = 8.0
    mix["prompt"].update(median=24, min=8, max=64)
    mix["output"].update(median=10, min=4, max=16)
    mix["shared_prefix"].update(tokens=16)
    return dict(cell=cell, conf=conf, mix=mix, impl="ref", chips=False,
                cache=False)


def run_tiny(seed=5, **extra):
    import serve_cell

    return serve_cell.run(CELL, seed, 2.0, False,
                          overrides={**tiny(), **extra})


def test_no_tpu_exits_nonzero_without_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(harness.ROOT / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_sound_run_is_correct_and_its_control_is_not():
    rec = run_tiny()
    assert rec["correct"], rec["checks"]
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert rec["window"]["compiles"] == 0
    for name in ("output_tok_s", "setup_s", "compile_s",
                 "batch_occupancy.tput"):
        v = harness.metric_reader(name).read(rec)
        assert v is not None and v > 0, name
    json.dumps(rec["checks"])
    # the control: the reference in float8 in the program's place, judged
    # by the run's own comparison
    ctl = run_tiny(control="float8_e4m3fn")
    assert not ctl["correct"], ctl["checks"]
    assert ctl["checks"]["max_logit_gap"]["value"] > \
        ctl["checks"]["max_logit_gap"]["limit"]


def test_token_altered_where_produced_is_not_correct():
    import faults

    with faults.token_altered():
        rec = run_tiny()
    assert not rec["correct"], rec["checks"]


def test_sampler_without_its_top_p_cut_is_not_correct():
    import faults

    with faults.top_p_ignored():
        rec = run_tiny()
    assert rec["checks"]["nucleus_ratio"]["value"] > NUCLEUS_LIMIT
    assert not rec["correct"], rec["checks"]


def test_decode_step_returning_its_cache_unchanged_is_not_correct():
    import faults

    with faults.cache_unchanged():
        rec = run_tiny()
    assert not rec["correct"], rec["checks"]
