#!/usr/bin/env python3
"""A cell's traced run, explained by the program's own names.

    python3 benchmarks/chip/explain.py --workload <cell> --seed <n> \
        --seconds <s> [--trace-dir DIR]

Runs the cell's window as ``run.py --trace 1`` does, keeps the trace (in
``DIR`` where given) and prints one JSON line: what ``trace_scopes`` reads
there (device time by named scope inside the tick or train-step runs, idle
gaps labelled by the innermost benchmark, engine or compile span, idle
time inside engine spans) beside the window's throughput, the idle time
per decode tick, and the run's compiles by program name. Runs no
reference, prints no result line and judges nothing; exits 3 without a
TPU.
"""

from __future__ import annotations

import argparse
import glob
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import trace_scopes  # noqa: E402


def serve_window(cell: dict, seed: int, seconds: float, trace_dir: Path):
    """``serve_cell.run`` up to the end of its window, traced into
    ``trace_dir``: the output tokens/s and the counters of the traced
    span."""
    import numpy as np

    import serve_cell
    import traffic

    harness.enable_cache()
    clock = harness.CompileClock()
    conf = harness.config_file(cell["config"])
    mix = harness.traffic_mix(cell["traffic"])
    cfg, _, eng = serve_cell.build(cell, conf, mix,
                                   harness.reference(cell["config"]), seed)
    _, nseed = harness.split_seed(seed)
    serve_cell.warm_shapes(eng, cfg, np.random.default_rng(nseed))
    arrivals = traffic.generate(mix, nseed, cfg.vocab_size, seconds)
    w = serve_cell.Window(eng, arrivals, time.monotonic() + mix["warm_s"],
                          seconds, trace_dir, clock,
                          drain=cell.get("drain", True))
    w.run()
    win = serve_cell.counters(w, "open", "close")
    return (w.window_tokens / win["seconds"],
            serve_cell.counters(w, "trace_start", "trace_stop"))


def train_window(cell: dict, seed: int, seconds: float, trace_dir: Path):
    """``train_cell.run`` up to the end of its window, traced into
    ``trace_dir``: the train tokens/s and the traced span."""
    import jax

    import train_cell

    devs = jax.devices()[:cell["chips"]]
    harness.enable_cache()
    clock = harness.CompileClock()
    conf = harness.config_file(cell["config"])
    job = harness.traffic_mix(cell["traffic"])
    cfg, recipe, make, key, state, step, make_batch = train_cell.build(
        cell, conf, job, harness.reference(cell["config"]), seed, devs)
    state, batch, _ = train_cell.program_readings(
        state, step, make_batch, key, make, recipe.adam.b1)
    _, win = train_cell.Window(step, make_batch, key, seconds, trace_dir,
                               clock).run(state, batch,
                                          train_cell.CHECK_STEPS)
    return ((win["steps"] + win["partial"]) * job["batch"] * job["seq"]
            / win["seconds"],
            {"seconds": win["trace_seconds"], "steps": win["trace_steps"]})


def explain(data: dict, n_devices: int, window: dict, kind: str) -> dict:
    """What the trace ``data`` (``trace_scopes.load``) says, for a
    ``window`` of the traced span's counters."""
    plain = trace_reduce.reduce(data, n_devices)
    seconds = window["seconds"]
    gaps = trace_scopes.idle_gaps(data)
    every = trace_scopes.spans(data)
    labelled = trace_scopes.label_gaps(gaps, every)
    s, e = max(gaps, key=lambda g: g[1] - g[0], default=(0.0, 0.0))
    module = (trace_scopes.TICK_MODULE if kind == "serve"
              else trace_scopes.TRAIN_MODULE)
    shares = trace_scopes.scope_shares(data, module)
    out = {
        "window_s": seconds,
        "busy_s": plain["busy_s"],
        "device_idle_share": 100.0 * (1 - plain["busy_s"] / seconds),
        "scope_shares": dict(sorted(shares.items(), key=lambda kv: -kv[1])),
        "gaps": labelled[:20],
        "gaps_1ms": [g for g in labelled if g[1] >= 1e-3],
        "longest_gap": [s, e, [[n, a, b - a] for a, b, n in every
                               if a < e and s < b]],
        "compile_spans": [[n, a, b - a] for a, b, n in every
                          if n == trace_scopes.COMPILE_SPAN],
    }
    if kind == "serve":
        program = trace_scopes.spans(data, program_only=True)
        out.update(
            sampling_share=trace_scopes.share(data, module,
                                              trace_scopes.SAMPLING),
            engine_idle_share=trace_scopes.engine_idle_share(data, seconds),
            engine_idle_by_span=trace_scopes.engine_idle(gaps, program)[1],
            idle_per_tick_ms=trace_scopes.idle_per_tick_ms(plain),
            tick_runs=sum(1 for m in plain["modules"]
                          if trace_scopes.TICK_MODULE.match(m[0])),
            window_counters=window)
    else:
        out.update(
            cgmq_share=trace_scopes.share(data, module, trace_scopes.CGMQ),
            fake_quant_share=trace_scopes.share(data, module,
                                                ("fake_quant",)),
            adam_share=trace_scopes.share(data, module, ("adam",)))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-dir", type=Path,
                    help="keep the trace here (default: a temporary "
                         "directory, removed at the end)")
    args = ap.parse_args()
    import jax

    # op metadata (the scopes) joins the persistent cache's key, so no
    # executable compiled from another tree, without scopes, is loaded;
    # the program leaves it out, since every edit and entry point would
    # then compile anew (DESIGN.md §18)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        from repro.compile_cache import compile_counts
    except ImportError:  # a program without the compile counter
        def compile_counts():
            return None
    cell = harness.cell(args.workload)
    try:
        devs = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"explain.py: {e}; nothing measured", file=sys.stderr)
        return 3
    compile_counts()
    trace_dir = args.trace_dir or Path(
        tempfile.mkdtemp(prefix="chipbench_explain_"))
    run = serve_window if cell["kind"] == "serve" else train_window
    tok_s, window = run(cell, args.seed, args.seconds, trace_dir)
    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    out = explain(trace_scopes.load(path), len(devs), window, cell["kind"])
    if args.trace_dir is None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "tok_s": tok_s, "compiles_by_program": compile_counts(),
                      **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
