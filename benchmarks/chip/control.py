#!/usr/bin/env python3
"""Readings behind a cell's correctness limits (not run by the
benchmark's own runs).

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 1,2,3 --seconds 15 [--computes float8_e4m3fn] \
        [--faults name,...]

For each seed, in one process: one run of the cell at its own size and
load (the same ``serve_cell.run`` or ``train_cell.run`` the benchmark
makes, with a short window), whose compared numbers are the program's
readings; then the control, the plain reference computed in each
``--computes`` dtype in the program's place, and a run with each named
fault of ``faults.py`` planted in the program. Each is judged by the
cell's own limits. A train cell's control is the reference's two steps
in that dtype, held to the float32 reference like the program; a serve
cell's is a run whose checked greedy tokens are the ones the reference
in that dtype puts first at each position of the same prompts and
served tokens. With ``--together`` a serve cell makes one run per seed
with the control and every fault in place, and no program run: valid
where each touches other checked tokens (the control the greedy ones,
``top_p_ignored`` the sampled ones). One JSON line per seed and kind of
run.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import faults  # noqa: E402
import harness  # noqa: E402


def row(seed: int, kind: str, correct: bool, numbers: dict) -> None:
    print(json.dumps({"seed": seed, "run": kind, "correct": bool(correct),
                      **numbers}), flush=True)


def train_rows(args, cell) -> None:
    import train_cell

    conf = harness.config_file(cell["config"])
    ref = harness.reference(cell["config"])
    lim = cell["correct"]
    for seed in args.seeds:
        rec = train_cell.run(args.workload, seed, args.seconds, False,
                             overrides={"keep": True})
        gaps = rec["timings"]["gaps"]
        row(seed, "program", rec["correct"], gaps)
        for comp in args.computes:
            low = train_cell.reference_readings(
                ref, conf, rec["make"], rec["make_batch"], rec["key"],
                rec["adam"], rec["recipe"], compute=comp)
            got = train_cell.compare(low, rec["reference"],
                                     lim["leaf_rule"])
            # the control stands in for the step's arithmetic, not for
            # where the program placed its state
            got["state_share_max"] = gaps["state_share_max"]
            row(seed, f"control_{comp}", train_cell.judge(got, lim)[1], got)
        del rec
        gc.collect()
        for name in args.faults:
            with faults.TRAIN[name]():
                rec = train_cell.run(args.workload, seed, args.seconds,
                                     False)
            row(seed, f"fault_{name}", rec["correct"],
                rec["timings"]["gaps"])
            del rec
            gc.collect()


def serve_rows(args) -> None:
    import serve_cell

    def numbers(rec):
        return {k: c["value"] for k, c in rec["checks"].items()} | {
            "nucleus_ratio": rec["timings"]["nucleus_ratio"]}

    for seed in args.seeds:
        if args.together:
            runs = [("+".join([f"control_{c}" for c in args.computes]
                              + [f"fault_{n}" for n in args.faults]),
                     args.faults, {"control": args.computes[0]})]
        else:
            runs = [("program", [], {})]
            runs += [(f"control_{c}", [], {"control": c})
                     for c in args.computes]
            runs += [(f"fault_{n}", [n], {}) for n in args.faults]
        for kind, names, over in runs:
            with contextlib.ExitStack() as stack:
                for n in names:
                    stack.enter_context(faults.SERVE[n]())
                rec = serve_cell.run(args.workload, seed, args.seconds,
                                     False, overrides=over)
            row(seed, kind, rec["correct"], numbers(rec))
            del rec
            gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--computes", default="float8_e4m3fn")
    ap.add_argument("--faults", default="",
                    help="faults to plant, comma-separated (faults.TRAIN "
                         "or faults.SERVE)")
    ap.add_argument("--together", action="store_true",
                    help="serve cells: the control and the faults in one "
                         "run per seed")
    args = ap.parse_args()
    args.seeds = [int(s) for s in args.seeds.split(",")]
    args.computes = [c for c in args.computes.split(",") if c]
    args.faults = [f for f in args.faults.split(",") if f]
    cell = harness.cell(args.workload)
    if cell["kind"] == "train":
        train_rows(args, cell)
    else:
        serve_rows(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
