#!/usr/bin/env python3
"""The chip benchmark's one command.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on. The
cell's file (``workloads/<cell>.json``) says what kind of run it is and
what it uses; every metric that ``BENCHMARK.json`` lists for the cell is
read by its own file under ``metrics/``. With ``--trace 0`` the result
carries the cell's end-to-end metrics; with ``--trace 1`` its per-layer
metrics, read from a profiler trace of part of the window and from the
program's counters. Exits 3 with no result line where JAX finds no TPU or
fewer chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

BENCHMARK = harness.REPO / "BENCHMARK.json"


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metrics(entries: list, rec: dict) -> tuple[dict, dict]:
    """Each listed metric its reader finds something to read, and what
    the readers that explain themselves say about the run."""
    out, notes = {}, {}
    for m in entries:
        reader = harness.metric_reader(m["name"])
        v = reader.read(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
            if hasattr(reader, "explain"):
                notes[m["name"]] = reader.explain(rec)
    return out, notes


def breakdown(red: dict, top: int = 10) -> dict:
    return {"device_ops": [[k, v] for k, v in red["op_totals"][:top]],
            "idle_gaps": [[k, v] for k, v in red["gaps"][:top]]}


def run(args) -> dict:
    bench = json.loads(BENCHMARK.read_text())
    cell = harness.cell(args.workload)
    if cell["kind"] == "serve":
        import serve_cell as kind
    elif cell["kind"] == "train":
        import train_cell as kind
    else:
        raise ValueError(f"unknown cell kind {cell['kind']!r}")
    rec = kind.run(args.workload, args.seed, args.seconds, bool(args.trace))
    conf = harness.config_file(cell["config"])
    devs = rec.pop("devs")
    dev = harness.device_info(devs)
    rec["conf"] = conf
    rec["peaks"] = harness.peaks(dev["kind"])
    dev["memory_peak_bytes"] = rec["memory_peak_bytes"]
    metrics, notes = read_metrics(
        metrics_for(bench, args.workload, bool(args.trace)), rec)
    result = {"correct": rec["correct"], "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": dev}
    if args.trace:
        dev["busy_s"] = rec["trace"]["busy_s"]
        dev["window_s"] = rec["trace_window"]["seconds"]
        result["breakdown"] = breakdown(rec["trace"])
    print(json.dumps({"timings": rec["timings"], "notes": notes}),
          flush=True)
    result["checks"] = rec["checks"]
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        result = run(args)
    except harness.NoChip as e:
        print(f"run.py: {e}; nothing measured", file=sys.stderr)
        return 3
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
