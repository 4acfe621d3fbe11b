#!/usr/bin/env python3
"""Find a serve cell's knee once, by a sweep on the chip (not run by the
benchmark's own runs).

    python3 benchmarks/chip/sweep.py --workload <cell> \
        --rates 1,2,3,4 --seconds 30

One process, one engine: for each fixed arrival rate, the cell's traffic
mix at that rate (its warm load, a window of ``--seconds``, the drain).
Per rate: the share of requests due in the window that met both limits
(TTFT from the due time and TPOT; a failed request misses), the tails,
the output tokens per second in the window, and the waiting queue at the
window's start and end. The knee is the highest rate at which the share
is at least ``--attain`` and the queue does not grow. One JSON line per
rate.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import serve_cell  # noqa: E402
import traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--ttft-s", type=float, default=2.0)
    ap.add_argument("--tpot-s", type=float, default=0.1)
    ap.add_argument("--attain", type=float, default=0.9)
    args = ap.parse_args()
    cell = harness.cell(args.workload)
    devs = harness.require_chips(cell["chips"])
    harness.enable_cache()
    clock = harness.CompileClock()
    conf = harness.config_file(cell["config"])
    ref = harness.reference(cell["config"])
    base = harness.traffic_mix(cell["traffic"])
    cfg, _, eng = serve_cell.build(cell, conf, base, ref, args.seed)
    _, nseed = harness.split_seed(args.seed)
    serve_cell.warm_shapes(eng, cfg, np.random.default_rng(nseed))
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(base)
        mix["arrivals"]["rate_per_s"] = rate
        arr = traffic.generate(mix, nseed, cfg.vocab_size, args.seconds)
        w = serve_cell.Window(eng, arr, time.monotonic() + mix["warm_s"],
                              args.seconds, None, clock)
        w.run()
        eng.finished.clear()
        lat = serve_cell.latencies(w)
        win = serve_cell.counters(w, "open", "close")
        met = sum(a <= args.ttft_s and b <= args.tpot_s
                  for a, b in zip(lat["ttft_s"], lat["tpot_s"]))
        n = len(lat["ttft_s"])
        share = met / n if n else 0.0
        growing = win["waiting_at_end"] > max(2, 2 * win["waiting_at_start"])
        row = {"rate_per_s": rate, "requests": n, "failed": lat["failed"],
               "attained": share, "queue_growing": growing,
               "waiting_at_start": win["waiting_at_start"],
               "waiting_at_end": win["waiting_at_end"],
               "ttft_s": harness.summary(lat["ttft_s"]),
               "tpot_s": harness.summary(lat["tpot_s"]),
               "output_tok_s": w.window_tokens / win["seconds"],
               "generator_lateness_s": harness.summary(lat["lateness_s"]),
               "compiles_in_window": win["compiles"]}
        print(json.dumps(row), flush=True)
        if share >= args.attain and not growing:
            knee = rate
    print(json.dumps({"knee_rate_per_s": knee, "device":
                      harness.device_info(devs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
