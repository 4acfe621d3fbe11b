"""``decode_tick_ms.*``: mean device time of one run of the engine's
jitted decode tick in the traced window (its events on the trace's
``XLA Modules`` line)."""

import re

TICK = re.compile(r"^jit__tick\b")


def tick_runs(rec) -> list:
    return [m for m in rec["trace"]["modules"] if TICK.match(m[0])]


def read(rec):
    if "trace" not in rec:
        return None
    runs = tick_runs(rec)
    if not runs:
        return None
    return 1e-6 * sum(m[2] for m in runs) / len(runs)
