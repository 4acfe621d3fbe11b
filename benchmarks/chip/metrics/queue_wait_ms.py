"""``queue_wait_ms.*``: mean time a request waited in the engine's queue,
from its submit to its first binding to a slot, over the requests first
bound inside the window, in ms (engine counters ``queue_wait_s`` /
``admissions``). None where the engine keeps no such counters."""


def read(rec):
    w = rec["window"]
    if not w.get("admissions"):
        return None
    return 1e3 * w["queue_wait_s"] / w["admissions"]
