"""``device_idle_share.*``: share of the traced window in which no op ran
on the device, in % (1 - busy union / window)."""


def read(rec):
    if "trace" not in rec:
        return None
    window = rec["trace_window"]["seconds"]
    return 100.0 * (1.0 - rec["trace"]["busy_s"] / window)
