"""``batch_occupancy.*``: share of decode slots that emitted a token,
over the window: decode emissions / (decode ticks x slots), in %. The
engine's ``decode_ticks`` counter; emissions counted from ``step``'s
token events."""


def read(rec):
    ticks = rec["window"]["decode_ticks"]
    if not ticks:
        return None
    return 100.0 * rec["window_decode_tokens"] / (ticks * rec["slots"])
