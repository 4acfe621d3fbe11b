"""``serve_mfu.*``: model FLOPs of the work the engine finished in the
traced window, over the window times the chip's bf16 peak, in %. Each
later token counts its forward at its context; each prompt whose first
token came in the window counts its causal prefill from the end of the
prefix the cache served it (``flops.prefill``). The engine counts a
prompt's cache hits in the step that finishes its prefill; where one step
finishes several, its hits go to the longest prompts first, each at most
the full blocks before its last token."""

import flops


def prefill_spans(step: dict, block: int) -> list:
    """``(hit, prompt length)`` of each prefill the step finished."""
    hits, out = step["hit_blocks"] * block, []
    for plen in sorted(step["prefills"], reverse=True):
        h = min(hits, (plen - 1) // block * block)
        out.append((h, plen))
        hits -= h
    return out


def read(rec):
    if "trace" not in rec:
        return None
    c = rec["conf"]["config"]
    total = 0.0
    for step in rec["trace_steps"]:
        for hit, plen in prefill_spans(step, rec["block_size"]):
            total += flops.prefill(c, hit, plen)
        total += sum(flops.decode_token(c, x) for x in step["decode_ctx"])
    if total <= 0:
        return None
    window = rec["trace_window"]["seconds"]
    return 100.0 * total / (window * rec["peaks"]["bf16_flops"])
