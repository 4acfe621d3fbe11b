"""``paged_attn_roofline``: the paged-attention kernel of the decode tick
against its roofline, in %. The least time for every decoded token in the
traced window (its query against the live blocks of its context, int8
codes and fp16 scales, every layer; ``flops.paged_attention``) over the
summed device time of the kernel's events inside the tick runs."""

import re

import flops
import trace_reduce

KERNEL = re.compile(r"^paged_attention")


def _tick():
    import harness

    return harness.metric_reader("decode_tick_ms")


def read(rec):
    if "trace" not in rec:
        return None
    runs = _tick().tick_runs(rec)
    ops = [o for o in trace_reduce.within(rec["trace"]["ops"], runs)
           if KERNEL.search(o[0])]
    ctx = [x for step in rec["trace_steps"] for x in step["decode_ctx"]]
    if not ops or not ctx:
        return None
    c = rec["conf"]["config"]
    o, b = flops.paged_attention(
        ctx, heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        block=rec["block_size"])
    p = rec["peaks"]
    ideal = c["num_hidden_layers"] * max(o / p["bf16_flops"],
                                         b / p["hbm_bytes_per_s"])
    return 100.0 * ideal / (1e-9 * sum(x[2] for x in ops))
