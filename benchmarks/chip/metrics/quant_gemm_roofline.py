"""``quant_gemm_roofline.*``: the packed 2/4/8-bit weight GEMMs of the
decode tick against their roofline, in %. For every tick run in the
traced window, the least time the chip could take for each GEMM (the
larger of its operations over the bf16 peak and its bytes over HBM
bandwidth, ``flops.gemm``, every slot row counted because the tick
computes every slot), summed, over the summed device time of the GEMM
kernels' events inside those runs."""

import re

import flops
import trace_reduce

KERNEL = re.compile(r"^quant_matmul")


def _tick():
    import harness

    return harness.metric_reader("decode_tick_ms")


def ideal_s(rec) -> tuple[float, str]:
    c = rec["conf"]["config"]
    bits = rec["cell"]["artifact"]["site_bits"]
    p = rec["peaks"]
    rows = rec["slots"]
    total, mem = 0.0, 0.0
    sites = [(k, n, bits[s], c["num_hidden_layers"])
             for s, (k, n) in flops.layer_sites(c).items()]
    sites.append((c["hidden_size"], c["vocab_size"], bits["head"], 1))
    for k, n, b, times in sites:
        ops, byt = flops.gemm(rows, k, n, b)
        t = max(ops / p["bf16_flops"], byt / p["hbm_bytes_per_s"])
        total += times * t
        mem += times * (byt / p["hbm_bytes_per_s"] >= ops / p["bf16_flops"])
    return total, ("memory" if mem * 2 >= sum(s[3] for s in sites)
                   else "compute")


def read(rec):
    if "trace" not in rec:
        return None
    runs = _tick().tick_runs(rec)
    ops = [o for o in trace_reduce.within(rec["trace"]["ops"], runs)
           if KERNEL.search(o[0])]
    if not ops:
        return None
    spent = 1e-9 * sum(o[2] for o in ops)
    return 100.0 * len(runs) * ideal_s(rec)[0] / spent


def explain(rec):
    return "bound per tick: " + ideal_s(rec)[1]
