"""Serving example: batched requests against a (smoke) LM through the
request-lifecycle API — ``engine.generate(prompts, SamplingParams(...))``
over batched prefill, the device-resident sampled generation loop, and the
CGMQ mixed-precision packed-int decode path (DESIGN.md §8/§11/§12).

    PYTHONPATH=src python examples/serve_quantized.py --arch tinyllama-1.1b
    PYTHONPATH=src python examples/serve_quantized.py --mixed  # 2/4/8-bit
    PYTHONPATH=src python examples/serve_quantized.py --fp32   # skip int
    PYTHONPATH=src python examples/serve_quantized.py --act-bits 8  # int8×int8
    PYTHONPATH=src python examples/serve_quantized.py \\
        --temperature 0.8 --top-p 0.9 --seed 7 --stream
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.models import transformer as tfm
from repro.serving import (SamplingParams, ServingEngine, WindowSpec,
                           export_int_codes, make_mixed_quant_state,
                           make_uniform_quant_state)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--fp32", action="store_true",
                    help="serve fp32 instead of the quantized export")
    ap.add_argument("--mixed", action="store_true",
                    help="mixed 2/4/8-bit gates (packed sub-byte storage) "
                         "instead of uniform 8-bit")
    ap.add_argument("--act-bits", default="none", choices=["8", "4", "none"],
                    help="quantize GEMM input activations at this width and "
                         "serve fully-integer int8×int8 MACs (DESIGN.md "
                         "§16); none = int-weight × fp32-act GEMMs")
    ap.add_argument("--kv-layout", default="auto",
                    choices=["auto", "paged", "ring"],
                    help="KV cache substrate (DESIGN.md §10); auto = paged "
                         "for attention archs")
    ap.add_argument("--same-prefix", action="store_true",
                    help="submit every request with one shared prompt to "
                         "demo paged prefix sharing (N admissions ~ 1 "
                         "prefill)")
    ap.add_argument("--prefix-lru-blocks", type=int, default=0,
                    help="retain up to this many fully-retired prefix "
                         "blocks in an LRU pool (0 = evict at zero refs)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax, the "
                         "bit-exact oracle path)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest-probability tokens "
                         "(0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off)")
    ap.add_argument("--seed", type=int, default=None,
                    help="per-request sampling seed (requests get "
                         "seed, seed+1, ... so streams are reproducible "
                         "yet distinct)")
    ap.add_argument("--stream", action="store_true",
                    help="use generate_stream() and print tokens as the "
                         "ticks emit them")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="continuous-batching chunked prefill: split each "
                         "prompt into chunks of this many tokens and "
                         "interleave them with decode ticks (DESIGN.md "
                         "§15; default = legacy whole-prompt waves)")
    ap.add_argument("--window", type=int, default=None,
                    help="long-context sliding attention window in tokens "
                         "(DESIGN.md §17): every attention site masks to "
                         "the last WINDOW positions and, on the paged "
                         "layout, out-of-window KV blocks are evicted "
                         "in-tick so residency stays O(window)")
    ap.add_argument("--sink-blocks", type=int, default=0,
                    help="with --window: leading paged KV blocks pinned "
                         "forever (attention sinks) — always attended, "
                         "never evicted")
    args = ap.parse_args()
    enable_compile_cache()
    if args.sink_blocks and args.window is None:
        ap.error("--sink-blocks requires --window")

    cfg = get_smoke_config(args.arch)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    if args.fp32:
        qs = None
    elif args.mixed:
        qs = make_mixed_quant_state(cfg, params)
    else:
        qs = make_uniform_quant_state(cfg, params)
    act_bits = None if args.act_bits == "none" else int(args.act_bits)
    if act_bits is not None and qs is None:
        ap.error("--act-bits requires a quantized export (drop --fp32)")
    window = None
    if args.window is not None:
        window = WindowSpec(window=args.window,
                            sink_blocks=args.sink_blocks)
    eng = ServingEngine(cfg, params, slots=args.slots, max_seq=128,
                        quant_state=qs, act_bits=act_bits,
                        kv_layout=args.kv_layout,
                        prefix_lru_blocks=args.prefix_lru_blocks,
                        prefill_chunk_tokens=args.prefill_chunk,
                        attention_window=window)
    if eng.qweights:
        storages = sorted({qt.storage_bits for qt in eng.qweights.values()})
        print(f"serving quantized export: {len(eng.qweights)} sites at "
              f"{storages}-bit packed storage")
        rep = eng.quant_report()
        t = rep["totals"]
        print(f"  device bytes/weight {t['bytes_per_weight']:.3f} incl. "
              f"affine aux (uniform int8 = "
              f"{t['uniform_int8_bytes_per_weight']:.3f}, fp32 = 4.0); "
              f"{t['fallback_sites']} fake-quant fallback sites; "
              f"RBOP {rep['bops']['rbop']*100:.2f}%")
        if act_bits is not None:
            a = rep["acts"]
            print(f"  fully-integer GEMMs: {a['covered']}/{a['total']} "
                  f"activation sites calibrated at {act_bits}-bit "
                  f"(int8×int8 integer accumulation; "
                  f"{len(a['fallback_sites'])} float-input fallbacks)")
    print(f"kv layout: {eng.kv_layout}"
          + (f" ({eng.num_blocks} blocks x {eng.block_size} tokens, "
             f"prefix sharing {'on' if eng.prefix_sharing else 'off'}, "
             f"LRU retention {eng.lru_capacity} blocks)"
             if eng.paged else ""))

    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, (16,))
    prompts, plist = [], []
    for i in range(args.requests):
        plen = int(rng.integers(3, 10))
        prompts.append(shared if args.same_prefix
                       else rng.integers(0, cfg.vocab_size, (plen,)))
        plist.append(SamplingParams(
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, max_new=args.max_new,
            seed=None if args.seed is None else args.seed + i))
    t0 = time.time()
    if args.stream:
        # FIFO admission means first-seen event order == submission order,
        # so number the requests as their first tokens arrive
        ridx, streamed = {}, {}
        for ev in eng.generate_stream(prompts, plist):
            i = ridx.setdefault(ev.rid, len(ridx))
            print(f"  tick -> req {i} token {ev.token}"
                  + (f" [{ev.finish_reason}]" if ev.done else ""))
            streamed.setdefault(i, []).append(ev.token)
        results = None
    else:
        results = eng.generate(prompts, plist)
    dt = time.time() - t0
    st = eng.stats
    total_new = st["generated_tokens"]
    print(f"served {args.requests} requests / {total_new} tokens "
          f"in {dt:.1f}s with {args.slots} slots "
          f"({'sampled t=%.2f' % args.temperature if args.temperature > 0 else 'greedy'}; "
          f"{st['tick_syncs']} host syncs over {st['decode_ticks']} ticks)")
    print(f"  batched prefill: {st['prefill_forwards']} forwards for "
          f"{st['prompt_tokens']} prompt tokens (seed scan-of-decode-steps "
          f"would have run {st['seed_equiv_forwards']} x {args.slots}-wide)")
    if args.prefill_chunk:
        print(f"  chunked prefill: {st['prefill_chunks']} chunks of "
              f"<= {args.prefill_chunk} tokens interleaved with decode ticks")
    if eng.paged:
        ps = eng.pool_stats()
        print(f"  paged KV: prefix-hit rate {ps['prefix_hit_rate']:.2f}, "
              f"{st['shared_admissions']} shared admissions, "
              f"{st['cow_copies']} CoW copies, "
              f"{ps['blocks_in_use']} blocks still in use "
              f"({ps['retained_blocks']} LRU-retained)")
        if "window" in ps:
            w = ps["window"]
            print(f"  attention window: {w['window']} tokens + "
                  f"{w['sink_blocks']} sink blocks -> "
                  f"{w['live_blocks_per_slot']} live blocks/slot of "
                  f"{w['table_blocks_per_slot']} table blocks "
                  f"(residency {w['residency_ratio']:.2f}x)")
    if results is not None:
        for i, r in enumerate(results):
            print(f"  req {i}: {r.tokens} [{r.finish_reason}]")
    else:
        for i in sorted(streamed):
            print(f"  req {i}: {streamed[i]}")

    # single-tensor export path: packed codes for one weight
    b0 = params["blocks"][0]
    if "attn" in b0:
        w = b0["attn"]["wq"][0]
    elif "ssd" in b0:
        w = b0["ssd"]["in_proj"][0]
    else:
        w = b0["rglru"]["wx"][0]
    for gate, label in ((2.5, "8-bit"), (1.5, "4-bit packed")):
        q = export_int_codes(w, gate=jnp.asarray(gate),
                             beta=jnp.max(jnp.abs(w)), signed=True)
        deq_err = float(jnp.abs(q.dequantize() - w).max())
        print(f"exported wq[0] {label}: {q.codes_bytes()} code bytes for "
              f"{q.weight_count()} weights; max dequant error {deq_err:.4f} "
              f"(|w|max {float(jnp.abs(w).max()):.3f})")


if __name__ == "__main__":
    main()
