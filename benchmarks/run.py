"""Benchmark harness entry point — one function per paper table + kernel
micro-benchmarks + serving throughput.

Prints ``name,us_per_call,derived`` CSV rows (derived = the table's headline
metric) and writes full tables under artifacts/tables/. With ``--json``,
serving throughput (prefill/decode tok/s, time-to-first-token, prefill
forward counts vs the seed scan-of-decode-steps) and the kernel micro-bench
numbers are written to ``BENCH_serving.json``, and training-engine
throughput (steps/s, host syncs per epoch, scan vs python-loop speedup) to
``BENCH_training.json``, so the perf trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.run [--tier smoke|quick|paper]
                                            [--skip-tables] [--json [PATH]]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402


def _time(fn, *args, iters=10, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


# ---------------------------------------------------------------------------
# Paper tables (Table 1 / 2 / 3)
# ---------------------------------------------------------------------------


def bench_table1(tier: str):
    """Paper Table 1: CGMQ (dir x granularity) vs FP32 at bound 0.40%."""
    from benchmarks.repro_tables import save_rows, table1

    t0 = time.time()
    rows = table1(tier=tier, log=lambda s: print("   ", s))
    path = save_rows(rows, f"table1_{tier}")
    dt = (time.time() - t0) * 1e6
    best = max((r for r in rows if r.method == "CGMQ"), key=lambda r: r.acc)
    print(f"table1_{tier},{dt:.0f},best_acc={best.acc:.4f}@rbop="
          f"{best.rgbop*100:.3f}%")
    return rows, path


def bench_table_bounds(tier: str, gran: str, tableno: int):
    """Paper Tables 2/3: dir x bound sweeps (layer / indiv gates)."""
    from benchmarks.repro_tables import save_rows, table_bounds

    t0 = time.time()
    rows = table_bounds(gran, tier=tier, log=lambda s: print("   ", s))
    path = save_rows(rows, f"table{tableno}_{tier}")
    dt = (time.time() - t0) * 1e6
    sat = sum(r.satisfied for r in rows)
    print(f"table{tableno}_{tier},{dt:.0f},satisfied={sat}/{len(rows)}")
    return rows, path


# ---------------------------------------------------------------------------
# Kernel micro-benchmarks (interpret-mode correctness + XLA-path timing)
# ---------------------------------------------------------------------------


def bench_fake_quant():
    """Fused fake-quant vs the unfused 5-level residual chain (XLA path).

    On CPU we time the jnp reference paths; the derived metric is the
    bytes-moved ratio the fusion eliminates (the kernel's raison d'etre).
    """
    from repro.core.gates import gated_fake_quant, residual_fake_quant

    x = jnp.asarray(np.random.default_rng(0).normal(size=(2048, 2048)),
                    jnp.float32)
    g = jnp.asarray(2.5)
    b = jnp.asarray(1.0)
    fused = jax.jit(lambda x: gated_fake_quant(x, g, b, True))
    unfused = jax.jit(lambda x: residual_fake_quant(x, g, b, True))
    t_f = _time(fused, x)
    t_u = _time(unfused, x)
    print(f"kernel_fake_quant_fused,{t_f:.0f},speedup_vs_residual="
          f"{t_u/t_f:.2f}x")
    return {"fused_us": t_f, "residual_us": t_u, "speedup_x": t_u / t_f}


def bench_quant_matmul():
    """int8 dequant GEMM (jnp path) vs fp32 GEMM — weight-bytes ratio."""
    from repro.core.quantizer import quantize_to_int
    from repro.kernels.quant_matmul.ref import quant_matmul_ref

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(256, 2048)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2048, 2048)), jnp.float32)
    codes, scale, bias = quantize_to_int(w, 8, jnp.max(jnp.abs(w), axis=0), True)
    qmm = jax.jit(lambda x: quant_matmul_ref(x, codes, scale, bias))
    mm = jax.jit(lambda x: x @ w)
    t_q = _time(qmm, x)
    t_m = _time(mm, x)
    print(f"kernel_quant_matmul,{t_q:.0f},weight_bytes_ratio=0.25"
          f";fp32_ref_us={t_m:.0f}")
    return {"int8_ref_us": t_q, "fp32_us": t_m, "weight_bytes_ratio": 0.25}


def bench_flash_attention():
    """Interpret-mode flash attention vs dense reference (correctness run)."""
    from repro.kernels.flash_attention.ops import flash_attention_op
    from repro.kernels.flash_attention.ref import attention_ref

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    t_ref = _time(jax.jit(lambda q, k, v: attention_ref(q, k, v)), q, k, v,
                  iters=3, warmup=1)
    impl = "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"
    got = flash_attention_op(q, k, v, impl=impl)
    want = attention_ref(q, k, v)
    err = float(jnp.abs(got - want).max())
    print(f"kernel_flash_attention,{t_ref:.0f},interpret_max_err={err:.2e}")
    return {"dense_ref_us": t_ref, "interpret_max_err": err}


# ---------------------------------------------------------------------------
# Serving throughput (prefill / decode / TTFT)
# ---------------------------------------------------------------------------


def _serving_run(cfg, params, *, quant_state=None, slots=4, plen=12,
                 max_new=16, nreq=8, kv_layout="auto", same_prefix=False,
                 max_seq=64, sample=None, kv_dtype="bf16", act_bits=None):
    """One measured engine pass. Compiles on a throwaway request first so the
    numbers reflect steady-state serving, not jit tracing. With
    ``same_prefix`` every request reuses ONE prompt, exercising the paged
    prefix cache (N admissions ~ 1 prefill, DESIGN.md §10). ``sample``
    (e.g. ``dict(temperature=0.8, top_p=0.9)``) runs the in-tick stochastic
    sampling path instead of greedy argmax (DESIGN.md §12); per-request
    seeds keep the run reproducible."""
    from repro.serving import Request, SamplingParams, ServingEngine

    eng = ServingEngine(cfg, params, slots=slots, max_seq=max_seq,
                        quant_state=quant_state, kv_layout=kv_layout,
                        kv_dtype=kv_dtype, act_bits=act_bits)
    rng = np.random.default_rng(7)
    warm_sp = SamplingParams(max_new=2, **(sample or {}))
    eng.generate([rng.integers(0, cfg.vocab_size, (plen,))], warm_sp)
    eng.finished.clear()
    eng.stats = {k: 0 if isinstance(v, int) else 0.0
                 for k, v in eng.stats.items()}

    shared_prompt = rng.integers(0, cfg.vocab_size, (plen,))

    def _prompt():
        return (shared_prompt if same_prefix
                else rng.integers(0, cfg.vocab_size, (plen,)))

    def _params(i):
        return SamplingParams(max_new=max_new, seed=i, **(sample or {}))

    for i in range(nreq):
        eng.submit(Request(rid=i, prompt=_prompt(), params=_params(i)))
    blocks_hwm = 0
    ticks = 0
    while (eng.waiting or any(r is not None for r in eng.slot_req)) \
            and ticks < 1000:  # same bound as run_to_completion
        if not eng.step():
            break
        ticks += 1
        if eng.paged and eng.stats["decode_ticks"] == 1:
            blocks_hwm = eng.pool_stats()["blocks_in_use"]
    fin = eng.finished
    assert len(fin) == nreq
    st = eng.stats
    # SLO latencies from per-request arrival stamps (DESIGN.md §15): each
    # TTFT runs from ITS OWN submit, not engine start, so queue wait is in
    # the number and percentiles stay meaningful under ragged admission
    slo = eng.slo_stats()
    decode_tokens = st["generated_tokens"] - nreq
    # every model forward an admission costs: the batched prefill(s) plus
    # teacher-forced steps (prefix-shared sub-block replays) and SSM tail
    # forwards — dividing by prefills alone would overstate the reduction
    # on the prefix-sharing workload
    admission_forwards = (st["prefill_forwards"] + st["teacher_steps"]
                          + st["tail_forwards"])
    out = {
        "slots": slots,
        "requests": nreq,
        "prompt_len": plen,
        "max_new": max_new,
        "kv_layout": eng.kv_layout,
        "sampling": sample or "argmax",
        # the §8/§12 ledger: the tick must cost exactly ONE host transfer,
        # sampling enabled or not (CI-asserted from BENCH_serving.json)
        "host_syncs_per_tick":
            st["tick_syncs"] / max(st["decode_ticks"], 1),
        "ttft_s": slo["ttft_s"]["mean"],
        "slo": slo,
        "prefill_tok_s": st["prompt_tokens"] / max(st["prefill_time_s"], 1e-9),
        "decode_tok_s": decode_tokens / max(st["decode_time_s"], 1e-9),
        "prefill_forwards": st["prefill_forwards"],
        "seed_equiv_forwards": st["seed_equiv_forwards"],
        # seed prefill ran one decode forward per prompt token, each `slots`
        # wide; the batched path runs ONE single-row forward per admission.
        "admission_forwards": admission_forwards,
        "model_forward_reduction_x":
            st["seed_equiv_forwards"] / max(admission_forwards, 1),
        "slot_forward_reduction_x":
            st["seed_equiv_forwards"] * slots / max(admission_forwards, 1),
        "int8_sites": len(eng.qweights),
    }
    if eng.export_ledger is not None:
        # bytes/BOPs ledger of the artifact this run actually served
        out["quant_report"] = eng.quant_report()
    if eng.kv_spec is not None:
        # §14 KV-cache footprint: ceil-packed bytes/cached-token vs the
        # bf16 and fp32 float pools of the same geometry
        out["kv_report"] = eng.kv_report()
    if eng.paged:
        ps = eng.pool_stats()
        out.update({
            "block_size": ps["block_size"],
            "num_blocks": ps["num_blocks"],
            "blocks_in_use_early": blocks_hwm,
            "prefix_hit_rate": ps["prefix_hit_rate"],
            "shared_admissions": st["shared_admissions"],
            "cow_copies": st["cow_copies"],
        })
    return out


def _chaos_run(cfg, params, *, slots=4, plen=12, max_new=24, nreq=4,
               extra=2):
    """Serving-under-pressure smoke (DESIGN.md §13): the same seeded
    workload is run once solo-per-request on an ample pool (the reference
    streams) and once on a pool too small for the offered load with a
    bounded queue. The pressured run must preempt, resume every victim to
    a BIT-IDENTICAL stream, bounce the over-capacity submissions with
    ``FINISHED_REJECTED``, and keep the tick at one host sync."""
    from repro.serving import (FINISHED_LENGTH, FINISHED_REJECTED,
                               AdmissionConfig, Request, SamplingParams,
                               ServingEngine)

    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, (plen,))
               for _ in range(nreq + extra)]
    sps = [SamplingParams(temperature=0.8, top_p=0.9, seed=100 + i,
                          max_new=max_new) for i in range(nreq + extra)]

    solo = ServingEngine(cfg, params, slots=2, max_seq=64)
    ref = []
    for i in range(nreq):
        r = solo.submit(Request(rid=i, prompt=prompts[i], params=sps[i]))
        while not r.done:
            solo.step()
        ref.append(list(r.output))

    # 14 blocks can't back 4 slots at max_seq=64 (needs 33): preemption
    # auto-enables; queue capacity nreq bounces the extra submissions
    eng = ServingEngine(cfg, params, slots=slots, max_seq=64, num_blocks=14,
                        admission=AdmissionConfig(queue_capacity=nreq,
                                                  on_full="reject"))
    reqs = [eng.submit(Request(rid=i, prompt=prompts[i], params=sps[i]))
            for i in range(nreq + extra)]
    ticks = 0
    while (eng.waiting or any(r is not None for r in eng.slot_req)) \
            and ticks < 2000:
        eng.step()
        ticks += 1
    st = eng.stats
    served = [r for r in reqs if r.finish_reason == FINISHED_LENGTH]
    assert len(served) == nreq and all(r.done for r in reqs)
    return {
        "requests": nreq + extra,
        "num_blocks": 14,
        "preemptions": st["preemptions"],
        "resumed_admissions": st["resumed_admissions"],
        "preempted_stream_equal": bool(all(
            list(r.output) == ref[i] for i, r in enumerate(reqs[:nreq]))),
        "rejected_requests": st["rejected_requests"],
        "rejected_expected": sum(
            r.finish_reason == FINISHED_REJECTED for r in reqs),
        "host_syncs_per_tick":
            st["tick_syncs"] / max(st["decode_ticks"], 1),
        "blocks_leaked": eng.pool_stats()["blocks_in_use"],
    }


def _continuous_batching_run(cfg, params, *, slots=40, n_requests=48,
                             max_seq=64, chunk=8):
    """Continuous batching under trace-replay load (DESIGN.md §15): a
    seeded open-loop trace — ragged Poisson arrivals, mixed prompt-length
    buckets, prefix-shared bursts, mixed greedy/seeded-stochastic sampling
    — replayed against the chunked-prefill scheduler at 10x the smoke
    wave geometry's slot count. SLO latencies (TTFT/TPOT p50/p95/p99) come
    from per-request arrival stamps via ``slo_stats``; CI asserts the one-
    sync-per-tick ledger, a drained pool and a TTFT p95 smoke bound off
    this row."""
    from benchmarks.loadgen import make_trace, replay
    from repro.serving import SamplingParams, ServingEngine

    eng = ServingEngine(cfg, params, slots=slots, max_seq=max_seq,
                        prefill_chunk_tokens=chunk)
    rng = np.random.default_rng(21)
    # warm the jit caches (chunk prefill, armed decode, admission sync) so
    # the replay measures steady-state serving, not tracing
    eng.generate([rng.integers(0, cfg.vocab_size, (17,))],
                 SamplingParams(max_new=3, temperature=0.8, seed=1))
    eng.finished.clear()
    eng.stats = {k: 0 if isinstance(v, int) else 0.0
                 for k, v in eng.stats.items()}

    trace = make_trace(33, n_requests, cfg.vocab_size, mean_iat_s=0.003,
                       plen_buckets=(4, 12, 24, 48),
                       bucket_weights=(1, 3, 3, 1),
                       prefix_groups=3, prefix_len=12, prefix_fraction=0.25,
                       max_new=(2, 12), sampled_fraction=0.5)
    t0 = time.perf_counter()
    res = replay(eng, trace)
    wall = time.perf_counter() - t0
    reqs = list(res["requests"].values())
    assert len(reqs) == n_requests and all(r.done for r in reqs)
    st = eng.stats
    slo = eng.slo_stats()
    ps = eng.pool_stats()
    return {
        "slots": slots,
        "requests": n_requests,
        "max_seq": max_seq,
        "prefill_chunk_tokens": chunk,
        "tick_token_budget": eng.tick_token_budget,
        "prefill_chunks": st["prefill_chunks"],
        "ticks": res["ticks"],
        "wall_s": wall,
        "generated_tokens": st["generated_tokens"],
        "decode_tok_s": (st["generated_tokens"] - len(reqs))
        / max(st["decode_time_s"], 1e-9),
        "prefill_tok_s":
            st["prompt_tokens"] / max(st["prefill_time_s"], 1e-9),
        "host_syncs_per_tick":
            st["tick_syncs"] / max(st["decode_ticks"], 1),
        "ttft_s": slo["ttft_s"],
        "tpot_s": slo["tpot_s"],
        "preemptions": st["preemptions"],
        "prefix_hit_rate": ps["prefix_hit_rate"],
        "blocks_leaked": ps["blocks_in_use"] - ps["retained_blocks"],
    }


def _long_context_run(cfg, params, *, prompt_tokens=32_768, window=1024,
                      sink_blocks=1, block_size=8, chunk=512, max_new=8):
    """Long-context serving on a window-sized pool (DESIGN.md §17): one
    32k-token synthetic prompt decodes through a pool holding only the
    window demand — ~1/25th of the block-table width — because chunked
    prefill evicts out-of-window KV blocks in-tick as it streams forward.
    ``peak_blocks_in_use`` is sampled every engine step (prefill ticks
    included, where residency peaks at live-set + one chunk); CI asserts
    ``peak <= bound``, the one-sync-per-tick ledger and a drained pool."""
    from repro.serving import (SamplingParams, ServingEngine, WindowSpec,
                               window_demand_blocks)
    from repro.serving.engine import Request

    spec = WindowSpec(window=window, sink_blocks=sink_blocks)
    max_seq = prompt_tokens + max_new + block_size
    max_blocks = -(-max_seq // block_size)
    demand = window_demand_blocks(spec.bind(block_size), max_blocks,
                                  chunk, block_size)
    num_blocks = demand + 1  # + garbage block: the engine's floor exactly
    eng = ServingEngine(cfg, params, slots=1, max_seq=max_seq,
                        block_size=block_size, num_blocks=num_blocks,
                        prefill_chunk_tokens=chunk,
                        attention_window=spec)
    rng = np.random.default_rng(29)
    prompt = rng.integers(0, cfg.vocab_size, (prompt_tokens,))
    eng.submit(Request(rid=0, prompt=prompt,
                       params=SamplingParams(temperature=0.0,
                                             max_new=max_new)))
    t0 = time.perf_counter()
    peak = 0
    steps = 0
    # drive tick-by-tick so residency is sampled DURING chunked prefill,
    # where the §17 peak (live set + one chunk) actually occurs
    while eng.waiting or any(r is not None for r in eng.slot_req):
        eng.step()
        peak = max(peak, eng.pool_stats()["blocks_in_use"])
        steps += 1
        assert steps < 10_000
    wall = time.perf_counter() - t0
    st = eng.stats
    ps = eng.pool_stats()
    req = eng.finished[-1]
    assert len(req.output) == max_new, req.finish_reason
    return {
        "prompt_tokens": prompt_tokens,
        "window": window,
        "sink_blocks": sink_blocks,
        "num_blocks": num_blocks,
        "table_blocks": max_blocks,
        "peak_blocks_in_use": peak,
        "bound": demand,
        "window_report": ps["window"],
        "prefill_chunks": st["prefill_chunks"],
        "wall_s": wall,
        "decode_tok_s": (st["generated_tokens"] - 1)
        / max(st["decode_time_s"], 1e-9),
        "prefill_tok_s":
            st["prompt_tokens"] / max(st["prefill_time_s"], 1e-9),
        "host_syncs_per_tick":
            st["tick_syncs"] / max(st["decode_ticks"], 1),
        "blocks_leaked": ps["blocks_in_use"] - ps["retained_blocks"],
    }


def _kv_oracle_err(cfg, params, kv_dtype, plen=9, steps=4):
    """Max |logit| gap of a teacher-forced paged decode under quantized KV
    vs the fp32 float-pool oracle — same tokens, same block geometry, so
    the gap isolates KV storage error (DESIGN.md §14)."""
    import math

    from repro.core.sites import QuantContext
    from repro.models import transformer as tfm
    from repro.quant import KVQuantSpec
    from repro.serving import kv_pool

    spec = KVQuantSpec(bits=8 if kv_dtype == "int8" else 4,
                       group_size=math.gcd(cfg.head_dim, 32),
                       head_dim=cfg.head_dim)
    qc = QuantContext(mode="off")
    bs, max_seq = 8, 32
    x = jax.random.randint(jax.random.PRNGKey(1), (1, plen), 0,
                           cfg.vocab_size)
    rng = np.random.default_rng(2)
    toks = [int(rng.integers(0, cfg.vocab_size)) for _ in range(steps)]
    outs = []
    for kv_spec in (None, spec):
        mb = max_seq // bs
        cache = tfm.init_paged_cache(
            cfg, 1, mb + 1, bs,
            kv_dtype=jnp.float32 if kv_spec is None else jnp.bfloat16,
            kv_spec=kv_spec)
        alloc = kv_pool.init_alloc(mb + 1, 1, mb)
        alloc = kv_pool.alloc_range(alloc, 0, 0, -(-plen // bs))
        lg, cache = tfm.prefill_slot(qc, params, x, plen, cache, 0, cfg,
                                     block_table=alloc["table"])
        rows = [np.asarray(lg[0, plen - 1, : cfg.vocab_size])]
        adv = jnp.ones((1,), jnp.int32)
        for t in toks:
            alloc = kv_pool.tick_alloc(alloc, cache["pos"], adv, bs)
            lg, cache = tfm.decode_step(qc, params, cache,
                                        jnp.asarray([t], jnp.int32), cfg,
                                        advance=adv,
                                        block_table=alloc["table"])
            rows.append(np.asarray(lg[0, 0, : cfg.vocab_size]))
        outs.append(np.stack(rows))
    return float(np.abs(outs[0] - outs[1]).max())


def bench_serving(tier: str):
    """Serving engine throughput on the smoke LM: fp32 and int8 paths."""
    from repro.configs import get_smoke_config
    from repro.models import transformer as tfm
    from repro.serving.engine import make_uniform_quant_state

    nreq = {"smoke": 8, "quick": 16, "paper": 32}.get(tier, 8)
    cfg = get_smoke_config("tinyllama-1.1b")
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))

    t0 = time.time()
    fp32 = _serving_run(cfg, params, nreq=nreq)
    print(f"serving_fp32,{fp32['decode_tok_s']:.0f},ttft_ms="
          f"{fp32['ttft_s']*1e3:.1f};prefill_tok_s="
          f"{fp32['prefill_tok_s']:.0f};forward_reduction="
          f"{fp32['model_forward_reduction_x']:.1f}x")
    # ring baseline on the same workload: the paged layout pays block-table
    # gather/scatter overhead on unshared traffic (bought back by prefix
    # sharing + block-granular memory); tracking both keeps the §8 perf
    # trajectory honest about that tradeoff.
    ring = _serving_run(cfg, params, nreq=nreq, kv_layout="ring")
    print(f"serving_fp32_ring,{ring['decode_tok_s']:.0f},ttft_ms="
          f"{ring['ttft_s']*1e3:.1f};paged_vs_ring_decode="
          f"{fp32['decode_tok_s']/max(ring['decode_tok_s'],1e-9):.2f}x")

    qs = make_uniform_quant_state(cfg, params)  # T(2.2) = 8 bits
    int8 = _serving_run(cfg, params, quant_state=qs, nreq=nreq)
    print(f"serving_int8,{int8['decode_tok_s']:.0f},ttft_ms="
          f"{int8['ttft_s']*1e3:.1f};int8_sites={int8['int8_sites']}")

    # fully-integer decode (DESIGN.md §16): calibrated per-tensor ``.in``
    # activation specs route every exported site through the int8×int8
    # integer-accumulation GEMM. CI asserts from BENCH_serving.json that the
    # row exists, the tick still costs exactly ONE host sync, and the BOP
    # certificate covers every activation site (acts.covered == acts.total).
    intgemm = _serving_run(cfg, params, quant_state=qs, nreq=nreq,
                           act_bits=8)
    acts = intgemm["quant_report"]["acts"]
    intgemm["bops_vs_int_weight_fp32_act"] = (
        intgemm["quant_report"]["bops"]["model"]
        / max(int8["quant_report"]["bops"]["model"], 1e-9))
    print(f"serving_int_gemm_decode,{intgemm['decode_tok_s']:.0f},"
          f"vs_fp32_act={intgemm['decode_tok_s']/max(int8['decode_tok_s'],1e-9):.2f}x;"
          f"act_sites={acts['covered']}/{acts['total']};"
          f"bops_model={intgemm['quant_report']['bops']['model']:.3g};"
          f"host_syncs_per_tick={intgemm['host_syncs_per_tick']:.2f}")

    # mixed 2/4/8-bit export: packed sub-byte storage (DESIGN.md §11). The
    # quant_report ledger in BENCH_serving.json is CI-asserted: packed
    # bytes/weight must land strictly below the uniform-int8 baseline.
    from repro.serving.engine import make_mixed_quant_state

    qs_mixed = make_mixed_quant_state(cfg, params)
    mixed = _serving_run(cfg, params, quant_state=qs_mixed, nreq=nreq)
    t = mixed["quant_report"]["totals"]
    print(f"serving_mixed_sub_byte,{mixed['decode_tok_s']:.0f},"
          f"bytes_per_weight={t['bytes_per_weight']:.3f};"
          f"vs_int8={t['bytes_per_weight']/t['uniform_int8_bytes_per_weight']:.2f}x;"
          f"rbop={mixed['quant_report']['bops']['rbop']*100:.2f}%")

    # sampled decode (DESIGN.md §12): the in-tick temperature/top-p path vs
    # the argmax baseline above, same workload. host_syncs_per_tick must
    # stay at exactly 1.0 in both (CI-asserted) — sampling lives inside the
    # jitted tick, it is not allowed to buy tokens with extra host traffic.
    sampled = _serving_run(cfg, params, nreq=nreq,
                           sample=dict(temperature=0.8, top_p=0.9))
    print(f"serving_sampled_decode,{sampled['decode_tok_s']:.0f},"
          f"vs_argmax={sampled['decode_tok_s']/max(fp32['decode_tok_s'],1e-9):.2f}x;"
          f"host_syncs_per_tick={sampled['host_syncs_per_tick']:.2f}")

    # paged-KV additions (DESIGN.md §10): decode throughput at a high slot
    # count, and same-prefix admission cost through the prefix cache.
    hi_slots = {"smoke": 16, "quick": 24, "paper": 32}.get(tier, 16)
    high = _serving_run(cfg, params, slots=hi_slots, nreq=2 * hi_slots,
                        max_new=8)
    print(f"serving_paged_high_slots,{high['decode_tok_s']:.0f},slots="
          f"{hi_slots};blocks_in_use={high['blocks_in_use_early']}")
    prefix = _serving_run(cfg, params, slots=8, nreq=nreq, plen=16,
                          same_prefix=True)
    print(f"serving_prefix_sharing,{prefix['decode_tok_s']:.0f},"
          f"prefills_for_{nreq}_same_prefix_reqs="
          f"{prefix['prefill_forwards']};hit_rate="
          f"{prefix['prefix_hit_rate']:.2f}")
    # quantized KV blocks (DESIGN.md §14): int8 (and packed int4) group-wise
    # codes with fused dequant in the paged-attention kernel. kv_report
    # gives ceil-packed bytes/cached-token; slots_at_bf16_pool_bytes is how
    # many concurrent slots the SAME pool byte budget backs vs bf16; the
    # logits error is a teacher-forced paged decode vs the fp32 float-pool
    # oracle. CI asserts the bytes ratio, the error bound, and one host
    # sync per tick from BENCH_serving.json.
    kv_rows = {}
    for name, kvd in (("kv_int8", "int8"), ("kv_int4", "int4")):
        row = _serving_run(cfg, params, nreq=nreq, kv_dtype=kvd)
        rep = row["kv_report"]
        row["bytes_per_cached_token"] = rep["bytes_per_cached_token"]
        row["slots_at_bf16_pool_bytes"] = int(
            row["slots"] / max(rep["vs_bf16"], 1e-9))
        row["logits_max_abs_err"] = _kv_oracle_err(cfg, params, kvd)
        print(f"serving_{name},{row['decode_tok_s']:.0f},"
              f"bytes_per_cached_token={rep['bytes_per_cached_token']};"
              f"vs_bf16={rep['vs_bf16']:.3f};vs_fp32={rep['vs_fp32']:.3f};"
              f"slots_at_bf16_pool_bytes={row['slots_at_bf16_pool_bytes']};"
              f"logits_max_abs_err={row['logits_max_abs_err']:.2e};"
              f"host_syncs_per_tick={row['host_syncs_per_tick']:.2f}")
        kv_rows[name] = row

    # serving under pressure (DESIGN.md §13): undersized pool + bounded
    # queue; preemption must happen, every resumed stream must be
    # bit-identical to its solo reference, overflow must bounce as typed
    # rejections, and the tick stays at ONE host sync (CI-asserted).
    chaos = _chaos_run(cfg, params)
    print(f"serving_chaos,{chaos['preemptions']},"
          f"stream_equal={chaos['preempted_stream_equal']};"
          f"rejected={chaos['rejected_requests']};"
          f"host_syncs_per_tick={chaos['host_syncs_per_tick']:.2f}")

    # continuous batching under trace-replay load (DESIGN.md §15): chunked
    # prefill interleaved with decode at 10x the smoke wave geometry.
    cont = _continuous_batching_run(cfg, params)
    print(f"serving_continuous_batching,{cont['decode_tok_s']:.0f},"
          f"slots={cont['slots']};requests={cont['requests']};"
          f"prefill_chunks={cont['prefill_chunks']};"
          f"ttft_p95_ms={cont['ttft_s']['p95']*1e3:.1f};"
          f"tpot_p95_ms={cont['tpot_s']['p95']*1e3:.1f};"
          f"host_syncs_per_tick={cont['host_syncs_per_tick']:.2f};"
          f"blocks_leaked={cont['blocks_leaked']}")
    # long-context serving (DESIGN.md §17): a 32k-token prompt decodes on a
    # pool sized for the attention window — in-tick out-of-window eviction
    # keeps residency O(window) while the block table spans the full prompt.
    # CI asserts peak_blocks_in_use <= bound, one host sync per tick, and a
    # drained pool from BENCH_serving.json.
    longctx = _long_context_run(cfg, params)
    print(f"serving_long_context,{longctx['decode_tok_s']:.0f},"
          f"prompt_tokens={longctx['prompt_tokens']};"
          f"window={longctx['window']};"
          f"peak_blocks_in_use={longctx['peak_blocks_in_use']}"
          f"/{longctx['bound']};"
          f"table_blocks={longctx['table_blocks']};"
          f"prefill_tok_s={longctx['prefill_tok_s']:.0f};"
          f"host_syncs_per_tick={longctx['host_syncs_per_tick']:.2f};"
          f"blocks_leaked={longctx['blocks_leaked']}")
    total_reqs = (5 * nreq + 2 * hi_slots + nreq + chaos["requests"]
                  + cont["requests"] + 1)
    print(f"serving_total,{(time.time()-t0)*1e6:.0f},"
          f"requests={total_reqs}")
    return {"fp32": fp32, "fp32_ring": ring, "int8": int8,
            "int_gemm_decode": intgemm,
            "mixed_sub_byte": mixed, "sampled_decode": sampled,
            "paged_high_slots": high, "prefix_sharing": prefix,
            **kv_rows, "chaos": chaos, "continuous_batching": cont,
            "long_context": longctx}


# ---------------------------------------------------------------------------
# Training engine throughput (scan epochs vs python-loop reference)
# ---------------------------------------------------------------------------


def bench_training(tier: str):
    """CGMQ stage-4 throughput on LeNet: jitted-scan epochs vs the per-batch
    python dispatch reference. Same staging + step functions, so the speedup
    is pure dispatch/host-sync overhead removed by the scan engine. Two
    regimes: the tier's batch size (compute-bound: the scan win is small on
    CPU and grows with dispatch cost) and a small-batch dispatch-bound config
    where the scan advantage dominates."""
    from benchmarks.repro_tables import _data, _pcfg, get_bundle
    from repro.core import bop as bop_lib
    from repro.core.controller import CGMQConfig
    from repro.core.pipeline import steps_per_epoch
    from repro.models import lenet
    from repro.train import EngineConfig, TrainEngine

    epochs = {"smoke": 6, "quick": 8, "paper": 10}.get(tier, 6)
    bundle = get_bundle(tier, "layer", log=lambda s: None)
    train, test = _data(tier)
    pcfg = _pcfg(tier, log=lambda s: None)

    def _measure(batch_size):
        spe = steps_per_epoch(train[0].shape[0], batch_size)
        ccfg = CGMQConfig(budget_rbop=0.02, direction="dir1", gate_lr=0.01,
                          check_every=spe)
        res = {"steps_per_epoch": spe, "batch_size": batch_size,
               "epochs": epochs}
        for loop in ("scan", "python"):
            eng = TrainEngine(
                lenet.forward,
                EngineConfig(batch_size=batch_size, lr=pcfg.lr,
                             eval_every=epochs, loop=loop,
                             log=lambda s: None),
                qcfg=bundle.qcfg)
            eng.bind_sites(bundle.sites, bundle.signed)
            eng.bind_controller(ccfg,
                                bop_lib.budget_from_rbop(bundle.sites, 0.02))
            state = eng.init_quant_state(bundle.params, bundle.betas,
                                         bundle.gates, bundle.probes, seed=0)
            state, _ = eng.run_stage(state, "cgmq", train, 1)  # compile warmup
            syncs0 = eng.host_syncs
            t0 = time.perf_counter()
            state, _ = eng.run_stage(state, "cgmq", train, 1 + epochs,
                                     start_epoch=1)
            dt = time.perf_counter() - t0
            res[loop] = {
                "seconds": dt,
                "steps_per_s": epochs * spe / dt,
                "host_syncs_per_epoch": (eng.host_syncs - syncs0) / epochs,
            }
        res["scan_speedup_x"] = (res["scan"]["steps_per_s"]
                                 / res["python"]["steps_per_s"])
        return res

    out = {
        "compute_bound": _measure(pcfg.batch_size),
        "dispatch_bound": _measure(8),
    }
    for name, res in out.items():
        print(f"training_scan_{name},"
              f"{res['scan']['seconds']/epochs/res['steps_per_epoch']*1e6:.0f},"
              f"steps_per_s={res['scan']['steps_per_s']:.1f};"
              f"speedup_vs_python_loop={res['scan_speedup_x']:.2f}x;"
              f"host_syncs_per_epoch={res['scan']['host_syncs_per_epoch']:.2f}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", default="smoke",
                    choices=["smoke", "quick", "paper"])
    ap.add_argument("--skip-tables", action="store_true")
    ap.add_argument("--json", nargs="?", const="BENCH_serving.json",
                    default=None, metavar="PATH",
                    help="write serving + kernel numbers to PATH "
                         "(default BENCH_serving.json)")
    args = ap.parse_args()
    enable_compile_cache()

    print("name,us_per_call,derived")
    kernels = {
        "fake_quant": bench_fake_quant(),
        "quant_matmul": bench_quant_matmul(),
        "flash_attention": bench_flash_attention(),
    }
    serving = bench_serving(args.tier)
    training = bench_training(args.tier)
    if not args.skip_tables:
        bench_table1(args.tier)
        bench_table_bounds(args.tier, "layer", 2)
        bench_table_bounds(args.tier, "indiv", 3)

    if args.json:
        import json

        payload = {
            "schema": 1,
            "tier": args.tier,
            "backend": jax.default_backend(),
            "serving": serving,
            "kernels": kernels,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.json}")

        tpath = "BENCH_training.json"
        tpayload = {
            "schema": 1,
            "tier": args.tier,
            "backend": jax.default_backend(),
            "training": training,
        }
        with open(tpath, "w") as f:
            json.dump(tpayload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {tpath}")


if __name__ == "__main__":
    main()
