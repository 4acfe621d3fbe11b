"""A serving cell: the program's ``ServingEngine`` under open-loop traffic.

Set-up makes the weights on the device from the seed (one jitted call),
builds the served artifact (packed 2/4/8-bit sites named in the cell
file), builds the engine, runs one request per prefill-chunk shape the
traffic can produce, then runs the mix's warm load. The window opens at a
fixed offset after that and lasts ``--seconds``; arrivals stop when it
closes. A cell that drains (the default) then runs until every request
due in the window has finished; a throughput cell (``"drain": false``)
stops at the close. Latency is timed from each request's due time.

Then ``memory_peak_bytes`` is read, the engine is freed, and the
configuration's plain reference checks seeded samples of the greedy and
of the sampled requests that finished, the longest of each among them.
"""

from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

import harness
import traffic

# gate values whose bit-width T(g) (CGMQ Eq. 4) is exactly 2 / 4 / 8 / 32
GATE_FOR_BITS = {2: 0.5, 4: 1.5, 8: 2.5, 32: 5.0}
DRAIN_LIMIT_S = 120.0


@dataclasses.dataclass
class Served:
    """What the run saw of one request."""

    arrival: traffic.Arrival
    due: float                      # absolute, on the monotonic clock
    submit: float = 0.0
    req: object = None


def make_quant_state(cfg, params, site_bits: dict, ref):
    """The served artifact's state: each weight site at its cell-file
    bit-width with per-output-channel ranges max |w|; activation sites at
    full width."""
    import jax.numpy as jnp

    from repro.core.sites import (QuantConfig, collect_sites, init_gates,
                                  init_ranges_from_weights,
                                  split_learnable_ranges)
    from repro.models import transformer as tfm

    qcfg = QuantConfig(granularity="per_channel")
    dummy = jnp.zeros((1, 8), jnp.int32)
    sites = collect_sites(
        lambda qc, p, x: tfm.forward_train(qc, p, x, cfg,
                                           moe_impl="dense_all", remat=False),
        params, dummy, cfg=qcfg)
    gates = init_gates(sites, qcfg, init=GATE_FOR_BITS[32])
    for key, g in gates.items():
        if key.endswith(".w"):
            leaf = key[:-2].rsplit("/", 1)[-1]
            gates[key] = jnp.full_like(g, GATE_FOR_BITS[site_bits[leaf]])
    ranges = init_ranges_from_weights(
        sites, qcfg, lambda name: ref.weight_for_site(params, name))
    betas, signed = split_learnable_ranges(ranges)
    return {"qcfg": qcfg, "gates": gates, "betas": betas, "signed": signed}


def check_export(eng, site_bits: dict) -> None:
    """Every weight site is served from packed codes at its stated width."""
    for key, qt in eng.qweights.items():
        leaf = key[:-2].rsplit("/", 1)[-1]
        if qt.storage_bits != site_bits[leaf]:
            raise RuntimeError(f"{key} served at {qt.storage_bits} bits, "
                               f"cell states {site_bits[leaf]}")
    missing = {k for k in site_bits} - {
        k[:-2].rsplit("/", 1)[-1] for k in eng.qweights}
    if missing:
        raise RuntimeError(f"sites not exported: {sorted(missing)}")


def warm_lengths(chunk: int) -> list[int]:
    """Prompt lengths that hit every padded chunk shape the engine can run
    (its last chunk pads to a power of two from 8 up to ``chunk``)."""
    out, b = [], 8
    while b < chunk:
        out.append(b)
        b *= 2
    return out + [chunk, chunk + 8]



def build(cell: dict, conf: dict, mix: dict, ref, seed: int, *, cfg=None,
          impl: str | None = None):
    """Weights, artifact and engine of a serve cell."""
    import jax

    from repro.serving import ServingEngine

    if cfg is None:
        cfg = harness.model_config(conf)
    jseed, _ = harness.split_seed(seed)
    make = jax.jit(lambda k: ref.make_weights(conf, k, cfg.padded_vocab))
    params = make(jax.random.PRNGKey(jseed))
    site_bits = cell["artifact"]["site_bits"]
    qs = make_quant_state(cfg, params, site_bits, ref)
    e = cell["engine"]
    eng = ServingEngine(
        cfg, params, slots=e["slots"],
        max_seq=traffic.max_prompt(mix) + traffic.max_output(mix),
        quant_state=qs, kv_dtype=e["kv_dtype"], block_size=e["block_size"],
        prefix_sharing=e["prefix_sharing"],
        prefill_chunk_tokens=e["prefill_chunk_tokens"],
        matmul_impl=impl or e["matmul_impl"])
    check_export(eng, site_bits)
    return cfg, params, eng


def warm_shapes(eng, cfg, rng) -> None:
    """Short requests, greedy and sampled, that run every chunk shape and
    pass through every slot (the engine reads a slot's table row with a
    static index, one small program per slot), one of them sharing the
    prefix of a request still decoding (the engine registers a prompt's
    blocks when its prefill ends, so sharing needs the first one live by
    then); runs to completion."""
    from repro.serving import Request, SamplingParams

    def request(i, prompt, max_new=3):
        return Request(rid=-1 - i, prompt=prompt.astype(np.int32),
                       params=SamplingParams(
                           max_new=max_new, temperature=0.7 * (i % 2),
                           top_p=0.9, seed=i))

    lens = warm_lengths(eng.prefill_chunk_tokens)
    shared = rng.integers(0, cfg.vocab_size, 2 * eng.block_size)
    first = request(0, np.concatenate(
        [shared, rng.integers(0, cfg.vocab_size, lens[0])]), max_new=8)
    eng.submit(first)
    while not first.output:
        eng.step()
    hits = eng.stats["prefix_hit_blocks"]
    for i in range(1, max(eng.slots, len(lens)) + 1):
        prompt = rng.integers(0, cfg.vocab_size, lens[i % len(lens)])
        if i == 1:
            prompt = np.concatenate([shared, prompt])
        eng.submit(request(i, prompt))
    eng.run_to_completion(max_ticks=10_000)
    if eng.prefix_sharing and eng.stats["prefix_hit_blocks"] == hits:
        raise RuntimeError("the warm-up shared no prefix")
    eng.finished.clear()


class Window:
    """Drives the engine from the warm load through the window and the
    drain, taking every timing on one monotonic clock."""

    def __init__(self, eng, arrivals, t_open: float, seconds: float,
                 trace_dir: Path | None, clock: harness.CompileClock, *,
                 drain: bool = True):
        self.eng = eng
        self.drain = drain
        self.t_open = t_open
        self.t_close = t_open + seconds
        self.served = [Served(a, t_open + a.due_s) for a in arrivals]
        self.tracing = trace_dir is not None
        self.trace_dir = trace_dir
        n_tr = min(5.0, seconds / 2)
        self.t_trace = (t_open + (seconds - n_tr) / 2,
                        t_open + (seconds + n_tr) / 2)
        self.clock = clock
        self.snap = {}
        self.window_tokens = 0
        self.window_decode_tokens = 0
        # per step while tracing: prefix-hit blocks the step counted, and
        # the requests whose first and later tokens it emitted
        self.trace_steps = []

    def _stats(self, name: str) -> None:
        self.snap[name] = (time.monotonic(), dict(self.eng.stats),
                           self.clock.count, self.clock.seconds,
                           len(self.eng.waiting))

    def run(self) -> None:
        import jax

        eng = self.eng
        i, n = 0, len(self.served)
        phase = "warm"
        trace_state = "before" if self.tracing else "off"
        while True:
            now = time.monotonic()
            if phase == "warm" and now >= self.t_open:
                phase = "window"
                self._stats("open")
            if phase == "window" and now >= self.t_close:
                phase = "drain"
                self._stats("close")
            if phase == "drain" and not self.drain and trace_state in (
                    "off", "done"):
                break
            if trace_state == "before" and now >= self.t_trace[0]:
                harness.start_trace(self.trace_dir)
                self._stats("trace_start")
                trace_state = "on"
            if trace_state == "on" and now >= self.t_trace[1]:
                jax.block_until_ready(eng.cache)
                self._stats("trace_stop")
                jax.profiler.stop_trace()
                trace_state = "done"
            tracing = trace_state == "on"
            with harness.span(tracing, "submit"):
                while (i < n and self.served[i].due <= now
                       and self.served[i].due < self.t_close):
                    s = self.served[i]
                    s.submit = now
                    s.req = eng.submit(_request(s.arrival))
                    i += 1
            arrivals_done = i >= n or self.served[i].due >= self.t_close
            busy = eng.waiting or any(r is not None for r in eng.slot_req)
            if not busy:
                if arrivals_done and phase == "drain" and trace_state in (
                        "off", "done"):
                    break
                nxt = (self.served[i].due if not arrivals_done
                       else self.t_close)
                for t in (self.t_open, self.t_close, *self.t_trace):
                    if t > now:
                        nxt = min(nxt, t)
                with harness.span(tracing, "wait_arrival"):
                    time.sleep(max(min(nxt - now, 0.05), 0.0))
                continue
            if phase == "drain" and now > self.t_close + DRAIN_LIMIT_S:
                break
            hits = eng.stats["prefix_hit_blocks"]
            with harness.span(tracing, "step"):
                events = eng.step()
            t = time.monotonic()
            emitted = [ev for ev in events if ev.token >= 0]
            if self.t_open <= t < self.t_close:
                self.window_tokens += len(emitted)
                self.window_decode_tokens += sum(ev.index > 0
                                                 for ev in emitted)
            if tracing:
                self.trace_steps.append(
                    (eng.stats["prefix_hit_blocks"] - hits,
                     [ev.rid for ev in emitted if ev.index == 0],
                     [(ev.rid, ev.index) for ev in emitted if ev.index > 0]))
        self._stats("end")


def _request(a: traffic.Arrival):
    from repro.serving import Request, SamplingParams

    return Request(rid=a.rid, prompt=a.prompt,
                   params=SamplingParams(max_new=a.max_new,
                                         temperature=a.temperature,
                                         top_p=a.top_p, seed=a.seed))


def latencies(w: Window) -> dict:
    """TTFT and TPOT of every request due in the window that finished; a
    request that failed, or in a cell that drains never finished, misses
    with the time the run waited on it. In a cell that stops at the close
    a request still in the engine is unfinished, not failed."""
    ttft, tpot, failed, finished, unfinished = [], [], 0, 0, 0
    end = w.snap["end"][0]
    late = []
    for s in w.served:
        if not (w.t_open <= s.due < w.t_close):
            continue
        late.append(s.submit - s.due)
        r = s.req
        ok = (r is not None and r.done and r.finish_reason in
              ("length", "stop") and r.first_token_s is not None)
        if not ok:
            if not w.drain and r is not None and not r.done:
                unfinished += 1
                continue
            failed += 1
            ttft.append(end - s.due)
            tpot.append(end - s.due)
            continue
        finished += 1
        ttft.append(r.first_token_s - s.due)
        n = len(r.output)
        tpot.append((r.finish_s - r.first_token_s) / (n - 1) if n > 1
                    else 0.0)
    return {"ttft_s": ttft, "tpot_s": tpot, "failed": failed,
            "finished": finished, "unfinished": unfinished,
            "lateness_s": late}


def counters(w: Window, a: str, b: str) -> dict:
    t0, s0, c0, cs0, q0 = w.snap[a]
    t1, s1, c1, cs1, q1 = w.snap[b]
    out = {k: s1[k] - s0[k] for k in s0}
    out["waiting_at_start"], out["waiting_at_end"] = q0, q1
    out["seconds"] = t1 - t0
    out["compiles"] = c1 - c0
    out["compile_s"] = cs1 - cs0
    return out


def check_sample(w: Window, rng, k: int, sampled: bool) -> list:
    """A seeded sample of the greedy (or sampled) requests that finished,
    with the longest among them."""
    done = [s for s in w.served
            if s.req is not None and s.req.done
            and (s.arrival.temperature > 0.0) == sampled
            and s.req.finish_reason in ("length", "stop")]
    if not done or k < 1:
        return []
    longest = max(done, key=lambda s: len(s.arrival.prompt)
                  + len(s.req.output))
    rest = [s for s in done if s is not longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def _padded(seqs, block: int) -> int:
    longest = max(len(p) + len(o) - 1 for p, o in seqs)
    return -(-longest // block) * block


def logit_gaps(ref, weights, conf, site_bits, seqs, *,
               block: int = 512) -> list[np.ndarray]:
    """Per ``(prompt, served)`` pair: for each served token, how far its
    reference logit lies below the reference's best at that position."""
    pad = _padded(seqs, block)
    out = []
    for prompt, served in seqs:
        toks = np.concatenate([prompt, served[:-1]])
        tgt = np.concatenate([np.zeros(len(prompt) - 1, np.int64), served])
        best, got, _, _ = ref.position_logits(weights, conf, site_bits,
                                              toks, tgt, pad_to=pad)
        p = len(prompt) - 1
        out.append(best[p:] - got[p:])
    return out


def nucleus_ratios(ref, weights, conf, site_bits, seqs, params, *,
                   block: int = 512) -> list[np.ndarray]:
    """Per ``(prompt, served)`` pair, sampled at ``params`` ``(temperature,
    top_p)``: for each served token, (1 - top_p) / (1 - m), m the
    reference's probability mass at that temperature on the tokens whose
    logit lies strictly above the served token's. A token inside the
    reference's nucleus reads under 1."""
    pad = _padded(seqs, block)
    out = []
    for (prompt, served), (temp, top_p) in zip(seqs, params):
        toks = np.concatenate([prompt, served[:-1]])
        tgt = np.concatenate([np.zeros(len(prompt) - 1, np.int64), served])
        _, _, _, above = ref.position_logits(weights, conf, site_bits, toks,
                                             tgt, pad_to=pad,
                                             temperature=temp)
        p = len(prompt) - 1
        out.append((1.0 - top_p) / np.maximum(1.0 - above[p:], 1e-300))
    return out


def control_gaps(ref, weights, conf, site_bits, seqs, compute: str, *,
                 block: int = 512) -> list[np.ndarray]:
    """The control: at each position of the same prompts and served
    tokens, the token the reference computed in ``compute`` puts first,
    and how far the float32 reference's logit for it lies below its
    best."""
    pad = _padded(seqs, block)
    out = []
    for prompt, served in seqs:
        toks = np.concatenate([prompt, served[:-1]])
        zero = np.zeros(len(toks), np.int64)
        _, _, top, _ = ref.position_logits(weights, conf, site_bits, toks,
                                           zero, compute=compute, pad_to=pad)
        best, got, _, _ = ref.position_logits(weights, conf, site_bits,
                                              toks, top, pad_to=pad)
        p = len(prompt) - 1
        out.append(best[p:] - got[p:])
    return out


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        overrides: dict | None = None) -> dict:
    """One run of a serve cell; returns the result line's fields.
    ``overrides`` (tests and ``control.py`` only) may replace the ``cell``,
    ``conf`` and ``mix`` files, the ``cfg``, the kernel ``impl``; skip the
    chip check (``chips: False``) and the persistent cache (``cache:
    False``); keep the weights and the checked sequences in the record
    (``keep: True``); put the control in the program's place (``control:
    <dtype>``: each checked greedy token is the one the reference computed
    in that dtype puts first at its position)."""
    import jax

    o = overrides or {}
    cell = o.get("cell") or harness.cell(cell_name)
    devs = (harness.require_chips(cell["chips"]) if o.get("chips", True)
            else jax.devices()[:1])
    if o.get("cache", True):
        harness.enable_cache()
    clock = harness.CompileClock()
    conf = o.get("conf") or harness.config_file(cell["config"])
    mix = o.get("mix") or harness.traffic_mix(cell["traffic"])
    ref = harness.reference(cell["config"])
    cfg, params, eng = build(cell, conf, mix, ref, seed, cfg=o.get("cfg"),
                             impl=o.get("impl"))
    _, nseed = harness.split_seed(seed)
    rng = np.random.default_rng(nseed)
    warm_shapes(eng, cfg, rng)
    arrivals = traffic.generate(mix, nseed, cfg.vocab_size, seconds)
    trace_dir = Path(tempfile.mkdtemp(prefix="chipbench_trace_")) \
        if trace else None
    t_open = time.monotonic() + float(mix["warm_s"])
    setup_s = t_open - harness.PROCESS_T0
    setup_compile_s = clock.seconds
    w = Window(eng, arrivals, t_open, seconds, trace_dir, clock,
               drain=cell.get("drain", True))
    w.run()
    lat = latencies(w)
    win = counters(w, "open", "close")
    mem = harness.peak_bytes(devs)
    record = {"cell": cell, "window": win, "latency": lat,
              "window_tokens": w.window_tokens,
              "window_decode_tokens": w.window_decode_tokens,
              "slots": eng.slots,
              "block_size": eng.block_size, "setup_compile_s":
              setup_compile_s}
    if trace:
        record.update(trace_record(w, trace_dir, devs))
        shutil.rmtree(trace_dir, ignore_errors=True)
    lim = cell["correct"]
    greedy = check_sample(w, rng, lim["requests"], False)
    sampled = check_sample(w, rng, lim["sampled_requests"], True)
    lengths_ok = all(len(s.req.output) == s.arrival.max_new
                     and all(0 <= t < cfg.vocab_size for t in s.req.output)
                     for s in greedy + sampled)
    del eng, w
    site_bits = cell["artifact"]["site_bits"]
    t_ref = time.monotonic()
    seqs = [(np.asarray(s.arrival.prompt), np.asarray(s.req.output))
            for s in greedy]
    sseqs = [(np.asarray(s.arrival.prompt), np.asarray(s.req.output))
             for s in sampled]
    if not seqs:
        gaps = []
    elif o.get("control"):
        gaps = control_gaps(ref, params, conf, site_bits, seqs,
                            o["control"])
    else:
        gaps = logit_gaps(ref, params, conf, site_bits, seqs)
    ratios = nucleus_ratios(
        ref, params, conf, site_bits, sseqs,
        [(s.arrival.temperature, s.arrival.top_p) for s in sampled]) \
        if sseqs else []
    widest = float(max((g.max() for g in gaps), default=float("nan")))
    deepest = float(max((r.max() for r in ratios), default=float("nan")))
    ref_s = time.monotonic() - t_ref
    n_tokens = sum(len(o_) for _, o_ in seqs)
    n_sampled = sum(len(o_) for _, o_ in sseqs)
    checks = {
        "max_logit_gap": {"value": widest, "limit": lim["max_logit_gap"]},
        "checked_tokens": {"value": n_tokens, "limit": lim["min_tokens"]},
        "sampled_tokens": {"value": n_sampled,
                           "limit": lim["min_sampled_tokens"]},
        "lengths_in_vocab": {"value": int(lengths_ok), "limit": 1},
    }
    if lim["nucleus_ratio"] is not None:
        checks["nucleus_ratio"] = {"value": deepest,
                                   "limit": lim["nucleus_ratio"]}
    correct = bool(
        widest <= lim["max_logit_gap"] and n_tokens >= lim["min_tokens"]
        and n_sampled >= lim["min_sampled_tokens"] and lengths_ok
        and (lim["nucleus_ratio"] is None
             or deepest <= lim["nucleus_ratio"]))
    timings = {
        "ttft_s": harness.summary(lat["ttft_s"]),
        "tpot_s": harness.summary(lat["tpot_s"]),
        "generator_lateness_s": harness.summary(lat["lateness_s"]),
        "unfinished": lat["unfinished"],
        "window": win, "setup_s": setup_s,
        "setup_compile_s": setup_compile_s, "reference_s": ref_s,
        "checked_requests": [len(greedy), len(sampled)],
        "nucleus_ratio": deepest}
    if o.get("keep"):
        record["weights"] = params
        record["checked"] = seqs
        record["gaps"] = gaps
        record["ratios"] = ratios
    record.update(checks=checks, setup_s=setup_s, memory_peak_bytes=mem,
                  devs=devs, correct=correct, timings=timings,
                  attempted=lat["finished"] + lat["failed"]
                  + lat["unfinished"], failed=lat["failed"])
    return record


def trace_record(w: Window, trace_dir: Path, devs) -> dict:
    """The reduced trace, the engine's counters over the traced span, and
    per traced step the prefix-hit blocks it counted, the prompt length of
    each request whose first token it emitted, and the context of each
    later token it emitted."""
    import trace_reduce

    span = counters(w, "trace_start", "trace_stop")
    red = trace_reduce.reduce_dir(trace_dir, len(devs))
    plens = {s.arrival.rid: len(s.arrival.prompt) for s in w.served}
    steps = [{"hit_blocks": hits, "prefills": [plens[r] for r in first],
              "decode_ctx": [plens[r] + i for r, i in later]}
             for hits, first, later in w.trace_steps]
    return {"trace": red, "trace_window": span, "trace_steps": steps}
