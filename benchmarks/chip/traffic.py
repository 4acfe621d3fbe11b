"""The one traffic generator: reads a mix's parameters (``traffic/<mix>.json``)
and makes an open-loop arrival schedule from a seed.

Steadiness across seeds: the mix's own ``sizes_seed`` fixes the multiset
of the work (every inter-arrival gap, and every request's prompt and
output length, shared-prefix group and sampling flag). The run's seed
draws the order of the gaps and of the requests, every token (the
shared prompts, the rest of each prompt) and each request's sampling
key. So every seed offers the same work, in another order and with
other content.

Mix keys (all required unless marked):

- ``arrivals``: ``{"process": "poisson" | "gamma", "rate_per_s": r,
  "cv": c}`` — gamma gaps with coefficient of variation ``c`` (bursts);
  poisson is gamma with ``cv`` 1.
- ``warm_s``: seconds of arrivals before the window opens (they fill the
  engine; the window's counts start at 0).
- ``warm_burst`` (optional): requests due at once when the warm load
  starts, before the arrival process, so that a saturated engine is full
  when the window opens.
- ``prompt`` / ``output``: lognormal lengths ``{"median", "sigma", "min",
  "max"}``, clipped.
- ``shared_prefix`` (optional): ``{"share", "groups", "tokens"}`` — that
  share of requests starts with one of ``groups`` prompts of ``tokens``
  tokens; the drawn prompt length is the part after it, and the whole
  prompt is clipped to ``prompt.max``.
- ``sampled``: ``{"share", "temperature", "top_p"}`` — the rest is greedy.
- ``sizes_seed``: the seed of the multiset above.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Arrival:
    """One request: due ``due_s`` seconds after the window opens (negative
    during the warm load)."""

    rid: int
    due_s: float
    prompt: np.ndarray
    max_new: int
    temperature: float
    top_p: float
    seed: int
    group: int  # shared-prefix group, -1 if none


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rng, arrivals: dict, n: int) -> np.ndarray:
    rate = float(arrivals["rate_per_s"])
    cv = 1.0 if arrivals["process"] == "poisson" else float(arrivals["cv"])
    if arrivals["process"] not in ("poisson", "gamma"):
        raise ValueError(arrivals["process"])
    shape = 1.0 / (cv * cv)
    return rng.gamma(shape, 1.0 / (rate * shape), n)


def sizes(mix: dict, horizon_s: float) -> dict:
    """The seed-independent work of the mix over ``warm_s + horizon_s``
    seconds: the arrival process's gaps, and per request (the warm
    burst's first) prompt and output lengths, groups, flags."""
    rng = np.random.default_rng(int(mix["sizes_seed"]))
    span = float(mix["warm_s"]) + float(horizon_s)
    want = int(span * float(mix["arrivals"]["rate_per_s"]) * 2 + 64)
    gaps = _gaps(rng, mix["arrivals"], want)
    gaps = gaps[:int(np.searchsorted(np.cumsum(gaps), span))]
    n = len(gaps) + int(mix.get("warm_burst", 0))
    prompt = _lognormal(rng, mix["prompt"], n)
    output = _lognormal(rng, mix["output"], n)
    sp = mix.get("shared_prefix")
    group = np.full(n, -1)
    if sp:
        shared = rng.random(n) < sp["share"]
        group[shared] = rng.integers(0, sp["groups"], int(shared.sum()))
    sampled = rng.random(n) < mix["sampled"]["share"]
    return {"gaps": gaps, "prompt": prompt, "output": output,
            "group": group, "sampled": sampled}


def generate(mix: dict, seed: int, vocab: int,
             horizon_s: float) -> list[Arrival]:
    """The requests of one run: arrivals from ``-warm_s`` up to
    ``horizon_s`` seconds after the window opens, in due order."""
    s = sizes(mix, horizon_s)
    rng = np.random.default_rng(int(seed))
    warm = float(mix["warm_s"])
    burst = int(mix.get("warm_burst", 0))
    due = np.concatenate([np.full(burst, -warm),
                          np.cumsum(rng.permutation(s["gaps"])) - warm])
    order = rng.permutation(len(due))
    s = {k: v[order] for k, v in s.items() if k != "gaps"}
    sp = mix.get("shared_prefix")
    prefixes = ([rng.integers(0, vocab, sp["tokens"]).astype(np.int32)
                 for _ in range(sp["groups"])] if sp else [])
    pmax = mix["prompt"]["max"]
    samp = mix["sampled"]
    out = []
    for rid in range(len(due)):
        plen, group = int(s["prompt"][rid]), int(s["group"][rid])
        if group >= 0:
            tail = max(min(plen, pmax - len(prefixes[group])), 1)
            prompt = np.concatenate(
                [prefixes[group],
                 rng.integers(0, vocab, tail).astype(np.int32)])
        else:
            prompt = rng.integers(0, vocab, plen).astype(np.int32)
        sampled = bool(s["sampled"][rid])
        out.append(Arrival(
            rid=rid, due_s=float(due[rid]), prompt=prompt,
            max_new=int(s["output"][rid]),
            temperature=float(samp["temperature"]) if sampled else 0.0,
            top_p=float(samp["top_p"]) if sampled else 1.0,
            seed=int(rng.integers(2**31 - 1)), group=group))
    return out


def max_prompt(mix: dict) -> int:
    return int(mix["prompt"]["max"])


def max_output(mix: dict) -> int:
    return int(mix["output"]["max"])
