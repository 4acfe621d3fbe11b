"""Request-lifecycle sampling: ``SamplingParams`` + device-resident sampling.

The serving API's sampling surface (DESIGN.md §12). A ``SamplingParams``
rides on every request and is lowered at admission into per-slot rows of the
engine's device-resident generation state (temperature / top-k / top-p /
stop tokens / a per-request ``jax.random`` key), so the stochastic pick of
the next token runs INSIDE the jitted decode tick — the §8 contract of one
small host sync per tick survives sampling unchanged.

Two layers:

  * ``SamplingParams`` — the user-facing request knobs, a frozen host-side
    dataclass validated at construction. ``temperature=0`` (the default) is
    the greedy path and is BIT-IDENTICAL to pre-sampling argmax decoding:
    ``sample_tokens`` selects ``argmax`` for zero-temperature rows and
    ``lax.cond``-skips the masking/categorical work entirely when no row in
    the batch samples, so the argmax oracle gates (packed-vs-int8, ring-vs-
    paged) keep holding and all-greedy batches pay zero sampling compute.
  * ``mask_logits`` / ``sample_tokens`` — the device-side math. Every op is
    row-independent (per-slot vmap / axis=-1 reductions), which is what
    makes a request's token stream a pure function of its
    ``(seed, prompt, params)`` and NOT of slot placement, admission order,
    or KV layout: the seed-determinism contract tested in
    ``tests/test_serving.py``.

Key discipline: a request's key is created from its seed at admission and
split once per emitted token (the first, prefill-sampled token included).
Keys advance only for rows that actually emit, so the stream position in
the key chain equals the number of tokens emitted — identical across every
admission path (batched prefill, SSM tail, teacher-forced prefix replay).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# Lowest fp32 the masking writes into rejected lanes; -inf would make
# categorical's gumbel-add produce NaN for fully-masked rows (which cannot
# happen — the top-ranked token is always kept — but finfo.min keeps the
# math total anyway).
_MASKED = float(jnp.finfo(jnp.float32).min)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request generation knobs (DESIGN.md §12).

    ``temperature=0`` is greedy argmax — the bit-exact oracle path.
    ``top_k=0`` / ``top_p=1.0`` disable the respective truncation.
    ``seed=None`` lets the engine draw a per-request seed from its own
    deterministic stream (reproducible per engine instance, not across
    processes — pass an explicit seed for that).
    ``stop``: token ids that end the request early; the stop token itself is
    emitted (like an EOS) and the request retires in the SAME tick, blocks
    and all. ``max_new`` counts every emitted token, stop included.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int | None = None
    stop: tuple = ()
    max_new: int = 16

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0: {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off): {self.top_k}")
        if not 0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1]: {self.top_p}")
        if self.max_new < 1:
            raise ValueError(f"max_new must be >= 1: {self.max_new}")
        object.__setattr__(self, "stop", tuple(int(t) for t in self.stop))
        if any(t < 0 for t in self.stop):
            raise ValueError(f"stop token ids must be >= 0: {self.stop}")

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def finite_rows(logits):
    """Per-row non-finite guard (DESIGN.md §13): True where every logit in
    the row is finite. ``jax.random.categorical`` (and argmax) on a NaN/Inf
    row silently emits an arbitrary token, so the engine folds this mask
    into the decode tick — a False row is not emitted and fails alone with
    ``FINISHED_ERROR``, no extra host sync, rest of the batch unaffected."""
    return jnp.isfinite(logits).all(axis=-1)


def mask_logits(logits, top_k, top_p):
    """Top-k / top-p (nucleus) truncation, per row.

    ``logits``: (B, V) fp32 (already temperature-scaled); ``top_k``: (B,)
    int32 (0 = off); ``top_p``: (B,) fp32 in (0, 1]. Returns (B, V) with
    rejected lanes at ``finfo.min``. Nucleus keeps the smallest
    probability-sorted set whose cumulative mass reaches ``top_p`` (the
    first token is always kept), computed on the post-top-k renormalized
    distribution; ranking ties resolve by stable sort, so the result is
    deterministic and row-independent.

    The kept set is found in rank space and applied in vocabulary order as
    a per-row cut (DESIGN.md §12): the lowest-ranked kept token's id
    ``cut`` and its logit ``thresh`` keep every token above ``thresh`` and
    the ties at ``thresh`` up to id ``cut`` (the stable sort ranks ties by
    ascending id). One sort carries the ids; no gather, no inverse sort.
    """
    # One materialized copy feeds the sort, the cut's read-back and the
    # compare, so a producer fused into each (the temperature divide) can
    # not round differently per consumer and empty a row.
    logits = jax.lax.optimization_barrier(logits)
    v = logits.shape[-1]
    ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    neg, order = jax.lax.sort((-logits, ids), dimension=1, num_keys=1,
                              is_stable=True)
    ranked = -neg
    rank = jnp.arange(v)[None, :]
    k = jnp.where(top_k > 0, top_k, v)[:, None]
    in_k = rank < k
    probs = jax.nn.softmax(jnp.where(in_k, ranked, _MASKED), axis=-1)
    mass_before = jnp.cumsum(probs, axis=-1) - probs
    keep = in_k & (mass_before < top_p[:, None])
    keep = keep | (rank == 0)
    last = jnp.max(jnp.where(keep, rank, -1), axis=-1, keepdims=True)
    cut = jnp.max(jnp.where(rank == last, order, -1), axis=-1, keepdims=True)
    # the cut's logit read back in vocabulary order: token ``cut`` is kept
    # whatever bits the sort handed back for it
    thresh = jnp.max(jnp.where(ids == cut, logits, -jnp.inf), axis=-1,
                     keepdims=True)
    kept = (logits > thresh) | ((logits == thresh) & (ids <= cut))
    return jnp.where(kept, logits, _MASKED)


def sample_tokens(logits, keys, temperature, top_k, top_p):
    """Next-token choice for a batch of slots, on device.

    ``logits``: (B, V) fp32; ``keys``: (B, 2) uint32 per-slot subkeys;
    ``temperature`` / ``top_k`` / ``top_p``: (B,) per-slot rows. Rows with
    ``temperature <= 0`` take the argmax — bit-identical to the pre-sampling
    greedy path — and when NO row samples, ``lax.cond`` skips the sort/
    categorical work at runtime, so all-greedy ticks cost what they always
    did. Returns (B,) int32.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _drawn(_):
        scaled = logits / jnp.maximum(temperature, 1e-3)[:, None]
        masked = mask_logits(scaled, top_k, top_p)
        drawn = jax.vmap(jax.random.categorical)(keys, masked)
        return jnp.where(temperature > 0.0, drawn.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(temperature > 0.0), _drawn,
                        lambda _: greedy, operand=None)
