"""Faults planted under the timed path, to show that ``correct`` catches
them: the CPU tests and ``control.py`` use these. Each is a context
manager that patches the program while it is active."""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(module, name, make):
    real = getattr(module, name)
    setattr(module, name, make(real))
    try:
        yield
    finally:
        setattr(module, name, real)


def _step(wrap):
    from repro.launch import steps

    return _patched(steps, "make_train_step",
                    lambda real: lambda recipe, plan: wrap(real(recipe,
                                                                plan)))


def state_unchanged():
    """The train step returns the state it was given."""
    def wrap(step):
        def run(state, batch):
            return state, step(state, batch)[1]
        return run
    return _step(wrap)


def half_batch():
    """The train step leaves out half the batch: the mean is taken over
    the rest."""
    def wrap(step):
        def run(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return run
    return _step(wrap)


def cgmq_unchanged():
    """The CGMQ controller update returns the controller state it was
    given: gates, flag and BOP stay where they were."""
    from repro.core import controller

    return _patched(controller, "controller_update",
                    lambda real: lambda state, *a, **k: state)


def state_replicated():
    """The train state is placed whole on every chip of the mesh."""
    from repro.launch import steps

    return _patched(steps, "train_state_shardings",
                    lambda real: lambda recipe, sds, plan:
                    plan.replicated(sds))


def exchange_skipped():
    """The exchange between chips inside the step's ``shard_map`` blocks
    is left out: each ``psum`` (the vocabulary-parallel embedding's and
    cross-entropy's) returns the chip's own part."""
    import jax

    return _patched(jax.lax, "psum", lambda real: lambda x, *a, **k: x)


def token_altered():
    """Every sampled or greedy token is shifted by one where the engine
    produces it."""
    from repro.serving import engine

    return _patched(engine, "sample_tokens",
                    lambda real: lambda logits, *a: (real(logits, *a) + 1)
                    % logits.shape[-1])


def top_p_ignored():
    """Sampled tokens are drawn from the whole tempered distribution: the
    nucleus cut is left out where the engine samples."""
    from repro.serving import engine

    return _patched(engine, "sample_tokens",
                    lambda real: lambda logits, keys, temp, top_k, top_p:
                    real(logits, keys, temp, top_k, top_p * 0.0 + 1.0))


def cache_unchanged():
    """The decode step returns the cache it was given (positions
    advance, nothing is written)."""
    from repro.models import transformer as tfm

    def make(real):
        def stale(qc, params, cache, *a, **k):
            logits, new = real(qc, params, cache, *a, **k)
            return logits, {**cache, "pos": new["pos"]}
        return stale
    return _patched(tfm, "decode_step", make)


TRAIN = {"state_unchanged": state_unchanged, "half_batch": half_batch,
         "cgmq_unchanged": cgmq_unchanged,
         "state_replicated": state_replicated,
         "exchange_skipped": exchange_skipped}
SERVE = {"token_altered": token_altered, "top_p_ignored": top_p_ignored,
         "cache_unchanged": cache_unchanged}
