#!/usr/bin/env python3
"""Prove the main path runs on a TPU: serve full-width TinyLlama-1.1B
through the uniform 8-bit CGMQ export, and take CGMQ train steps.

    python chip_smoke.py                # one chip: serve phase + train phase
    python chip_smoke.py --four-chips   # one train step on a 2x2 (data, model)
                                        # mesh vs the same step on one chip

Weights are random, made from ``--seed``. The serve phase answers greedy
requests through ``ServingEngine`` (paged int8 KV, chunked prefill, Pallas
kernels) and checks them against the same engine built with
``matmul_impl="ref"`` on the same chip, because a compiler fault can make
correct kernels return wrong numbers. The train phase takes CGMQ
gate-descent steps of ``launch/steps.make_train_step`` at full width.

Runs in one process. Exits non-zero, and prints no result line, when JAX
finds no TPU; any failed check raises. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.core.sites import QuantContext  # noqa: E402
from repro.data.synthetic import lm_tokens  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.serving import SamplingParams, ServingEngine, kv_pool  # noqa: E402
from repro.serving.engine import make_uniform_quant_state  # noqa: E402

ARCH = "tinyllama-1.1b"
# Largest relative L2 gap ||pallas - ref|| / ||ref|| of a logits row that
# the serve phase admits: the kernels accumulate in fp32 while the jnp
# oracle's f32 matmuls take XLA's default TPU precision and its paged
# attention rounds K/V and probabilities to bf16, so the two differ by
# bf16-level rounding through 22 layers (0.035 on a TPU v5e at seed 0); a
# miscompiled kernel is off by O(1).
LOGIT_RTOL = 5e-2
# Sharded vs one-chip loss of the same step on the same batch: only the
# reduction order of the (data, model) partial sums differs.
LOSS_RTOL = 1e-3


class CheckFailed(RuntimeError):
    """A smoke check did not hold."""


def check(ok, what) -> None:
    """Raise ``CheckFailed`` unless ``ok`` (kept under ``python -O``)."""
    if not ok:
        raise CheckFailed(what)


class CompileClock:
    """Seconds JAX spent in backend compilation (persistent-cache reads
    included, so a warm cache shows up as fewer seconds)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.total = 0.0
        self._mark = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration

    def lap(self) -> float:
        dt, self._mark = self.total - self._mark, self.total
        return dt


def peak_bytes() -> int | None:
    """Peak device bytes in use so far on device 0 (None where the backend
    does not report it)."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _logit_rows(eng: ServingEngine, impl: str, prompt: np.ndarray,
                steps: int = 3) -> np.ndarray:
    """Last prefill logits row + ``steps`` decode rows of ``eng``'s model,
    export and int8 KV pool layout, run with kernel backend ``impl``."""
    cfg, bs = eng.cfg, eng.block_size
    plen = len(prompt)
    mb = -(-(plen + steps) // bs)
    cache = tfm.init_paged_cache(cfg, 1, mb + 1, bs, kv_spec=eng.kv_spec)
    alloc = kv_pool.alloc_range(kv_pool.init_alloc(mb + 1, 1, mb), 0, 0,
                                -(-plen // bs))
    adv = jnp.ones((1,), jnp.int32)

    def qc(qweights):
        return QuantContext(mode="serve", cfg=eng.quant_state["qcfg"],
                            specs=eng.specs, qweights=qweights,
                            matmul_impl=impl)

    @jax.jit
    def prefill(params, qweights, cache, table, toks):
        lg, cache = tfm.prefill_slot(qc(qweights), params, toks, plen, cache,
                                     0, cfg, block_table=table)
        return lg[0, plen - 1, :cfg.vocab_size], cache

    @jax.jit
    def decode(params, qweights, cache, alloc, tok):
        alloc = kv_pool.tick_alloc(alloc, cache["pos"], adv, bs)
        lg, cache = tfm.decode_step(qc(qweights), params, cache, tok, cfg,
                                    advance=adv, block_table=alloc["table"])
        return lg[0, 0, :cfg.vocab_size], cache, alloc

    row, cache = prefill(eng.params, eng.qweights, cache, alloc["table"],
                         jnp.asarray(prompt[None], jnp.int32))
    rows = [row]
    for t in range(steps):
        tok = jnp.asarray([prompt[t]], jnp.int32)
        row, cache, alloc = decode(eng.params, eng.qweights, cache, alloc, tok)
        rows.append(row)
    return np.asarray(jax.device_get(jnp.stack(rows)), np.float32)


def serve_phase(cfg, *, impl: str, seed: int, slots: int = 4,
                requests: int = 8, min_prompt: int = 64,
                max_prompt: int = 256, max_new: int = 32,
                chunk: int = 128) -> dict:
    """Answer ``requests`` greedy requests with the int8 export on an
    engine of kernel backend ``impl`` and on a ``ref`` engine; check both
    and return what was measured."""
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    qs = make_uniform_quant_state(cfg, params)
    rng = np.random.default_rng(seed)
    lens = rng.integers(min_prompt, max_prompt + 1, requests)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)) for n in lens]
    sp = SamplingParams(max_new=max_new)
    out: dict = {"prompt_lens": [int(n) for n in lens]}
    tokens = {}
    engines = {}
    for name in (impl, "ref"):
        eng = ServingEngine(cfg, params, slots=slots,
                            max_seq=max_prompt + max_new, quant_state=qs,
                            kv_dtype="int8", prefill_chunk_tokens=chunk,
                            matmul_impl=name)
        t0 = time.perf_counter()
        res = eng.generate(prompts, sp)
        out[f"{name}_generate_s"] = time.perf_counter() - t0
        for r in res:
            check(len(r.tokens) == max_new, (name, r.finish_reason))
            check(all(0 <= t < cfg.vocab_size for t in r.tokens), name)
        tokens[name] = [list(r.tokens) for r in res]
        tick = eng._tick.lower(eng.params, eng.qweights, eng.cache,
                               eng.state, eng.alloc).as_text()
        out[f"{name}_tick_tpu_custom_calls"] = tick.count("tpu_custom_call")
        engines[name] = eng
    if impl == "pallas":
        # the served decode tick really runs Mosaic kernels
        check(out["pallas_tick_tpu_custom_calls"] > 0, out)
    out["sites"] = len(engines[impl].qweights)
    out["storage_bits"] = sorted({q.storage_bits
                                  for q in engines[impl].qweights.values()})
    # token agreement: greedy streams match up to the first argmax flip
    same = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b), len(x))
            for x, y in zip(tokens[impl], tokens["ref"])]
    out["matching_prefix_tokens"] = same
    # first-step logits, kernels vs oracle, on the longest prompt
    longest = prompts[int(np.argmax(lens))]
    got = _logit_rows(engines[impl], impl, longest)
    want = _logit_rows(engines["ref"], "ref", longest)
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          "non-finite logits")
    rel = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    out["logit_rel_l2_gap"] = [float(r) for r in rel]
    out["logit_max_abs_gap"] = float(np.max(np.abs(got - want)))
    out["logit_max_abs_ref"] = float(np.max(np.abs(want)))
    check(rel.max() <= LOGIT_RTOL,
          f"{impl} logits differ from ref: relative L2 gaps {rel}")
    out["first_tokens_agree"] = int(sum(x[0] == y[0] for x, y in
                                        zip(tokens[impl], tokens["ref"])))
    out["checked"] = [
        f"{requests} requests x {max_new} in-vocab tokens on both engines",
        f"{impl} decode tick lowers to tpu_custom_call" if impl == "pallas"
        else f"{impl} decode tick lowered",
        f"prefill + 3 decode logit rows finite, relative L2 gap <= "
        f"{LOGIT_RTOL} vs ref"]
    return out


def _lm_batch(cfg, batch: int, seq: int, seed: int) -> dict:
    data = lm_tokens(batch, seq, cfg.vocab_size, seed=seed, noise=0.05)
    return {"tokens": jnp.asarray(data[:, :-1]),
            "targets": jnp.asarray(data[:, 1:])}


def _recipe(cfg, *, batch: int, seq: int, state_bits: int):
    shape = ShapeConfig("train", seq_len=seq, global_batch=batch, kind="train")
    return steps_lib.make_recipe(cfg, shape, state_bits=state_bits)


def train_phase(cfg, *, seed: int, batch: int = 1, seq: int = 256,
                steps: int = 3, state_bits: int = 8) -> dict:
    """``steps`` CGMQ gate-descent steps on one device; every loss finite."""
    recipe = _recipe(cfg, batch=batch, seq=seq, state_bits=state_bits)
    # built op by op: one jit of the whole initializer costs more compile
    # (tens of seconds at this width) than it saves
    state = steps_lib.init_train_state(recipe, jax.random.PRNGKey(seed))
    step = jax.jit(steps_lib.make_train_step(recipe, None),
                   donate_argnums=(0,))
    fp_bop = float(steps_lib.bop_lib.fp32_bop(recipe.sites))
    rows = []
    for i in range(steps):
        state, metrics = step(state, _lm_batch(cfg, batch, seq, seed + i))
        m = jax.device_get(metrics)
        rows.append({"loss": float(m["loss"]), "bop": float(m["bop"]),
                     "rbop": float(m["bop"]) / fp_bop, "sat": bool(m["sat"])})
        check(math.isfinite(rows[-1]["loss"]), rows[-1])
    return {"batch": batch, "seq": seq, "state_bits": state_bits,
            "sites": len(recipe.sites), "steps": rows,
            "checked": [f"{steps} finite losses"]}


def _bytes_on(tree, device) -> int:
    return sum(s.data.nbytes for a in jax.tree.leaves(tree)
               for s in a.addressable_shards if s.device == device)


def four_chip_phase(cfg, *, seed: int, batch: int = 2, seq: int = 256,
                    state_bits: int = 8) -> dict:
    """One train step on a 2x2 (data, model) mesh and the same step, on the
    same batch, on one device; the losses and BOPs must agree and the
    state must really be spread over the mesh."""
    from jax.sharding import SingleDeviceSharding

    from repro.distributed.sharding import ShardingPlan
    from repro.launch.mesh import batch_axes_of, make_test_mesh

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, got {devices}")
    recipe = _recipe(cfg, batch=batch, seq=seq, state_bits=state_bits)
    batch_np = _lm_batch(cfg, batch, seq, seed)

    def metrics_of(m):
        m = jax.device_get(m)
        return float(m["loss"]), float(m["bop"])

    # one set of weights, built op by op on device 0, placed twice
    state = steps_lib.init_train_state(recipe, jax.random.PRNGKey(seed))
    mesh = make_test_mesh((2, 2), ("data", "model"))
    plan = ShardingPlan(mesh=mesh, cfg=cfg, batch_axes=batch_axes_of(mesh))
    shardings = steps_lib.train_state_shardings(
        recipe, jax.eval_shape(lambda: state), plan)
    # a copy in fresh buffers: the one-chip step below donates (deletes)
    # ``state``, and device_put would let replicated leaves share them
    state_mesh = jax.jit(lambda s: s, out_shardings=shardings)(state)
    per_device = [_bytes_on(state_mesh, d) for d in devices]
    total = sum(a.nbytes for a in jax.tree.leaves(state_mesh))
    # an unsharded placement would hold the whole state on every device
    check(max(per_device) < 0.5 * total, (per_device, total))

    one = SingleDeviceSharding(devices[0])
    step = jax.jit(steps_lib.make_train_step(recipe, None),
                   donate_argnums=(0,))
    _, m1 = step(state, jax.device_put(batch_np, one))
    loss1, bop1 = metrics_of(m1)
    del state, m1, step
    gc.collect()

    bsh = plan.batch_dict_shardings(batch_np)
    step = jax.jit(steps_lib.make_train_step(recipe, plan),
                   donate_argnums=(0,))
    _, m4 = step(state_mesh, {k: jax.device_put(v, bsh[k])
                              for k, v in batch_np.items()})
    loss4, bop4 = metrics_of(m4)
    check(math.isfinite(loss1)
          and abs(loss4 - loss1) <= LOSS_RTOL * abs(loss1), (loss1, loss4))
    check(bop4 == bop1, (bop1, bop4))
    return {"batch": batch, "seq": seq, "state_bits": state_bits,
            "loss_one_chip": loss1, "loss_mesh": loss4,
            "bop_one_chip": bop1, "bop_mesh": bop4,
            "state_bytes_total": total, "state_bytes_per_device": per_device,
            "checked": ["no device holds half the state",
                        f"mesh loss within {LOSS_RTOL} relative of one-chip",
                        "equal BOP after the controller update"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh train step and its one-chip "
                         "comparison (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    clock = CompileClock()
    cfg = get_config(ARCH)
    print(f"device {dev.device_kind} x{len(jax.devices())}; {ARCH}: "
          f"{cfg.n_layers} layers, d_model {cfg.d_model}, d_ff {cfg.d_ff}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads, vocab {cfg.vocab_size}; "
          f"depth not cut; train Adam state_bits=8 (fp32 moments exceed "
          f"16 GB at this width)")

    phases = ({"four_chips": lambda: four_chip_phase(cfg, seed=args.seed)}
              if args.four_chips else
              {"serve": lambda: serve_phase(cfg, impl="pallas",
                                            seed=args.seed),
               "train": lambda: train_phase(cfg, seed=args.seed)})
    for name, run in phases.items():
        t0 = time.perf_counter()
        result = run()
        result["wall_s"] = time.perf_counter() - t0
        result["compile_s"] = clock.lap()
        result["peak_device_bytes_so_far"] = peak_bytes()
        print(f"phase {name}: {json.dumps(result)}", flush=True)
        del result
        gc.collect()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
