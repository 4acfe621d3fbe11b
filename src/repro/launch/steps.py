"""Jit-able train / prefill / decode steps with CGMQ as a first-class feature.

``make_train_step`` builds the full production step: quantized (fake-quant)
forward, vocab-parallel cross-entropy, backward, Adam (optionally 8-bit
states), learnable-range update, and the CGMQ gate/controller update — this
is the graph the multi-pod dry-run lowers and the roofline reads.

State is the unified ``repro.train.TrainState`` (DESIGN.md §9) — the same
pytree the classification pipeline's scan engine carries — so gates,
controller flags, probes, RNG and the step counter all checkpoint/restore
together, and the LeNet and LLM stacks share one resumable state layout.

Distribution is GSPMD: parameters/batch carry NamedShardings (from
``ShardingPlan``), activations are constrained at block boundaries inside the
models, and two vocab-sharded primitives are written with ``shard_map``
(mask-psum embedding lookup; Megatron-style vocab-parallel cross-entropy)
because gather/take along a sharded axis is exactly where GSPMD falls back to
all-gathering a multi-GB table.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import bop as bop_lib
from repro.core import controller as ctrl
from repro.core.sites import (
    QuantConfig,
    QuantContext,
    collect_sites,
    init_gates,
    init_probes,
    init_ranges_from_weights,
    merge_ranges,
    split_learnable_ranges,
)
from repro.distributed.sharding import ShardingPlan
from repro.models import transformer as tfm
from repro.models.layers import COMPUTE_DTYPE
from repro.optim.adam import AdamConfig, AdamState, adam, apply_updates
from repro.train.state import TrainState


# ---------------------------------------------------------------------------
# Vocab-sharded primitives (shard_map)
# ---------------------------------------------------------------------------


def sharded_embed_lookup(plan: ShardingPlan, table, tokens):
    """Mask-psum lookup from a vocab-sharded table (V:model, d:replicated).

    Each model shard gathers its local rows (out-of-range -> 0) and the
    partial results psum over 'model' — one (B, S, d) all-reduce instead of
    all-gathering the table.
    """
    mesh = plan.mesh
    m = plan.model_axis
    bspec = plan.batch_spec(tokens.shape)

    def _local(tab, tok):
        rows = tab.shape[0]
        idx = jax.lax.axis_index(m)
        local = tok - idx * rows
        ok = (local >= 0) & (local < rows)
        safe = jnp.clip(local, 0, rows - 1)
        out = jnp.take(tab, safe, axis=0)
        out = jnp.where(ok[..., None], out, 0)
        return jax.lax.psum(out, m)

    return jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(m, None), bspec),
        out_specs=P(*bspec, None),
        check_vma=False,
    )(table, tokens)


def vocab_parallel_xent(plan: ShardingPlan | None, logits, targets, vocab: int):
    """Cross-entropy over a (possibly model-sharded) vocab axis.

    logits: (B, S, Vp) fp32 (padded ids already masked to -inf);
    targets: (B, S) int32 in [0, vocab).
    """
    if plan is None:
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(ll)

    mesh = plan.mesh
    m = plan.model_axis
    bspec = plan.batch_spec(targets.shape)

    def _local(lg, tg):
        shard_v = lg.shape[-1]
        idx = jax.lax.axis_index(m)
        # max is a stability shift only (gradient cancels); pmax has no VJP
        # rule, so gather the per-shard maxes (all_gather differentiates).
        local_max = jax.lax.stop_gradient(jnp.max(lg, axis=-1))
        gmax = jnp.max(jax.lax.all_gather(local_max, m, axis=0), axis=0)
        ex = jnp.exp(lg - gmax[..., None])
        denom = jax.lax.psum(jnp.sum(ex, axis=-1), m)             # (B, S)
        local_t = tg - idx * shard_v
        ok = (local_t >= 0) & (local_t < shard_v)
        safe = jnp.clip(local_t, 0, shard_v - 1)
        picked = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
        picked = jnp.where(ok, picked, 0.0)
        correct = jax.lax.psum(picked, m)                         # (B, S)
        nll = jnp.log(denom) + gmax - correct
        # nll is m-replicated (all terms psum'd over m); mean over batch axes
        total = jax.lax.psum(jnp.sum(nll), tuple(plan.batch_axes))
        cnt = jax.lax.psum(jnp.asarray(nll.size, jnp.float32),
                           tuple(plan.batch_axes))
        return total / cnt

    loss = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(*bspec, m), bspec),
        out_specs=P(),
        check_vma=False,
    )(logits, targets)
    return loss


# ---------------------------------------------------------------------------
# State: TrainState is the unified pytree from repro.train.state, imported
# above so both training stacks share one resumable layout.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Recipe:
    """Everything needed to build/lower the steps for one arch."""

    cfg: ModelConfig
    qcfg: QuantConfig
    ccfg: ctrl.CGMQConfig
    adam: AdamConfig
    sites: dict
    signed: dict
    budget_bop: float
    moe_impl: str = "capacity"
    quant_enabled: bool = True
    scan_unroll: bool = False
    microbatches: int = 1   # gradient accumulation (activation memory / mb)
    accum_dtype: str = "float32"  # bf16 halves the accumulator for 100B+ models
    gather_dtype: str | None = None  # 'bfloat16': cast params before use so
                                     # FSDP all-gathers move half the bytes


def make_recipe(cfg: ModelConfig, shape: ShapeConfig, *,
                direction="dir2", budget_rbop=0.0625, check_every=100,
                state_bits: int | None = None, quant_impl="direct",
                quant_enabled=True, moe_impl="capacity",
                scan_unroll=False, microbatches: int | None = None,
                gather_dtype: str | None = None) -> Recipe:
    """Collect sites (abstract; no allocation) and freeze the recipe.

    budget_rbop default 6.25% == uniform W8A8 deployment target.
    """
    qcfg = QuantConfig(granularity="per_tensor", impl=quant_impl,
                       enabled=quant_enabled)
    b = min(shape.global_batch, 2)  # site collection is shape-independent
    s = min(shape.seq_len, 512) if shape.kind != "decode" else 512
    s = max(s, cfg.ssm_chunk)
    batch_sds = _abstract_batch(cfg, b, s)

    def fwd(qc, p, x, mp):
        return tfm.forward_train(qc, p, x, cfg, mrope_pos=mp, moe_impl=moe_impl)

    params_sds = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    sites = collect_sites(
        fwd, params_sds, batch_sds["tokens"], batch_sds.get("mrope"), cfg=qcfg
    )
    gates = init_gates(sites, qcfg)
    ranges = init_ranges_from_weights(sites, qcfg, lambda n: None)
    _, signed = split_learnable_ranges(ranges)
    if state_bits is None:
        # 8-bit Adam states where fp32 m/v would not fit 16 GiB/chip
        state_bits = 8 if cfg.param_count() > 2e11 else 32
    if microbatches is None:
        # gradient accumulation for the widest models: activation temp
        # scales down by the microbatch count
        microbatches = 4 if (cfg.d_model >= 7168 and shape.kind == "train"
                             and shape.global_batch % 64 == 0) else 1
    accum_dtype = "bfloat16" if cfg.param_count() > 2e11 else "float32"
    return Recipe(
        cfg=cfg, qcfg=qcfg,
        # dir_clip 10 * lr 0.01 = at most 0.1 gate-units per step: a gate
        # needs >= 10 steps to cross one bit-width level (stability at scale)
        ccfg=ctrl.CGMQConfig(budget_rbop=budget_rbop, direction=direction,
                             gate_lr=0.01, check_every=check_every,
                             dir_clip=10.0),
        adam=AdamConfig(lr=1e-4, state_bits=state_bits, grad_clip_norm=1.0),
        sites=sites, signed=signed,
        budget_bop=bop_lib.budget_from_rbop(sites, budget_rbop),
        moe_impl=moe_impl, quant_enabled=quant_enabled,
        scan_unroll=scan_unroll, microbatches=microbatches,
        accum_dtype=accum_dtype, gather_dtype=gather_dtype,
    )


def _abstract_batch(cfg: ModelConfig, b: int, s: int, *, targets=True):
    out = {}
    if cfg.embed_input:
        out["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    else:
        out["tokens"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), COMPUTE_DTYPE)
    if cfg.mrope_sections is not None:
        out["mrope"] = jax.ShapeDtypeStruct((3, b, s), jnp.int32)
    if targets:
        out["targets"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    return out


def init_probe_taps(recipe: Recipe, gates) -> dict:
    """Activation probes + weight gradient taps, sized from the gates."""
    probes = init_probes(recipe.sites, recipe.qcfg)
    for s in recipe.sites.values():
        probes[s.name + ".w"] = jnp.zeros_like(
            jnp.asarray(gates[s.name + ".w"], jnp.float32))
    return probes


def init_train_state(recipe: Recipe, key) -> TrainState:
    """Concrete (or eval_shape-able) state initializer."""
    cfg = recipe.cfg
    params = tfm.init_params(cfg, key)
    gates = init_gates(recipe.sites, recipe.qcfg)
    ranges = init_ranges_from_weights(recipe.sites, recipe.qcfg, lambda n: None)
    betas, _ = split_learnable_ranges(ranges)
    opt_init, _ = adam(recipe.adam)
    opt = opt_init((params, betas))
    cgmq = ctrl.init_state(gates, recipe.sites)
    return TrainState(params=params, betas=betas, opt=opt, cgmq=cgmq,
                      probes=init_probe_taps(recipe, gates),
                      rng=jax.random.fold_in(key, 1),
                      step=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------


def _embed_override(plan):
    if plan is None:
        return None
    return functools.partial(sharded_embed_lookup, plan)


def _split_microbatches(batch: dict, mb: int, plan: ShardingPlan | None):
    """Reshape batch leaves (B, ...) -> (mb, B/mb, ...); mrope at dim 1."""
    out = {}
    for k, v in batch.items():
        if k == "mrope":
            b = v.shape[1]
            r = v.reshape(v.shape[0], mb, b // mb, *v.shape[2:])
            r = jnp.moveaxis(r, 1, 0)
            if plan is not None and (b // mb) % plan.dp_size == 0:
                r = jax.lax.with_sharding_constraint(
                    r, plan.named(P(None, None, plan.batch_axes, None)))
        else:
            b = v.shape[0]
            r = v.reshape(mb, b // mb, *v.shape[1:])
            if plan is not None and (b // mb) % plan.dp_size == 0:
                spec = P(None, plan.batch_axes,
                         *((None,) * (v.ndim - 1)))
                r = jax.lax.with_sharding_constraint(r, plan.named(spec))
        out[k] = r
    return out


def make_train_step(recipe: Recipe, plan: ShardingPlan | None):
    cfg = recipe.cfg
    _, opt_update = adam(recipe.adam)
    mb = recipe.microbatches

    def train_step(state: TrainState, batch: dict):
        # probe taps travel in the state (always zero; only their gradients
        # are read); ad-hoc states from before the unified layout still work
        probes = state.probes if state.probes is not None else init_probe_taps(
            recipe, state.cgmq.gates)

        def loss_fn(params, betas, probes, mb_batch):
            if recipe.gather_dtype is not None:
                # cast BEFORE use: GSPMD's per-layer FSDP all-gathers then
                # move half-precision bytes; fp32 masters still get exact
                # gradients (cast transpose), and the quantizer computes in
                # fp32 internally so fake-quant codes are unchanged.
                gd = jnp.dtype(recipe.gather_dtype)
                params = jax.tree.map(
                    lambda p: p.astype(gd)
                    if (p.dtype == jnp.float32 and p.ndim >= 2) else p,
                    params)
            qc = QuantContext(
                mode="train" if recipe.quant_enabled else "off",
                cfg=recipe.qcfg, gates=state.cgmq.gates,
                ranges=merge_ranges(betas, recipe.signed), probes=probes,
            )
            if plan is not None and cfg.embed_input:
                # swap the lookup for the vocab-sharded mask-psum version
                logits = _forward_with_sharded_embed(
                    qc, params, mb_batch, cfg, plan, recipe.moe_impl,
                    recipe.scan_unroll)
            else:
                logits = tfm.forward_train(
                    qc, params, mb_batch["tokens"], cfg,
                    mrope_pos=mb_batch.get("mrope"), plan=plan,
                    moe_impl=recipe.moe_impl,
                    scan_unroll=recipe.scan_unroll)
            loss = vocab_parallel_xent(plan, logits, mb_batch["targets"],
                                       cfg.vocab_size)
            return loss, (qc.act_stats, qc.weight_stats)

        grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1, 2), has_aux=True)

        if mb == 1:
            (loss, (astats, wstats)), grads = grad_fn(
                state.params, state.betas, probes, batch)
        else:
            # gradient accumulation: scan over microbatches, mean-reduce
            split = _split_microbatches(batch, mb, plan)
            adt = jnp.dtype(recipe.accum_dtype)
            zero_like = jax.eval_shape(
                lambda: grad_fn(state.params, state.betas, probes,
                                jax.tree.map(lambda x: x[0], split)))
            acc0 = jax.tree.map(
                lambda s: jnp.zeros(
                    s.shape, adt if s.dtype == jnp.float32 else s.dtype),
                zero_like)

            def mb_body(acc, mb_batch):
                out = grad_fn(state.params, state.betas, probes, mb_batch)
                return jax.tree.map(
                    lambda a, o: a + o.astype(a.dtype) / mb, acc, out), None

            accum, _ = jax.lax.scan(mb_body, acc0, split)
            (loss, (astats, wstats)), grads = accum
        gp, gb, gprobe = grads
        # named scopes carry each phase into the ops' metadata (§18)
        with jax.named_scope("adam"):
            upd, opt = opt_update((gp, gb), state.opt,
                                  (state.params, state.betas))
            params, betas = apply_updates((state.params, state.betas), upd)
        with jax.named_scope("cgmq_controller"):
            cgmq = ctrl.controller_update(
                state.cgmq, recipe.ccfg, recipe.sites, gprobe, wstats,
                astats, recipe.budget_bop,
            )
        metrics = {
            "loss": loss,
            "bop": cgmq.bop,
            "rbop": cgmq.bop / bop_lib.fp32_bop(recipe.sites),
            "sat": cgmq.sat,
        }
        new = TrainState(
            params=params, betas=betas, opt=opt, cgmq=cgmq, probes=probes,
            rng=state.rng,
            step=None if state.step is None else state.step + 1)
        return new, metrics

    return train_step


def _forward_with_sharded_embed(qc, params, batch, cfg, plan, moe_impl,
                                scan_unroll=False):
    """forward_train with the embedding lookup done via shard_map."""
    tokens = batch["tokens"]
    h = sharded_embed_lookup(plan, params["embed"], tokens)
    if cfg.scale_embed:
        h = h * (cfg.d_model**0.5)
    # re-enter the standard forward from the embedded representation by
    # treating it as a stub-modality input
    cfg_stub = dataclasses.replace(cfg, embed_input=False)
    params_stub = dict(params)
    if "head" not in params_stub:
        params_stub["head"] = params["embed"].T
    return tfm.forward_train(qc, params_stub, h.astype(COMPUTE_DTYPE), cfg_stub,
                             mrope_pos=batch.get("mrope"), plan=plan,
                             moe_impl=moe_impl, scan_unroll=scan_unroll)


def make_prefill_step(recipe: Recipe, plan: ShardingPlan | None, max_seq: int):
    cfg = recipe.cfg

    def prefill_step(params, batch):
        qc = QuantContext(mode="off")
        if plan is not None and cfg.embed_input:
            tokens = batch["tokens"]
            h = sharded_embed_lookup(plan, params["embed"], tokens)
            if cfg.scale_embed:
                h = h * (cfg.d_model**0.5)
            cfg_stub = dataclasses.replace(cfg, embed_input=False)
            params_stub = dict(params)
            if "head" not in params_stub:
                params_stub["head"] = params["embed"].T
            logits, cache = tfm.prefill(
                qc, params_stub, h.astype(COMPUTE_DTYPE), cfg_stub,
                max_seq=max_seq, mrope_pos=batch.get("mrope"), plan=plan,
                moe_impl=recipe.moe_impl, scan_unroll=recipe.scan_unroll)
        else:
            logits, cache = tfm.prefill(
                qc, params, batch["tokens"], cfg, max_seq=max_seq,
                mrope_pos=batch.get("mrope"), plan=plan,
                moe_impl=recipe.moe_impl, scan_unroll=recipe.scan_unroll)
        return logits[:, -1:], cache

    return prefill_step


def make_decode_step(recipe: Recipe, plan: ShardingPlan | None):
    cfg = recipe.cfg

    def decode_step(params, cache, tokens):
        qc = QuantContext(mode="off")
        mp = None
        if cfg.mrope_sections is not None:
            b = tokens.shape[0]
            mp = jnp.broadcast_to(cache["pos"][None, :, None], (3, b, 1))
        if plan is not None and cfg.embed_input:
            h = sharded_embed_lookup(plan, params["embed"], tokens[:, None])
            if cfg.scale_embed:
                h = h * (cfg.d_model**0.5)
            cfg_stub = dataclasses.replace(cfg, embed_input=False)
            params_stub = dict(params)
            if "head" not in params_stub:
                params_stub["head"] = params["embed"].T
            logits, cache = tfm.decode_step(
                qc, params_stub, cache, h.astype(COMPUTE_DTYPE), cfg_stub,
                plan=plan, mrope_pos=mp, scan_unroll=recipe.scan_unroll)
        else:
            logits, cache = tfm.decode_step(
                qc, params, cache, tokens, cfg, plan=plan, mrope_pos=mp,
                scan_unroll=recipe.scan_unroll)
        return logits, cache

    return decode_step


# ---------------------------------------------------------------------------
# Abstract state/batch builders for the dry run (no allocation)
# ---------------------------------------------------------------------------


def abstract_train_args(recipe: Recipe, shape: ShapeConfig,
                        plan: ShardingPlan | None):
    """(state_sds, batch_sds) with shardings attached; nothing allocated."""
    state = jax.eval_shape(
        lambda: init_train_state(recipe, jax.random.PRNGKey(0)))
    batch = _abstract_batch(recipe.cfg, shape.global_batch, shape.seq_len)
    if plan is None:
        return state, batch
    state_sh = train_state_shardings(recipe, state, plan)
    batch_sh = plan.batch_dict_shardings(batch)
    state = jax.tree.map(
        lambda sds, sh: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sh),
        state, state_sh)
    batch = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=batch_sh[k])
        for k, v in batch.items()
    }
    return state, batch


def train_state_shardings(recipe: Recipe, state_sds: TrainState,
                          plan: ShardingPlan):
    params_sh = plan.params_shardings(state_sds.params)
    betas_sh = plan.replicated(state_sds.betas)
    cgmq_sh = plan.replicated(state_sds.cgmq)

    if recipe.adam.state_bits == 8:
        # row-wise int8 moments: codes share the owner param's sharding;
        # the per-row scale drops the (size-1) last-dim axis from the spec.
        owners_sh = (params_sh, betas_sh)

        def _q_sh(q_sds, owner_sharding):
            spec = owner_sharding.spec
            scale_spec = P(*(tuple(spec[:-1]) + (None,))) if len(spec) else P()
            return {
                "codes": owner_sharding,
                "scale": plan.named(scale_spec),
            }

        m_sh = jax.tree.map(
            _q_sh, state_sds.opt.m, owners_sh,
            is_leaf=lambda x: isinstance(x, dict) and "codes" in x)
        v_sh = jax.tree.map(
            _q_sh, state_sds.opt.v, owners_sh,
            is_leaf=lambda x: isinstance(x, dict) and "codes" in x)
    else:
        m_sh = params_shardings_like(plan, state_sds.opt.m, params_sh, betas_sh)
        v_sh = params_shardings_like(plan, state_sds.opt.v, params_sh, betas_sh)
    opt_sh = AdamState(step=plan.named(P()), m=m_sh, v=v_sh)
    return TrainState(params=params_sh, betas=betas_sh, opt=opt_sh,
                      cgmq=cgmq_sh,
                      probes=plan.replicated(state_sds.probes),
                      rng=plan.named(P()), step=plan.named(P()))


def params_shardings_like(plan, opt_tree, params_sh, betas_sh):
    """Adam moments over (params, betas) reuse their owners' shardings."""
    return (params_sh, betas_sh)


def abstract_serve_args(recipe: Recipe, shape: ShapeConfig,
                        plan: ShardingPlan | None, *, max_seq: int,
                        serve_dtype=None):
    """(params_sds, cache_sds, tokens_sds) for decode lowering.

    ``serve_dtype``: cast >=2D fp32 weights for serving (bf16 halves the
    per-token FSDP gather traffic AND the resident weight bytes).
    """
    cfg = recipe.cfg
    params = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    if serve_dtype is not None:
        sd = jnp.dtype(serve_dtype)
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, sd if (x.dtype == jnp.float32 and len(x.shape) >= 2)
                else x.dtype),
            params)
    cache = jax.eval_shape(
        lambda: tfm.init_cache(cfg, shape.global_batch, max_seq))
    if cfg.embed_input:
        tokens = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
    else:
        tokens = jax.ShapeDtypeStruct((shape.global_batch, 1, cfg.d_model),
                                      COMPUTE_DTYPE)
    if plan is None:
        return params, cache, tokens
    params_sh = plan.params_shardings(params)
    cache_sh = plan.cache_shardings(cache)
    params = jax.tree.map(
        lambda sds, sh: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sh),
        params, params_sh)

    def _attach(sds, sh):
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sh)

    cache = jax.tree.map(_attach, cache, cache_sh)
    tokens = jax.ShapeDtypeStruct(
        tokens.shape, tokens.dtype,
        sharding=plan.named(plan.batch_spec(tokens.shape)))
    return params, cache, tokens
