"""End-to-end driver: train a decoder LM under a CGMQ BOP budget.

The production loop in miniature: synthetic token pipeline -> sharded-or-not
CGMQ train step (fake-quant forward, Adam, gate controller) -> supervised
loop with async checkpointing, crash recovery and straggler detection.

Defaults are CPU-sized (a ~10M-param tinyllama-family model, 200 steps,
minutes). ``--preset 100m`` selects a ~100M-param model for a real machine;
``--arch`` accepts any registry architecture (reduced with --smoke).

    PYTHONPATH=src python examples/train_llm_cgmq.py --steps 200
    PYTHONPATH=src python examples/train_llm_cgmq.py --preset 100m --steps 300
"""

import argparse
import dataclasses
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import bop as bop_lib
from repro.data.synthetic import lm_tokens
from repro.distributed.fault_tolerance import SupervisorConfig, TrainSupervisor
from repro.launch import steps as steps_lib

PRESETS = {
    # ~10M params: CPU-friendly default
    "10m": dict(n_layers=4, d_model=256, n_heads=8, n_kv_heads=4, head_dim=32,
                d_ff=704, vocab_size=2048),
    # ~100M params (the deliverable-scale run; heavy on 1 CPU core)
    "100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
                 head_dim=64, d_ff=2048, vocab_size=8192),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="10m", choices=list(PRESETS))
    ap.add_argument("--arch", default=None, help="registry arch (smoke-reduced)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--budget-rbop", type=float, default=0.0625,
                    help="deployment BOP bound (0.0625 == W8A8)")
    ap.add_argument("--direction", default="dir2")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_llm_ckpt")
    ap.add_argument("--resume", action="store_true",
                    help="keep existing checkpoints (default: start fresh)")
    ap.add_argument("--inject-failure-at", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    if not args.resume:
        import shutil
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    if args.arch:
        cfg = get_smoke_config(args.arch)
    else:
        base = get_config("tinyllama-1.1b")
        cfg = dataclasses.replace(base, name=f"lm-{args.preset}",
                                  vocab_pad_multiple=64, **PRESETS[args.preset])
    print(f"model: {cfg.name}  params={cfg.param_count()/1e6:.1f}M")

    shape = ShapeConfig("train", seq_len=args.seq, global_batch=args.batch,
                        kind="train")
    recipe = steps_lib.make_recipe(
        cfg, shape, direction=args.direction, budget_rbop=args.budget_rbop,
        check_every=20)
    # gentler gate dynamics than the dry-run default: the paper anneals over
    # hundreds of epochs; at a few hundred steps we cap dir at 2 (0.02/step)
    recipe = dataclasses.replace(
        recipe, ccfg=dataclasses.replace(recipe.ccfg, dir_clip=2.0))
    state = steps_lib.init_train_state(recipe, jax.random.PRNGKey(0))
    step_fn = jax.jit(steps_lib.make_train_step(recipe, None),
                      donate_argnums=(0,))
    # FP32 warmup step (paper stage 1): same state, quantization off
    fp_recipe = dataclasses.replace(recipe, quant_enabled=False)
    fp_step_fn = jax.jit(steps_lib.make_train_step(fp_recipe, None),
                         donate_argnums=(0,))

    data = lm_tokens(4096, args.seq, cfg.vocab_size, seed=0, noise=0.05)

    def batches(step):
        if step >= args.steps:
            return None
        rng = np.random.default_rng(step)
        idx = rng.integers(0, data.shape[0], args.batch)
        chunk = data[idx]
        return {
            "tokens": jnp.asarray(chunk[:, :-1]),
            "targets": jnp.asarray(chunk[:, 1:]),
        }

    fp_bop = bop_lib.fp32_bop(recipe.sites)
    hist = []

    def metrics_cb(step, metrics):
        if step % 20 == 0 or step == args.steps:
            m = jax.device_get(metrics)
            hist.append(m)
            print(f"step {step:5d} loss {float(m['loss']):.4f} "
                  f"rbop {float(m['bop'])/fp_bop*100:6.2f}% "
                  f"sat={bool(m['sat'])}")

    # ---- stage 1/2: FP32 warmup + range calibration (paper §2.4) ----
    warmup = max(10, args.steps // 10)
    t0 = time.time()
    for i in range(warmup):
        state, m = fp_step_fn(state, batches(i))
    print(f"[warmup] {warmup} fp32 steps, loss {float(m['loss']):.4f}")

    from repro.core.calibration import apply_act_calibration, calibrate_activations
    from repro.core.sites import init_ranges_from_weights, split_learnable_ranges
    from repro.models import transformer as tfm

    calib = calibrate_activations(
        lambda qc, b: tfm.forward_train(qc, state.params, b["tokens"], cfg),
        (batches(i) for i in range(3)), recipe.qcfg)
    ranges = init_ranges_from_weights(recipe.sites, recipe.qcfg, lambda n: None)
    ranges = apply_act_calibration(ranges, calib)
    betas, _ = split_learnable_ranges(ranges)
    # activation ranges carry the calibration; weight betas are learnable and
    # adapt from their placeholder during the CGMQ stage
    state = dataclasses.replace(state, betas=betas)
    print(f"[calibrate] {len(calib)} activation ranges set")

    # ---- stage 4: CGMQ under the supervisor ----
    sup = TrainSupervisor(
        SupervisorConfig(args.ckpt_dir, checkpoint_every=50), log=print)
    if args.inject_failure_at is not None:
        sup.inject_failure_at = args.inject_failure_at

    state, step, status = sup.run(state, step_fn, batches,
                                  metrics_cb=metrics_cb)
    dt = time.time() - t0
    toks = args.steps * args.batch * args.seq
    print(f"\n{status} at step {step} in {dt:.1f}s "
          f"({toks/dt:.0f} tok/s on CPU)")
    final_rbop = float(jax.device_get(state.cgmq.bop)) / fp_bop
    print(f"final RBOP {final_rbop*100:.2f}% (bound "
          f"{args.budget_rbop*100:.2f}%) "
          f"best-certified={bool(jax.device_get(state.cgmq.best_valid))}")


if __name__ == "__main__":
    main()
