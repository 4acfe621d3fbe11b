"""Production training launcher.

On a real fleet each host runs this with its own process index and the
coordinator address (see scripts/launch_pod.sh); ``jax.distributed`` then
assembles the global device mesh. On this single-process container it runs
the same code path on the local devices.

State is the unified ``repro.train.TrainState`` (DESIGN.md §9): the
supervisor checkpoints the whole pytree — params, betas, Adam moments,
gates, controller flags, probes, RNG, step — so a restarted run resumes the
exact trajectory, including the §3 last-certified-snapshot guarantee.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b-smoke \
        --steps 50 --batch 8 --seq 64 --ckpt /tmp/ck
"""

from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--budget-rbop", type=float, default=0.0625)
    ap.add_argument("--direction", default="dir2")
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x2 to build a (data,model) mesh; default: no mesh")
    ap.add_argument("--coordinator", default=None,
                    help="host:port for multi-host jax.distributed")
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--num-processes", type=int, default=None)
    args = ap.parse_args()

    if args.coordinator:
        import jax

        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core import bop as bop_lib
    from repro.data.synthetic import lm_tokens
    from repro.distributed.fault_tolerance import (
        SupervisorConfig,
        TrainSupervisor,
    )
    from repro.distributed.sharding import ShardingPlan
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import batch_axes_of, make_test_mesh

    enable_compile_cache()
    cfg = get_config(args.arch)
    shape = ShapeConfig("train", seq_len=args.seq, global_batch=args.batch,
                        kind="train")
    plan = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_test_mesh(dims, ("data", "model")[: len(dims)])
        plan = ShardingPlan(mesh=mesh, cfg=cfg, batch_axes=batch_axes_of(mesh))

    recipe = steps_lib.make_recipe(cfg, shape, direction=args.direction,
                                   budget_rbop=args.budget_rbop,
                                   check_every=max(10, args.steps // 10))
    key = jax.random.PRNGKey(0)

    def init(k):
        return steps_lib.init_train_state(recipe, k)

    shardings = None
    if plan is None:
        state = init(key)
    else:
        # build the state in place on the mesh: materializing it on one
        # device first would need the whole state to fit there
        shardings = steps_lib.train_state_shardings(
            recipe, jax.eval_shape(init, key), plan)
        state = jax.jit(init, out_shardings=shardings)(key)
    step_fn = jax.jit(steps_lib.make_train_step(recipe, plan),
                      donate_argnums=(0,))

    data = lm_tokens(2048, args.seq, cfg.vocab_size, seed=0, noise=0.05)

    def batches(step):
        if step >= args.steps:
            return None
        rng = np.random.default_rng(step)
        idx = rng.integers(0, data.shape[0], args.batch)
        chunk = data[idx]
        b = {"tokens": jnp.asarray(chunk[:, :-1]),
             "targets": jnp.asarray(chunk[:, 1:])}
        if cfg.mrope_sections is not None:
            b["mrope"] = jnp.broadcast_to(
                jnp.arange(args.seq)[None, None], (3, args.batch, args.seq)
            ).astype(jnp.int32)
        if not cfg.embed_input:
            rngx = np.random.default_rng(1000 + step)
            b["tokens"] = jnp.asarray(
                rngx.normal(size=(args.batch, args.seq, cfg.d_model)),
                jnp.bfloat16)
        if plan is not None:
            sh = plan.batch_dict_shardings(b)
            b = {k: jax.device_put(v, sh[k]) for k, v in b.items()}
        return b

    fp_bop = bop_lib.fp32_bop(recipe.sites)
    sup = TrainSupervisor(
        SupervisorConfig(args.ckpt, checkpoint_every=args.checkpoint_every),
        log=print)

    def metrics_cb(step, metrics):
        if step % 10 == 0:
            m = jax.device_get(metrics)
            print(f"step {step} loss {float(m['loss']):.4f} "
                  f"rbop {float(m['bop'])/fp_bop*100:.2f}% "
                  f"sat={bool(m['sat'])}")

    state, step, status = sup.run(state, step_fn, batches,
                                  shardings=shardings, metrics_cb=metrics_cb)
    print(f"{status} at step {step}")


if __name__ == "__main__":
    main()
