"""Quickstart: CGMQ end to end in under two minutes on CPU.

Shows the full public API surface: define a model with QuantContext sites,
collect sites, run the four-stage pipeline, verify the cost constraint,
export deployment bit-widths — then serve a quantized smoke LM through the
request-lifecycle API (``engine.generate`` + ``SamplingParams``,
DESIGN.md §12).

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py \\
        --temperature 0.8 --top-k 40 --top-p 0.9 --seed 7
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import bop as bop_lib
from repro.core.controller import CGMQConfig, export_bits
from repro.core.pipeline import PipelineConfig, run_pipeline
from repro.core.sites import QuantConfig


D_IN, D_H, D_OUT = 16, 64, 4


def forward(qc, params, x):
    """A 2-layer MLP with CGMQ sites on every matmul."""
    x = qc.input(x)  # fixed 8-bit input (paper §4.2)
    w1 = qc.weight("fc1", params["w1"])
    qc.register_matmul("fc1", params["w1"].shape, fan_in=D_IN, out_features=D_H)
    h = jax.nn.relu(x @ w1 + params["b1"])
    h = qc.act("fc1", h)
    w2 = qc.weight("fc2", params["w2"])
    qc.register_matmul("fc2", params["w2"].shape, fan_in=D_H,
                       out_features=D_OUT, act_quantized=False)  # fp head
    return h @ w2 + params["b2"]


def serve_demo(args):
    """Part 2: serve a CGMQ-quantized smoke LM via ``generate()``."""
    from repro.configs import get_smoke_config
    from repro.models import transformer as tfm
    from repro.serving import (SamplingParams, ServingEngine,
                               make_uniform_quant_state)

    cfg = get_smoke_config("tinyllama-1.1b")
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, slots=2, max_seq=64,
                        quant_state=make_uniform_quant_state(cfg, params))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)) for n in (5, 8)]
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p, seed=args.seed, max_new=6)
    print(f"\n=== serving (int8 decode, {eng.kv_layout} KV, "
          + ("greedy argmax" if sp.greedy
             else f"temperature={sp.temperature}") + ") ===")
    for r in eng.generate(prompts, sp):
        print(f"  prompt[{len(r.prompt)} toks] -> {r.tokens} "
              f"[{r.finish_reason}]")
    st = eng.stats
    print(f"  {st['decode_ticks']} decode ticks, {st['tick_syncs']} host "
          f"syncs (one per tick, sampling included)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="serving-demo sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0, help="top-k (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus mass (1.0 = off)")
    ap.add_argument("--seed", type=int, default=None,
                    help="per-request sampling seed")
    args = ap.parse_args()
    enable_compile_cache()
    rng = np.random.default_rng(0)
    # 4-class toy problem with a planted linear rule + noise
    w_true = rng.normal(size=(D_IN, D_OUT))
    x = rng.normal(size=(2048, D_IN)).astype(np.float32)
    y = (x @ w_true + 0.1 * rng.normal(size=(2048, D_OUT))).argmax(-1)
    xtr, ytr = jnp.asarray(x[:1536]), jnp.asarray(y[:1536].astype(np.int32))
    xte, yte = jnp.asarray(x[1536:]), jnp.asarray(y[1536:].astype(np.int32))

    k = jax.random.PRNGKey(0)
    params = {
        "w1": jax.random.normal(k, (D_IN, D_H)) * 0.3,
        "b1": jnp.zeros((D_H,)),
        "w2": jax.random.normal(jax.random.fold_in(k, 1), (D_H, D_OUT)) * 0.3,
        "b2": jnp.zeros((D_OUT,)),
    }

    res = run_pipeline(
        forward,
        lambda p: lambda name: p.get({"fc1": "w1", "fc2": "w2"}[name]),
        params,
        (xtr, ytr), (xte, yte),
        QuantConfig(granularity="per_tensor"),
        CGMQConfig(budget_rbop=0.02, direction="dir1", gate_lr=0.01),
        PipelineConfig(pretrain_epochs=15, range_epochs=3, cgmq_epochs=40,
                       batch_size=128, eval_every=10),
    )

    print("\n=== quickstart results ===")
    print(f"FP32 accuracy      : {res.fp32_test_acc:.3f}")
    print(f"Quantized accuracy : {res.final_test_acc:.3f}")
    print(f"RBOP               : {res.final_rbop*100:.3f}% "
          f"(bound 2.000%) satisfied={res.satisfied}")
    bits = export_bits(res.state)
    for k_, v in bits.items():
        print(f"  {k_:8s} -> {int(np.max(v))} bits")
    assert res.satisfied, "constraint violated!"

    serve_demo(args)


if __name__ == "__main__":
    main()
