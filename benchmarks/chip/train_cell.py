"""A training cell: the program's CGMQ gate-descent step
(``launch/steps.make_train_step``) on seeded token batches.

Set-up makes the weights on the device from the seed (one jitted call),
builds the step's state around them, compiles the step, and drives that
same compiled step through its first two steps on the window's own
feed. Those two give the numbers the reference is held to: each step's
loss, the first gradient per leaf as the optimizer got it (its first
moment after one step, over 1 - beta1), each leaf's change after the two
(two and not three, so that the reference stays shorter than the
window), the CGMQ gates after the two controller updates, the learnable
ranges' change and the controller's BOP. The window then runs the same object for ``--seconds``, one step in
flight while the next batch is staged; a step counts when it completed
inside the window.

After the window ``memory_peak_bytes`` is read, the program's state is
freed, and the plain reference repeats the two steps in float32 from
the same weights and batches, with its own gate update and BOP count.
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

import harness

CHECK_STEPS = 2
# the numbers the reference is held to; a null limit in the cell file
# prints the number without comparing it
COMPARED = ("loss_gap", "grad_gap", "change_gap", "gate_gap_mean",
            "gate_gap_worst", "range_change", "bop_gap")



def build(cell: dict, conf: dict, job: dict, ref, seed: int, *, cfg=None):
    """Recipe, weights maker, state and the jitted step of a train cell."""
    import jax

    from repro.configs.base import ShapeConfig
    from repro.launch import steps as steps_lib

    if cfg is None:
        cfg = harness.model_config(conf)
    r = cell["recipe"]
    shape = ShapeConfig("train", seq_len=job["seq"],
                        global_batch=job["batch"], kind="train")
    recipe = steps_lib.make_recipe(cfg, shape, state_bits=r["state_bits"])
    check_recipe(recipe, r)
    jseed, _ = harness.split_seed(seed)
    key = jax.random.PRNGKey(jseed)
    make = jax.jit(lambda k: ref.make_weights(conf, k, cfg.padded_vocab))

    def init(k):
        st = steps_lib.init_train_state(recipe, k)
        st.params = make(k)
        return st

    state = jax.jit(init)(key)
    step = jax.jit(steps_lib.make_train_step(recipe, None),
                   donate_argnums=(0,))
    return cfg, recipe, make, key, state, step


def check_recipe(recipe, r: dict) -> None:
    """The program's recipe is the one the cell file states."""
    from repro.core import gates as G

    cc = recipe.ccfg
    got = {"direction": cc.direction, "gate_lr": cc.gate_lr,
           "dir_clip": cc.dir_clip, "eps": cc.eps,
           "budget_rbop": cc.budget_rbop, "check_every": cc.check_every,
           "gate_init": G.GATE_INIT, "gate_min": G.GATE_MIN,
           "gate_max": G.GATE_MAX, "adam_lr": recipe.adam.lr,
           "grad_clip_norm": recipe.adam.grad_clip_norm,
           "state_bits": recipe.adam.state_bits}
    bad = {k: (v, r[k]) for k, v in got.items() if v != r[k]}
    if bad:
        raise RuntimeError(f"program recipe departs from the cell: {bad}")


def gate_name(key: str) -> str:
    """A program gate key (``p0_global/attn/attn_q.w``) as the reference
    names it (``attn_q.w``)."""
    site, kind = key.rsplit(".", 1)
    return site.rsplit("/", 1)[-1] + "." + kind


def host_gates(gates) -> dict:
    import jax

    out = {}
    for key, g in jax.device_get(gates).items():
        name = gate_name(key)
        if name in out:
            raise RuntimeError(f"two program gates read as {name}")
        out[name] = np.asarray(g, np.float64)
    return out


def batch_maker(cfg, batch: int, seq: int):
    """``(key, i) -> batch i``: distinct uniform token rows, on device."""
    import jax

    @jax.jit
    def make(key, i):
        toks = jax.random.randint(jax.random.fold_in(key, i),
                                  (batch, seq + 1), 0, cfg.vocab_size)
        return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    return make


def leaf_norms(tree) -> list:
    import jax
    import jax.numpy as jnp

    return [float(x) for x in jax.device_get(
        [jnp.linalg.norm(jnp.ravel(a).astype(jnp.float32))
         for a in jax.tree.leaves(tree)])]


def first_moment(opt_m):
    """The optimizer's first moment per parameter leaf, decoded from its
    int8 codes and per-row scales where it keeps them so."""
    import jax

    def dec(q):
        if isinstance(q, dict) and "codes" in q:
            return q["codes"].astype("float32") * q["scale"]
        return q

    return jax.tree.map(dec, opt_m, is_leaf=lambda q: isinstance(q, dict)
                        and "codes" in q)


def change_norms(after, before) -> list:
    """Per leaf, the norm of ``after - before`` (both on the device)."""
    import jax
    import jax.numpy as jnp

    return [float(x) for x in jax.device_get(
        [jnp.linalg.norm(jnp.ravel(a - b))
         for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))])]


def program_readings(state, step, make_batch, key, make, b1):
    """The first two steps through the window's own call and feed; the
    numbers the reference is held to. Returns the state after them."""
    import jax

    losses, grad_norms = [], None
    betas0 = jax.device_get(state.betas)
    batch = make_batch(key, 0)
    for i in range(CHECK_STEPS):
        state, metrics = step(state, batch)
        batch = make_batch(key, i + 1)
        losses.append(float(jax.device_get(metrics["loss"])))
        if i == 0:
            m = first_moment(state.opt.m[0])
            grad_norms = [n / (1.0 - b1) for n in leaf_norms(m)]
            del m
    w0 = make(key)
    change = change_norms(state.params, w0)
    del w0
    betas = jax.device_get(state.betas)
    range_change = max(
        (float(np.max(np.abs(np.asarray(b) - np.asarray(a))))
         for a, b in zip(jax.tree.leaves(betas0), jax.tree.leaves(betas))),
        default=0.0)
    return state, batch, {"loss": losses, "grad": grad_norms,
                          "change": change,
                          "gates": host_gates(state.cgmq.gates),
                          "bop": float(jax.device_get(state.cgmq.bop)),
                          "sat": bool(jax.device_get(state.cgmq.sat)),
                          "range_change": range_change}


def leaf_groups(weights) -> list:
    """Leaf indices of ``weights`` in groups small enough that one group's
    gradient, its accumulator and the backward pass's temporaries fit
    beside the float32 weights: the attention weights and norms, each MLP
    matrix, and the embedding with the final norm."""
    import jax

    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(weights)[0]]
    groups = [[i for i, p in enumerate(paths)
               if "embed" in p or "final_norm" in p]]
    groups += [[i for i, p in enumerate(paths) if key in p]
               for key in ("w_gate", "w_up", "w_down")]
    seen = {i for g in groups for i in g}
    groups.append([i for i in range(len(paths)) if i not in seen])
    return [tuple(g) for g in groups if g]


def site_leaves(ref, weights) -> dict:
    """Reference weight site -> index of its stacked leaf."""
    import jax

    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(weights)[0]]
    out = {}
    for site, (blk, name) in ref.WEIGHT_SITES.items():
        hit = [i for i, p in enumerate(paths)
               if f"['{blk}']['{name}']" in p]
        if len(hit) != 1:
            raise RuntimeError(f"site {site}: leaves {hit}")
        out[site] = hit[0]
    out["head"] = next(i for i, p in enumerate(paths) if "embed" in p)
    return out


def cgmq_stats(ref, conf, leaves, sites, grads, probe_grads, sums,
               tokens: int) -> tuple[dict, dict]:
    """The directions' statistics of one step, per gate: the batch
    gradient summed over the group (a weight's per layer from its
    gradient, an activation's and the head's from their probes) and the
    group's mean magnitude (mean |w| per layer; |mean a| per layer over
    the batch). Activations with no quantizer read zero."""
    import jax
    import jax.numpy as jnp

    grad, mag = {}, {}
    n = conf["config"]["num_hidden_layers"]
    for site, (_, width) in ref.gate_sites(conf).items():
        i = sites[site]
        grad[site + ".w"] = np.abs(np.sum(grads[i], axis=(1, 2),
                                          dtype=np.float64))
        mag[site + ".w"] = np.asarray(jax.device_get(
            jnp.mean(jnp.abs(leaves[i]), axis=(1, 2))), np.float64)
        if site in ref.ACT_POINTS:
            grad[site + ".a"] = np.abs(np.asarray(probe_grads[site],
                                                  np.float64))
            mag[site + ".a"] = np.abs(np.asarray(sums[site], np.float64)
                                      / (tokens * width))
        else:
            grad[site + ".a"] = mag[site + ".a"] = np.zeros(n)
    grad["head.w"] = abs(float(probe_grads["head"]))
    mag["head.w"] = float(jax.device_get(
        jnp.mean(jnp.abs(leaves[sites["head"]]))))
    return grad, mag


def reference_readings(ref, conf, make, make_batch, key, adam,
                       recipe: dict, *, compute: str = "float32"):
    """The same two steps by the plain reference: float32 loss and
    gradients (one group of leaves per backward pass), global-norm
    clipping, and Adam in float32 written out for two steps: with
    u_t = (m_t / (1 - b1^t)) / (sqrt(v_t / (1 - b2^t)) + eps),
    u_1 = g_1 / (|g_1| + eps) and m_2, v_2 from g_1 and g_2, the change
    after two steps is -lr (u_1 + u_2). Gradients wait on the host. Each
    step also takes one CGMQ gate update from its statistics; while every
    gate reads 32 bits the quantizer passes values through and the
    learnable ranges have no gradient, so they do not move."""
    import jax
    import jax.numpy as jnp

    leaves, tdef = jax.tree.flatten(make(key))
    weights = tdef.unflatten(leaves)
    groups = leaf_groups(weights)
    sites = site_leaves(ref, weights)
    del weights
    b1, b2, eps, lr = adam.b1, adam.b2, adam.eps, adam.lr

    def loss_of(idx, sub, probes, full, batch):
        full = list(full)
        for j, i in enumerate(idx):
            full[i] = sub[j]
        return ref.train_loss_probed(tdef.unflatten(full), probes, conf,
                                     batch["tokens"], batch["targets"],
                                     compute=compute)

    grad_fn = jax.jit(jax.value_and_grad(loss_of, argnums=(1, 2),
                                         has_aux=True),
                      static_argnums=0)

    def clipped_grads(batch):
        """Loss, per-leaf gradients parked on the host (each group's as
        soon as its pass ends, so one group's gradient is on the device
        at a time), the clip scale, the clipped per-leaf norms, the
        probes' gradients and the activations' sums."""
        loss, grads, norms = None, [None] * len(leaves), [0.0] * len(leaves)
        probes = ref.zero_probes(conf)
        for idx in groups:
            (loss, sums), (gs, gp) = grad_fn(
                idx, [leaves[i] for i in idx], probes, leaves, batch)
            for i, g, n in zip(idx, gs, leaf_norms(gs)):
                grads[i], norms[i] = np.asarray(jax.device_get(g)), n
            del gs
        gn = float(np.sqrt(sum(n * n for n in norms)))
        scale = min(1.0, adam.grad_clip_norm / gn) \
            if adam.grad_clip_norm else 1.0
        return (float(loss), grads, scale, [scale * n for n in norms],
                jax.device_get(gp), jax.device_get(sums))

    @jax.jit
    def step1(p, g, scale):
        g = g * scale
        return p - lr * g / (jnp.abs(g) + eps)

    @jax.jit
    def change2(g1, g2, s1, s2):
        g1, g2 = g1 * s1, g2 * s2
        u1 = g1 / (jnp.abs(g1) + eps)
        m2 = (b1 * (1 - b1) * g1 + (1 - b1) * g2) / (1 - b1 ** 2)
        v2 = (b2 * (1 - b2) * g1 * g1 + (1 - b2) * g2 * g2) / (1 - b2 ** 2)
        u2 = m2 / (jnp.sqrt(v2) + eps)
        return jnp.linalg.norm(jnp.ravel(lr * (u1 + u2)))

    cg = ref.cgmq_init(conf, recipe)
    gates0 = cg["gates"]
    batch = make_batch(key, 0)
    tokens = int(batch["tokens"].size)
    loss1, g1, s1, grad_norms, gp, sums = clipped_grads(batch)
    if not ref.all_full_width(cg, recipe):
        raise NotImplementedError("reference ranges cover 32-bit gates only")
    cg = ref.cgmq_update(cg, *cgmq_stats(ref, conf, leaves, sites, g1, gp,
                                         sums, tokens), conf, recipe)
    for i, g in enumerate(g1):
        leaves[i] = step1(leaves[i], g, jnp.float32(s1))
    loss2, g2, s2, _, gp, sums = clipped_grads(make_batch(key, 1))
    if not ref.all_full_width(cg, recipe):
        raise NotImplementedError("reference ranges cover 32-bit gates only")
    cg = ref.cgmq_update(cg, *cgmq_stats(ref, conf, leaves, sites, g2, gp,
                                         sums, tokens), conf, recipe)
    change = [float(change2(a, b, jnp.float32(s1), jnp.float32(s2)))
              for a, b in zip(g1, g2)]
    return {"loss": [loss1, loss2], "grad": grad_norms, "change": change,
            "gates": cg["gates"], "gates0": gates0, "bop": cg["bop"],
            "sat": cg["sat"], "range_change": 0.0}


def compare(prog: dict, ref: dict, rule: float) -> dict:
    """Each number as the gap between the program's reading and the
    reference's: losses relative to the reference's; norms per leaf
    relative to the larger of the reference leaf's norm and the median
    leaf's, worst leaf. Leaves whose reference gradient is under ``rule``
    times the median leaf's are left out (round-off moves them)."""
    g = np.asarray(ref["grad"])
    med_g = float(np.median(g))
    keep = g >= rule * med_g
    out = {"loss_gap": max(abs(a - b) / abs(b)
                           for a, b in zip(prog["loss"], ref["loss"]))}
    for name in ("grad", "change"):
        r = np.asarray(ref[name])[keep]
        p = np.asarray(prog[name])[keep]
        den = np.maximum(r, float(np.median(r)))
        out[f"{name}_gap"] = float(np.max(np.abs(p - r) / den))
    out["leaves_compared"] = int(keep.sum())
    if set(prog["gates"]) != set(ref["gates"]):
        raise RuntimeError(f"gates differ: {sorted(prog['gates'])} against "
                           f"{sorted(ref['gates'])}")
    names = sorted(ref["gates"])
    moved = np.concatenate([np.ravel(ref["gates"][k] - ref["gates0"][k])
                            for k in names])
    miss = np.concatenate([np.ravel(prog["gates"][k] - ref["gates"][k])
                           for k in names])
    den = np.maximum(np.abs(moved), max(float(np.median(np.abs(moved))),
                                        1e-12))
    gap = np.abs(miss) / den
    out["gate_gap_mean"] = float(np.mean(gap))
    out["gate_gap_worst"] = float(np.max(gap))
    out["range_change"] = abs(prog["range_change"] - ref["range_change"])
    out["bop_gap"] = abs(prog["bop"] - ref["bop"]) / ref["bop"]
    out["sat_agrees"] = prog["sat"] == ref["sat"]
    return out


def judge(gaps: dict, lim: dict) -> tuple[dict, bool]:
    """Each compared number beside its limit, and whether all hold."""
    checks = {k: {"value": gaps[k], "limit": lim[k]} for k in COMPARED
              if lim[k] is not None}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def printable(readings: dict) -> dict:
    """Readings for the timings line: gates as lists, the start left out."""
    out = {k: v for k, v in readings.items() if k != "gates0"}
    out["gates"] = {k: np.ravel(v).tolist()
                    for k, v in readings["gates"].items()}
    return out


class Window:
    """Runs the step for ``seconds`` with one step in flight; counts the
    steps that completed inside the window."""

    def __init__(self, step, make_batch, key, seconds: float,
                 trace_dir: Path | None, clock: harness.CompileClock):
        self.step, self.make_batch, self.key = step, make_batch, key
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.clock = clock

    def run(self, state, batch, first: int) -> dict:
        import jax

        tracing = False
        t_open = time.monotonic()
        c0, cs0 = self.clock.count, self.clock.seconds
        t_close = t_open + self.seconds
        tr = (t_open + self.seconds / 2 - 1.5, t_open + self.seconds / 2 + 1.5)
        trace_span = None
        done, i, pending = 0, first, None
        trace_steps = 0
        synced = [t_open]
        while True:
            if self.trace_dir is not None and trace_span is None \
                    and not tracing and time.monotonic() >= tr[0]:
                jax.block_until_ready(state)
                harness.start_trace(self.trace_dir)
                tracing, t_tr, n_tr = True, time.monotonic(), done
            with harness.span(tracing, "train_step"):
                state, metrics = self.step(state, batch)
            with harness.span(tracing, "stage_batch"):
                batch = self.make_batch(self.key, i + 1)
            i += 1
            if pending is not None:
                with harness.span(tracing, "sync"):
                    jax.block_until_ready(pending)
                synced.append(time.monotonic())
                if synced[-1] <= t_close:
                    done += 1
            pending = metrics["loss"]
            if tracing and time.monotonic() >= tr[1]:
                jax.block_until_ready(state)
                trace_span = (t_tr, time.monotonic())
                trace_steps = done - n_tr
                jax.profiler.stop_trace()
                tracing = False
            if time.monotonic() > t_close:
                break
        jax.block_until_ready(pending)
        if time.monotonic() <= t_close:
            done += 1
        out = {"steps": done, "seconds": t_close - t_open,
               "step_s": harness.summary(list(np.diff(synced[1:]))),
               "compiles": self.clock.count - c0,
               "compile_s": self.clock.seconds - cs0,
               "last_loss": float(jax.device_get(pending))}
        if trace_span is not None:
            out["trace_seconds"] = trace_span[1] - trace_span[0]
            out["trace_steps"] = trace_steps
        return state, out


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        overrides: dict | None = None) -> dict:
    """One run of a train cell; returns the result line's fields.
    ``overrides`` (tests only) as for ``serve_cell.run``, plus ``fault``:
    a function that wraps the jitted step."""
    import jax

    o = overrides or {}
    cell = o.get("cell") or harness.cell(cell_name)
    devs = (harness.require_chips(cell["chips"]) if o.get("chips", True)
            else jax.devices()[:1])
    if o.get("cache", True):
        harness.enable_cache()
    clock = harness.CompileClock()
    conf = o.get("conf") or harness.config_file(cell["config"])
    ref = harness.reference(cell["config"])
    job = o.get("mix") or harness.traffic_mix(cell["traffic"])
    cfg, recipe, make, key, state, step = build(cell, conf, job, ref, seed,
                                                cfg=o.get("cfg"))
    if o.get("fault"):
        step = o["fault"](step)
    job = o.get("mix") or harness.traffic_mix(cell["traffic"])
    make_batch = batch_maker(cfg, job["batch"], job["seq"])
    state, batch, prog = program_readings(state, step, make_batch, key, make,
                                          recipe.adam.b1)
    jax.block_until_ready(state)
    setup_s = time.monotonic() - harness.PROCESS_T0
    setup_compile_s = clock.seconds
    trace_dir = Path(tempfile.mkdtemp(prefix="chipbench_trace_")) \
        if trace else None
    state, win = Window(step, make_batch, key, seconds, trace_dir,
                        clock).run(state, batch, CHECK_STEPS)
    mem = harness.peak_bytes(devs)
    record = {"cell": cell, "window": win,
              "tokens_per_step": job["batch"] * job["seq"],
              "seq": job["seq"],
              "setup_compile_s": setup_compile_s, "program": prog}
    if trace:
        import trace_reduce

        if "trace_seconds" in win:
            record["trace"] = trace_reduce.reduce_dir(trace_dir, len(devs))
            record["trace_window"] = {"seconds": win["trace_seconds"],
                                      "steps": win["trace_steps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    del state, batch, step
    gc.collect()
    t_ref = time.monotonic()
    want = reference_readings(ref, conf, make, make_batch, key, recipe.adam,
                              cell["recipe"])
    ref_s = time.monotonic() - t_ref
    gaps = compare(prog, want, cell["correct"]["leaf_rule"])
    checks, correct = judge(gaps, cell["correct"])
    record.update(
        checks=checks, setup_s=setup_s, memory_peak_bytes=mem, devs=devs,
        correct=bool(correct), attempted=win["steps"], failed=0,
        timings={"window": win, "setup_s": setup_s,
                 "setup_compile_s": setup_compile_s, "reference_s": ref_s,
                 "program": printable(prog), "reference": printable(want),
                 "gaps": gaps})
    if o.get("keep"):
        record.update(make=make, make_batch=make_batch, key=key,
                      adam=recipe.adam, conf=conf, reference=want,
                      recipe=cell["recipe"])
    return record
