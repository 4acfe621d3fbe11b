"""Plain reference of the served Qwen3 stack, and the seeded weights.

Written from the published architecture (Qwen3: pre-norm decoder, RMSNorm
with a (1 + gain) scale, grouped-query causal attention with RMSNorm on
each query and key head, rotary embeddings on split halves, SwiGLU MLP,
tied embedding and head) and from the served artifact's definition in the
cell file: each weight matrix on a signed uniform grid of ``2**bits - 1``
steps over [-r, r], r the largest |w| of its output channel; activations
at full width; the embedding output on an 8-bit grid over [-m, m], m the
largest |x| of the tokens run together. It imports nothing of the program
and takes nothing the program made: the weights are the benchmark's, and
it quantizes them itself. float32 throughout, every product at
``Precision.HIGHEST``. The KV cache is not quantized here.

For training it also holds CGMQ's gate update (paper §2.1-2.3, §2.5)
written from the paper and the recipe in the cell file: one gate per
weight and per output activation of every layer's projections (per
tensor), the bit-width ``T(g)`` of Eq. 4, the ``dir2`` direction from
the batch gradient summed over each group and its mean magnitude, one
plain SGD step clipped to the gate bounds, and the BOP count with the
constraint flag evaluated every ``check_every`` steps and lagged.

``make_weights`` lays the seeded weights out as the program takes them
(``blocks[0]`` holds every layer stacked on a leading axis).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# activations the decoder quantizes (attention output, the gated MLP
# product, MLP output); the other projections' outputs feed rotary
# embeddings, attention or the gate nonlinearity and carry a gate but no
# quantizer, so their statistics are zero
ACT_POINTS = ("attn_o", "mlp_up", "mlp_down")
WEIGHT_SITES = {  # artifact site -> (block key, weight key)
    "attn_q": ("attn", "wq"), "attn_k": ("attn", "wk"),
    "attn_v": ("attn", "wv"), "attn_o": ("attn", "wo"),
    "mlp_gate": ("mlp", "w_gate"), "mlp_up": ("mlp", "w_up"),
    "mlp_down": ("mlp", "w_down"),
}


def make_weights(conf: dict, key, vocab_rows: int) -> dict:
    """Seeded weights, float32, in one traced function (jit it once)."""
    c = conf["config"]
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    n = c["num_hidden_layers"]
    shapes = {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
              "wo": (h * hd, d), "w_gate": (d, f), "w_up": (d, f),
              "w_down": (f, d)}
    keys = iter(jax.random.split(key, 16))

    def mat(shape):
        return (jax.random.normal(next(keys), (n,) + shape, jnp.float32)
                / np.sqrt(shape[0]))

    def gain(width, stack=True):
        shape = (n, width) if stack else (width,)
        return 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    attn = {k: mat(shapes[k]) for k in ("wq", "wk", "wv", "wo")}
    attn["q_norm"] = gain(hd)
    attn["k_norm"] = gain(hd)
    block = {"attn": attn, "ln1": gain(d), "ln2": gain(d),
             "mlp": {k: mat(shapes[k]) for k in ("w_gate", "w_up",
                                                  "w_down")}}
    embed = 0.02 * jax.random.normal(next(keys), (vocab_rows, d), jnp.float32)
    return {"blocks": [block], "rem": [], "final_norm": gain(d, False),
            "embed": embed}


def weight_for_site(weights: dict, site: str):
    """The weight matrix behind an artifact site name such as
    ``p0_global/attn/attn_q`` or ``head`` (stacked over layers)."""
    if site == "head":
        return weights["embed"].T
    leaf = site.rsplit("/", 1)[-1]
    blk, name = WEIGHT_SITES[leaf]
    return weights["blocks"][0][blk][name]


def grid(x, bits, r):
    """Signed uniform grid of 2**bits - 1 steps over [-r, r]."""
    r = jnp.maximum(r, 1e-8)
    s = 2.0 * r / (2.0 ** bits - 1.0)
    return -r + s * jnp.round((jnp.clip(x, -r, r) + r) / s)


def quantized(w, bits):
    """Per output channel: r = max |w| over the input axis."""
    return grid(w, bits, jnp.max(jnp.abs(w), axis=-2, keepdims=True))


def rms_norm(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g)


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dot(spec, a, b, compute):
    if compute == "float32":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    dt = jnp.dtype(compute)
    return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                      preferred_element_type=jnp.float32)


def _layers(weights, x, c: dict, bits, compute: str, remat: bool,
            probes=None):
    """The decoder stack over one sequence ``x`` (S, d). ``bits`` maps each
    weight site to its width, or is None for float weights. ``probes``
    (site -> per-layer zeros) are added to the activations of
    ``ACT_POINTS``; returns the normed output and, per site and layer, the
    sum of that activation."""
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    s = x.shape[0]
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]
    if probes is None:
        n = c["num_hidden_layers"]
        probes = {k: jnp.zeros((n,), jnp.float32) for k in ACT_POINTS}

    def w(mat, site):
        return mat if bits is None else quantized(mat, bits[site])

    def layer(x, lw_pr):
        lw, pr = lw_pr
        a, m = lw["attn"], lw["mlp"]
        hn = rms_norm(x, lw["ln1"], eps)
        q = _dot("sd,de->se", hn, w(a["wq"], "attn_q"),
                 compute).reshape(s, h, hd)
        k = _dot("sd,de->se", hn, w(a["wk"], "attn_k"),
                 compute).reshape(s, kv, hd)
        v = _dot("sd,de->se", hn, w(a["wv"], "attn_v"),
                 compute).reshape(s, kv, hd)
        q = rope(rms_norm(q, a["q_norm"], eps), pos, theta)
        k = rope(rms_norm(k, a["k_norm"], eps), pos, theta)
        q = q.reshape(s, kv, h // kv, hd)
        sc = _dot("qkgd,tkd->kgqt", q, k, compute) * hd ** -0.5
        sc = jnp.where(causal[None, None], sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        o = _dot("kgqt,tkd->qkgd", p, v, compute).reshape(s, h * hd)
        y = _dot("se,ed->sd", o, w(a["wo"], "attn_o"), compute)
        sums = {"attn_o": jnp.sum(y)}
        x = x + y + pr["attn_o"]
        hn = rms_norm(x, lw["ln2"], eps)
        g = _dot("sd,df->sf", hn, w(m["w_gate"], "mlp_gate"), compute)
        u = _dot("sd,df->sf", hn, w(m["w_up"], "mlp_up"), compute)
        gu = jax.nn.silu(g) * u
        sums["mlp_up"] = jnp.sum(gu)
        y = _dot("sf,fd->sd", gu + pr["mlp_up"], w(m["w_down"], "mlp_down"),
                 compute)
        sums["mlp_down"] = jnp.sum(y)
        return x + y + pr["mlp_down"], sums

    if remat:
        layer = jax.checkpoint(layer)
    x, sums = jax.lax.scan(layer, x, (weights["blocks"][0], probes))
    return rms_norm(x, weights["final_norm"], eps), sums


def _embed_in(weights, toks, valid):
    """Embedding rows on an 8-bit grid over [-m, m], m the largest |x|
    among the valid rows (identity gradient: straight-through)."""
    x = weights["embed"][toks]
    r = jax.lax.stop_gradient(
        jnp.max(jnp.where(valid[..., None], jnp.abs(x), 0.0)))
    return x + jax.lax.stop_gradient(grid(x, 8, r) - x)


@functools.partial(jax.jit, static_argnames=("conf_key", "bits_key",
                                             "compute"))
def _positions(weights, toks, n_valid, targets, temperature, *, conf_key,
               bits_key, compute):
    c = dict(conf_key)
    bits = dict(bits_key)
    vocab = c["vocab_size"]
    valid = jnp.arange(toks.shape[0]) < n_valid
    x, _ = _layers(weights, _embed_in(weights, toks, valid), c, bits,
                   compute, remat=False)
    head = quantized(weights["embed"].T, bits["head"])[:, :vocab]
    logits = _dot("sd,dv->sv", x, head, compute)
    best = jnp.max(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    prob = jax.nn.softmax(logits / temperature, axis=-1)
    above = jnp.sum(jnp.where(logits > tgt[:, None], prob, 0.0), axis=-1)
    return best, tgt, jnp.argmax(logits, axis=-1), above


def train_loss(weights, conf: dict, tokens, targets, *,
               compute: str = "float32"):
    """Mean next-token cross-entropy of a (B, T) batch under the float
    model (every CGMQ gate at full width)."""
    zero = zero_probes(conf)
    return train_loss_probed(weights, zero, conf, tokens, targets,
                             compute=compute)[0]


def zero_probes(conf: dict) -> dict:
    """Zeros added to each quantized activation of every layer and to the
    head's weight: their gradients are the batch gradient summed over the
    group (the statistic of CGMQ's directions)."""
    n = conf["config"]["num_hidden_layers"]
    out = {k: jnp.zeros((n,), jnp.float32) for k in ACT_POINTS}
    out["head"] = jnp.zeros((), jnp.float32)
    return out


def train_loss_probed(weights, probes, conf: dict, tokens, targets, *,
                      compute: str = "float32"):
    """``train_loss`` with ``probes`` (``zero_probes``) in place, and per
    site and layer the sum of each quantized activation over the batch.
    The embedding output on its 8-bit grid over the whole batch.
    Differentiable; one row at a time with its layers rematerialized, so
    the backward pass fits beside the weights."""
    c = conf["config"]
    vocab = c["vocab_size"]
    x = _embed_in(weights, tokens, jnp.ones(tokens.shape, bool))
    acts = {k: probes[k] for k in ACT_POINTS}
    head = weights["embed"][:vocab] + probes["head"]

    def row(args):
        xr, tr = args
        h, sums = _layers(weights, xr, c, None, compute, remat=True,
                          probes=acts)
        logp = jax.nn.log_softmax(_dot("sd,vd->sv", h, head, compute), -1)
        return -jnp.sum(jnp.take_along_axis(logp, tr[:, None], -1)), sums

    nll, sums = jax.lax.map(jax.checkpoint(row), (x, targets))
    return (jnp.sum(nll) / targets.size,
            {k: jnp.sum(v, axis=0) for k, v in sums.items()})


# ---- CGMQ (paper §2.1-2.3, §2.5) -----------------------------------------

def gate_bits(g, gate_min: float):
    """Eq. 4: gate -> bit-width, after the no-pruning floor."""
    g = np.maximum(np.asarray(g, np.float64), gate_min)
    return np.select([g > 4, g > 3, g > 2, g > 1], [32.0, 16.0, 8.0, 4.0],
                     2.0)


def gate_sites(conf: dict) -> dict:
    """Site -> ``(fan_in, out)`` of each layer's projections. Each has a
    weight gate ``<site>.w`` and an output-activation gate ``<site>.a``
    per layer; the tied head has a weight gate ``head.w`` alone (its
    output stays floating point)."""
    c = conf["config"]
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    shapes = {"attn_q": (d, h * hd), "attn_k": (d, kv * hd),
              "attn_v": (d, kv * hd), "attn_o": (h * hd, d),
              "mlp_gate": (d, f), "mlp_up": (d, f), "mlp_down": (f, d)}
    return shapes


def cgmq_init(conf: dict, recipe: dict) -> dict:
    """Every gate at ``gate_init`` (32 bits), constraint flag unset, BOP of
    those gates."""
    n = conf["config"]["num_hidden_layers"]
    gates = {}
    for site in gate_sites(conf):
        gates[site + ".w"] = np.full(n, recipe["gate_init"], np.float64)
        gates[site + ".a"] = np.full(n, recipe["gate_init"], np.float64)
    gates["head.w"] = np.full((), recipe["gate_init"], np.float64)
    return {"gates": gates, "sat": False, "step": 0,
            "bop": bop(conf, gates, recipe)}


def bop(conf: dict, gates: dict, recipe: dict) -> float:
    """Paper §2.5 per token: over every layer's projections, MACs x weight
    bits x activation bits (the head's output is not counted)."""
    total = 0.0
    for site, (k, n) in gate_sites(conf).items():
        bw = gate_bits(gates[site + ".w"], recipe["gate_min"])
        ba = gate_bits(gates[site + ".a"], recipe["gate_min"])
        total += float(np.sum(k * n * bw * ba))
    return total


def fp32_bop(conf: dict) -> float:
    return 32.0 * 32.0 * sum(k * n for k, n in gate_sites(conf).values())


def cgmq_update(state: dict, grad_stat: dict, mag_stat: dict, conf: dict,
                recipe: dict) -> dict:
    """One gate step: ``dir2`` (Unsat: 1 / (grad + mag), clipped to
    [eps, dir_clip]; Sat: -(|g| + mag), clipped to [-dir_clip, 0]) under
    the flag of the last check; ``g <- clip(g - lr * dir, gate_min,
    gate_max)``; the flag and BOP re-evaluated every ``check_every``
    steps."""
    if recipe["direction"] != "dir2":
        raise NotImplementedError(recipe["direction"])
    eps, clip = recipe["eps"], recipe["dir_clip"]
    new = {}
    for key, g in state["gates"].items():
        gs, ms = grad_stat[key], mag_stat[key]
        if state["sat"]:
            d = -np.clip(np.abs(g) + ms, 0.0, clip)
        else:
            d = np.clip(1.0 / (gs + ms + eps), eps, clip)
        new[key] = np.clip(g - recipe["gate_lr"] * d, recipe["gate_min"],
                           recipe["gate_max"])
    step = state["step"] + 1
    out = {"gates": new, "sat": state["sat"], "step": step,
           "bop": state["bop"]}
    if step % recipe["check_every"] == 0:
        cost = bop(conf, new, recipe)
        out["bop"] = cost
        out["sat"] = cost <= recipe["budget_rbop"] * fp32_bop(conf)
    return out


def all_full_width(state: dict, recipe: dict) -> bool:
    """Whether every gate still reads 32 bits: the quantizer then passes
    every value through, so no learnable range has a gradient."""
    return all(np.all(gate_bits(g, recipe["gate_min"]) >= 32)
               for g in state["gates"].values())


def position_logits(weights, conf: dict, site_bits: dict, toks, targets, *,
                    compute: str = "float32", pad_to: int,
                    temperature: float = 1.0):
    """For each position of ``toks``: the best logit, the logit of
    ``targets`` there, the token the model puts first, and the probability
    mass at ``temperature`` of the tokens whose logit lies strictly above
    the target's. ``toks`` is padded to ``pad_to`` (causal, so padding
    changes no earlier row)."""
    n = len(toks)
    t = np.zeros(pad_to, np.int32)
    t[:n] = toks
    g = np.zeros(pad_to, np.int32)
    g[:n] = targets
    conf_key = tuple(sorted((k, v) for k, v in conf["config"].items()
                            if not isinstance(v, (dict, list))))
    out = _positions(
        weights, jnp.asarray(t), jnp.asarray(n, jnp.int32), jnp.asarray(g),
        jnp.asarray(temperature, jnp.float32), conf_key=conf_key,
        bits_key=tuple(sorted(site_bits.items())), compute=compute)
    best, tgt, top, above = jax.device_get(out)
    return (np.asarray(best[:n], np.float64), np.asarray(tgt[:n], np.float64),
            np.asarray(top[:n]), np.asarray(above[:n], np.float64))
