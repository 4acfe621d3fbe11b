"""Quantization sites and the QuantContext threaded through model forwards.

A *site* is one weight-matmul (dense / conv / expert GEMM) together with the
activation-quantization point of its output (paper Fig. 1: ``Q(W) -> layer ->
activation -> Q(a)``). Models never touch gates directly; they call::

    w_q      = qc.weight(name, w)             # quantize a weight tensor
    a_q      = qc.act(name, a)                # quantize an output activation
    qc.register_matmul(name, w_shape, positions=..., stack=k, active_frac=f)

``QuantContext`` operates in one of six modes:

  off        -- identity; used for FP32 pretraining and baselines.
  collect    -- abstract tracing (``jax.eval_shape``): records site metadata
                (MAC counts, shapes, signedness defaults) without compute.
  calibrate  -- FP32 forward that additionally records running range/mean
                statistics per site (returned functionally, jit-safe).
  train      -- fake quantization using gates + learnable ranges; also emits
                per-site activation statistics needed by the CGMQ directions
                (paper §2.3) and injects zero-valued "probe" parameters whose
                gradients equal the batch-summed activation gradients.
  export     -- weight-capture pass: ``weight()`` records the full tensor per
                site name in ``weight_stats`` (stacked along the scan axis by
                the existing stats plumbing) and everything else is identity.
                Used by ``quant.export.export_sites`` (via
                ``serving.engine.export_int_model``) to build the site-name
                -> weight mapping without a hand-maintained table.
  serve      -- deployment forward (DESIGN.md §8/§11). Serve mode carries NO
                gates or ranges: it runs off ``specs`` (site ->
                ``quant.QuantSpec``, the frozen bits/range/sign the
                controller certified) plus ``qweights`` (site ->
                ``quant.QuantizedTensor``, the packed int-code export).
                Matmul sites with an export dispatch the bit-width-matched
                fused-dequant GEMM (``layers.qmatmul`` consults
                ``serving_weight``); non-matmul callers of ``weight()`` get
                the dequantized frozen codes; remaining sites fall back to
                fake quantization at the spec bit-width. Activations
                fake-quantize at the spec bits — numerically the train-mode
                path with ``bits = T(g)`` precomputed — so serve logits
                match the train-mode fake-quant reference.

The probe trick: ``a + probe`` with ``probe = 0`` of the gate-group shape makes
``dL/dprobe = sum over batch (and group) of dL/da`` — exactly the
``|sum_i grad_a L|`` statistic the paper's directions need, without hooks.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import IMPLS

from . import gates as G
from .quantizer import fake_quant

# Gate granularities (paper §2.1 "two settings", plus per-channel for LLMs).
PER_TENSOR = "per_tensor"    # one gate per weight tensor / activation tensor ("layer")
PER_CHANNEL = "per_channel"  # one gate per output channel
PER_WEIGHT = "per_weight"    # one gate per element ("indiv.")

GRANULARITIES = (PER_TENSOR, PER_CHANNEL, PER_WEIGHT)


@dataclasses.dataclass(frozen=True)
class SiteInfo:
    """Static metadata for one matmul site (recorded in collect mode)."""

    name: str
    weight_shape: tuple[int, ...]   # full weight tensor shape
    fan_in: int                     # MACs contributed per output element
    out_features: int               # number of output channels
    positions: int                  # output positions per token/sample (conv spatial, seq kept out)
    stack: int                      # scan-stacked copies (leading gate dim), 1 if unstacked
    active_frac: float              # MoE: fraction of experts active per token
    act_quantized: bool             # False for fp outputs (head) -- excluded from BOP
    w_signed: bool = True
    a_signed: bool = True

    @property
    def macs_per_token(self) -> float:
        """MACs per token for ONE stacked copy of this site."""
        return float(self.fan_in) * self.out_features * self.positions * self.active_frac


@dataclasses.dataclass
class QuantConfig:
    enabled: bool = True
    granularity: str = PER_TENSOR
    impl: str = "direct"            # 'direct' (telescoped) | 'residual' (paper-literal)
    input_bits: int = 8             # fixed input quantization (paper §4.2)
    quantize_acts: bool = True
    act_granularity: str | None = None   # defaults to `granularity`
    # Gate the matmul INPUT activations too (".in" sites, DESIGN.md §16):
    # per-tensor affine, so the cost certificate covers compute
    # (w_bits x a_bits x MACs) and serving can run integer GEMMs. Off by
    # default: weight-only configs keep their exact pytree structure.
    quantize_inputs: bool = False

    def __post_init__(self):
        if self.act_granularity is None:
            self.act_granularity = (
                PER_CHANNEL if self.granularity == PER_WEIGHT else self.granularity
            )


def _group_shape(granularity: str, full_shape: tuple[int, ...], out_features: int):
    if granularity == PER_TENSOR:
        return ()
    if granularity == PER_CHANNEL:
        return (out_features,)
    return tuple(full_shape)


class QuantContext:
    """Threaded through model forwards; see module docstring for modes."""

    def __init__(
        self,
        mode: str = "off",
        cfg: QuantConfig | None = None,
        gates: dict[str, jnp.ndarray] | None = None,
        ranges: dict[str, Any] | None = None,
        probes: dict[str, jnp.ndarray] | None = None,
        qweights: dict[str, Any] | None = None,
        specs: dict[str, Any] | None = None,
        matmul_impl: str = "ref",
    ):
        assert mode in ("off", "collect", "calibrate", "train", "export",
                        "serve")
        assert matmul_impl in IMPLS, matmul_impl
        self.mode = mode
        self.cfg = cfg or QuantConfig()
        self.gates = gates or {}
        self.ranges = ranges or {}
        self.probes = probes or {}
        # serve mode: site name -> quant.QuantizedTensor (packed int codes)
        self.qweights = qweights or {}
        # serve mode: site name -> quant.QuantSpec (frozen bits/range/sign)
        self.specs = specs or {}
        self.matmul_impl = matmul_impl
        # Outputs populated during tracing:
        self.sites: dict[str, SiteInfo] = {}
        self.act_stats: dict[str, dict[str, jnp.ndarray]] = {}
        self.weight_stats: dict[str, jnp.ndarray] = {}
        # Stack context for scan-over-layers bodies.
        self._stack = 1
        self._prefix: list[str] = []

    # ---- naming / scan support -------------------------------------------
    def child(self, gates=None, ranges=None, probes=None,
              qweights=None, specs=None) -> "QuantContext":
        """Sub-context for a ``lax.scan`` body with per-layer slices.

        The body must return ``(child.act_stats, child.weight_stats)`` as scan
        outputs; the caller merges them back via ``absorb_stacked_stats``.
        """
        c = QuantContext(
            mode=self.mode,
            cfg=self.cfg,
            gates=self.gates if gates is None else gates,
            ranges=self.ranges if ranges is None else ranges,
            probes=self.probes if probes is None else probes,
            qweights=self.qweights if qweights is None else qweights,
            specs=self.specs if specs is None else specs,
            matmul_impl=self.matmul_impl,
        )
        c._prefix = list(self._prefix)
        c._stack = self._stack
        c.sites = self.sites  # collect mode: share the registry
        return c

    def absorb_stacked_stats(self, act_stats, weight_stats):
        """Merge stacked per-layer stats (scan outputs) into this context."""
        for k, v in act_stats.items():
            self.act_stats[k] = v
        for k, v in weight_stats.items():
            self.weight_stats[k] = v

    @contextlib.contextmanager
    def scope(self, name: str):
        """Sites registered inside are named ``name/...``, and the ops
        traced inside carry ``name`` in their metadata (``jax.named_scope``,
        so a profile attributes each site's GEMM and fusions; DESIGN.md
        §18)."""
        self._prefix.append(name)
        try:
            with jax.named_scope(name):
                yield
        finally:
            self._prefix.pop()

    def layer_stack(self, k: int):
        ctx = self

        class _Stack:
            def __enter__(self_s):
                ctx._stack *= k

            def __exit__(self_s, *a):
                ctx._stack //= k

        return _Stack()

    def _full(self, name: str) -> str:
        return "/".join(self._prefix + [name])

    # ---- site registration ------------------------------------------------
    def register_matmul(
        self,
        name: str,
        weight_shape: tuple[int, ...],
        fan_in: int,
        out_features: int,
        positions: int = 1,
        active_frac: float = 1.0,
        act_quantized: bool = True,
        w_signed: bool = True,
        a_signed: bool = True,
    ) -> str:
        full = self._full(name)
        if self.mode in ("collect", "export") and full not in self.sites:
            self.sites[full] = SiteInfo(
                name=full,
                weight_shape=tuple(int(d) for d in weight_shape),
                fan_in=int(fan_in),
                out_features=int(out_features),
                positions=int(positions),
                stack=self._stack,
                active_frac=float(active_frac),
                act_quantized=bool(act_quantized),
                w_signed=w_signed,
                a_signed=a_signed,
            )
        return full

    # ---- quantization entry points -----------------------------------------
    def serving_weight(self, name: str):
        """Int-code export for this site, or None (serve mode only)."""
        if self.mode != "serve":
            return None
        return self.qweights.get(self._full(name) + ".w")

    def weight(self, name: str, w: jnp.ndarray) -> jnp.ndarray:
        full = self._full(name)
        if self.mode == "export":
            # Capture pass: record the full tensor under its site name; the
            # scan-stats plumbing stacks per-layer slices back to (R, ...).
            self.weight_stats[full + ".w"] = w
            return w
        if self.mode in ("off", "collect", "calibrate") or not self.cfg.enabled:
            return w
        key = full + ".w"
        if self.mode == "serve":
            qt = self.qweights.get(key)
            if qt is not None:
                # Non-matmul consumers of an exported site (e.g. LeNet's
                # explicit `h @ w`): serve the dequantized frozen codes, so
                # every serving path reads the same artifact.
                return qt.dequantize().astype(w.dtype)
            # Fallback for sites without an int-code export (per-weight
            # granularity, >8-bit, MoE/conv shapes): fake-quant at the
            # spec bit-width, no stats or probes.
            spec = self.specs[key]
            return fake_quant(w, spec.bits, spec.beta, spec.signed)
        g = self.gates[key]
        beta = self.ranges[key]["beta"]
        signed = self.ranges[key]["signed"]
        with jax.named_scope("cgmq_stats"):
            # Group-reduced |w| for dir_2/dir_3 (paper §2.3).
            self.weight_stats[key] = self._w_group_stat(w, g)
            # Probe param: dL/dprobe == (group-summed) dL/dw through the
            # STE.
            if key in self.probes:
                w = w + jnp.broadcast_to(
                    self._expand_w_probe(self.probes[key], w), w.shape
                ).astype(w.dtype)
        return self._fq(w, g, beta, signed)

    def act(self, name: str, a: jnp.ndarray, *, feature_axis: int = -1) -> jnp.ndarray:
        """Quantize an output activation; records stats per mode."""
        full = self._full(name)
        key = full + ".a"
        if self.mode in ("off", "export") or not self.cfg.enabled \
                or not self.cfg.quantize_acts:
            return a
        if self.mode == "collect":
            return a
        if self.mode == "serve":
            spec = self.specs[key]
            return fake_quant(a, self._expand_act_gate(spec.bits, a),
                              self._expand_act_gate(spec.beta, a),
                              spec.signed)
        if self.mode == "calibrate":
            # Running-range statistics (momentum handled by the caller loop).
            red = tuple(i for i in range(a.ndim) if i != a.ndim + feature_axis)
            self.act_stats[key] = {
                "max": jnp.max(jnp.abs(a)),
                "max_per_ch": jnp.max(jnp.abs(a), axis=red),
                "min": jnp.min(a),
                "mean_abs": jnp.mean(jnp.abs(a)),
            }
            return a
        # train mode
        g = self.gates[key]
        beta = self.ranges[key]["beta"]
        signed = self.ranges[key]["signed"]
        with jax.named_scope("cgmq_stats"):
            # Activation statistic for dir_2/dir_3 (|mean over batch of a|),
            # reduced to the gate-group shape.
            stat = self._act_group_stat(a, g)
            self.act_stats[key] = {"mean_abs": stat}
            if key in self.probes:
                a = a + jnp.broadcast_to(self.probes[key],
                                         a.shape).astype(a.dtype)
        return self._fq(a, self._expand_act_gate(g, a), self._expand_act_gate(beta, a), signed)

    def input_spec(self, name: str):
        """Activation spec for this matmul's INPUT, or None (serve only).

        Serve-mode ``layers.qmatmul`` consults this next to
        ``serving_weight``: an exported int-code weight PLUS a calibrated
        input spec dispatches the int8×int8 integer-accumulation kernel
        (DESIGN.md §16).
        """
        if self.mode != "serve":
            return None
        return self.specs.get(self._full(name) + ".in")

    def act_in(self, name: str, x: jnp.ndarray) -> jnp.ndarray:
        """Quantize a matmul INPUT activation (the ``.in`` site, §16).

        Per-tensor affine, gated like any other site so gate descent trades
        weight vs activation precision and the BOP certificate covers
        compute. In serve mode the integer GEMM quantizes its own tile
        (``quant_matmul_qt``); this path fake-quants only the fp-fallback
        sites that still carry a spec, keeping their logits on the same
        grid as the integer path.
        """
        key = self._full(name) + ".in"
        if not self.cfg.enabled:
            return x
        if self.mode == "serve":
            spec = self.specs.get(key)
            if spec is None:
                return x
            return fake_quant(x, jnp.asarray(spec.bits, jnp.float32),
                              jnp.asarray(spec.beta, jnp.float32),
                              spec.signed)
        if self.mode in ("off", "collect", "export") \
                or not self.cfg.quantize_inputs:
            return x
        if self.mode == "calibrate":
            # Per-tensor running-range stats (same EMA loop as ``.a`` sites).
            self.act_stats[key] = {
                "max": jnp.max(jnp.abs(x)),
                "min": jnp.min(x),
                "mean_abs": jnp.mean(jnp.abs(x)),
            }
            return x
        # train mode — tolerate states trained before ``.in`` gates existed.
        g = self.gates.get(key)
        if g is None:
            return x
        beta = self.ranges[key]["beta"]
        signed = self.ranges[key]["signed"]
        with jax.named_scope("cgmq_stats"):
            self.act_stats[key] = {"mean_abs": self._act_group_stat(x, g)}
            if key in self.probes:
                x = x + jnp.broadcast_to(self.probes[key],
                                         x.shape).astype(x.dtype)
        return self._fq(x, self._expand_act_gate(g, x),
                        self._expand_act_gate(beta, x), signed)

    def input(self, x: jnp.ndarray) -> jnp.ndarray:
        """Fixed-width input quantization (paper: 8-bit sensor data)."""
        if self.mode not in ("train", "serve") or not self.cfg.enabled:
            return x
        beta = jnp.maximum(jnp.max(jnp.abs(jax.lax.stop_gradient(x))), 1e-8)
        signed = True
        return fake_quant(x, jnp.asarray(float(self.cfg.input_bits)), beta, signed)

    # ---- helpers ------------------------------------------------------------
    def _fq(self, x, g, beta, signed):
        with jax.named_scope("fake_quant"):
            if self.cfg.impl == "residual":
                return G.residual_fake_quant(x, g, beta, signed)
            return G.gated_fake_quant(x, g, beta, signed)

    @staticmethod
    def _expand_act_gate(g: jnp.ndarray, a: jnp.ndarray):
        """Broadcast a group-shaped array against activation ``a`` (feature-last)."""
        g = jnp.asarray(g)
        if g.ndim == 0:
            return g
        return g.reshape((1,) * (a.ndim - g.ndim) + g.shape)

    @staticmethod
    def _act_group_stat(a: jnp.ndarray, g: jnp.ndarray):
        """|mean over batch (and non-group dims) of a|, shaped like the gate."""
        g = jnp.asarray(g)
        a = jax.lax.stop_gradient(a)
        if g.ndim == 0:
            return jnp.abs(jnp.mean(a))
        red = tuple(range(a.ndim - g.ndim))
        return jnp.abs(jnp.mean(a, axis=red))

    @staticmethod
    def _w_group_stat(w: jnp.ndarray, g: jnp.ndarray):
        """Group-reduced |w| (mean within group), shaped like the gate."""
        g = jnp.asarray(g)
        w = jax.lax.stop_gradient(w)
        if g.ndim == 0:
            return jnp.mean(jnp.abs(w))
        if g.shape == w.shape:
            return jnp.abs(w)
        # per-channel (last axis) or stacked variants: reduce all axes whose
        # sizes don't line up with the trailing gate shape.
        extra = w.ndim - g.ndim
        red = tuple(i for i in range(w.ndim) if not (
            i >= extra and w.shape[i] == g.shape[i - extra]
        ))
        return jnp.mean(jnp.abs(w), axis=red)

    @staticmethod
    def _expand_w_probe(p: jnp.ndarray, w: jnp.ndarray):
        """Broadcast a probe of group shape against weight ``w``.

        Per-tensor: scalar. Per-weight: same shape. Per-channel / stacked:
        align trailing dims (channel-last convention).
        """
        p = jnp.asarray(p)
        if p.ndim == 0 or p.shape == w.shape:
            return p
        return p.reshape((1,) * (w.ndim - p.ndim) + p.shape)


# ---------------------------------------------------------------------------
# State initialization from collected sites
# ---------------------------------------------------------------------------


def collect_sites(forward, *abstract_args, cfg: QuantConfig | None = None):
    """Trace ``forward(qc, *args)`` under eval_shape and return its sites."""
    qc = QuantContext(mode="collect", cfg=cfg)

    def _fn(*args):
        return forward(qc, *args)

    jax.eval_shape(_fn, *abstract_args)
    return qc.sites


def _stacked(shape: tuple[int, ...], stack: int) -> tuple[int, ...]:
    return ((stack,) + shape) if stack > 1 else shape


def init_gates(
    sites: dict[str, SiteInfo], cfg: QuantConfig, init: float = G.GATE_INIT
) -> dict[str, jnp.ndarray]:
    """Gate pytree: one array per weight site and per quantized activation."""
    out = {}
    for s in sites.values():
        wshape = _group_shape(cfg.granularity, s.weight_shape, s.out_features)
        out[s.name + ".w"] = jnp.full(_stacked(wshape, s.stack), init, jnp.float32)
        if s.act_quantized:
            ashape = _group_shape(cfg.act_granularity, (s.out_features,), s.out_features)
            out[s.name + ".a"] = jnp.full(_stacked(ashape, s.stack), init, jnp.float32)
        if cfg.quantize_inputs and s.act_quantized:
            # ``.in`` sites are per-tensor by contract: the integer GEMM
            # quantizes the whole input tile against ONE affine grid (§16).
            out[s.name + ".in"] = jnp.full(_stacked((), s.stack), init,
                                           jnp.float32)
    return out


def init_probes(sites: dict[str, SiteInfo], cfg: QuantConfig) -> dict[str, jnp.ndarray]:
    """Zero probe params added to quantized activations (gradient taps)."""
    out = {}
    for s in sites.values():
        if s.act_quantized:
            ashape = _group_shape(cfg.act_granularity, (s.out_features,), s.out_features)
            out[s.name + ".a"] = jnp.zeros(_stacked(ashape, s.stack), jnp.float32)
        if cfg.quantize_inputs and s.act_quantized:
            out[s.name + ".in"] = jnp.zeros(_stacked((), s.stack), jnp.float32)
    return out


def init_ranges_from_weights(
    sites: dict[str, SiteInfo],
    cfg: QuantConfig,
    weight_lookup,
) -> dict[str, Any]:
    """Weight ranges from min/max (paper §2.4). ``weight_lookup(name)->array``.

    Activation ranges are placeholders (beta=1) until calibration runs.
    """
    ranges: dict[str, Any] = {}
    for s in sites.values():
        w = weight_lookup(s.name)
        if w is None:
            beta = jnp.ones(_stacked((), s.stack), jnp.float32)
            signed = True
        else:
            w = jnp.asarray(w)
            if cfg.granularity == PER_CHANNEL:
                red = tuple(range(w.ndim - 1)) if s.stack == 1 else tuple(
                    range(1, w.ndim - 1)
                )
                beta = jnp.max(jnp.abs(w), axis=red)
                all_pos = jnp.all(jnp.min(w, axis=red) >= 0)
            elif cfg.granularity == PER_WEIGHT:
                beta = jnp.abs(w) + 1e-8
                all_pos = jnp.all(w >= 0)
            else:
                if s.stack > 1:
                    red = tuple(range(1, w.ndim))
                    beta = jnp.max(jnp.abs(w), axis=red)
                else:
                    beta = jnp.max(jnp.abs(w))
                all_pos = jnp.all(w >= 0)
            signed = not bool(all_pos)
        ranges[s.name + ".w"] = {"beta": beta.astype(jnp.float32), "signed": signed}
        if s.act_quantized:
            ashape = _group_shape(cfg.act_granularity, (s.out_features,), s.out_features)
            ranges[s.name + ".a"] = {
                "beta": jnp.ones(_stacked(ashape, s.stack), jnp.float32),
                "signed": True,
            }
        if cfg.quantize_inputs and s.act_quantized:
            ranges[s.name + ".in"] = {
                "beta": jnp.ones(_stacked((), s.stack), jnp.float32),
                "signed": True,
            }
    return ranges


def split_learnable_ranges(ranges: dict[str, Any]):
    """Split into (learnable betas pytree, static signed map)."""
    betas = {k: v["beta"] for k, v in ranges.items()}
    signed = {k: bool(v["signed"]) for k, v in ranges.items()}
    return betas, signed


def merge_ranges(betas: dict[str, jnp.ndarray], signed: dict[str, bool]):
    return {k: {"beta": betas[k], "signed": signed[k]} for k in betas}


def total_gate_count(gts: dict[str, jnp.ndarray]) -> int:
    return int(sum(np.prod(v.shape) if v.ndim else 1 for v in gts.values()))
