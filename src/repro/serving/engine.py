"""Batched serving engine over the CGMQ-quantized model.

The deployment half of the CGMQ story (DESIGN.md §8/§11).
``export_int_model`` freezes a trained (params, gates, ranges) triple into
``quant.QuantizedTensor``s — packed sub-byte codes + affine terms per site,
the ``quant_matmul`` kernel family's format — and ``ServingEngine`` runs a
slot-based continuous-batching scheduler whose hot path actually serves
that artifact:

  * **batched prefill** — each admitted request runs its whole prompt through
    ONE causal forward (``tfm.prefill_slot``), which writes the slot's KV
    range / recurrent state in one shot. The seed engine scanned
    ``decode_step`` token-by-token with the token broadcast across all
    slots: O(prompt_len x slots) slot-forwards per admission, now 1.
  * **mixed-precision integer decode** — with a ``quant_state``, decode runs
    in serve mode: every exported matmul site dispatches the bit-width-
    matched fused-dequant GEMM (``quant_matmul_qt``: Pallas on TPU, jnp
    reference elsewhere) straight off packed 2/4/8-bit codes instead of
    fake-quant-then-fp32-matmul, so decode streams the weight bytes the
    controller certified — ``bits/8`` of a byte per weight, not a uniform
    int8 (let alone fp32) footprint.
  * **device-resident generation loop** — sampling (greedy argmax OR the
    stochastic temperature / top-k / top-p pick, per slot), the per-slot
    position bump, stop-token detection and done-flag computation all live
    inside the jitted tick; the Python loop does ONE small host sync per
    batch tick (next tokens + emitted/done masks), not one per slot. The
    ``stats`` host-sync ledger (``tick_syncs`` / ``admit_syncs``) records
    every transfer, and the tick stays at exactly one with sampling enabled.

The request lifecycle (DESIGN.md §12): each ``Request`` carries a
``SamplingParams`` (temperature, top-k, top-p, per-request seed, stop
tokens, max_new) that admission lowers into per-slot rows of the device
state; ``engine.generate(prompts, params)`` is the user-facing facade
(submit → drive → collect ``GenerationResult``s) and
``engine.generate_stream(...)`` yields per-tick ``TokenEvent`` deltas.
Requests join a waiting queue; free slots prefill and join the running
batch; finished slots — stop-token hits included — free immediately, in the
same tick. Per-slot KV state lives in the cache pytree indexed by slot, at
per-slot positions (``cache["pos"]`` is a vector), so slots at unrelated
sequence positions share one decode step.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import itertools
import math
import time
from typing import Any, Callable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.sites import QuantContext
from repro.models import transformer as tfm
from repro.obs import span
from repro.core.calibration import calibrate_activations
from repro.quant import (ActQuantSpec, KVQuantSpec, QuantizedTensor,
                         QuantSpec, export_act_sites, export_sites,
                         quant_report, specs_from_state)
from repro.quant.kv import kv_cache_report
from repro.serving import kv_pool
from repro.serving.admission import (FINISHED_DEADLINE, FINISHED_ERROR,
                                     FINISHED_LENGTH, FINISHED_REJECTED,
                                     FINISHED_STOP, AdmissionConfig,
                                     WaitingQueue, latency_percentiles,
                                     projected_blocks)
from repro.serving.sampling import (SamplingParams, finite_rows,
                                    sample_tokens)
from repro.serving.window import (WindowSpec, as_window_spec,
                                  window_demand_blocks, window_report)


# ---------------------------------------------------------------------------
# Int-code export
# ---------------------------------------------------------------------------


def export_int_codes(w, gate, beta, signed: bool) -> QuantizedTensor:
    """Single-tensor export at its learned bit-width (packed sub-byte).

    The gate→bits→storage-class decision is ``QuantSpec.from_gate`` /
    ``storage_bits`` — the same constructor the full-model exporter uses.
    Gates above 8 bits clamp to the 8-bit storage ceiling here (this helper
    has no fake-quant fallback to reject into).
    """
    spec = QuantSpec.from_gate(gate, beta, signed)
    storage = spec.storage_bits() or 8
    bits = jnp.minimum(spec.bits, float(storage))
    return QuantizedTensor.from_float(w, bits, spec.beta, spec.signed,
                                      storage_bits=storage)


def export_int_model(params, cfg: ModelConfig, quant_state: dict, *,
                     plan=None, pack: bool = True, warn: bool = True):
    """Full-model quantized export for the serving GEMMs.

    Captures every matmul site's weight tensor via an export-mode forward —
    the same code path serving runs, so site names line up by construction
    (scan-stacked sites come back stacked along the scan axis, exactly the
    layout the decode scan re-slices) — then freezes each eligible dense
    site through ``quant.export.export_sites`` at its learned per-site
    (per-layer, per-channel) bit-widths, packed into its 2/4/8-bit storage
    class (``pack=False`` keeps the unpacked int8 oracle layout).

    ``quant_state``: {"qcfg", "gates", "betas", "signed"} as used for
    train-mode forwards. Returns ``(qweights, ledger)``: ``qweights`` maps
    "<site>.w" -> ``QuantizedTensor`` (the pytree ``decode_step`` threads
    through its scan alongside the specs); ``ledger`` is the
    ``quant.ExportLedger`` recording EVERY site — including the ones
    rejected to fake-quant fallback (per-weight granularity, >8-bit,
    MoE/conv weight shapes), which used to be silently invisible.
    """
    qc = QuantContext(mode="export")
    s = 8  # long enough for chunked-SSD block sizes at smoke scale
    if cfg.embed_input:
        dummy = jnp.zeros((1, s), jnp.int32)
    else:
        dummy = jnp.zeros((1, s, cfg.d_model), jnp.float32)
    mrope = None
    if cfg.mrope_sections is not None:
        mrope = jnp.broadcast_to(jnp.arange(s)[None, None, :], (3, 1, s))
    tfm.forward_train(qc, params, dummy, cfg, plan=plan, mrope_pos=mrope,
                      moe_impl="dense_all", remat=False)
    return export_sites(qc, quant_state["gates"], quant_state["betas"],
                        quant_state["signed"], pack=pack, warn=warn)


def make_uniform_quant_state(cfg: ModelConfig, params, *, gate_init=2.2,
                             granularity="per_channel"):
    """A stand-in trained CGMQ state with one uniform gate everywhere
    (default T(2.2) = 8 bits): the shape real training produces, without
    running the controller. Shared by the serving example, the throughput
    benchmark and the serving tests so they can't drift apart; NOT a
    substitute for a trained state in real deployments.
    """
    from repro.core.sites import (QuantConfig, collect_sites, init_gates,
                                  init_ranges_from_weights,
                                  split_learnable_ranges)

    qcfg = QuantConfig(granularity=granularity)
    s = 8
    if cfg.embed_input:
        dummy = jnp.zeros((1, s), jnp.int32)
    else:  # modality stub: embeddings come in directly
        dummy = jnp.zeros((1, s, cfg.d_model), jnp.float32)
    mrope = None
    if cfg.mrope_sections is not None:
        mrope = jnp.broadcast_to(jnp.arange(s)[None, None, :], (3, 1, s))
    sites = collect_sites(
        lambda qc, p, x: tfm.forward_train(qc, p, x, cfg, mrope_pos=mrope,
                                           moe_impl="dense_all", remat=False),
        params, dummy, cfg=qcfg)
    gates = init_gates(sites, qcfg, init=gate_init)
    betas, signed = split_learnable_ranges(
        init_ranges_from_weights(sites, qcfg, lambda n: None))
    return {"qcfg": qcfg, "gates": gates, "betas": betas, "signed": signed}


# Gate values landing exactly on T(g) = 2 / 4 / 8 bits (core.gates Eq. 4).
MIXED_GATE_LEVELS = (0.8, 1.5, 2.5)

# bits -> the gate value whose T(g) is exactly that width; used to fold
# served activation widths back into the BOP certificate (DESIGN.md §16).
ACT_GATE_LEVELS = {2: 0.8, 4: 1.5, 8: 2.5}


def make_act_specs(cfg: ModelConfig, params, act_bits: int, *, plan=None,
                   batches: int = 2, seq: int = 16, seed: int = 0) -> dict:
    """Calibrate per-tensor ``.in`` activation specs for serving (§16).

    Runs a few seeded random batches through the SAME calibrate-mode
    forward training uses (``QuantConfig(quantize_inputs=True)`` turns the
    ``.in`` recording on), EMA-aggregates the per-batch ranges via
    ``core.calibration.calibrate_activations``, and freezes each GEMM-input
    site into an ``ActQuantSpec`` at ``act_bits``. Scan-stacked sites come
    back with a leading layer axis on ``beta`` — the layout the decode scan
    re-slices. Returns {"<site>.in": ActQuantSpec}; merge into a serve
    context's ``specs`` (the engine's ``act_bits=`` knob does this) to run
    the int8×int8 integer GEMM path end to end.
    """
    from repro.core.sites import QuantConfig

    qcfg = QuantConfig(quantize_inputs=True)
    rng = np.random.default_rng(seed)
    if cfg.embed_input:
        data = [jnp.asarray(rng.integers(0, cfg.vocab_size, (1, seq)),
                            jnp.int32) for _ in range(batches)]
    else:
        data = [jnp.asarray(rng.normal(size=(1, seq, cfg.d_model)),
                            jnp.float32) for _ in range(batches)]
    mrope = None
    if cfg.mrope_sections is not None:
        mrope = jnp.broadcast_to(jnp.arange(seq)[None, None, :], (3, 1, seq))

    def _fwd(qc, batch):
        tfm.forward_train(qc, params, batch, cfg, plan=plan, mrope_pos=mrope,
                          moe_impl="dense_all", remat=False)

    act_ranges = calibrate_activations(_fwd, data, qcfg)
    return {
        key: ActQuantSpec(bits=int(act_bits),
                          beta=jnp.asarray(v["beta"], jnp.float32),
                          signed=bool(v["signed"]))
        for key, v in act_ranges.items() if key.endswith(".in")
    }


def make_mixed_quant_state(cfg: ModelConfig, params, *,
                           levels=MIXED_GATE_LEVELS,
                           granularity="per_channel"):
    """A stand-in trained CGMQ state with MIXED 2/4/8-bit weight sites.

    Weight gates cycle through ``levels`` site-by-site (deterministic: sorted
    site order), activations stay 8-bit — the shape of a real
    budget-constrained CGMQ outcome, without running the controller. This is
    the workload for the packed sub-byte serving path: exported storage is
    2/4/8-bit packed, so device bytes land strictly below the uniform-int8
    baseline (asserted in CI via ``quant_report``).
    """
    qs = make_uniform_quant_state(cfg, params, gate_init=2.5,
                                  granularity=granularity)
    gates = {}
    wi = 0
    for key in sorted(qs["gates"]):
        g = qs["gates"][key]
        if key.endswith(".w"):
            gates[key] = jnp.full_like(g, levels[wi % len(levels)])
            wi += 1
        else:
            gates[key] = g
    qs["gates"] = gates
    return qs


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One unit of the serving lifecycle: waiting → slot → finished.

    ``params`` carries the request's ``SamplingParams``; ``max_new`` is kept
    as a construction convenience (the pre-§12 call signature) and is folded
    into a default-greedy ``params`` when none is given — after
    construction ``req.max_new`` always mirrors ``req.params.max_new``.

    The §13 failure-model fields: ``ttft_deadline_s`` / ``deadline_s`` are
    per-request budgets (seconds from submit to first token / to
    completion) overriding the engine ``AdmissionConfig`` defaults;
    ``seed_used`` pins the sampling seed actually drawn at first admission,
    so a preempted request resumes its exact key chain (a seedless request
    must NOT redraw on re-admission); ``preemptions`` counts evictions;
    ``seq`` is the submission sequence number (preemption keeps it, so
    re-admission sorts ahead of newer arrivals).
    """

    rid: int
    prompt: np.ndarray          # (S,) int32
    max_new: int = 16
    done: bool = False
    output: list = dataclasses.field(default_factory=list)
    # paged layout: the chain-hash keys of this request's full prompt blocks
    # in the engine's prefix map (for eviction at retirement)
    prefix_keys: list = dataclasses.field(default_factory=list)
    params: SamplingParams | None = None
    finish_reason: str | None = None    # a FINISHED_* reason once done
    ttft_deadline_s: float | None = None
    deadline_s: float | None = None
    seed_used: int | None = None
    preemptions: int = 0
    seq: int | None = None
    submit_s: float = 0.0
    ttft_by: float = math.inf       # absolute expiry times, resolved at
    deadline_by: float = math.inf   # submit() against the engine clock
    # SLO stamps (DESIGN.md §15), taken against the engine's injectable
    # clock: first_token_s when the first output token reaches the host,
    # finish_s at the terminal transition. TTFT = first_token_s - submit_s;
    # TPOT = (finish_s - first_token_s) / (len(output) - 1).
    first_token_s: float | None = None
    finish_s: float | None = None
    # admit_s: when the request was first bound to a slot (its queue wait
    # is admit_s - submit_s; a re-admission after preemption keeps it);
    # prefix_hit_blocks: prompt blocks the prefix cache served it, summed
    # over its admissions (DESIGN.md §18)
    admit_s: float | None = None
    prefix_hit_blocks: int = 0

    def __post_init__(self):
        if self.params is None:
            self.params = SamplingParams(max_new=self.max_new)
        self.max_new = self.params.max_new

    @property
    def deadline_key(self):
        """The expiry that matters while this request WAITS: a fresh request
        dies when either budget passes (no first token yet); a preempted
        one already met its TTFT, so only the wall deadline applies. Also
        the queue's priority key (earliest-expiring first)."""
        if self.output:
            return self.deadline_by
        return min(self.ttft_by, self.deadline_by)


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One emitted token, as yielded by ``generate_stream`` (one event per
    request per tick; the admission tick yields the prefill-sampled first
    token). ``done``/``finish_reason`` ride on the request's final event."""

    rid: int
    token: int
    index: int                  # position in the request's output
    done: bool = False
    finish_reason: str | None = None


@dataclasses.dataclass(frozen=True)
class GenerationResult:
    """Terminal state of one request, as returned by ``generate``."""

    rid: int
    prompt: np.ndarray
    tokens: list
    finish_reason: str
    params: SamplingParams


class ServingEngine:
    """Slot-based continuous batching around prefill_slot / decode_step.

    The user-facing surface is the request lifecycle (DESIGN.md §12):
    ``generate(prompts, params)`` / ``generate_stream(...)`` with a
    ``SamplingParams`` per request — temperature / top-k / top-p sampling
    runs inside the jitted tick off per-slot key chains, ``temperature=0``
    (default) being bit-identical to greedy argmax. ``submit``/``step`` stay
    public as the scheduler-level API the facade drives.

    ``quant_state=None`` serves fp32; with a quant_state the engine serves
    the packed mixed-precision export (``use_int8=True``, the default) or
    pure fake-quant. ``matmul_impl`` picks the kernel backend of every
    served path, fp32/bf16 included (fused-dequant GEMMs and paged
    attention): "pallas" on TPU, "pallas_interpret" for kernel validation
    in CPU tests, "ref" (jnp) elsewhere; the default auto-detects.

    ``kv_layout`` picks the attention cache substrate (DESIGN.md §10):

      * ``"paged"`` (the "auto" default whenever the arch has attention
        layers) — K/V lives in a block pool addressed through a per-slot
        block table with a device-resident free-list allocator, and the
        scheduler shares physical blocks between requests with a common
        prompt prefix (copy-on-write at the first divergent write). A fully
        cached prompt admits with NO prefill forward: its table row maps the
        existing blocks and only the sub-block remainder is teacher-forced.
      * ``"ring"`` — the §8 contiguous per-slot rows (local layers as ring
        buffers). Kept as the equivalence oracle for the paged path and used
        automatically for attention-free (pure recurrent-state) archs.

    Prefix sharing applies only to pure-attention archs (recurrent state is
    per-slot and can't be block-shared); ``prefix_sharing=False`` disables
    it. ``block_size``/``num_blocks`` size the pool — the default pool
    (``slots * ceil(max_seq/bs) + 1 + prefix_lru_blocks`` blocks) can always
    hold every slot at ``max_seq``, so the in-tick allocator can never run
    dry.

    ``prefix_lru_blocks`` (default 0 = retire-time eviction, the old
    behavior) keeps up to that many fully-unreferenced prefix blocks alive
    in an LRU pool: the prefix cache itself holds a device refcount, so a
    popular prompt's blocks survive all its requests retiring and the next
    same-prefix admission still skips the prefill. Retained blocks live in
    pool surplus beyond the worst-case slot reservation (the pool is sized
    up by exactly ``prefix_lru_blocks``), so generation can never be starved
    by the cache; past capacity the least-recently-used key is evicted and
    its block released.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_seq: int = 256, quant_state: dict | None = None,
                 plan=None, use_int8: bool = True, act_bits: int | None = None,
                 matmul_impl: str | None = None, kv_layout: str = "auto",
                 kv_dtype: str = "bf16",
                 block_size: int = 8, num_blocks: int | None = None,
                 prefix_sharing: bool = True, prefix_lru_blocks: int = 0,
                 max_stop: int = 4,
                 admission: AdmissionConfig | None = None,
                 preemption: bool | str = "auto",
                 prefill_chunk_tokens: int | None = None,
                 tick_token_budget: int | None = None,
                 attention_window: "int | WindowSpec | None" = None,
                 clock: Callable[[], float] = time.monotonic):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.plan = plan
        self.quant_state = quant_state
        # KV storage class (DESIGN.md §14): bf16 (default) / fp32 float
        # pools, or int8/int4 group-wise quantized codes + fp16 scales.
        assert kv_dtype in ("bf16", "fp32", "int8", "int4"), kv_dtype
        self.kv_dtype = kv_dtype
        self._kv_store = jnp.float32 if kv_dtype == "fp32" else jnp.bfloat16
        if kv_dtype in ("int8", "int4"):
            # largest power-of-two group <= 32 that divides head_dim, so the
            # fused kernel path never sees a ragged group (§14 alignment rule)
            gs = math.gcd(cfg.head_dim, 32)
            assert cfg.head_dim % gs == 0, (cfg.head_dim, gs)
            self.kv_spec = KVQuantSpec(bits=8 if kv_dtype == "int8" else 4,
                                       group_size=gs, head_dim=cfg.head_dim)
        else:
            self.kv_spec = None
        if matmul_impl is None:
            matmul_impl = "pallas" if jax.default_backend() == "tpu" else "ref"
        self.qweights: dict[str, QuantizedTensor] = {}
        self.export_ledger = None
        self.specs: dict[str, QuantSpec] = {}
        if quant_state is not None:
            self.specs = specs_from_state(quant_state["gates"],
                                          quant_state["betas"],
                                          quant_state["signed"])
        if quant_state is not None and use_int8:
            self.qweights, self.export_ledger = export_int_model(
                params, cfg, quant_state, plan=plan)
        # Fully-integer GEMMs (DESIGN.md §16): calibrate per-tensor ``.in``
        # activation specs and merge them into the serve specs — every site
        # with an int-code export then dispatches the int8×int8 kernel.
        self.act_bits = act_bits
        self.act_specs: dict[str, ActQuantSpec] = {}
        if act_bits is not None:
            if quant_state is None:
                raise ValueError("act_bits requires a quant_state")
            self.act_specs = make_act_specs(cfg, params, act_bits, plan=plan)
            self.specs = {**self.specs, **self.act_specs}
            if self.export_ledger is not None:
                self.export_ledger.act_entries = export_act_sites(
                    self.act_specs, self.export_ledger.sites)

        kinds = list(cfg.block_pattern) + list(cfg.remainder_kinds)
        has_attn = any(k in ("global", "local") for k in kinds)
        self._state_only = not has_attn
        assert kv_layout in ("auto", "paged", "ring"), kv_layout
        if kv_layout == "auto":
            kv_layout = "paged" if has_attn else "ring"
        if not has_attn:
            kv_layout = "ring"  # nothing to page: pure state rows
        self.kv_layout = kv_layout
        self.paged = kv_layout == "paged"
        self.prefix_sharing = (
            self.paged and prefix_sharing
            and all(k in ("global", "local") for k in kinds))
        self.lru_capacity = prefix_lru_blocks if self.prefix_sharing else 0
        assert preemption in ("auto", True, False), preemption
        # Long-context window (DESIGN.md §17): None keeps dense attention
        # bit-identical to an unwindowed engine; an int or WindowSpec caps
        # every global layer's reach (local layers clip to min(cfg.window,
        # W)) and, on the paged layout, bounds KV residency via in-tick
        # out-of-window eviction. The spec binds the engine block size so
        # sink_tokens is block-aligned.
        self.window_spec = as_window_spec(attention_window, block_size)
        self._window = (self.window_spec.mask
                       if self.window_spec is not None else None)
        if self.paged:
            self.block_size = block_size
            self.max_blocks = -(-max_seq // block_size)
            # Per-slot worst-case residency: the full table without a
            # window; with a window AND chunked prefill (between-chunk
            # eviction, §17) only live-window + sink + one-chunk blocks.
            self._slot_demand = window_demand_blocks(
                self.window_spec, self.max_blocks, prefill_chunk_tokens,
                block_size)
            # Retained (LRU) prefix blocks live in pool surplus BEYOND the
            # worst-case slot reservation, so the in-tick allocator can
            # never be starved by the cache (DESIGN.md §10).
            min_blocks = slots * self._slot_demand + 1 + self.lru_capacity
            # An undersized pool is legal WITH preemption (§13): the pool
            # only has to back one slot's worst-case residency, so a
            # preempted request can always be replayed once the others
            # drain. Below that floor not even a lone request fits and no
            # policy can help. (Windowed + chunked engines shrink the floor
            # to window + chunk blocks: §17 long-context sizing.)
            floor_blocks = self._slot_demand + 1 + self.lru_capacity
            if num_blocks is not None and num_blocks < floor_blocks:
                raise ValueError(
                    f"num_blocks={num_blocks} can't back even one slot at "
                    f"max_seq={max_seq} with {self.lru_capacity} retained "
                    f"prefix blocks (need >= {floor_blocks})")
            undersized = num_blocks is not None and num_blocks < min_blocks
            self.preemption = undersized if preemption == "auto" \
                else bool(preemption)
            if undersized and not self.preemption:
                # without the in-tick preemption branch an exhausted free
                # stack would silently alias a live block into two slots
                raise ValueError(
                    f"num_blocks={num_blocks} can't back {slots} slots at "
                    f"max_seq={max_seq} with {self.lru_capacity} retained "
                    f"prefix blocks (need >= {min_blocks}); pass "
                    f"preemption=True (or leave it 'auto') to oversubscribe "
                    f"the pool with victim preemption")
            self.num_blocks = num_blocks or min_blocks
            self.cache = tfm.init_paged_cache(cfg, slots, self.num_blocks,
                                              block_size,
                                              kv_dtype=self._kv_store,
                                              kv_spec=self.kv_spec)
            self.alloc = kv_pool.init_alloc(self.num_blocks, slots,
                                            self.max_blocks)
        else:
            # nothing to page: every slot owns its contiguous rows, so the
            # in-tick exhaustion path can't exist; host-side ``preempt()``
            # still works (deadlines / fault injection).
            self.preemption = False
            self.cache = tfm.init_cache(cfg, slots, max_seq,
                                        kv_dtype=self._kv_store,
                                        kv_spec=self.kv_spec)
            self.alloc = None
        self._assert_kv_contract()
        self.admission = admission
        self._clock = clock
        # Continuous batching (DESIGN.md §15): with ``prefill_chunk_tokens``
        # set, admission binds a request to a free slot immediately and its
        # prompt prefills in fixed-size chunks interleaved with decode
        # ticks, at most ``tick_token_budget`` prompt tokens started per
        # tick (default: one chunk's worth, falling back to the
        # AdmissionConfig's budget if it carries one). ``None`` keeps the
        # wave scheduler: whole-prompt prefill at admission.
        self.prefill_chunk_tokens = prefill_chunk_tokens
        if prefill_chunk_tokens is not None and prefill_chunk_tokens < 1:
            raise ValueError(f"prefill_chunk_tokens must be >= 1 or None: "
                             f"{prefill_chunk_tokens}")
        if tick_token_budget is None and admission is not None:
            tick_token_budget = admission.tick_token_budget
        if tick_token_budget is None:
            tick_token_budget = prefill_chunk_tokens
        if tick_token_budget is not None and tick_token_budget < 1:
            raise ValueError(f"tick_token_budget must be >= 1 or None: "
                             f"{tick_token_budget}")
        self.tick_token_budget = tick_token_budget
        self._has_state = any(k in ("ssm", "recurrent") for k in kinds)
        self._ssm_arch = "ssm" in kinds
        # slot -> in-flight chunked-prefill record (PREFILLING slots); the
        # device row stays inactive until the final chunk arms it
        self._pending: dict[int, dict] = {}
        # host side of the prefix cache: chain-hash of full-block prompt
        # content -> physical block id, plus live-request counts per key
        self._prefix_map: dict[Any, int] = {}
        self._key_refs: dict[Any, int] = {}
        # LRU retention (ROADMAP item): keys whose last live user retired
        # but whose physical block the cache still holds (device ref +1),
        # in eviction order. Only keys in ``_cache_held`` carry that ref.
        self._lru: "collections.OrderedDict[Any, int]" = \
            collections.OrderedDict()
        self._cache_held: set = set()
        # Device-resident generation state: one row per slot. The sampling
        # rows (key / temperature / top-k / top-p / stop) are the lowered
        # form of each slot's SamplingParams (DESIGN.md §12), written once
        # at admission so the tick samples without any host traffic.
        self.max_stop = max_stop
        # gen / stamp feed the §13 preemption victim policy (fewest
        # generated tokens, oldest admission stamp on ties); bomb is the
        # fault-injection seam — a per-slot additive logit perturbation,
        # cleared whenever the slot is (re-)armed.
        self.state = {
            "last_tok": jnp.zeros((slots,), jnp.int32),
            "active": jnp.zeros((slots,), bool),
            "remaining": jnp.zeros((slots,), jnp.int32),
            "key": jnp.zeros((slots, 2), jnp.uint32),
            "temp": jnp.zeros((slots,), jnp.float32),
            "top_k": jnp.zeros((slots,), jnp.int32),
            "top_p": jnp.ones((slots,), jnp.float32),
            "stop": jnp.full((slots, max_stop), -1, jnp.int32),
            "gen": jnp.zeros((slots,), jnp.int32),
            "stamp": jnp.zeros((slots,), jnp.int32),
            "bomb": jnp.zeros((slots,), jnp.float32),
        }
        self.slot_req: list[Request | None] = [None] * slots
        self.waiting = WaitingQueue()
        self.finished: list[Request] = []
        # seed stream for requests that don't pin one (deterministic per
        # engine instance, not across processes) + facade request ids
        self._seed_rng = np.random.default_rng(0x5EED)
        # facade rids start high so they can't collide with hand-numbered
        # Requests submitted alongside a generate() batch
        self._auto_rid = itertools.count(1 << 20)
        self._seq_counter = itertools.count()       # submission order
        self._stamp_counter = itertools.count(1)    # admission order
        self._stolen: list = []                     # fault-injected steals
        # Perf accounting (consumed by benchmarks/run.py --json):
        #   prefill_forwards       batched prompt forwards actually run
        #   seed_equiv_forwards    decode_step forwards the seed's
        #                          scan-of-decode-steps prefill would have run
        #                          (one per prompt token, each slots wide)
        #   prefix_hit_blocks /    paged: prompt blocks served from the
        #     prompt_blocks        prefix cache vs total full prompt blocks
        #   shared_admissions      admissions that skipped the prefill
        #                          forward entirely (fully cached prompt)
        #   tick_syncs / admit_syncs   the host-sync ledger (DESIGN.md §12):
        #                          every device_get on the serving path is
        #                          counted at its call site, so the §8
        #                          one-sync-per-tick contract is a tested
        #                          number, not a comment (pool_stats() is
        #                          benchmarking-only and ledgered separately)
        #   preemptions / resumed_admissions / rejected_requests /
        #     deadline_expired / nan_failures   the §13 failure-model
        #                          counters: victim evictions, replays after
        #                          eviction, submit-time rejections, deadline
        #                          expiries, non-finite-logit failures
        #   admissions / queue_wait_s   first bindings to a slot and their
        #                          summed admit_s - submit_s (§18)
        self.stats = {"prefill_forwards": 0, "tail_forwards": 0,
                      "teacher_steps": 0, "prefill_chunks": 0,
                      "prompt_tokens": 0, "seed_equiv_forwards": 0,
                      "decode_ticks": 0, "generated_tokens": 0,
                      "prefix_hit_blocks": 0, "prompt_blocks": 0,
                      "shared_admissions": 0, "cow_copies": 0,
                      "preemptions": 0, "resumed_admissions": 0,
                      "rejected_requests": 0, "deadline_expired": 0,
                      "nan_failures": 0, "admissions": 0,
                      "queue_wait_s": 0.0,
                      "tick_syncs": 0, "admit_syncs": 0, "stat_syncs": 0,
                      "prefill_time_s": 0.0, "decode_time_s": 0.0}

        # The small frozen specs (bits/ranges) ride as jit closure
        # constants; the packed codes are passed as a jit ARGUMENT so the
        # (potentially large) artifact isn't baked into every compiled
        # executable — _tick plus each per-bucket _prefill specialization
        # would otherwise embed its own copy.
        specs = self.specs

        def _qc(qweights):
            if quant_state is None:
                return QuantContext(mode="off", matmul_impl=matmul_impl)
            return QuantContext(
                mode="serve", cfg=quant_state["qcfg"], specs=specs,
                qweights=qweights, matmul_impl=matmul_impl,
            )

        paged = self.paged
        preemption = self.preemption
        # §17 closure constants: the (window, sink_tokens) tuple threads
        # into every model entry point; the block-granular split drives the
        # in-tick eviction pass.
        wmask = self._window
        if wmask is not None:
            win_w, win_sinks = wmask
            win_sink_blocks = win_sinks // block_size
        else:
            win_w = win_sink_blocks = 0

        @jax.jit
        def _tick(params, qweights, cache, state, alloc):
            """One device-resident generation step for the whole batch.

            Sampling (per-slot temperature / top-k / top-p off the slot's
            key chain; zero-temperature rows take the bit-exact argmax), the
            per-slot position bump (via ``advance``), stop-token detection,
            the done-flag updates — and, in the paged layout, the free-list
            pop for rows entering an unallocated block, preceded on an
            oversubscribed pool by §13 victim preemption — all happen on
            device. The non-finite-logit guard runs here too: rows whose
            logits went NaN/Inf (model blow-up or an injected ``bomb``) are
            not emitted and deactivate in place. The caller fetches
            (next_tokens, emitted, done, preempted, bad) in a single host
            transfer — the failure masks ride the same sync the stats
            ledger already pays for, so the §8 contract holds under faults.
            """
            table = None
            live = state["active"]
            pre = jnp.zeros_like(live)
            if paged:
                with jax.named_scope("kv_alloc"):
                    if wmask is not None:
                        # §17 out-of-window eviction: release every block
                        # wholly behind the sliding window (sink blocks
                        # pinned) BEFORE preemption/allocation, so freed
                        # blocks relieve pool pressure within the same tick.
                        # ``fl`` matches the kernel's first-live-block walk
                        # exactly, so no evicted block is ever read.
                        fl = jnp.maximum(
                            (cache["pos"] - win_w + 1) // block_size,
                            win_sink_blocks)
                        alloc = kv_pool.evict_out_of_window(
                            alloc, fl, live, win_sink_blocks)
                    if preemption:
                        alloc, pre = kv_pool.preempt_for_free(
                            alloc, cache["pos"], live, state["gen"],
                            state["stamp"], block_size)
                        live = live & ~pre
                    alloc = kv_pool.tick_alloc(alloc, cache["pos"], live,
                                               block_size)
                    table = alloc["table"]
            logits, cache = tfm.decode_step(
                _qc(qweights), params, cache, state["last_tok"], cfg,
                plan=plan, advance=live, block_table=table, window=wmask)
            pair = jax.vmap(jax.random.split)(state["key"])
            rows = logits[:, 0, : cfg.vocab_size] + state["bomb"][:, None]
            ok = finite_rows(rows)
            emitted = live & ok
            bad = live & ~ok
            # gate idle rows' (stale) temperature to 0 so a retired sampled
            # request can't defeat the all-greedy lax.cond fast path
            temp = jnp.where(emitted, state["temp"], 0.0)
            with jax.named_scope("sample"):
                nxt = sample_tokens(rows, pair[:, 1], temp, state["top_k"],
                                    state["top_p"])
            nxt = jnp.where(emitted, nxt, state["last_tok"])
            # keys advance only on emission, so a request's position in its
            # key chain equals its emitted-token count — slot placement,
            # admission order, KV layout and preemption can't perturb the
            # stream
            key = jnp.where(emitted[:, None], pair[:, 0], state["key"])
            hit_stop = (nxt[:, None] == state["stop"]).any(axis=-1)
            remaining = state["remaining"] - emitted.astype(jnp.int32)
            done_now = emitted & ((remaining <= 0) | hit_stop)
            state = {**state, "last_tok": nxt, "active": emitted & ~done_now,
                     "remaining": remaining, "key": key,
                     "gen": state["gen"] + emitted.astype(jnp.int32)}
            return cache, state, alloc, nxt, emitted, done_now, pre, bad

        self._tick = _tick

        @jax.jit
        def _prefill(params, qweights, cache, table, toks, plen, slot,
                     start_blk):
            """Admit one request: batched prefill into the slot.

            Specializes per padded prompt-bucket shape; ``plen``/``slot``/
            ``start_blk`` are traced, so admissions don't recompile. In the
            paged layout ``table`` is the block table and ``start_blk``
            skips writing a shared prompt prefix. Returns the final prompt
            position's logits row — ``_arm`` samples the first token from
            it, so every admission path shares ONE sampling seam.
            """
            logits, cache = tfm.prefill_slot(
                _qc(qweights), params, toks, plen, cache, slot, cfg,
                plan=plan, block_table=table if paged else None,
                start_blk=start_blk, window=wmask)
            return cache, logits[0, plen - 1, : cfg.vocab_size]

        self._prefill = _prefill

        @jax.jit
        def _prefill_tail(params, qweights, cache, toks, slot):
            """Continue an SSM prefill: absorb the < ssm_chunk remainder in
            one batched forward threading the slot's carried recurrent state
            into the chunked scan (DESIGN.md §8)."""
            logits, cache = tfm.prefill_slot_tail(
                _qc(qweights), params, toks, cache, slot, cfg, plan=plan)
            return cache, logits[0, -1, : cfg.vocab_size]

        self._prefill_tail = _prefill_tail

        @jax.jit
        def _prefill_chunk(params, qweights, cache, table, toks, clen, slot,
                           pos0):
            """One chunk of a chunk-resumable prefill (DESIGN.md §15): run
            ``clen`` prompt tokens (``toks`` may be right-padded to a bucket
            shape) into the slot's KV/state at absolute offset ``pos0``.
            ``clen``/``slot``/``pos0`` are traced, so every chunk of every
            admission shares one compilation per padded shape. Returns the
            chunk's final position's logits row — only the LAST chunk's row
            is consumed (by ``_arm``), keeping the one-sampling-seam
            contract."""
            logits, cache = tfm.prefill_chunk(
                _qc(qweights), params, toks, clen, cache, slot, cfg,
                pos0=pos0, plan=plan,
                block_table=table if paged else None, window=wmask)
            return cache, logits[0, clen - 1, : cfg.vocab_size]

        self._prefill_chunk = _prefill_chunk

        @jax.jit
        def _teacher_step(params, qweights, cache, state, table, tok, slot):
            """Teacher-forced decode of one PROMPT token into one slot.

            Used to replay the sub-block remainder of a prefix-shared
            admission. Only ``slot`` advances (and, paged, only it writes);
            every other row's cache state is untouched, so concurrent slots
            are unaffected. Returns the slot's logits row (consumed only by
            the final replay step, via ``_arm``).
            """
            toks = state["last_tok"].at[slot].set(tok)
            adv = jnp.zeros((slots,), jnp.int32).at[slot].set(1)
            logits, cache = tfm.decode_step(
                _qc(qweights), params, cache, toks, cfg, plan=plan,
                advance=adv, block_table=table if paged else None,
                window=wmask)
            return cache, logits[slot, 0, : cfg.vocab_size]

        self._teacher_step = _teacher_step

        @jax.jit
        def _arm(state, slot, logits_row, temp, top_k, top_p, key, stop_row,
                 max_new, stamp):
            """Arm a slot for generation: lower the request's SamplingParams
            into the slot's state rows and sample its FIRST token from the
            admission logits — the one sampling seam shared by every
            admission path (batched prefill, SSM tail, teacher-forced
            prefix replay). All operands are traced, so admissions with
            different params never recompile. ``ok`` (returned alongside the
            first token, fetched in the same batched admission sync) is the
            §13 non-finite guard on the admission logits: a False row arms
            INACTIVE so retirement can free it without a device round-trip.
            """
            pair = jax.random.split(key)
            ok = jnp.isfinite(logits_row).all()
            first = sample_tokens(logits_row[None], pair[1][None],
                                  temp[None], top_k[None], top_p[None])[0]
            remaining = jnp.asarray(max_new, jnp.int32) - 1
            return {
                "last_tok": state["last_tok"].at[slot].set(first),
                "active": state["active"].at[slot].set(ok & (remaining > 0)),
                "remaining": state["remaining"].at[slot].set(remaining),
                "key": state["key"].at[slot].set(pair[0]),
                "temp": state["temp"].at[slot].set(temp),
                "top_k": state["top_k"].at[slot].set(top_k),
                "top_p": state["top_p"].at[slot].set(top_p),
                "stop": state["stop"].at[slot].set(stop_row),
                "gen": state["gen"].at[slot].set(1),
                "stamp": state["stamp"].at[slot].set(stamp),
                "bomb": state["bomb"].at[slot].set(0.0),
            }, first, ok

        self._arm = _arm

        @jax.jit
        def _rearm(state, slot, last_tok, temp, top_k, top_p, key, stop_row,
                   remaining, gen, stamp):
            """Re-arm a preempted request's slot after its replay (§13): no
            sampling — the resumed stream continues the original key chain
            from ``key`` (recomputed by ``_replay_key``) with ``last_tok``
            = the last token emitted before eviction, so the next tick
            produces exactly the token the unpreempted run would have."""
            return {
                "last_tok": state["last_tok"].at[slot].set(last_tok),
                "active": state["active"].at[slot].set(remaining > 0),
                "remaining": state["remaining"].at[slot].set(remaining),
                "key": state["key"].at[slot].set(key),
                "temp": state["temp"].at[slot].set(temp),
                "top_k": state["top_k"].at[slot].set(top_k),
                "top_p": state["top_p"].at[slot].set(top_p),
                "stop": state["stop"].at[slot].set(stop_row),
                "gen": state["gen"].at[slot].set(gen),
                "stamp": state["stamp"].at[slot].set(stamp),
                "bomb": state["bomb"].at[slot].set(0.0),
            }

        self._rearm = _rearm

        @jax.jit
        def _replay_key(seed, k):
            """The slot key after ``k`` emitted tokens of a request seeded
            with ``seed``: arming splits once and each emission advances
            ``key -> split(key)[0]`` — ``k`` is traced, so resumes at any
            depth share one compilation."""
            key = jax.random.PRNGKey(seed)
            return jax.lax.fori_loop(
                0, k, lambda _, kk: jax.random.split(kk)[0], key)

        self._replay_key = _replay_key

        self._set_bomb = jax.jit(
            lambda state, slot, v:
            {**state, "bomb": state["bomb"].at[slot].set(v)})

        @jax.jit
        def _deactivate(state, slot):
            """Host-side retirement of a slot the device still thinks is
            live (first token hit a stop token): without this the row would
            keep generating — and, paged, keep popping free blocks — after
            its request retired."""
            return {**state,
                    "active": state["active"].at[slot].set(False),
                    "remaining": state["remaining"].at[slot].set(0)}

        self._deactivate = _deactivate

        if self.paged:
            self._alloc_range = jax.jit(kv_pool.alloc_range)
            self._evict_window = jax.jit(kv_pool.evict_out_of_window,
                                         static_argnums=(3,))
            self._share_prefix = jax.jit(kv_pool.share_prefix)
            self._free_slot_op = jax.jit(kv_pool.free_slot)
            self._retain_block = jax.jit(kv_pool.retain_block)
            self._release_block = jax.jit(kv_pool.release_block)
            self._steal = jax.jit(kv_pool.steal_blocks)
            self._unsteal = jax.jit(kv_pool.unsteal_blocks)
            self._set_pos = jax.jit(
                lambda cache, slot, p:
                {**cache, "pos": cache["pos"].at[slot].set(p)})

            @jax.jit
            def _cow(alloc, cache, slot, blk):
                alloc, layers = kv_pool.cow_block(alloc, cache["layers"],
                                                  slot, blk)
                return alloc, {**cache, "layers": layers}

            self._cow = _cow

    # ------------------------------------------------------------------
    def _prefill_shape(self, plen: int) -> tuple[int, int]:
        """(batched-forward length, teacher-forced tail length) per prompt.

        Attention-only archs right-pad to a power-of-two bucket (padding is
        masked, see tfm.prefill_slot). Recurrent state (ssm / rglru) is an
        unconditional scan over every input position with no masking
        analogue, so those archs prefill at the exact prompt length —
        ssd_chunked additionally requires chunk-multiple lengths, so SSM
        prompts run the largest chunk-aligned prefix in the batched forward
        and teacher-force the < chunk remaining tokens through decode steps.
        """
        kinds = list(self.cfg.block_pattern) + list(self.cfg.remainder_kinds)
        if "ssm" in kinds:
            cs = self.cfg.ssm_chunk
            if plen <= cs:
                return plen, 0
            l0 = (plen // cs) * cs
            return l0, plen - l0
        if "recurrent" in kinds:
            return plen, 0
        b = 8
        while b < plen:
            b *= 2
        return min(b, self.max_seq), 0

    def _chunk_len(self, remaining: int) -> int:
        """Length of the next prefill chunk given ``remaining`` prompt
        tokens (DESIGN.md §15). Chunk boundaries are canonical — a function
        of position only, never of budget or pool pressure — so the chunked
        forward's internal groupings (ssd_chunked's chunk scan, the
        recurrent left fold) are identical no matter how ticks interleave.
        SSM archs additionally align every boundary to ``ssm_chunk`` (the
        chunked-scan grouping is length-dependent below that), with the
        < ssm_chunk remainder as the exact-length final chunk."""
        c = min(self.prefill_chunk_tokens, remaining)
        if self._ssm_arch:
            cs = self.cfg.ssm_chunk
            if remaining >= cs:
                c = min(max((c // cs) * cs, cs), (remaining // cs) * cs)
            else:
                c = remaining
        return c

    def _chunk_shape(self, clen: int) -> int:
        """Padded device shape for a ``clen``-token chunk. Attention-only
        archs bucket to a power of two (padding is masked and never
        written); recurrent-state archs scan every input position
        unconditionally, so they run at the exact chunk length."""
        if self._has_state:
            return clen
        b = 8
        while b < clen:
            b *= 2
        return min(b, self.max_seq)

    def _validate_request(self, req: Request):
        """Uniform ValueError at the API boundary (§13): malformed requests
        used to surface as shape errors or silent garbage deep in prefill.
        ``max_new <= 0`` is already rejected by ``SamplingParams`` at
        construction — the remaining holes are all prompt-shaped."""
        if len(req.params.stop) > self.max_stop:
            raise ValueError(
                f"request {req.rid} has {len(req.params.stop)} stop tokens; "
                f"engine holds {self.max_stop} per slot (max_stop=...)")
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(
                f"request {req.rid}: prompt must be a non-empty 1-D token "
                f"sequence (got shape {prompt.shape})")
        if prompt.size > self.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt length {prompt.size} exceeds "
                f"max_seq={self.max_seq}")
        if not np.issubdtype(prompt.dtype, np.integer):
            ids = prompt.astype(np.int64, casting="unsafe")
            if not np.array_equal(ids, prompt):
                raise ValueError(
                    f"request {req.rid}: prompt must hold integer token ids "
                    f"(got dtype {prompt.dtype})")
        vocab = self.cfg.vocab_size
        lo, hi = int(prompt.min()), int(prompt.max())
        if lo < 0 or hi >= vocab:
            raise ValueError(
                f"request {req.rid}: prompt token ids outside [0, {vocab}) "
                f"(min {lo}, max {hi})")

    def _reject(self, req: Request) -> Request:
        req.finish_reason = FINISHED_REJECTED
        req.done = True
        req.finish_s = self._clock()
        self.finished.append(req)
        self.stats["rejected_requests"] += 1
        return req

    def submit(self, req: Request) -> Request:
        """Enqueue one validated request. Under an ``AdmissionConfig`` with
        a full queue this is where backpressure lives (§13): ``reject``
        finishes the request immediately with ``FINISHED_REJECTED``,
        ``block`` drives engine ticks inline until a queue slot frees
        (``evict_lru_prefix`` first drops retained prefix blocks to help
        the pool drain). Returns the request (possibly already done)."""
        with span("engine.submit", rid=req.rid):
            self._validate_request(req)
            req.prompt = np.asarray(req.prompt, np.int32)
            ad = self.admission
            if ad is not None and ad.queue_capacity is not None \
                    and len(self.waiting) >= ad.queue_capacity:
                if ad.on_full == "evict_lru_prefix":
                    self._drop_retained()
                if ad.on_full in ("block", "evict_lru_prefix"):
                    for _ in range(ad.block_max_ticks):
                        if len(self.waiting) < ad.queue_capacity:
                            break
                        self.step()
                if len(self.waiting) >= ad.queue_capacity:
                    return self._reject(req)
            if req.seq is None:
                req.seq = next(self._seq_counter)
            now = self._clock()
            req.submit_s = now
            ttft = req.ttft_deadline_s if req.ttft_deadline_s is not None \
                else (ad.ttft_deadline_s if ad else None)
            wall = req.deadline_s if req.deadline_s is not None \
                else (ad.deadline_s if ad else None)
            req.ttft_by = now + ttft if ttft is not None else math.inf
            req.deadline_by = now + wall if wall is not None else math.inf
            self.waiting.push(req)
            return req

    def _sync(self, tree, kind: str):
        """Host transfer + ledger entry: every ``device_get`` on the serving
        path goes through here, so ``stats["tick_syncs"]`` /
        ``stats["admit_syncs"]`` are an audited count, and the §8/§12
        one-sync-per-tick contract is testable. The ``engine.sync`` span
        (§18) times the wait under the same ``kind``."""
        self.stats[kind + "_syncs"] += 1
        with span("engine.sync", kind=kind):
            return jax.device_get(tree)

    def _bind(self, s: int, req: Request):
        """Bind a request to slot ``s``; the first binding stamps
        ``admit_s`` and ends its queue wait."""
        self.slot_req[s] = req
        if req.admit_s is None:
            req.admit_s = self._clock()
            self.stats["admissions"] += 1
            self.stats["queue_wait_s"] += req.admit_s - req.submit_s

    def _count_prefix(self, req: Request, hits: int, blocks: int):
        """Count an admission's prefix-cache hits and full prompt blocks in
        ``stats`` and the request's own ``prefix_hit_blocks``."""
        self.stats["prefix_hit_blocks"] += hits
        self.stats["prompt_blocks"] += blocks
        req.prefix_hit_blocks += hits

    def _param_rows(self, req: Request):
        """Lower a request's SamplingParams to the traced operands ``_arm``
        writes into the slot's device state rows. The effective seed is
        PINNED on the request at first admission (``seed_used``): a
        seedless request that gets preempted must resume the same key
        chain, not redraw (§13)."""
        p = req.params
        if req.seed_used is None:
            req.seed_used = p.seed if p.seed is not None \
                else int(self._seed_rng.integers(2**31 - 1))
        stop = np.full((self.max_stop,), -1, np.int32)
        stop[: len(p.stop)] = p.stop
        return (jnp.asarray(p.temperature, jnp.float32),
                jnp.asarray(p.top_k, jnp.int32),
                jnp.asarray(p.top_p, jnp.float32),
                jax.random.PRNGKey(req.seed_used),
                jnp.asarray(stop),
                p.max_new)

    # ------------------------------------------------------------------
    # Prefix cache (host side; DESIGN.md §10)
    # ------------------------------------------------------------------

    def _block_keys(self, prompt: np.ndarray):
        """Chain-digest keys for the prompt's FULL blocks: key_j hashes
        key_{j-1} with block j's tokens, so it commits to the entire content
        of blocks 0..j and equal keys imply equal prefixes — at O(1) key
        size and O(plen) total work per admission (a nested-tuple chain
        would re-hash the whole prefix on every map probe).

        §17 sink-block contract: under a windowed engine, sharing and
        registration are restricted to the pinned sink region — sink blocks
        are the only blocks the out-of-window eviction pass can never free,
        so a ``_prefix_map`` entry can't go stale pointing at a recycled
        physical block. (A windowed engine with ``sink_blocks=0`` therefore
        does no prefix sharing at all.)"""
        bs = self.block_size
        nmax = len(prompt) // bs
        if self.window_spec is not None:
            nmax = min(nmax, self.window_spec.sink_blocks)
        keys, h = [], b""
        for j in range(nmax):
            h = hashlib.blake2b(
                h + np.ascontiguousarray(prompt[j * bs:(j + 1) * bs],
                                         np.int32).tobytes(),
                digest_size=16).digest()
            keys.append(h)
        return keys

    def _admit_paged(self, s: int, req: Request, prompt: np.ndarray):
        """Paged admission: map any cached prompt prefix onto its existing
        physical blocks, allocate the rest, and prefill only what the cache
        can't supply. Returns the final prompt position's logits row (the
        caller samples the first token from it via ``_arm``)."""
        plen = len(prompt)
        bs = self.block_size
        nblk = -(-plen // bs)
        fb = plen // bs
        keys = self._block_keys(prompt) if self.prefix_sharing else []
        shared: list[int] = []
        for key in keys:
            if key not in self._prefix_map:
                break
            shared.append(self._prefix_map[key])
        ns = len(shared)
        if ns:
            phys = np.zeros((self.max_blocks,), np.int32)
            phys[:ns] = shared
            self.alloc = self._share_prefix(self.alloc, s,
                                            jnp.asarray(phys), ns)
        if nblk > ns:
            self.alloc = self._alloc_range(self.alloc, s, ns, nblk - ns)

        if ns and ns == fb:
            # Fully cached prompt: NO prefill forward. Teacher-force the sub-
            # block remainder (and at least the final prompt token, which
            # must run to produce the first-token logits). A block-aligned
            # prompt replays its last token INTO the shared final block, so
            # that block is copy-on-write'd to a private one first.
            r = plen - ns * bs
            t0 = ns * bs if r else plen - 1
            kept_keys = keys[:ns]
            if r == 0:
                self.alloc, self.cache = self._cow(self.alloc, self.cache,
                                                   s, fb - 1)
                self.stats["cow_copies"] += 1
                # after CoW this slot no longer maps the registered physical
                # block for the final key — holding it would keep the map
                # entry alive past the block's device refcount reaching 0
                # (a later sharer would then map a freed/recycled block)
                kept_keys = keys[:ns - 1]
            self.cache = self._set_pos(self.cache, s, t0)
            row = None
            for t in prompt[t0:]:
                self.cache, row = self._teacher_step(
                    self.params, self.qweights, self.cache, self.state,
                    self.alloc["table"], jnp.asarray(int(t), jnp.int32), s)
                self.stats["teacher_steps"] += 1
            self.stats["shared_admissions"] += 1
            req.prefix_keys = kept_keys
        else:
            l0, tail = self._prefill_shape(plen)
            # tail > 0 only for hybrid ssm+attention archs (pure-SSM archs
            # take the ring/state layout): the attention layers rule out the
            # state-threaded tail forward, so teacher-force the remainder.
            toks = np.zeros((1, max(l0, plen - tail)), np.int32)
            toks[0, : plen - tail] = prompt[: plen - tail]
            self.cache, row = self._prefill(
                self.params, self.qweights, self.cache,
                self.alloc["table"], jnp.asarray(toks), plen - tail, s, ns)
            self.stats["prefill_forwards"] += 1
            for t in prompt[plen - tail:]:
                self.cache, row = self._teacher_step(
                    self.params, self.qweights, self.cache, self.state,
                    self.alloc["table"], jnp.asarray(int(t), jnp.int32), s)
                self.stats["teacher_steps"] += 1
            if keys:
                self._register_prompt_blocks(s, req, keys)
        for key in req.prefix_keys:
            self._key_refs[key] = self._key_refs.get(key, 0) + 1
        self._touch_lru(keys)
        self._count_prefix(req, ns, fb)
        return row

    def _register_prompt_blocks(self, s: int, req: Request, keys):
        """Register the slot's full prompt blocks for later sharers (the
        table row read is an admission-time sync, not a tick sync) and set
        ``req.prefix_keys`` to the keys whose map entry names this slot's
        own block. A key another request registered first (under chunked
        prefill both can miss the cache before either finishes) names that
        request's block: holding it would keep the entry alive past that
        block's release, and a later sharer would map a freed block."""
        trow = np.asarray(self._sync(self.alloc["table"][s], "admit"))
        held = []
        for j, key in enumerate(keys):
            if key not in self._prefix_map:
                self._prefix_map[key] = int(trow[j])
                if self.lru_capacity > 0:
                    # LRU retention: the cache itself holds a device ref,
                    # so the block outlives its live users
                    self.alloc = self._retain_block(
                        self.alloc, jnp.asarray(int(trow[j]), jnp.int32))
                    self._cache_held.add(key)
            if self._prefix_map[key] == int(trow[j]):
                held.append(key)
        req.prefix_keys = held

    def _admit_ring(self, s: int, req: Request, prompt: np.ndarray):
        """Contiguous-layout admission. SSM prompts run the chunk-aligned
        prefix in one forward, then absorb the < ssm_chunk remainder in a
        SECOND batched forward that threads the slot's recurrent state into
        the chunked scan (``prefill_slot_tail``) — no teacher-forced single
        steps. A hybrid arch mixing recurrent-state and attention blocks
        can't take the tail forward (attention has no carried state to
        resume from), so its tail falls back to teacher-forced steps.
        Returns the final prompt position's logits row."""
        plen = len(prompt)
        l0, tail = self._prefill_shape(plen)
        toks = np.zeros((1, max(l0, plen - tail)), np.int32)
        toks[0, : plen - tail] = prompt[: plen - tail]
        self.cache, row = self._prefill(
            self.params, self.qweights, self.cache, None,
            jnp.asarray(toks), plen - tail, s, 0)
        self.stats["prefill_forwards"] += 1
        if tail and self._state_only:
            tail_toks = np.asarray(prompt[plen - tail:], np.int32)[None, :]
            self.cache, row = self._prefill_tail(
                self.params, self.qweights, self.cache,
                jnp.asarray(tail_toks), s)
            self.stats["tail_forwards"] += 1
        elif tail:
            for t in prompt[plen - tail:]:
                self.cache, row = self._teacher_step(
                    self.params, self.qweights, self.cache, self.state,
                    None, jnp.asarray(int(t), jnp.int32), s)
                self.stats["teacher_steps"] += 1
        return row

    # ------------------------------------------------------------------
    # Prefix-cache LRU retention (DESIGN.md §10)
    # ------------------------------------------------------------------

    def _touch_lru(self, keys):
        """Re-derive LRU membership for ``keys``: cache-held keys with zero
        live users sit in the LRU (most-recently-touched last); any live use
        lifts them out. Then evict past capacity (oldest first), dropping
        the cache's device ref — the only place retained blocks are
        released, so capacity bounds cache-only blocks and the pool surplus
        covers them."""
        for key in keys:
            if key not in self._cache_held:
                continue
            if self._key_refs.get(key, 0) == 0:
                self._lru[key] = self._prefix_map[key]
                self._lru.move_to_end(key)
            else:
                self._lru.pop(key, None)
        while len(self._lru) > self.lru_capacity:
            key, blk = self._lru.popitem(last=False)
            self._cache_held.discard(key)
            self._prefix_map.pop(key, None)
            self.alloc = self._release_block(self.alloc,
                                             jnp.asarray(blk, jnp.int32))

    def _drop_prefix_refs(self, req: Request):
        """Release the host side of a request's hold on its prefix keys
        (shared by retirement and preemption)."""
        for key in req.prefix_keys:
            self._key_refs[key] -= 1
            if self._key_refs[key] == 0:
                del self._key_refs[key]
                if key not in self._cache_held:
                    self._prefix_map.pop(key, None)
        self._touch_lru(req.prefix_keys)

    def _drop_retained(self):
        """Evict the entire retained-prefix LRU (the ``evict_lru_prefix``
        on-full policy): every cache-only block goes back on the free
        stack, trading prefix hits for pool headroom."""
        while self._lru:
            key, blk = self._lru.popitem(last=False)
            self._cache_held.discard(key)
            self._prefix_map.pop(key, None)
            self.alloc = self._release_block(self.alloc,
                                             jnp.asarray(blk, jnp.int32))

    def _retire(self, s: int, req: Request):
        req.done = True
        req.finish_s = self._clock()
        self.finished.append(req)
        self.slot_req[s] = None
        self._pending.pop(s, None)
        if self.paged:
            self.alloc = self._free_slot_op(self.alloc, s)
            self._drop_prefix_refs(req)

    def _requeue_slot(self, s: int, *, blocks_freed: bool):
        """Host side of a preemption (§13): detach the victim request from
        its slot and put it back on the waiting queue with its original
        submission seq (so re-admission sorts ahead of newer arrivals).
        ``blocks_freed`` says whether the device already freed the slot's
        blocks (the in-tick path did; host-side ``preempt()`` hasn't).
        Returns a terminal TokenEvent if resuming is impossible."""
        req = self.slot_req[s]
        self.slot_req[s] = None
        self._pending.pop(s, None)
        if self.paged:
            if not blocks_freed:
                self.alloc = self._free_slot_op(self.alloc, s)
            self._drop_prefix_refs(req)
            req.prefix_keys = []
        req.preemptions += 1
        self.stats["preemptions"] += 1
        # resume replays prompt + output[:-1] into one slot, so it must fit
        # a slot's cache; a request oversubscribed past max_seq can't be
        # replayed (the unpreempted run would have overrun its row too)
        if len(req.prompt) + len(req.output) - 1 > self.max_seq:
            req.finish_reason = FINISHED_ERROR
            req.done = True
            req.finish_s = self._clock()
            self.finished.append(req)
            return TokenEvent(rid=req.rid, token=-1, index=len(req.output),
                              done=True, finish_reason=FINISHED_ERROR)
        self.waiting.push(req)
        return None

    def preempt(self, slot: int):
        """Forcibly preempt one running slot from the host (both KV
        layouts): deadline policy and the fault injector use this; the
        in-tick exhaustion path never does (it frees blocks on device
        inside the tick). The request re-queues and resumes normally."""
        if self.slot_req[slot] is None:
            return None
        self.state = self._deactivate(self.state, slot)
        return self._requeue_slot(slot, blocks_freed=False)

    def _expire_deadlines(self) -> list:
        """Expire waiting requests past their TTFT/wall budget and running
        requests past their wall deadline (§13). Host-side bookkeeping
        only — no device sync; an expiry surfaces as a terminal
        ``TokenEvent`` with the ``-1`` sentinel token."""
        now = self._clock()
        events = []
        for req in self.waiting.expired(now):
            self.waiting.remove(req)
            req.finish_reason = FINISHED_DEADLINE
            req.done = True
            req.finish_s = now
            self.finished.append(req)
            self.stats["deadline_expired"] += 1
            events.append(TokenEvent(rid=req.rid, token=-1,
                                     index=len(req.output), done=True,
                                     finish_reason=FINISHED_DEADLINE))
        for s, req in enumerate(self.slot_req):
            if req is None:
                continue
            # a PREFILLING slot has emitted nothing yet, so its TTFT budget
            # still applies (same rule the waiting queue uses); armed slots
            # only answer to the wall deadline
            by = req.deadline_key if s in self._pending else req.deadline_by
            if by > now:
                continue
            self.state = self._deactivate(self.state, s)
            req.finish_reason = FINISHED_DEADLINE
            self._retire(s, req)
            self.stats["deadline_expired"] += 1
            events.append(TokenEvent(rid=req.rid, token=-1,
                                     index=len(req.output), done=True,
                                     finish_reason=FINISHED_DEADLINE))
        return events

    def _can_start(self, req: Request) -> bool:
        """Watermark + free-stack gate on starting a prefill (§13).

        The watermark is pure host arithmetic over worst-case projections.
        On an oversubscribed (preemption-enabled) pool there is a second,
        exact check: the admission-time fills (``alloc_range`` / CoW) have
        no in-tick preemption to save them, so the replay's immediate block
        demand must fit the actual free stack — that read is a small
        admission-time sync, ledgered under ``admit_syncs``, and only ever
        paid by engines that chose to oversubscribe."""
        if not self.paged:
            return True
        ad = self.admission
        # §17: a windowed engine's worst-case residency per slot is the
        # window demand (live-window + sink + one-chunk blocks), not the
        # full sequence — the in-tick eviction pass keeps every slot at or
        # below it, so both the watermark projection and the exact
        # free-stack check cap at ``self._slot_demand``.
        nblk = -(-(len(req.prompt) + max(len(req.output) - 1, 0))
                 // self.block_size)
        nblk = min(nblk, self._slot_demand)
        if ad is not None and ad.watermark is not None:
            usable = (self.num_blocks - 1 - len(self._lru)
                      - ad.reserve_blocks)
            committed = sum(
                projected_blocks(len(r.prompt), r.max_new, self.block_size,
                                 self.max_blocks,
                                 window_blocks=self._slot_demand)
                for r in self.slot_req if r is not None)
            mine = projected_blocks(len(req.prompt), req.max_new,
                                    self.block_size, self.max_blocks,
                                    window_blocks=self._slot_demand)
            if committed + mine > ad.watermark * usable:
                return False
        if self.preemption:
            n_free = int(self._sync(self.alloc["n_free"], "admit"))
            if nblk > n_free:
                return False
        return True

    def _rearm_slot(self, s: int, req: Request, k: int):
        """Restore a resumed request's sampling state after its replay
        prefill (§13): no new sample — the key chain continues from depth
        ``k`` (= emitted tokens) and ``last_tok`` is the last pre-eviction
        emission, so the next tick produces exactly the token the
        unpreempted run would have."""
        rows = self._param_rows(req)
        self.state = self._rearm(
            self.state, s, jnp.asarray(req.output[-1], jnp.int32),
            rows[0], rows[1], rows[2],
            self._replay_key(jnp.asarray(req.seed_used, jnp.uint32),
                             jnp.asarray(k, jnp.int32)),
            rows[4], jnp.asarray(req.max_new - k, jnp.int32),
            jnp.asarray(k, jnp.int32),
            jnp.asarray(next(self._stamp_counter), jnp.int32))
        self.stats["resumed_admissions"] += 1

    def _post_arm(self, admitted) -> list:
        """Host side of arming: ONE batched transfer for the wave's first
        tokens (the §13 non-finite flags ride in the same transfer), then
        the shared bookkeeping — first-token SLO stamp, stop-at-first /
        max_new=1 retirement, TokenEvents. Shared by the wave and
        continuous schedulers so their per-request semantics can't drift."""
        events = []
        firsts = self._sync([(f, o) for _, _, f, o in admitted], "admit") \
            if admitted else []
        now = self._clock()
        for (s, req, _, _), (first, ok) in zip(admitted, firsts):
            if not bool(ok):
                # admission logits went non-finite: the row armed inactive,
                # so retirement just frees it; nothing was emitted
                req.finish_reason = FINISHED_ERROR
                self.stats["nan_failures"] += 1
                self._retire(s, req)
                events.append(TokenEvent(rid=req.rid, token=-1, index=0,
                                         done=True,
                                         finish_reason=FINISHED_ERROR))
                continue
            tok = int(first)
            req.output.append(tok)
            if req.first_token_s is None:
                req.first_token_s = now
            self.stats["generated_tokens"] += 1
            stopped = tok in req.params.stop
            if stopped or req.max_new <= 1:
                req.finish_reason = FINISHED_STOP if stopped \
                    else FINISHED_LENGTH
                if stopped and req.max_new > 1:
                    # the device armed the row for more tokens — shut it
                    # down before retirement frees its blocks
                    self.state = self._deactivate(self.state, s)
                self._retire(s, req)
            events.append(TokenEvent(rid=req.rid, token=tok,
                                     index=len(req.output) - 1,
                                     done=req.done,
                                     finish_reason=req.finish_reason))
        return events

    def _admit(self):
        if self.prefill_chunk_tokens is not None:
            return self._admit_continuous()
        return self._admit_wave()

    def _admit_wave(self):
        t0 = time.perf_counter()
        admitted = []
        resumed = 0
        for s in range(self.slots):
            if self.slot_req[s] is not None:
                continue
            req = self.waiting.peek()
            if req is None:
                break
            if not self._can_start(req):
                # head-of-line hold: later (possibly smaller) requests do
                # NOT jump the queue — that's the no-starvation guarantee
                break
            self.waiting.pop()
            self._bind(s, req)
            prompt = np.asarray(req.prompt, np.int32)
            rows = self._param_rows(req)
            if req.output:
                # resume after preemption: replay prompt + generated tokens
                # through the ordinary admission path (prefix sharing and
                # all), then restore the sampling state — NO new sample
                replay = np.concatenate(
                    [prompt, np.asarray(req.output[:-1], np.int32)]) \
                    if len(req.output) > 1 else prompt
                if self.paged:
                    self._admit_paged(s, req, replay)
                else:
                    self._admit_ring(s, req, replay)
                self._rearm_slot(s, req, len(req.output))
                resumed += 1
                self.stats["prompt_tokens"] += len(replay)
                self.stats["seed_equiv_forwards"] += len(replay)
            else:
                if self.paged:
                    row = self._admit_paged(s, req, prompt)
                else:
                    row = self._admit_ring(s, req, prompt)
                self.state, first, ok = self._arm(
                    self.state, s, row, *rows,
                    jnp.asarray(next(self._stamp_counter), jnp.int32))
                self.stats["prompt_tokens"] += len(prompt)
                self.stats["seed_equiv_forwards"] += len(prompt)
                admitted.append((s, req, first, ok))
        events = self._post_arm(admitted)
        if admitted or resumed:
            self.stats["prefill_time_s"] += time.perf_counter() - t0
        return events

    # ------------------------------------------------------------------
    # Continuous batching: chunked prefill interleaved with decode
    # (DESIGN.md §15)
    # ------------------------------------------------------------------

    def _begin_prefill(self, s: int, req: Request):
        """Bind a request to a free slot as PREFILLING: build its pending
        record and, in the paged layout, map any cached prompt prefix onto
        its existing physical blocks (the chunked prefill then starts AFTER
        the shared region — it neither recomputes nor rewrites shared
        blocks). A fully cached prompt short-circuits exactly like the wave
        scheduler: teacher-force the sub-block remainder now and leave the
        record complete, so the next ``_prefill_tick`` pass arms it without
        spending any chunk budget."""
        prompt = np.asarray(req.prompt, np.int32)
        resume = bool(req.output)
        toks = prompt
        if resume and len(req.output) > 1:
            toks = np.concatenate(
                [prompt, np.asarray(req.output[:-1], np.int32)])
        st = {"req": req, "toks": toks, "resume": resume, "pos": 0,
              "blocks": 0, "ns": 0, "keys": [], "row": None,
              "registered": not self.paged,
              "order": next(self._stamp_counter)}
        self._pending[s] = st
        if not self.paged:
            return
        plen = len(toks)
        bs = self.block_size
        fb = plen // bs
        keys = self._block_keys(toks) if self.prefix_sharing else []
        st["keys"] = keys
        shared: list[int] = []
        for key in keys:
            if key not in self._prefix_map:
                break
            shared.append(self._prefix_map[key])
        ns = len(shared)
        st["ns"] = ns
        if ns:
            phys = np.zeros((self.max_blocks,), np.int32)
            phys[:ns] = shared
            self.alloc = self._share_prefix(self.alloc, s,
                                            jnp.asarray(phys), ns)
            st["blocks"] = ns
            st["pos"] = ns * bs
        if ns and ns == fb:
            # fully cached: same CoW / teacher-force path as _admit_paged
            r = plen - ns * bs
            t0 = ns * bs if r else plen - 1
            kept_keys = keys[:ns]
            if r == 0:
                self.alloc, self.cache = self._cow(self.alloc, self.cache,
                                                   s, fb - 1)
                self.stats["cow_copies"] += 1
                kept_keys = keys[:ns - 1]
            self.cache = self._set_pos(self.cache, s, t0)
            row = None
            for t in toks[t0:]:
                self.cache, row = self._teacher_step(
                    self.params, self.qweights, self.cache, self.state,
                    self.alloc["table"], jnp.asarray(int(t), jnp.int32), s)
                self.stats["teacher_steps"] += 1
            self.stats["shared_admissions"] += 1
            req.prefix_keys = kept_keys
            for key in kept_keys:
                self._key_refs[key] = self._key_refs.get(key, 0) + 1
            self._touch_lru(keys)
            self._count_prefix(req, ns, fb)
            st.update(pos=plen, row=row, registered=True)

    def _finish_prefill(self, s: int, st: dict):
        """The slot's last chunk has run: register its prompt blocks in the
        prefix map (paged, one admission-time sync for the table row), then
        arm the device row. Fresh requests return an ``(s, req, first, ok)``
        arm record for the batched ``_post_arm`` sync; resumes re-arm with
        no sample and return None."""
        req = st["req"]
        del self._pending[s]
        if self.paged and not st["registered"]:
            keys, ns = st["keys"], st["ns"]
            if keys:
                self._register_prompt_blocks(s, req, keys)
                for key in req.prefix_keys:
                    self._key_refs[key] = self._key_refs.get(key, 0) + 1
                self._touch_lru(keys)
            self._count_prefix(req, st["ns"],
                               len(st["toks"]) // self.block_size)
        total = len(st["toks"])
        self.stats["prompt_tokens"] += total
        self.stats["seed_equiv_forwards"] += total
        if st["resume"]:
            self._rearm_slot(s, req, len(req.output))
            return None
        rows = self._param_rows(req)
        self.state, first, ok = self._arm(
            self.state, s, st["row"], *rows,
            jnp.asarray(next(self._stamp_counter), jnp.int32))
        return (s, req, first, ok)

    def _prefill_tick(self):
        """Spend this tick's token budget on pending prefills, oldest bind
        first. The budget gates STARTING a chunk (chunk boundaries are
        canonical — see ``_chunk_len`` — so a budget can't reshape them);
        slots whose incremental block allocation would overdraw an
        oversubscribed pool skip this tick instead of corrupting the free
        stack. Completed prefills arm; returns (armed records, whether any
        chunk ran, whether any slot is blocked on blocks)."""
        budget = self.tick_token_budget
        armed, ran, blocked = [], False, False
        for s in sorted(self._pending,
                        key=lambda i: self._pending[i]["order"]):
            st = self._pending[s]
            total = len(st["toks"])
            while st["pos"] < total:
                if budget is not None and budget <= 0:
                    break
                c = self._chunk_len(total - st["pos"])
                if self.paged and self.window_spec is not None:
                    # §17 between-chunk eviction: before drawing blocks for
                    # the next chunk, release this slot's blocks that the
                    # window can no longer reach (queries resume at
                    # st["pos"]). This is what bounds a long prompt's
                    # residency to window + chunk blocks on a window-sized
                    # pool. st["blocks"] stays the logical high-water count:
                    # alloc_range keeps appending at fresh logical indices.
                    w, sink_t = self._window
                    sb = sink_t // self.block_size
                    fl = max((st["pos"] - w + 1) // self.block_size, sb)
                    if fl > sb:
                        one = jnp.zeros((self.slots,), bool).at[s].set(True)
                        flv = jnp.zeros((self.slots,),
                                        jnp.int32).at[s].set(fl)
                        self.alloc = self._evict_window(
                            self.alloc, flv, one, sb)
                if self.paged:
                    need = -(-(st["pos"] + c) // self.block_size) \
                        - st["blocks"]
                    if need > 0 and self.preemption:
                        n_free = int(self._sync(self.alloc["n_free"],
                                                "admit"))
                        if need > n_free:
                            blocked = True
                            break
                    if need > 0:
                        self.alloc = self._alloc_range(
                            self.alloc, s, st["blocks"], need)
                        st["blocks"] += need
                pad = self._chunk_shape(c)
                toks = np.zeros((1, pad), np.int32)
                toks[0, :c] = st["toks"][st["pos"]:st["pos"] + c]
                with span("engine.prefill_chunk", rid=st["req"].rid, slot=s,
                          tokens=c):
                    self.cache, st["row"] = self._prefill_chunk(
                        self.params, self.qweights, self.cache,
                        self.alloc["table"] if self.paged else None,
                        jnp.asarray(toks), jnp.asarray(c, jnp.int32),
                        jnp.asarray(s, jnp.int32),
                        jnp.asarray(st["pos"], jnp.int32))
                st["pos"] += c
                self.stats["prefill_chunks"] += 1
                self.stats["prefill_forwards"] += 1
                if budget is not None:
                    budget -= c
                ran = True
            if st["pos"] >= total:
                rec = self._finish_prefill(s, st)
                if rec is not None:
                    armed.append(rec)
        return armed, ran, blocked

    def _admit_continuous(self):
        """Continuous admission (DESIGN.md §15): bind waiting requests to
        free slots the moment watermark + free stack allow, then advance
        every PREFILLING slot by up to ``tick_token_budget`` prompt tokens.
        Unlike the wave scheduler there is no admission barrier — new
        requests join while others decode, and a long prompt holds the tick
        for at most one chunk forward."""
        t0 = time.perf_counter()
        bound = False
        for s in range(self.slots):
            if self.slot_req[s] is not None:
                continue
            req = self.waiting.peek()
            if req is None:
                break
            if not self._can_start(req):
                # head-of-line hold, same no-starvation rule as the wave
                break
            self.waiting.pop()
            self._bind(s, req)
            self._begin_prefill(s, req)
            bound = True
        armed, ran, blocked = self._prefill_tick()
        events = self._post_arm(armed)
        if blocked and not ran and not armed and len(self._pending) > 1 \
                and not any(r is not None and s not in self._pending
                            for s, r in enumerate(self.slot_req)):
            # Deadlock breaker for an oversubscribed pool: every live slot
            # is PREFILLING, none could place a chunk, and no decoder is
            # left to retire or preempt — release the youngest binding's
            # partial blocks back to the pool. The oldest survivor then
            # always completes: _can_start admitted it against the full
            # free stack and only younger bindings have drawn from it since.
            victim = max(self._pending,
                         key=lambda i: self._pending[i]["order"])
            ev = self._requeue_slot(victim, blocks_freed=False)
            if ev is not None:
                events.append(ev)
        if bound or ran or armed:
            self.stats["prefill_time_s"] += time.perf_counter() - t0
        return events

    def step(self) -> list:
        """One engine tick: expire deadlines, admit, decode the running
        batch, retire.

        Returns the tick's ``TokenEvent`` list — admission first-tokens plus
        one decode emission per active slot; empty when there was nothing to
        run (so the pre-§12 boolean use keeps working). Stop-token hits
        retire — and, paged, free their KV blocks — inside this same call;
        so do §13 preemptions (victim re-queued, blocks already freed
        in-tick) and non-finite-logit failures (victim retired with
        ``FINISHED_ERROR``, the rest of the batch unaffected). Its phases
        run under ``engine.*`` spans inside ``engine.step`` (§18).
        """
        with span("engine.step"):
            with span("engine.expire"):
                events = self._expire_deadlines()
            with span("engine.admit"):
                events += self._admit()
            # nothing ARMED -> no decode tick: PREFILLING slots (continuous
            # scheduler) hold inactive device rows and only consume admission
            # work until their final chunk arms them
            if not any(r is not None and s not in self._pending
                       for s, r in enumerate(self.slot_req)):
                return events
            t0 = time.perf_counter()
            with span("engine.tick"):
                (self.cache, self.state, self.alloc, nxt, emitted, done, pre,
                 bad) = self._tick(
                    self.params, self.qweights, self.cache, self.state,
                    self.alloc)
            # The one host sync of the tick: five (slots,)-sized vectors.
            nxt, emitted, done, pre, bad = map(
                np.asarray, self._sync((nxt, emitted, done, pre, bad), "tick"))
            self.stats["decode_time_s"] += time.perf_counter() - t0
            self.stats["decode_ticks"] += 1
            with span("engine.emit"):
                self._emit(events, nxt, emitted, done, pre, bad)
            return events

    def _emit(self, events: list, nxt, emitted, done, pre, bad) -> None:
        """Host side of a synced tick: requeue preemption victims, retire
        non-finite rows, append each emitted token and retire finished
        requests, adding their ``TokenEvent``s to ``events``."""
        for s in np.flatnonzero(pre):
            ev = self._requeue_slot(int(s), blocks_freed=True)
            if ev is not None:
                events.append(ev)
        for s in np.flatnonzero(bad):
            req = self.slot_req[int(s)]
            req.finish_reason = FINISHED_ERROR
            self.stats["nan_failures"] += 1
            self._retire(int(s), req)
            events.append(TokenEvent(rid=req.rid, token=-1,
                                     index=len(req.output), done=True,
                                     finish_reason=FINISHED_ERROR))
        for s, req in enumerate(self.slot_req):
            if req is None or not emitted[s]:
                continue
            tok = int(nxt[s])
            req.output.append(tok)
            self.stats["generated_tokens"] += 1
            if done[s]:
                req.finish_reason = (FINISHED_STOP if tok in req.params.stop
                                     else FINISHED_LENGTH)
                self._retire(s, req)
            events.append(TokenEvent(rid=req.rid, token=tok,
                                     index=len(req.output) - 1,
                                     done=req.done,
                                     finish_reason=req.finish_reason))

    # ------------------------------------------------------------------
    # Fault-injection seams (serving/faults.py drives these; DESIGN.md §13)
    # ------------------------------------------------------------------

    def inject_logit_fault(self, slot: int, value: float = float("nan")):
        """Add ``value`` to every logit of one slot from its next tick on
        (cleared when the slot is re-armed). ``nan``/``inf`` exercise the
        non-finite guard; finite values model a mild numeric skew."""
        self.state = self._set_bomb(self.state, slot,
                                    jnp.asarray(value, jnp.float32))

    def drain_free_blocks(self, leave: int = 0) -> int:
        """Steal the pool's free blocks (all but ``leave``) under an
        external reference, forcing the next allocating tick into the
        exhaustion path. Meant for preemption-enabled engines — a
        fully-provisioned pool has no recovery branch to steal from.
        Returns the number taken; ``restore_free_blocks`` gives them back.
        """
        assert self.paged, "no pool to drain in the ring layout"
        n_free = int(self._sync(self.alloc["n_free"], "stat"))
        n = max(n_free - leave, 0)
        if n:
            self.alloc, ids = self._steal(self.alloc,
                                          jnp.asarray(n, jnp.int32))
            self._stolen.append(ids)
        return n

    def restore_free_blocks(self):
        """Return every block taken by ``drain_free_blocks``."""
        while self._stolen:
            self.alloc = self._unsteal(self.alloc, self._stolen.pop())

    # ------------------------------------------------------------------
    # Request-lifecycle facade (DESIGN.md §12)
    # ------------------------------------------------------------------

    def _submit_batch(self, prompts: Sequence,
                      params: SamplingParams | Sequence | None):
        if params is None or isinstance(params, SamplingParams):
            plist = [params or SamplingParams()] * len(prompts)
        else:
            plist = list(params)
            if len(plist) != len(prompts):
                raise ValueError(f"{len(prompts)} prompts but "
                                 f"{len(plist)} SamplingParams")
        # build and validate the WHOLE batch before the first submit: a bad
        # member must not leave earlier ones orphaned in the waiting queue
        # of a call that raised
        reqs = []
        for prompt, p in zip(prompts, plist):
            req = Request(rid=next(self._auto_rid),
                          prompt=np.asarray(prompt), params=p)
            self._validate_request(req)
            reqs.append(req)
        for req in reqs:
            self.submit(req)
        return reqs

    def _result(self, req: Request) -> GenerationResult:
        return GenerationResult(rid=req.rid, prompt=req.prompt,
                                tokens=list(req.output),
                                finish_reason=req.finish_reason or "length",
                                params=req.params)

    def generate(self, prompts: Sequence,
                 params: SamplingParams | Sequence | None = None, *,
                 on_token: Callable | None = None,
                 max_ticks: int = 100_000) -> list:
        """Serve a batch of prompts to completion.

        ``prompts``: token-id sequences; ``params``: one ``SamplingParams``
        for all of them, a per-prompt sequence, or ``None`` for greedy
        defaults. Drives the engine (other outstanding requests ride along)
        until every prompt of THIS batch finishes and returns their
        ``GenerationResult``s in prompt order. ``on_token`` — called with
        each of this batch's ``TokenEvent``s as it is emitted — is the
        callback form of ``generate_stream``.
        """
        reqs = self._submit_batch(prompts, params)
        mine = {r.rid for r in reqs}
        for _ in range(max_ticks):
            if all(r.done for r in reqs):
                break
            for ev in self.step():
                if on_token is not None and ev.rid in mine:
                    on_token(ev)
        if not all(r.done for r in reqs):
            raise RuntimeError(f"generate() still running after "
                               f"{max_ticks} ticks")
        return [self._result(r) for r in reqs]

    def generate_stream(self, prompts: Sequence,
                        params: SamplingParams | Sequence | None = None, *,
                        max_ticks: int = 100_000) -> Iterator[TokenEvent]:
        """Streaming form of ``generate``: yields this batch's per-tick
        ``TokenEvent`` deltas (one per request per tick, admission tokens
        included) as they are emitted; each request's final event carries
        ``done=True`` and its ``finish_reason``. The batch is submitted
        EAGERLY — before the returned iterator is first advanced — so other
        engine traffic can pick the requests up either way."""
        reqs = self._submit_batch(prompts, params)
        mine = {r.rid for r in reqs}

        def _events():
            # requests finished AT submit (queue-capacity rejection) never
            # reach a tick: surface their terminal event here
            for r in reqs:
                if r.done:
                    yield TokenEvent(rid=r.rid, token=-1,
                                     index=len(r.output), done=True,
                                     finish_reason=r.finish_reason)
            for _ in range(max_ticks):
                if all(r.done for r in reqs):
                    return
                for ev in self.step():
                    if ev.rid in mine:
                        yield ev
            if not all(r.done for r in reqs):
                raise RuntimeError(f"generate_stream() still running after "
                                   f"{max_ticks} ticks")

        return _events()

    def slo_stats(self) -> dict:
        """Per-request latency summary over every finished request
        (DESIGN.md §15), measured against the engine's injectable clock:

          * TTFT — ``first_token_s - submit_s``, each request's OWN arrival
            stamp (not engine start), so percentiles are meaningful under
            ragged admission;
          * TPOT — ``(finish_s - first_token_s) / (len(output) - 1)``,
            requests with >= 2 output tokens only.

        Host arithmetic over stamps already taken on the serving path —
        calling this costs zero device syncs."""
        done = self.finished
        ttft = [r.first_token_s - r.submit_s for r in done
                if r.first_token_s is not None]
        tpot = [(r.finish_s - r.first_token_s) / (len(r.output) - 1)
                for r in done
                if r.first_token_s is not None and r.finish_s is not None
                and len(r.output) > 1]
        return {"requests": len(done),
                "ttft_s": latency_percentiles(ttft),
                "tpot_s": latency_percentiles(tpot)}

    def pool_stats(self) -> dict:
        """Paged-pool occupancy snapshot (one small host sync, ledgered as
        ``stat_syncs``; benchmarking only — never called on the tick
        path)."""
        if not self.paged:
            return {}
        n_free = int(self._sync(self.alloc["n_free"], "stat"))
        hits, total = self.stats["prefix_hit_blocks"], self.stats[
            "prompt_blocks"]
        out = {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "blocks_in_use": self.num_blocks - 1 - n_free,
            "retained_blocks": len(self._lru),
            "prefix_hit_rate": hits / total if total else 0.0,
        }
        if self.window_spec is not None:
            out["window"] = window_report(self.window_spec, self.max_blocks,
                                          self.block_size)
        return out

    def _assert_kv_contract(self):
        """The §10/§14 storage contract, asserted at construction: every
        attention cache entry holds exactly the declared dtype — the float
        store for bf16/fp32, or codes + fp16 scales for int8/int4."""
        for entry in jax.tree.leaves(
                self.cache["layers"], is_leaf=lambda e: isinstance(e, dict)):
            if not (isinstance(entry, dict) and "k" in entry
                    and "v" in entry):
                continue  # recurrent state rows
            if self.kv_spec is not None:
                assert "k_scale" in entry, "quantized cache missing scales"
                assert entry["k"].dtype == self.kv_spec.code_dtype, (
                    entry["k"].dtype, self.kv_spec)
                assert entry["k_scale"].dtype == jnp.dtype(
                    self.kv_spec.scale_dtype), entry["k_scale"].dtype
            else:
                assert entry["k"].dtype == jnp.dtype(self._kv_store), (
                    entry["k"].dtype, self._kv_store)

    def _expanded_kinds(self) -> list[str]:
        pat = list(self.cfg.block_pattern)
        return (pat * self.cfg.pattern_repeats
                + list(self.cfg.remainder_kinds))

    def kv_report(self) -> dict:
        """KV-cache footprint section (DESIGN.md §14): bytes per cached
        token per attention layer — codes + affine aux under ceil-packed
        accounting — against bf16 and fp32 pools of the same geometry.
        Works for float-weight engines too (no export required)."""
        return kv_cache_report(self._expanded_kinds(), self.cfg.n_kv_heads,
                               self.cfg.head_dim, spec=self.kv_spec,
                               dtype=self._kv_store, kv_dtype=self.kv_dtype)

    def quant_report(self) -> dict:
        """Bytes/BOPs ledger of the served artifact (DESIGN.md §11):
        per-site packed device bytes and model BOPs vs the fp32 and
        uniform-int8 baselines, plus the §14 KV-cache section. Requires an
        int export (use ``kv_report`` alone for float-weight engines)."""
        assert self.export_ledger is not None, "no quantized export to report"
        gates = self.quant_state["gates"]
        if self.act_specs:
            # Fold the SERVED activation widths into the certificate: each
            # ``.in`` spec contributes a per-tensor gate at the level whose
            # T(g) is exactly its bit-width, so ``model_bop`` certifies
            # true w_bits × a_bits × MACs compute (DESIGN.md §16).
            gates = dict(gates)
            for key, spec in self.act_specs.items():
                gates[key] = jnp.asarray(ACT_GATE_LEVELS[int(spec.bits)],
                                         jnp.float32)
        return quant_report(self.export_ledger, gates, kv=self.kv_report())

    def run_to_completion(self, max_ticks: int = 1000):
        ticks = 0
        while (self.waiting or any(r is not None for r in self.slot_req)) \
                and ticks < max_ticks:
            self.step()
            ticks += 1
        return self.finished
