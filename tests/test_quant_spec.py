"""QuantSpec / packing / export-ledger tests (DESIGN.md §11).

Property tests (hypothesis, deterministic-replay fallback shim without it):
  * pack/unpack round-trip at 2/4 bits — odd K, ragged groups, leading
    stack dims, signed/unsigned code ranges, byte-count accounting;
  * QuantizedTensor grid: dequantize lands on the Eq. 1 quantizer grid and
    packed codes equal the unpacked int8 layout bit-for-bit.

Plus direct tests for the gate→bits→storage-class constructor, the export
ledger (fallback visibility), the bytes/BOPs report, and the LeNet export
path sharing the same machinery.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # container without hypothesis: deterministic replay
    from _hyp_fallback import given, settings
    from _hyp_fallback import strategies as st

from repro.core.quantizer import quantize
from repro.quant import (QuantSpec, QuantizedTensor, pack_codes,
                         quant_report, specs_from_state, unpack_codes)
from repro.quant.pack import CODES_PER_BYTE, packed_rows
from repro.quant.spec import storage_class_for


# ---------------------------------------------------------------------------
# pack/unpack round-trip properties
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(k=st.integers(min_value=1, max_value=41),
       n=st.integers(min_value=1, max_value=9),
       bits=st.sampled_from([2, 4]),
       stacked=st.booleans(),
       unsigned_rng=st.booleans(),
       seed=st.integers(min_value=0, max_value=2**16))
def test_pack_unpack_roundtrip(k, n, bits, stacked, unsigned_rng, seed):
    """unpack(pack(c)) == c for every K (odd/ragged included), every stack
    layout, and both halves of the signed code range; the packed array is
    uint8 with exactly ceil(K/per) rows."""
    rng = np.random.default_rng(seed)
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    if unsigned_rng:      # unsigned grids center into the non-negative half
        lo = 0
    shape = ((3, k, n) if stacked else (k, n))
    codes = jnp.asarray(rng.integers(lo, hi + 1, shape), jnp.int8)
    packed = pack_codes(codes, bits)
    assert packed.dtype == jnp.uint8
    assert packed.shape[-2] == packed_rows(k, bits) == -(-k // (8 // bits))
    assert packed.shape[:-2] == codes.shape[:-2]
    out = unpack_codes(packed, bits, k)
    assert out.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(out), np.asarray(codes))


@settings(max_examples=25, deadline=None)
@given(k=st.integers(min_value=1, max_value=33),
       n=st.integers(min_value=1, max_value=8),
       storage=st.sampled_from([2, 4, 8]),
       signed=st.booleans(),
       seed=st.integers(min_value=0, max_value=2**16))
def test_quantized_tensor_grid_and_packing_lossless(k, n, storage, signed,
                                                    seed):
    """from_float at mixed per-channel bits: dequantize() agrees with the
    Eq. 1 quantizer grid, and the packed layout carries the SAME codes as
    the pack=False int8 oracle layout — packing is pure storage."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    levels = [b for b in (2, 4, 8) if b <= storage]
    bits = jnp.asarray(rng.choice(levels, size=(n,)).astype(np.float32))
    beta = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-3)
    qt = QuantizedTensor.from_float(w, bits[None, :], beta[None, :], signed,
                                    storage_bits=storage)
    oracle = QuantizedTensor.from_float(w, bits[None, :], beta[None, :],
                                        signed, storage_bits=storage,
                                        pack=False)
    np.testing.assert_array_equal(np.asarray(qt.int8_codes()),
                                  np.asarray(oracle.codes))
    fq = quantize(w, bits[None, :], beta[None, :], signed)
    np.testing.assert_allclose(np.asarray(qt.dequantize()), np.asarray(fq),
                               atol=1e-5)
    # ceil(bits/8)-packed byte accounting
    per = CODES_PER_BYTE[qt.storage_bits]
    assert qt.codes_bytes() == -(-k // per) * n
    assert qt.weight_count() == k * n


# ---------------------------------------------------------------------------
# QuantSpec: the one gate→bits→storage-class constructor
# ---------------------------------------------------------------------------


def test_spec_from_gate_storage_class():
    """T(g) thresholds map to storage classes; > 8 bits has none (fp
    fallback) — the clamp-to-[2,8] decision, in its single home."""
    for gate, bits, storage in [(0.2, 2, 2), (0.8, 2, 2), (1.5, 4, 4),
                                (2.5, 8, 8), (3.5, 16, None),
                                (4.5, 32, None)]:
        spec = QuantSpec.from_gate(jnp.asarray(gate), jnp.asarray(1.0), True)
        assert spec.max_bits() == bits, gate
        assert spec.storage_bits() == storage, gate
    # mixed per-channel gates: the site's class is set by its widest channel
    spec = QuantSpec.from_gate(jnp.asarray([0.8, 1.5]), jnp.ones((2,)), True)
    assert spec.max_bits() == 4 and spec.storage_bits() == 4
    assert storage_class_for(3) == 4 and storage_class_for(9) is None


def test_specs_from_state_is_a_pytree():
    """Specs thread through jit/scan like the gate arrays they replace."""
    specs = specs_from_state(
        {"a.w": jnp.asarray([2.5, 0.8])},
        {"a.w": jnp.asarray([1.0, 2.0])},
        {"a.w": True})
    leaves = jax.tree_util.tree_leaves(specs)
    assert len(leaves) == 2
    sliced = jax.tree.map(lambda x: x[0], specs)
    assert float(sliced["a.w"].bits) == 8.0
    assert sliced["a.w"].signed is True


# ---------------------------------------------------------------------------
# Export ledger + quant_report (LeNet path: same machinery as the LLM)
# ---------------------------------------------------------------------------


def _lenet_state(granularity="per_tensor", gate_init=2.5):
    from repro.core.sites import (QuantConfig, collect_sites, init_gates,
                                  init_ranges_from_weights,
                                  split_learnable_ranges)
    from repro.models import lenet

    params = lenet.init_params(jax.random.PRNGKey(0))
    qcfg = QuantConfig(granularity=granularity)
    sites = collect_sites(lenet.forward, params,
                          jnp.zeros((1, 28, 28, 1), jnp.float32), cfg=qcfg)
    gates = init_gates(sites, qcfg, init=gate_init)
    betas, signed = split_learnable_ranges(
        init_ranges_from_weights(sites, qcfg,
                                 lenet.weight_lookup(params)))
    return params, qcfg, sites, gates, betas, signed


def test_lenet_export_ledgers_conv_fallbacks_and_packs_fc():
    from repro.models import lenet

    params, qcfg, sites, gates, betas, signed = _lenet_state()
    # certify the fc sites at 2 bits, leave convs at 8
    for key in list(gates):
        if key.startswith("fc") and key.endswith(".w"):
            gates[key] = jnp.full_like(gates[key], 0.8)
    qw, ledger = lenet.export_qweights(params, gates, betas, signed)
    assert {"fc1.w", "fc2.w", "fc3.w"} <= set(qw)
    assert all(qw[f"fc{i}.w"].storage_bits == 2 for i in (1, 2, 3))
    fb = ledger.fallbacks()
    assert {"conv1.w", "conv2.w"} == set(fb)
    assert all(e["reason"] == "shape" for e in fb.values())

    rep = quant_report(ledger, gates)
    t = rep["totals"]
    assert t["fallback_sites"] == 2 and t["exported_sites"] == 3
    # fc codes at 2 bits: a quarter byte per weight (fan-ins divide by 4),
    # plus the per-tensor fp32 scale + bias (4 bytes each)
    for i in (1, 2, 3):
        e = rep["per_site"][f"fc{i}.w"]
        assert e["bytes"] == e["weight_count"] // 4 + 8
    assert t["bytes_device"] < t["bytes_fp32"]


def test_lenet_serve_mode_uses_frozen_codes():
    """LeNet serve-mode forward: fc sites read the dequantized frozen codes
    (bit-identical to dequantize()), convs fall back to spec fake-quant, and
    the logits match the train-mode fake-quant reference."""
    from repro.core.sites import QuantContext, merge_ranges
    from repro.models import lenet

    params, qcfg, sites, gates, betas, signed = _lenet_state()
    qw, _ = lenet.export_qweights(params, gates, betas, signed)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 28, 28, 1)),
                    jnp.float32)
    qc_t = QuantContext(mode="train", cfg=qcfg, gates=gates,
                        ranges=merge_ranges(betas, signed), probes={})
    lt = lenet.forward(qc_t, params, x)
    qc_s = QuantContext(mode="serve", cfg=qcfg,
                        specs=specs_from_state(gates, betas, signed),
                        qweights=qw)
    ls = lenet.forward(qc_s, params, x)
    np.testing.assert_allclose(np.asarray(ls), np.asarray(lt),
                               rtol=1e-4, atol=1e-4)


def test_per_weight_granularity_ledgered_not_exported():
    from repro.models import lenet

    params, qcfg, sites, gates, betas, signed = _lenet_state(
        granularity="per_weight")
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # per-weight fallback must NOT warn
        qw, ledger = lenet.export_qweights(params, gates, betas, signed)
    assert qw == {}
    assert all(e["reason"] in ("granularity", "shape")
               for e in ledger.fallbacks().values())


def test_ungated_site_ledgered_and_warns():
    """A captured site the quant_state knows nothing about (config /
    checkpoint mismatch) serves full precision — it must land in the ledger
    as reason='ungated' and trigger the not-fully-quantized warning, not
    silently vanish."""
    from repro.models import lenet

    params, qcfg, sites, gates, betas, signed = _lenet_state()
    del gates["fc2.w"]
    with pytest.warns(UserWarning, match="NOT fully integer-quantized"):
        qw, ledger = lenet.export_qweights(params, gates, betas, signed)
    assert "fc2.w" not in qw
    e = ledger.entries["fc2.w"]
    assert e["reason"] == "ungated" and e["served"] == "fake_quant"
    assert e["bits"] is None and e["fp_bytes"] == 4 * e["weight_count"]
    rep = quant_report(ledger, gates)
    assert rep["per_site"]["fc2.w"]["reason"] == "ungated"


def test_blockwise_int8_roundtrip_error_bounded():
    """The gradient-compression wire format now lives in quant.pack."""
    from repro.quant import blockwise_int8_decode, blockwise_int8_encode

    x = jnp.asarray(np.random.default_rng(5).normal(size=(130,)) * 3.0,
                    jnp.float32)
    codes, scale = blockwise_int8_encode(x, 64)
    assert codes.dtype == jnp.int8 and codes.shape == (3, 64)
    back = blockwise_int8_decode(codes, scale, (130,))
    assert back.shape == (130,)
    err = np.abs(np.asarray(back) - np.asarray(x)).max()
    assert err <= float(scale.max()) / 2 + 1e-7


def test_quant_report_bytes_accounting():
    """quant_report totals: packed < int8 < fp32 on a mixed export, and the
    per-site bytes follow the storage-class packing exactly."""
    from repro.configs import get_smoke_config
    from repro.models import transformer as tfm
    from repro.serving.engine import export_int_model, make_mixed_quant_state

    cfg = get_smoke_config("tinyllama-1.1b")
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    qs = make_mixed_quant_state(cfg, params)
    qw, ledger = export_int_model(params, cfg, qs)
    rep = quant_report(ledger, qs["gates"])
    t = rep["totals"]
    assert t["bytes_device"] < t["bytes_uniform_int8"] < t["bytes_fp32"]
    assert t["bytes_per_weight"] < t["uniform_int8_bytes_per_weight"]
    # the headline metric counts EVERYTHING resident on device: codes AND
    # the fp32 affine terms (same aux rides in the int8 baseline)
    assert t["bytes_device"] == t["bytes_packed"] + t["bytes_aux"]
    assert t["uniform_int8_bytes_per_weight"] > 1.0  # int8 codes + fp32 aux
    for key, qt in qw.items():
        per = CODES_PER_BYTE[qt.storage_bits]
        assert rep["per_site"][key]["bytes"] == (qt.codes_bytes()
                                                + qt.aux_bytes())
        # packed rows follow ceil(K / per) per stacked copy
        assert qt.codes.shape[-2] == -(-qt.k // per)
    assert rep["bops"]["model"] <= rep["bops"]["uniform_int8"]
    assert 0 < rep["bops"]["rbop"] < 1
