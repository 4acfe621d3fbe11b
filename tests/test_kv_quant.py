"""KV-cache codec property tests (DESIGN.md §14).

Property tests (hypothesis, deterministic-replay fallback shim without it):
  * round-trip error bounded by scale/2 per group — every bits/group_size
    combination, odd/ragged head_dim tails included;
  * exact idempotence: quantize(dequantize(x)) returns the SAME codes and
    scales bit-for-bit (what makes CoW copy codes+aux verbatim and
    preemption-resume bit-identical);
  * int4 packing round-trips through the pool byte layout.

Plus direct tests for spec accounting (ceil-packed bytes/vector,
bytes-per-cached-token report) and structural spec recovery from a cache
entry.
"""

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # container without hypothesis: deterministic replay
    from _hyp_fallback import given, settings
    from _hyp_fallback import strategies as st

from repro.quant import KVQuantSpec, dequantize_kv, quantize_kv
from repro.quant.kv import (SCALE_DTYPE, bytes_per_cached_token,
                            dequant_codes, kv_cache_report, spec_from_cache,
                            unpack_int4)


def _sample(seed, lead, head_dim, scale_pow):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(lead + (head_dim,)) * (10.0 ** scale_pow)
    # sprinkle exact zeros and a per-vector outlier channel
    x[..., 0] = 0.0
    if head_dim > 1:
        x[..., -1] *= 50.0
    return jnp.asarray(x, jnp.float32)


# ---------------------------------------------------------------------------
# round-trip error bound
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(head_dim=st.integers(min_value=1, max_value=37),
       group_size=st.integers(min_value=1, max_value=16),
       bits=st.sampled_from([4, 8]),
       scale_pow=st.integers(min_value=-3, max_value=2),
       seed=st.integers(min_value=0, max_value=2**16))
def test_roundtrip_error_bounded_per_group(head_dim, group_size, bits,
                                           scale_pow, seed):
    """|x - dequant(quant(x))| <= scale/2 within every group, for every
    head_dim (ragged group tails included) and both storage classes."""
    spec = KVQuantSpec(bits=bits, group_size=group_size, head_dim=head_dim)
    x = _sample(seed, (3, 2), head_dim, scale_pow)
    codes, scale = quantize_kv(x, spec)
    assert codes.dtype == spec.code_dtype
    assert codes.shape == x.shape[:-1] + (spec.packed_head,)
    assert scale.dtype == SCALE_DTYPE
    assert scale.shape == x.shape[:-1] + (spec.num_groups,)
    y = dequantize_kv(codes, scale, spec)
    err = jnp.abs(y - x)
    pad = spec.padded_head - head_dim
    if pad:
        err = jnp.pad(err, [(0, 0)] * (err.ndim - 1) + [(0, pad)])
    err_g = err.reshape(err.shape[:-1] + (spec.num_groups, group_size))
    bound = scale.astype(jnp.float32) * (0.5 + 1e-3) + 1e-7
    assert bool(jnp.all(jnp.max(err_g, axis=-1) <= bound)), (
        float(jnp.max(err_g)), float(jnp.min(scale)))


@settings(max_examples=40, deadline=None)
@given(head_dim=st.integers(min_value=1, max_value=37),
       group_size=st.integers(min_value=1, max_value=16),
       bits=st.sampled_from([4, 8]),
       scale_pow=st.integers(min_value=-3, max_value=2),
       seed=st.integers(min_value=0, max_value=2**16))
def test_quantize_dequantize_idempotent(head_dim, group_size, bits,
                                        scale_pow, seed):
    """quantize(dequantize(x)) == (codes, scale) EXACTLY: the fp16 scale
    floor puts requantization back on the identical grid, so re-encoding a
    decoded block is a bit-for-bit no-op."""
    spec = KVQuantSpec(bits=bits, group_size=group_size, head_dim=head_dim)
    x = _sample(seed, (2,), head_dim, scale_pow)
    codes, scale = quantize_kv(x, spec)
    codes2, scale2 = quantize_kv(dequantize_kv(codes, scale, spec), spec)
    assert bool(jnp.all(codes2 == codes))
    assert bool(jnp.all(scale2 == scale))
    # and a second decode lands on the same floats
    y = dequantize_kv(codes, scale, spec)
    y2 = dequantize_kv(codes2, scale2, spec)
    assert bool(jnp.all(y == y2))


@settings(max_examples=30, deadline=None)
@given(head_dim=st.integers(min_value=1, max_value=33),
       seed=st.integers(min_value=0, max_value=2**16))
def test_int4_pack_roundtrip_matches_int8_codes(head_dim, seed):
    """The packed int4 pool layout decodes to the same centered codes the
    int8 path would clip to the 4-bit range (nibble order = pack.py's)."""
    spec4 = KVQuantSpec(bits=4, group_size=8, head_dim=head_dim)
    x = _sample(seed, (4,), head_dim, 0)
    codes, scale = quantize_kv(x, spec4)
    assert codes.dtype == jnp.uint8
    assert codes.shape[-1] == spec4.packed_head == -(-head_dim // 2)
    unpacked = unpack_int4(codes, head_dim)
    assert bool(jnp.all(unpacked <= 7)) and bool(jnp.all(unpacked >= -7))
    # dequant via the generic code path agrees with dequantize_kv
    y = dequant_codes(unpacked, scale, head_dim, spec4.group_size)
    assert bool(jnp.all(y == dequantize_kv(codes, scale, spec4)))


def test_all_zero_vectors_code_to_zero():
    spec = KVQuantSpec(bits=8, group_size=4, head_dim=12)
    codes, scale = quantize_kv(jnp.zeros((2, 12)), spec)
    assert bool(jnp.all(codes == 0))
    assert bool(jnp.all(dequantize_kv(codes, scale, spec) == 0.0))


# ---------------------------------------------------------------------------
# spec accounting + structural recovery
# ---------------------------------------------------------------------------


def test_spec_accounting_ceil_packed():
    s8 = KVQuantSpec(bits=8, group_size=16, head_dim=16)
    assert s8.packed_head == 16 and s8.num_groups == 1
    assert s8.bytes_per_vector() == 16 + 2          # codes + one fp16 scale
    s4 = KVQuantSpec(bits=4, group_size=8, head_dim=17)
    assert s4.packed_head == 9                      # ceil(17/2) bytes
    assert s4.num_groups == 3                       # ragged tail group
    assert s4.bytes_per_vector() == 9 + 3 * 2
    with pytest.raises(ValueError):
        KVQuantSpec(bits=3, group_size=8, head_dim=16)


def test_bytes_per_cached_token_and_report():
    spec = KVQuantSpec(bits=8, group_size=16, head_dim=16)
    q = bytes_per_cached_token(2, 16, spec=spec)
    assert q == 2 * 2 * (16 + 2)                    # K+V, 2 heads
    bf16 = bytes_per_cached_token(2, 16, dtype=jnp.bfloat16)
    fp32 = bytes_per_cached_token(2, 16, dtype=jnp.float32)
    assert bf16 == 2 * 2 * 16 * 2 and fp32 == 2 * bf16
    rep = kv_cache_report(["global", "mlp", "local"], 2, 16, spec=spec,
                          kv_dtype="int8")
    assert rep["attention_layers"] == 2
    assert rep["bytes_per_cached_token"] == 2 * q
    assert rep["fp32_bytes_per_cached_token"] == 2 * fp32
    assert rep["vs_fp32"] == pytest.approx(q / fp32)
    # the §14 headline: int8 + fp16 group scales lands under 0.3x fp32
    assert rep["vs_fp32"] <= 0.3


def test_spec_recovered_structurally_from_cache_entry():
    spec = KVQuantSpec(bits=8, group_size=8, head_dim=16)
    x = jnp.ones((3, 4, 2, 16))
    k, ks = quantize_kv(x, spec)
    entry = {"k": k, "v": k, "k_scale": ks, "v_scale": ks}
    assert spec_from_cache(entry, 16) == spec
    assert spec_from_cache({"k": x, "v": x}, 16) is None
    s4 = KVQuantSpec(bits=4, group_size=8, head_dim=16)
    k4, ks4 = quantize_kv(x, s4)
    assert spec_from_cache({"k": k4, "v": k4, "k_scale": ks4,
                            "v_scale": ks4}, 16) == s4
