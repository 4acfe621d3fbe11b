"""Per-kernel allclose sweeps vs pure-jnp oracles (interpret mode on CPU).

Each Pallas kernel is swept across shapes/dtypes and asserted against its
ref.py oracle, per the deliverable (c) requirements.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gates import gated_fake_quant
from repro.core.quantizer import quantize_to_int
from repro.kernels.fake_quant.ops import fake_quant_op
from repro.kernels.fake_quant.ref import fake_quant_ref
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.quant_matmul.ops import quant_matmul_op
from repro.kernels.quant_matmul.ref import quant_matmul_ref


# ---------------------------------------------------------------------------
# fake_quant
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(8, 32), (128, 128), (300, 257), (1024, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("signed", [True, False])
def test_fake_quant_kernel_vs_ref(shape, dtype, signed):
    rng = np.random.default_rng(hash((shape, signed)) % 2**31)
    x = jnp.asarray(rng.normal(size=shape), dtype)
    gate = jnp.asarray(rng.uniform(0.2, 5.5, size=(shape[-1],)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.3, 2.0, size=(shape[-1],)), jnp.float32)
    got = fake_quant_op(x, gate, beta, signed=signed,
                        impl="pallas_interpret")
    want = fake_quant_ref(
        x.reshape(-1, shape[-1]).astype(jnp.float32), gate, beta, signed
    ).reshape(shape).astype(dtype)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_fake_quant_kernel_matches_core_path():
    """Kernel == the core gated_fake_quant used by training (bit-exact)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 96)).astype(np.float32))
    gate = jnp.asarray(2.5)   # 8-bit
    beta = jnp.asarray(1.2)
    got = fake_quant_op(x, gate, beta, signed=True, impl="pallas_interpret")
    want = gated_fake_quant(x, gate, beta, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_fake_quant_per_tensor_scalar_broadcast():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(33, 65)), jnp.float32)
    got = fake_quant_op(x, jnp.asarray(1.5), jnp.asarray(1.0), signed=True,
                        impl="pallas_interpret")
    want = fake_quant_ref(x.astype(jnp.float32), jnp.full((65,), 1.5),
                          jnp.ones((65,)), True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# ---------------------------------------------------------------------------
# quant_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mkn", [(16, 64, 32), (128, 256, 128), (200, 384, 96),
                                 (64, 1024, 256)])
@pytest.mark.parametrize("bits", [8, 4])
def test_quant_matmul_vs_ref(mkn, bits):
    m, k, n = mkn
    rng = np.random.default_rng(m + k + n + bits)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    beta = jnp.max(jnp.abs(w), axis=0)
    codes, scale, bias = quantize_to_int(w, bits, beta, True)
    got = quant_matmul_op(x, codes, scale, bias, impl="pallas_interpret")
    want = quant_matmul_ref(x, codes, scale, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("mkn", [(16, 64, 32), (96, 640, 72)])
def test_quant_matmul_bias_path_per_channel_asymmetric(mkn):
    """The kernel's affine epilogue: per-channel asymmetric export, bias != 0.

    Unsigned (alpha = 0) grids produce a nonzero per-channel ``bias``; the
    Pallas kernel must fold ``bias[n] * rowsum(x)[m]`` into the output tile
    (the rank-1 term of y = x @ (codes*scale + bias)). Checked against the
    jnp oracle AND the exact fp32 matmul on the dequantized weight.
    """
    m, k, n = mkn
    rng = np.random.default_rng(m * 7 + n)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = jnp.asarray(np.abs(rng.normal(size=(k, n))).astype(np.float32))
    beta = jnp.max(jnp.abs(w), axis=0)
    codes, scale, bias = quantize_to_int(w, 8, beta, signed=False)
    assert float(jnp.abs(bias).max()) > 0.0
    got = quant_matmul_op(x, codes, scale, bias, impl="pallas_interpret")
    want = quant_matmul_ref(x, codes, scale, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    exact = x @ (codes.astype(jnp.float32) * scale[None, :] + bias[None, :])
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                               rtol=2e-4, atol=2e-4)


def test_quant_matmul_mixed_bits_grid_matches_fake_quant():
    """Array-bits export: codes*scale+bias reproduces the fake-quant grid."""
    from repro.core.quantizer import quantize

    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(64, 48)).astype(np.float32))
    beta = jnp.max(jnp.abs(w), axis=0)
    bits = jnp.asarray(rng.choice([2.0, 4.0, 8.0], size=(48,)))
    codes, scale, bias = quantize_to_int(w, bits, beta, True)
    assert codes.dtype == jnp.int8
    deq = codes.astype(jnp.float32) * scale[None, :] + bias[None, :]
    fq = quantize(w, bits[None, :], beta[None, :], True)
    np.testing.assert_allclose(np.asarray(deq), np.asarray(fq), atol=1e-6)


@pytest.mark.parametrize("mkn", [(16, 64, 32), (8, 37, 24), (64, 130, 72)])
@pytest.mark.parametrize("bits", [2, 4])
def test_packed_quant_matmul_vs_int8_oracle(mkn, bits):
    """The packed fused unpack+dequant kernel (interpret) and its jnp ref
    against the unpacked int8 oracle — including ragged K that is not a
    multiple of the codes-per-byte packing factor."""
    from repro.quant import QuantizedTensor
    from repro.kernels.quant_matmul.ops import quant_matmul_qt

    m, k, n = mkn
    rng = np.random.default_rng(m + k + n + bits)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    beta = jnp.max(jnp.abs(w), axis=0)
    qt = QuantizedTensor.from_float(w, bits, beta[None, :], True,
                                    storage_bits=bits)
    oracle = QuantizedTensor.from_float(w, bits, beta[None, :], True,
                                        storage_bits=bits, pack=False)
    assert qt.packed and qt.codes.shape[0] == -(-k // (8 // bits))
    want = quant_matmul_qt(x, oracle, impl="ref")
    got_ref = quant_matmul_qt(x, qt, impl="ref")
    np.testing.assert_array_equal(np.asarray(got_ref), np.asarray(want))
    got_pl = quant_matmul_qt(x, qt, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got_pl), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_pallas_flags_refuse_interpreter_on_tpu(monkeypatch):
    """One backend name drives every kernel wrapper; the interpreter is for
    CPU tests and is refused where the default backend is a TPU."""
    from repro.kernels import pallas_flags

    assert pallas_flags("ref") == (False, False)
    assert pallas_flags("pallas") == (True, False)
    assert pallas_flags("pallas_interpret") == (True, True)
    with pytest.raises(ValueError, match="impl must be one of"):
        pallas_flags("interpret")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_flags("pallas") == (True, False)
    with pytest.raises(ValueError, match="CPU tests"):
        pallas_flags("pallas_interpret")


def test_quant_matmul_end_to_end_error_small():
    """x @ dequant(quant(w)) stays close to x @ w at 8 bits."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(32, 128)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32))
    beta = jnp.max(jnp.abs(w), axis=0)
    codes, scale, bias = quantize_to_int(w, 8, beta, True)
    got = quant_matmul_op(x, codes, scale, bias, impl="pallas_interpret")
    exact = x @ w
    rel = float(jnp.linalg.norm(got - exact) / jnp.linalg.norm(exact))
    assert rel < 1e-2  # int8 absmax grid: ~0.4% RMS weight error


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s", [64, 128, 256])
@pytest.mark.parametrize("d", [32, 64])
@pytest.mark.parametrize("window", [None, 64])
def test_flash_attention_vs_ref(s, d, window):
    rng = np.random.default_rng(s + d)
    q = jnp.asarray(rng.normal(size=(2, 3, s, d)).astype(np.float32)) * 0.5
    k = jnp.asarray(rng.normal(size=(2, 3, s, d)).astype(np.float32)) * 0.5
    v = jnp.asarray(rng.normal(size=(2, 3, s, d)).astype(np.float32))
    got = flash_attention_op(q, k, v, causal=True, window=window,
                             impl="pallas_interpret")
    want = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_softcap_and_gqa():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 4, 128, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 32)).astype(np.float32))
    got = flash_attention_op(q, k, v, causal=True, softcap=30.0,
                             impl="pallas_interpret")
    k_r = jnp.repeat(k, 2, axis=1)
    v_r = jnp.repeat(v, 2, axis=1)
    want = attention_ref(q, k_r, v_r, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    got = flash_attention_op(q, k, v, causal=True, impl="pallas_interpret")
    want = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
