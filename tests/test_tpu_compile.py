"""The served Pallas kernels compile for a TPU v5e at TinyLlama-1.1B widths.

Interpret-mode tests check what the kernels compute; they cannot see what
Mosaic refuses (1-D per-channel blocks, lane-splitting reshapes, f16 vector
loads). Here every kernel of the served path is compiled ahead of time for
one chip of a described ``v5e:2x2`` topology — nothing runs and no chip is
needed, only the TPU compiler that ships with ``libtpu`` — and the compiled
program must contain the Mosaic call (``tpu_custom_call``).

Widths are TinyLlama-1.1B's: d_model 2048, d_ff 5632, 32 query heads over 4
KV heads of head_dim 64. Decode GEMMs run at M = slots, prefill GEMMs at
M = chunk tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.ops import paged_attention_op
from repro.kernels.quant_matmul.ops import (int_matmul_op,
                                            int_matmul_packed_op,
                                            quant_matmul_op,
                                            quant_matmul_packed_op)

D_MODEL, D_FF = 2048, 5632
KV_HEADS, GROUPS, HEAD_DIM = 4, 8, 64
SLOTS, CHUNK = 4, 128
BLOCK, MAX_BLOCKS = 8, 40          # engine default block size, 320 tokens
NUM_BLOCKS = SLOTS * MAX_BLOCKS + 1
KV_GROUP = 32                      # engine's int8/int4 group at head_dim 64


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # libtpu would log under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compiled_text(fn, *args, **static) -> str:
    return fn.lower(*args, **static).compile().as_text()


@pytest.mark.parametrize("m", [SLOTS, CHUNK], ids=["decode", "prefill"])
@pytest.mark.parametrize("kind", ["int8", "packed4", "int8xint8",
                                  "packed4xint8"])
def test_gemm_compiles_for_v5e(one_chip, kind, m):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    k, n = D_MODEL, D_FF
    x = s((m, k), jnp.float32)
    vec = s((n,), jnp.float32)
    codes = s((k, n), jnp.int8)
    packed = s((k // 2, n), jnp.uint8)
    colsum = s((n,), jnp.int32)
    beta = s((), jnp.float32)
    if kind == "int8":
        text = _compiled_text(quant_matmul_op, x, codes, vec, vec,
                              impl="pallas")
    elif kind == "packed4":
        text = _compiled_text(quant_matmul_packed_op, x, packed, vec, vec,
                              bits=4, k=k, impl="pallas")
    elif kind == "int8xint8":
        text = _compiled_text(int_matmul_op, x, codes, vec, vec, colsum, beta,
                              act_bits=8, impl="pallas")
    else:
        text = _compiled_text(int_matmul_packed_op, x, packed, vec, vec,
                              colsum, beta, bits=4, k=k, act_bits=8,
                              impl="pallas")
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("window", [None, (128, 16)],
                         ids=["dense", "window+sinks"])
@pytest.mark.parametrize("kv", ["bf16", "int8", "int4"])
def test_paged_attention_compiles_for_v5e(one_chip, kv, window):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    head = HEAD_DIM // 2 if kv == "int4" else HEAD_DIM
    dtype = {"bf16": jnp.bfloat16, "int8": jnp.int8, "int4": jnp.uint8}[kv]
    pool = s((NUM_BLOCKS, BLOCK, KV_HEADS, head), dtype)
    scales = {}
    if kv != "bf16":
        sc = s((NUM_BLOCKS, BLOCK, KV_HEADS, HEAD_DIM // KV_GROUP),
               jnp.float16)
        scales = {"k_scale": sc, "v_scale": sc}
    w, sinks = window if window is not None else (None, 0)
    text = _compiled_text(
        paged_attention_op,
        s((SLOTS, KV_HEADS, GROUPS, HEAD_DIM), jnp.bfloat16), pool, pool,
        s((SLOTS, MAX_BLOCKS), jnp.int32), s((SLOTS,), jnp.int32),
        window=w, sinks=sinks, impl="pallas", **scales)
    assert "tpu_custom_call" in text
