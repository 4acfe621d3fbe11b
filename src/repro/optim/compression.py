"""int8 error-feedback gradient compression for the cross-pod reduction.

Distributed-optimization trick (DESIGN.md §4): inside a pod, gradients
reduce over the high-bandwidth ICI mesh in full precision; BETWEEN pods the
links are the scarce resource, so the pod-axis all-reduce runs on int8
block-quantized tensors with an error-feedback (EF-SGD / 1-bit-Adam family)
residual so compression error does not bias convergence:

    send    = quantize8(grad_pod_partial + residual)
    residual' = (grad + residual) - dequant(send)
    grad_out = psum_over_pods(dequant(send)) / n_pods

Implemented with ``shard_map`` over the ``pod`` axis only — the int8 payload
is what crosses pods, visible as an 8-bit collective in the dry-run HLO
(4x fewer inter-pod bytes than fp32, 2x fewer than bf16). The wire format
itself (blockwise symmetric int8) lives with every other integer
storage/wire format in ``repro.quant`` (DESIGN.md §11).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.quant import blockwise_int8_decode, blockwise_int8_encode

BLOCK = 256


def _q8_flat(x):
    return blockwise_int8_encode(x, BLOCK)


def _dq8_flat(codes, scale, shape):
    return blockwise_int8_decode(codes, scale, shape)


def compressed_psum_leaf(g, resid, axis: str):
    """One leaf: int8 EF-compressed mean over ``axis``. Returns (g', resid').

    Wire format: all_gather of the int8 codes (+ tiny fp32 block scales),
    then local dequant-accumulate — exact for any per-pod scales, and the
    inter-pod payload is the int8 tensor (4x smaller than fp32 psum traffic).
    """
    comp_in = g.astype(jnp.float32) + resid
    codes, scale = _q8_flat(comp_in)
    deq = _dq8_flat(codes, scale, g.shape)
    new_resid = comp_in - deq
    codes_g = jax.lax.all_gather(codes, axis)       # (npods, nblk, B) int8
    scale_g = jax.lax.all_gather(scale, axis)       # (npods, nblk, 1) fp32
    npods = codes_g.shape[0]
    summed = jnp.einsum(
        "pnb,pnk->nb", codes_g.astype(jnp.float32), scale_g
    )  # dequantized block sums
    n = 1
    for d in g.shape:
        n *= d
    total = summed.reshape(-1)[:n].reshape(g.shape) / npods
    return total.astype(g.dtype), new_resid


def make_compressed_pod_psum(mesh, *, pod_axis: str = "pod"):
    """Returns f(grads, residuals) -> (grads', residuals') using shard_map
    over the pod axis (other axes untouched; apply AFTER intra-pod
    reduction)."""

    def _one(g, r):
        def _local(gl, rl):
            return compressed_psum_leaf(gl, rl, pod_axis)

        # grads replicated over pod at this point of the pipeline
        return jax.shard_map(
            _local, mesh=mesh,
            in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False,
        )(g, r)

    def apply(grads, residuals):
        flat_g, td = jax.tree_util.tree_flatten(grads)
        flat_r = td.flatten_up_to(residuals)
        outs = [_one(g, r) for g, r in zip(flat_g, flat_r)]
        return (td.unflatten([o[0] for o in outs]),
                td.unflatten([o[1] for o in outs]))

    return apply


def init_residuals(grads_template):
    return jax.tree.map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads_template)
