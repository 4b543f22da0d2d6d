"""Int8 error-feedback gradient compression for the cross-pod all-reduce.

Cross-pod links (DCN) are ~10x slower than in-pod ICI, so the pod-axis
gradient all-reduce is the multi-pod bottleneck.  Compress: quantize the
local gradient to int8 with a per-tensor scale, psum the int8 payload over
the pod axis (exact in int32), dequantize, and keep the quantization
residual locally (error feedback) so the bias cancels over steps
(1-bit-Adam / EF-SGD family).

Implemented with shard_map over the pod axis; in-pod reduction stays in
bf16/f32 via the normal GSPMD path.  Used by the example trainer and the
distributed subprocess tests; enable with TrainConfig.grad_compression.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_psum(grads, residuals, axis_name: str, axis_size: int):
    """Inside shard_map: error-feedback int8 psum over ``axis_name``.

    grads/residuals: local f32 pytrees. Returns (mean_grads, new_residuals).
    """

    def one(g, r):
        g = g + r                                  # error feedback
        q, scale = quantize(g)
        total = jax.lax.psum(q.astype(jnp.int32), axis_name)
        scale_sum = jax.lax.psum(scale, axis_name)
        # each shard quantized with its own scale; use the mean scale for
        # the dequantized sum (scales are psum'd so every pod agrees)
        mean_scale = scale_sum / axis_size
        out = total.astype(jnp.float32) * mean_scale / axis_size
        new_r = g - dequantize(q, scale)           # local residual
        return out, new_r

    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = jax.tree.leaves(residuals)
    outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    mean = jax.tree.unflatten(treedef, [o[0] for o in outs])
    res = jax.tree.unflatten(treedef, [o[1] for o in outs])
    return mean, res


def make_compressed_allreduce(mesh, axis_name: str = "pod"):
    """Returns fn(grads, residuals) -> (mean, residuals) running the
    error-feedback int8 reduction over ``axis_name`` via shard_map, with
    all other mesh axes untouched (grads replicated over them)."""
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]

    def apply(grads, residuals):
        specs = jax.tree.map(lambda _: P(), grads)

        fn = jax.shard_map(
            functools.partial(compressed_psum, axis_name=axis_name,
                              axis_size=axis_size),
            mesh=mesh,
            in_specs=(specs, specs),
            out_specs=(specs, specs),
            check_vma=False)
        return fn(grads, residuals)

    return apply
