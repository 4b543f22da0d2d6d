"""GPipe-style pipeline parallelism via collective_permute in shard_map.

Not enabled for the assigned architectures (FSDP x TP fits every one in
16 GB/chip — verified in EXPERIMENTS §Dry-run); provided for >200B dense
configs and exercised at toy scale in tests/test_pipeline.py.

Layout: layers are grouped into S stages, one stage per shard of the
"stage" mesh axis.  Microbatches stream through: at step t, stage s runs
microbatch (t - s) and then shifts activations to stage s+1 with
collective_permute.  Total steps = n_micro + S - 1 (bubble = (S-1)/steps).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(mesh: Mesh, stage_fn: Callable, params_stacked,
                   x_micro, axis_name: str = "stage"):
    """Run x through S pipeline stages.

    stage_fn(stage_params, h) -> h  (one stage's computation)
    params_stacked: pytree with leading dim S (stage-sharded)
    x_micro: (n_micro, mb, ...) microbatched input, replicated
    Returns (n_micro, mb, ...) outputs (as produced by the LAST stage).
    """
    S = mesh.shape[axis_name]
    n_micro = x_micro.shape[0]
    steps = n_micro + S - 1

    def per_shard(params_local, xs):
        # params_local: stage's params (leading dim 1); xs: all microbatches
        params_local = jax.tree.map(lambda p: p[0], params_local)
        idx = jax.lax.axis_index(axis_name)
        S_ = S   # static mesh size (jax.lax.axis_size is not in older jax)
        buf = jnp.zeros_like(xs[0])              # current activation
        outs = jnp.zeros_like(xs)

        def step(carry, t):
            buf, outs = carry
            mb_in = t                             # stage 0 consumes mb t
            # stage 0 loads a fresh microbatch; others use the shifted buf
            fresh = jnp.where((mb_in >= 0) & (mb_in < n_micro), 1, 0)
            x0 = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(mb_in, 0, n_micro - 1), 0, keepdims=False)
            h_in = jnp.where(idx == 0, x0 * fresh, buf)
            h_out = stage_fn(params_local, h_in)
            # last stage writes its finished microbatch t - (S-1)
            mb_out = t - (S_ - 1)
            valid_out = (mb_out >= 0) & (mb_out < n_micro)
            outs = jax.lax.cond(
                valid_out,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, jnp.where(idx == S_ - 1, h_out,
                                 jax.lax.dynamic_index_in_dim(
                                     o, jnp.clip(mb_out, 0, n_micro - 1),
                                     0, keepdims=False)),
                    jnp.clip(mb_out, 0, n_micro - 1), 0),
                lambda o: o, outs)
            # shift activations downstream (ring permute; stage S-1 -> 0
            # wraps but stage 0 ignores its incoming buf)
            perm = [(i, (i + 1) % S_) for i in range(S_)]
            buf = jax.lax.ppermute(h_out, axis_name, perm)
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(step, (buf, outs),
                                    jnp.arange(steps))
        # only the last stage wrote real entries; everyone else holds
        # zeros, so a psum reconciles exactly
        return jax.lax.psum(outs, axis_name)

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis_name), params_stacked),
                  P()),
        out_specs=P(),
        check_vma=False)
    return fn(params_stacked, x_micro)
