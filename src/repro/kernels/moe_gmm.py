"""Expert-batched (grouped) matmul as a Pallas TPU kernel.

Computes out[e] = x[e] @ w[e] for every expert with (bc x bd) x (bd x bf)
MXU tiles and an accumulator in VMEM scratch across the contraction dim.
Capacity padding upstream makes the groups rectangular (GShard-style), so
"grouped" reduces to a batched matmul with expert-major tiling — the shape
the MoE dispatch feeds (E, C, D) x (E, D, F).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, acc_scr, *, n_d_blocks: int):
    dj = pl.program_id(3)

    @pl.when(dj == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(jnp.float32)     # (bc, bd)
    w = w_ref[0].astype(jnp.float32)     # (bd, bf)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(dj == n_d_blocks - 1)
    def _out():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d",
                                             "interpret"))
def gmm(x, w, *, block_c: int = 128, block_f: int = 128, block_d: int = 128,
        interpret: bool = False):
    """x: (E, C, D); w: (E, D, F) -> (E, C, F)."""
    E, C, D = x.shape
    F = w.shape[-1]
    bc, bf, bd = min(block_c, C), min(block_f, F), min(block_d, D)
    assert C % bc == 0 and F % bf == 0 and D % bd == 0
    nc, nf, nd = C // bc, F // bf, D // bd

    kernel = functools.partial(_kernel, n_d_blocks=nd)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))

    return pl.pallas_call(
        kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, i, j, d: (e, i, d)),
            pl.BlockSpec((1, bd, bf), lambda e, i, j, d: (e, d, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j, d: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=interpret,
        compiler_params=params,
    )(x, w)
