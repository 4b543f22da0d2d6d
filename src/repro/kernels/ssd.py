"""Mamba2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

Grid: (B*H, n_chunks) with the chunk axis sequential; the inter-chunk
recurrent state (P, N) lives in VMEM scratch, so HBM traffic per chunk is
exactly the chunk inputs + outputs (the SSD insight: intra-chunk work is
MXU-friendly matmuls, inter-chunk state is tiny).

Per chunk (Q = chunk length):
  cum   = cumsum(dA)                                    (Q,)
  Lmat  = exp(cum_q - cum_k) . tril                     (Q, Q)
  y     = ((C B^T) * Lmat) @ (x*dt)  +  (C @ state) * exp(cum)
  state = state * exp(cum_Q) + B^T @ ((x*dt) * exp(cum_Q - cum))

Validated on CPU (interpret=True) against kernels/ref.ssd_ref (the
sequential recurrence) — chunked vs sequential agreement is also the
correctness proof of the SSD algebra.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xd_ref, dA_ref, b_ref, c_ref, y_ref, state_out_ref, state_scr, *,
            chunk: int, n_chunks: int):
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xd = xd_ref[0].astype(jnp.float32)         # (Q, P) already dt-scaled
    dA = dA_ref[0].astype(jnp.float32)         # (Q,)
    Bm = b_ref[0].astype(jnp.float32)          # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)          # (Q, N)
    cum = jnp.cumsum(dA)                       # (Q,)
    seg = cum[:, None] - cum[None, :]
    causal = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    Lmat = jnp.where(causal, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * Lmat
    y = jax.lax.dot_general(scores, xd, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    state = state_scr[...]                     # (P, N)
    y += jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(cum)[:, None]
    tot = cum[-1]
    decay_out = jnp.exp(tot - cum)             # (Q,)
    add = jax.lax.dot_general((xd * decay_out[:, None]), Bm,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_scr[...] = state * jnp.exp(tot) + add
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(cj == n_chunks - 1)
    def _final():
        state_out_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, Bm, Cm, *, chunk: int = 256, interpret: bool = False):
    """x: (B, L, H, P); dt: (B, L, H); A: (H,); Bm/Cm: (B, L, G, N).

    Returns (y (B, L, H, P) f32, final_state (B, H, P, N) f32).
    """
    Bsz, L, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Q = min(chunk, L)
    assert L % Q == 0
    nc = L // Q

    xd = (x.astype(jnp.float32) * dt[..., None]).transpose(0, 2, 1, 3) \
        .reshape(Bsz * H, L, P)
    dA = (dt * A).transpose(0, 2, 1).reshape(Bsz * H, L)
    b2 = Bm.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(Bsz * G, L, N)
    c2 = Cm.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(Bsz * G, L, N)

    kernel = functools.partial(_kernel, chunk=Q, n_chunks=nc)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))

    def b_index(bh, j, rep=rep, G=G, H=H):
        b = bh // H
        h = bh % H
        return (b * G + h // rep, j, 0)

    y, state = pl.pallas_call(
        kernel,
        grid=(Bsz * H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, Q), lambda bh, j: (bh, j)),
            pl.BlockSpec((1, Q, N), b_index),
            pl.BlockSpec((1, Q, N), b_index),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, P, N), lambda bh, j: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz * H, L, P), jnp.float32),
            jax.ShapeDtypeStruct((Bsz * H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        compiler_params=params,
    )(xd, dA, b2, c2)
    y = y.reshape(Bsz, H, L, P).transpose(0, 2, 1, 3)
    state = state.reshape(Bsz, H, P, N)
    return y, state
