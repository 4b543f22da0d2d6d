"""Flash-decoding as a Pallas TPU kernel: one query token against a long
KV cache, online softmax over sequence blocks.

Grid: (B*KV, n_seq_blocks) — sequence sequential with (m, l, acc) carried
in VMEM scratch; q groups (GQA) ride along the second-minor dim so the MXU
sees (G x block) matmuls.  Masking by per-batch valid length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block: int, n_blocks: int):
    sj = pl.program_id(1)

    @pl.when(sj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)             # (G, D)
    k = k_ref[0].astype(jnp.float32)             # (bs, D)
    v = v_ref[0].astype(jnp.float32)             # (bs, Dv)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = sj * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < len_ref[0], s, NEG_INF)  # (G, bs)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    acc_scr[...] = (acc_scr[...] * corr[:, None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))
    m_scr[...] = m_new

    @pl.when(sj == n_blocks - 1)
    def _out():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def decode_attention(q, k, v, kv_len, *, block: int = 512,
                     interpret: bool = False):
    """q: (B, 1, H, D); k/v: (B, S, KV, D/Dv); kv_len: (B,) int32."""
    B, _, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    bs = min(block, S)
    assert S % bs == 0
    nb = S // bs
    q2 = q.reshape(B, KV, G, D).reshape(B * KV, G, D)
    k2 = k.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
    v2 = v.transpose(0, 2, 1, 3).reshape(B * KV, S, Dv)
    lens = jnp.broadcast_to(kv_len[:, None], (B, KV)).reshape(B * KV, 1)

    kernel = functools.partial(_kernel, scale=D ** -0.5, block=bs,
                               n_blocks=nb)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))

    out = pl.pallas_call(
        kernel,
        grid=(B * KV, nb),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bs, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bs, Dv), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1), lambda b, j: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, Dv), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=params,
    )(q2, k2, v2, lens)
    return out.reshape(B, 1, H, Dv)
