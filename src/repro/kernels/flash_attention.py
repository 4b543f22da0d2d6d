"""Flash attention as a Pallas TPU kernel.

GQA-native: grid dim 0 enumerates (batch x kv_head x q_group); the k/v
BlockSpec index map divides by the group count so kv blocks are fetched
once per kv head — no repeated-KV materialisation.  Online softmax carries
(m, l, acc) in VMEM scratch across the innermost (kv-block) grid dim.

TPU notes: block sizes default to 128 (MXU-aligned); dims 0..1 of the grid
are parallel, the kv dim is 'arbitrary' (sequential) so scratch persists.
Validated on CPU with interpret=True against kernels/ref.attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, block_q: int, block_k: int,
            n_kv_blocks: int, seq_q: int, seq_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kj * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)              # (bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_k
        if causal:
            mask &= qpos >= kpos
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _out():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, Sq, H, D); k/v: (B, Skv, KV, D/Dv). Returns (B, Sq, H, Dv)."""
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk

    # layouts: q (B*KV*G, Sq, D); k/v (B*KV, Skv, D)
    q2 = q.reshape(B, Sq, KV, G, D).transpose(0, 2, 3, 1, 4) \
          .reshape(B * KV * G, Sq, D)
    k2 = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, D)
    v2 = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, Dv)

    kernel = functools.partial(
        _kernel, scale=D ** -0.5, causal=causal, block_q=bq, block_k=bk,
        n_kv_blocks=nk, seq_q=Sq, seq_k=Skv)

    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    out = pl.pallas_call(
        kernel,
        grid=(B * KV * G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j, G=G: (b // G, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j, G=G: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV * G, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=params,
    )(q2, k2, v2)
    return (out.reshape(B, KV, G, Sq, Dv).transpose(0, 3, 1, 2, 4)
            .reshape(B, Sq, H, Dv))
