"""Attention: GQA (optional QKV bias), M-RoPE, MLA; XLA reference paths.

The training/prefill path is *blockwise* attention (online softmax over KV
tiles inside a scan) so the (Sq, Skv) score matrix is never materialised —
the XLA analogue of flash attention and the oracle for the Pallas kernel.
The decode path attends one query position against a (possibly
sequence-sharded) KV cache.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (apply_mrope, apply_rope, dtype_of,
                                 normal_init, rmsnorm)
from repro.parallel.sharding import shard

NEG_INF = -1e30


# ----------------------------------------------------------------------
def blockwise_attention(q, k, v, *, causal: bool, q_chunk: int, kv_chunk: int,
                        q_offset=0, kv_valid_len=None):
    """Online-softmax attention over KV tiles.

    q: (B, Sq, H, Dk); k: (B, Skv, H, Dk); v: (B, Skv, H, Dv) — GQA callers
    repeat kv heads to H first.  Returns (B, Sq, H, Dv) in q.dtype.
    q_offset: absolute position of q[0] (scalar, for causal masking).
    kv_valid_len: optional scalar/(B,) mask of valid kv positions.
    """
    B, Sq, H, Dk = q.shape
    _, Skv, _, Dv = v.shape
    scale = Dk ** -0.5
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Skv)
    nQ, nK = Sq // qc, Skv // kc
    assert Sq % qc == 0 and Skv % kc == 0, (Sq, qc, Skv, kc)

    qb = jnp.moveaxis(q.reshape(B, nQ, qc, H, Dk), 1, 0)      # (nQ,B,qc,H,Dk)
    kb = jnp.moveaxis(k.reshape(B, nK, kc, H, Dk), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nK, kc, H, Dv), 1, 0)
    kpos0 = jnp.arange(kc)

    @jax.checkpoint  # flash-style backward: recompute blocks, never store all
    def one_q_block(args):
        qi, qblk = args                                        # (B,qc,H,Dk)
        qpos = q_offset + qi * qc + jnp.arange(qc)

        @jax.checkpoint
        def kv_step(carry, xs):
            m, l, acc = carry
            ki, kblk, vblk = xs
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            kpos = ki * kc + kpos0
            mask = jnp.ones((qc, kc), dtype=bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if kv_valid_len is not None:
                kl = jnp.asarray(kv_valid_len)
                if kl.ndim == 0:
                    mask &= (kpos < kl)[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
            if kv_valid_len is not None and jnp.asarray(kv_valid_len).ndim == 1:
                s = jnp.where((kpos[None, :] < kv_valid_len[:, None])
                              [:, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vblk.dtype), vblk,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, qc), jnp.float32)
        a0 = jnp.zeros((B, H, qc, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (jnp.arange(nK), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]           # (B,H,qc,Dv)
        return out.transpose(0, 2, 1, 3)                       # (B,qc,H,Dv)

    outs = jax.lax.map(one_q_block, (jnp.arange(nQ), qb))      # (nQ,B,qc,H,Dv)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, Dv)
    return out.astype(q.dtype)


def decode_attention(q, k, v, kv_valid_len, block=None):
    """One-token GQA flash-decoding: q (B,1,H,Dk), cache (B,S,KV,D*).

    - GQA-native (no kv-head repeat: repeating a sequence-sharded cache made
      GSPMD gather the sequence dim — observed multi-GB buffers).
    - q is constrained REPLICATED: q is one token; if q stays head-sharded,
      the partitioner aligns the score einsum on heads and gathers the
      sequence-sharded cache instead (observed: full 500k-cache gather).
    - The cache is consumed in seq blocks with an online softmax so f32
      working buffers stay block-sized; optimization_barrier keeps the
      bf16->f32 dot-operand conversion from being hoisted to the full cache.
    The cache stays seq-sharded over "model"; the partial max/sum combines
    lower to small all-reduces (flash-decoding's combine, done by GSPMD).
    """
    B, S, KV, Dk = k.shape
    H = q.shape[2]
    Grp = H // KV
    Dv = v.shape[-1]
    scale = Dk ** -0.5
    qg = q.reshape(B, 1, KV, Grp, Dk)
    qg = shard(qg, "batch", None, "kv_heads", None, None)   # replicate q
    # block=None: single shot over the full (seq-sharded) cache — reshaping
    # the sharded seq dim into (nb, blk) fragments its sharding and makes
    # GSPMD gather the cache (observed: 1.6 GB all-gathers per layer)
    blk = S if block is None else min(block, S)
    nb = S // blk
    assert S % blk == 0, (S, blk)
    kb = jnp.moveaxis(k.reshape(B, nb, blk, KV, Dk), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, blk, KV, Dv), 1, 0)
    kl = jnp.asarray(kv_valid_len)
    kl_b = kl[:, None] if kl.ndim == 1 else kl[None, None]

    def step(carry, xs):
        m, l, acc = carry
        bi, kblk, vblk = xs
        kblk, vblk = jax.lax.optimization_barrier((kblk, vblk))
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kblk,
                       preferred_element_type=jnp.float32) * scale
        s = shard(s, "batch", "kv_heads", None, None, "kv_seq")
        pos = bi * blk + jnp.arange(blk)
        s = jnp.where((pos[None, :] < kl_b)[:, None, None, None, :],
                      s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, Grp, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, Grp, 1), jnp.float32)
    a0 = jnp.zeros((B, KV, Grp, 1, Dv), jnp.float32)
    if nb == 1:
        (m, l, acc), _ = step((m0, l0, a0), (jnp.int32(0), kb[0], vb[0]))
    else:
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                      (jnp.arange(nb), kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, 1, H, Dv)
    return out.astype(q.dtype)


def repeat_kv(x, n_rep: int):
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=2)


# ----------------------------------------------------------------------
# Standard GQA attention block
def init_attention(key, cfg, d_in: Optional[int] = None,
                   d_out: Optional[int] = None,
                   num_heads: Optional[int] = None,
                   num_kv_heads: Optional[int] = None,
                   head_dim: Optional[int] = None) -> Tuple[dict, dict]:
    dt = dtype_of(cfg)
    D = d_in or cfg.d_model
    Dout = d_out or cfg.d_model
    H = num_heads or cfg.padded_heads
    true_H = num_heads or cfg.num_heads
    KV = num_kv_heads or cfg.padded_kv
    dh = head_dim or cfg.head_dim
    ks = jax.random.split(key, 4)
    wq = normal_init(ks[0], (D, H, dh), D ** -0.5, dt)
    wk = normal_init(ks[1], (D, KV, dh), D ** -0.5, dt)
    wv = normal_init(ks[2], (D, KV, dh), D ** -0.5, dt)
    wo = normal_init(ks[3], (H, dh, Dout), (true_H * dh) ** -0.5, dt)
    if H > true_H:  # padded heads contribute exactly zero
        head_mask = (jnp.arange(H) < true_H).astype(dt)
        wq = wq * head_mask[None, :, None]
        wo = wo * head_mask[:, None, None]
    p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    lg = {"wq": ("embed", "heads", None), "wk": ("embed", "kv_heads", None),
          "wv": ("embed", "kv_heads", None), "wo": ("heads", None, "embed")}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, dh), dt)
        p["bk"] = jnp.zeros((KV, dh), dt)
        p["bv"] = jnp.zeros((KV, dh), dt)
        lg["bq"] = ("heads", None)
        lg["bk"] = ("kv_heads", None)
        lg["bv"] = ("kv_heads", None)
    return p, lg


def _project_qkv(p, cfg, x, x_kv=None):
    x_kv = x if x_kv is None else x_kv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x_kv, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x_kv, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = shard(q, "batch", "act_seq", "heads", None)
    k = shard(k, "batch", "act_seq", "kv_heads", None)
    v = shard(v, "batch", "act_seq", "kv_heads", None)
    return q, k, v


def _rope_qk(cfg, q, k, positions):
    if positions is None:
        return q, k
    if cfg.mrope:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


def attention_fwd(p, cfg, x, positions, *, causal=True, x_kv=None,
                  use_rope=True):
    """Full-sequence attention (train / prefill / encoder / cross).

    Returns (out (B,S,D), new_kv = (k, v) pre-repeat for cache use).
    """
    H = p["wq"].shape[1]
    KV = p["wk"].shape[1]
    q, k, v = _project_qkv(p, cfg, x, x_kv)
    if use_rope:
        q, k = _rope_qk(cfg, q, k, positions)
    kf, vf = repeat_kv(k, H // KV), repeat_kv(v, H // KV)
    out = blockwise_attention(q, kf, vf, causal=causal,
                              q_chunk=cfg.attn_q_chunk,
                              kv_chunk=cfg.attn_kv_chunk)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    # cache copies are seq-sharded HERE so a prefill's stacked ys never
    # materialise the full-sequence cache per device
    k_c = shard(k, "batch", "kv_seq", "kv_heads", None)
    v_c = shard(v, "batch", "kv_seq", "kv_heads", None)
    # output constrained seq-sharded DIRECTLY on the row-parallel dot so
    # GSPMD emits a reduce-scatter (an "act_seq" constraint here forced a
    # full all-reduce + slice — observed 3.2 GB f32 AR per layer)
    return shard(out, "batch", "residual_seq", None), (k_c, v_c)


def attention_decode(p, cfg, x, pos, k_cache, v_cache, cache_len, *,
                     update_cache=True, use_rope=True, scales=None):
    """Single-token decode. x: (B,1,D); caches (B,S,KV,dh) seq-sharded.

    pos: (B,) int32 current position (== cache_len for self-attention).
    scales: (k_scale, v_scale) (B,S,KV) f32 when the cache is int8
    (per-token symmetric quantization — the KV-quantization hillclimb).
    Returns (out (B,1,D), k_cache, v_cache, scales).
    """
    H = p["wq"].shape[1]
    KV = p["wk"].shape[1]
    q, k, v = _project_qkv(p, cfg, x)
    if use_rope:
        if cfg.mrope:
            pos3 = jnp.broadcast_to(pos[:, None, None], (*pos.shape, 1, 3))
            q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = apply_rope(q, pos[:, None], cfg.rope_theta)
            k = apply_rope(k, pos[:, None], cfg.rope_theta)
    int8 = scales is not None
    if update_cache:
        # scatter update: the one-hot multiply formulation reads + rewrites
        # the ENTIRE cache (2x full-cache HBM traffic per layer); a scatter
        # touches one row per sequence (hillclimb: -32% decode memory term)
        b_idx = jnp.arange(k_cache.shape[0])
        if int8:
            k_scale, v_scale = scales
            ks_new = jnp.max(jnp.abs(k[:, 0]), axis=-1) / 127.0 + 1e-9
            vs_new = jnp.max(jnp.abs(v[:, 0]), axis=-1) / 127.0 + 1e-9
            kq = jnp.clip(jnp.round(k[:, 0] / ks_new[..., None]),
                          -127, 127).astype(jnp.int8)
            vq = jnp.clip(jnp.round(v[:, 0] / vs_new[..., None]),
                          -127, 127).astype(jnp.int8)
            k_cache = k_cache.at[b_idx, cache_len].set(kq)
            v_cache = v_cache.at[b_idx, cache_len].set(vq)
            k_scale = k_scale.at[b_idx, cache_len].set(
                ks_new.astype(jnp.float32))
            v_scale = v_scale.at[b_idx, cache_len].set(
                vs_new.astype(jnp.float32))
            scales = (k_scale, v_scale)
        else:
            k_cache = k_cache.at[b_idx, cache_len].set(
                k[:, 0].astype(k_cache.dtype))
            v_cache = v_cache.at[b_idx, cache_len].set(
                v[:, 0].astype(v_cache.dtype))
        k_cache = shard(k_cache, "batch", "kv_seq", "kv_heads", None)
        v_cache = shard(v_cache, "batch", "kv_seq", "kv_heads", None)
    if int8:
        kf = k_cache.astype(q.dtype) * scales[0][..., None].astype(q.dtype)
        vf = v_cache.astype(q.dtype) * scales[1][..., None].astype(q.dtype)
    else:
        kf, vf = k_cache.astype(q.dtype), v_cache.astype(q.dtype)
    out = decode_attention(q, kf, vf, cache_len + 1)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return shard(out, "batch", "act_seq", None), k_cache, v_cache, scales


# ----------------------------------------------------------------------
# Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 style)
def init_mla(key, cfg) -> Tuple[dict, dict]:
    m = cfg.mla
    dt = dtype_of(cfg)
    D, H = cfg.d_model, cfg.padded_heads or cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    wuq = normal_init(ks[1], (m.q_lora_rank, H, qk), m.q_lora_rank ** -0.5, dt)
    wukv = normal_init(ks[3], (m.kv_lora_rank, H,
                               m.qk_nope_head_dim + m.v_head_dim),
                       m.kv_lora_rank ** -0.5, dt)
    wo = normal_init(ks[4], (H, m.v_head_dim, D),
                     (cfg.num_heads * m.v_head_dim) ** -0.5, dt)
    if H > cfg.num_heads:  # padded heads contribute exactly zero
        head_mask = (jnp.arange(H) < cfg.num_heads).astype(dt)
        wuq = wuq * head_mask[None, :, None]
        wukv = wukv * head_mask[None, :, None]
        wo = wo * head_mask[:, None, None]
    p = {
        "wdq": normal_init(ks[0], (D, m.q_lora_rank), D ** -0.5, dt),
        "wuq": wuq,
        "wdkv": normal_init(ks[2], (D, m.kv_lora_rank + m.qk_rope_head_dim),
                            D ** -0.5, dt),
        "wukv": wukv,
        "wo": wo,
        "q_norm": jnp.ones((m.q_lora_rank,), jnp.float32),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
    }
    lg = {"wdq": ("embed", None), "wuq": (None, "heads", None),
          "wdkv": ("embed", None), "wukv": (None, "heads", None),
          "wo": ("heads", None, "embed"),
          "q_norm": ("noshard",), "kv_norm": ("noshard",)}
    return p, lg


def _mla_q(p, cfg, x, positions):
    m = cfg.mla
    qa = jnp.einsum("bsd,dr->bsr", x, p["wdq"])
    qa = rmsnorm({"scale": p["q_norm"]}, qa, cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", qa, p["wuq"])
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p, cfg, x, positions):
    m = cfg.mla
    kva = jnp.einsum("bsd,dr->bsr", x, p["wdkv"])
    c_kv = rmsnorm({"scale": p["kv_norm"]},
                   kva[..., :m.kv_lora_rank], cfg.norm_eps)
    k_pe = kva[..., None, m.kv_lora_rank:]                    # (B,S,1,rope)
    k_pe = apply_rope(k_pe, positions, cfg.rope_theta)[..., 0, :]
    return c_kv, k_pe


def mla_fwd(p, cfg, x, positions, *, causal=True):
    """Expanded MLA for train/prefill. Returns (out, (c_kv, k_pe))."""
    m = cfg.mla
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv, k_pe = _mla_latent(p, cfg, x, positions)
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wukv"])
    k_nope = kv[..., :m.qk_nope_head_dim]
    v = kv[..., m.qk_nope_head_dim:]
    H = q_nope.shape[2]
    k_pe_h = jnp.broadcast_to(k_pe[:, :, None, :],
                              (*k_pe.shape[:2], H, m.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_pe_h], axis=-1)
    q = shard(q, "batch", "act_seq", "heads", None)
    k = shard(k, "batch", "act_seq", "heads", None)
    v = shard(v, "batch", "act_seq", "heads", None)
    out = blockwise_attention(q, k, v, causal=causal,
                              q_chunk=cfg.attn_q_chunk,
                              kv_chunk=cfg.attn_kv_chunk)
    out = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    c_kv_c = shard(c_kv, "batch", "kv_seq", None)
    k_pe_c = shard(k_pe, "batch", "kv_seq", None)
    return shard(out, "batch", "residual_seq", None), (c_kv_c, k_pe_c)


def mla_decode(p, cfg, x, pos, ckv_cache, kpe_cache, cache_len):
    """Absorbed-matrix MLA decode: attends in the latent space, so the cache
    is (B, S, kv_lora_rank) + (B, S, rope) — the MLA memory win."""
    m = cfg.mla
    q_nope, q_rope = _mla_q(p, cfg, x, pos[:, None])
    c_kv_new, k_pe_new = _mla_latent(p, cfg, x, pos[:, None])
    S = ckv_cache.shape[1]
    b_idx = jnp.arange(ckv_cache.shape[0])
    ckv_cache = ckv_cache.at[b_idx, cache_len].set(
        c_kv_new[:, 0].astype(ckv_cache.dtype))
    kpe_cache = kpe_cache.at[b_idx, cache_len].set(
        k_pe_new[:, 0].astype(kpe_cache.dtype))
    ckv_cache = shard(ckv_cache, "batch", "kv_seq", None)
    kpe_cache = shard(kpe_cache, "batch", "kv_seq", None)
    w_uk = p["wukv"][..., :m.qk_nope_head_dim]                # (r,H,n)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
    s = (jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv_cache.astype(q_lat.dtype),
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhp,bkp->bhqk", q_rope,
                      kpe_cache.astype(q_rope.dtype),
                      preferred_element_type=jnp.float32))
    s = s * ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5)
    mask = jnp.arange(S)[None, :] < (cache_len + 1)[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", probs.astype(x.dtype),
                       ckv_cache.astype(x.dtype))
    w_uv = p["wukv"][..., m.qk_nope_head_dim:]                # (r,H,v)
    o = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv)
    out = jnp.einsum("bqhv,hvd->bqd", o, p["wo"])
    return shard(out, "batch", "act_seq", None), ckv_cache, kpe_cache
