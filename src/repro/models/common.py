"""Shared building blocks: initializers, norms, RoPE / M-RoPE, embeddings.

All modules are functional: ``init_*`` returns ``(params, logical)`` where
``logical`` mirrors the param pytree with tuples of logical axis names used
for sharding (see repro.parallel.sharding).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.sharding import shard


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def normal_init(key, shape, stddev, dtype):
    return (stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                 jnp.float32)).astype(dtype)


# ----------------------------------------------------------------------
# RMSNorm
def init_rmsnorm(d: int, dtype) -> Tuple[dict, dict]:
    return {"scale": jnp.ones((d,), dtype=jnp.float32)}, {"scale": ("noshard",)}


def rmsnorm(p, x, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


# ----------------------------------------------------------------------
# Rotary embeddings
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: (B, S, H, D); positions: (B, S) int32 -> rotated x."""
    inv = rope_freqs(x.shape[-1], theta)                     # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * inv     # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
                sections: Tuple[int, int, int]):
    """Qwen2-VL multimodal RoPE.

    positions: (B, S, 3) — (temporal, height, width) position ids.  The
    D/2 frequency channels are partitioned into ``sections`` (t, h, w); each
    partition takes its angle from the corresponding position component.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    inv = rope_freqs(x.shape[-1], theta)                     # (D/2,)
    ang_per = positions[..., None, :].astype(jnp.float32) * inv[None, None, :, None]
    # ang_per: (B, S, D/2, 3); select the section-owner component per channel
    sel = jnp.repeat(jnp.arange(3), jnp.array(sections), total_repeat_length=half)
    ang = jnp.take_along_axis(ang_per, sel[None, None, :, None], axis=-1)[..., 0]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# Embedding + logits (padded vocab, vocab-parallel)
def init_embedding(key, cfg) -> Tuple[dict, dict]:
    dt = dtype_of(cfg)
    V, D = cfg.padded_vocab, cfg.d_model
    p = {"tok": normal_init(key, (V, D), 0.02, dt)}
    lg = {"tok": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["head"] = normal_init(k2, (V, D), cfg.d_model ** -0.5, dt)
        lg["head"] = ("vocab", "embed")
    return p, lg


def embed_tokens(p, cfg, tokens):
    emb = p["tok"]
    x = jnp.take(emb, tokens, axis=0)
    return shard(x, "batch", "act_seq", None)


def logits_from_hidden(p, cfg, h):
    """h: (B, S, D) -> logits (B, S, V_padded) f32 (padded vocab = -inf)."""
    table = p["tok"] if cfg.tie_embeddings else p["head"]
    logits = jnp.einsum("bsd,vd->bsv", h, table,
                        preferred_element_type=jnp.float32)
    logits = shard(logits, "batch", "act_seq", "vocab")
    if cfg.padded_vocab > cfg.vocab_size:
        mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(mask[None, None, :], logits, -1e30)
    return logits


# ----------------------------------------------------------------------
# SwiGLU MLP (column-parallel in, row-parallel out)
def init_mlp(key, cfg, d_ff: Optional[int] = None, d_in: Optional[int] = None,
             swiglu: bool = True) -> Tuple[dict, dict]:
    dt = dtype_of(cfg)
    D = d_in or cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"wi": normal_init(ks[0], (D, F), D ** -0.5, dt),
         "wo": normal_init(ks[1], (F, D), F ** -0.5, dt)}
    lg = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
    if swiglu:
        p["wg"] = normal_init(ks[2], (D, F), D ** -0.5, dt)
        lg["wg"] = ("embed", "mlp")
    return p, lg


def mlp(p, x, swiglu: bool = True):
    h = jnp.einsum("...d,df->...f", x, p["wi"])
    if swiglu:
        g = jnp.einsum("...d,df->...f", x, p["wg"])
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    h = shard(h, *(("batch",) + (None,) * (h.ndim - 2) + ("mlp",)))
    return jnp.einsum("...f,fd->...d", h, p["wo"])


# ----------------------------------------------------------------------
def stack_layer_params(init_one, key, n: int):
    """vmap an init function over layer indices -> stacked (n, ...) leaves."""
    keys = jax.random.split(key, n)
    params = jax.vmap(init_one)(keys)
    _, logical = jax.eval_shape(init_one, keys[0]), None
    return params


def chunked_cross_entropy(logits_fn, h, labels, cfg, valid_mask=None):
    """Cross-entropy computed in seq chunks to avoid a (B,S,V) f32 buffer.

    logits_fn: h_chunk (B, C, D) -> logits (B, C, V) f32.
    labels: (B, S) int32.  Returns (mean_nll, token_count).
    """
    B, S, D = h.shape
    C = min(cfg.loss_chunk, S)
    n = S // C
    assert S % C == 0, (S, C)
    h = h.reshape(B, n, C, D).swapaxes(0, 1)          # (n, B, C, D)
    labels = labels.reshape(B, n, C).swapaxes(0, 1)    # (n, B, C)
    if valid_mask is None:
        valid = jnp.ones_like(labels, dtype=jnp.float32)
    else:
        valid = valid_mask.reshape(B, n, C).swapaxes(0, 1).astype(jnp.float32)

    def body(carry, xs):
        tot, cnt = carry
        hc, lc, vc = xs
        logits = logits_fn(hc)                         # (B, C, V) f32
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (lse - picked) * vc
        return (tot + nll.sum(), cnt + vc.sum()), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                                 (h, labels, valid))
    return tot / jnp.maximum(cnt, 1.0), cnt
