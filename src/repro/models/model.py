"""Model assembly + family dispatch.

Public API (all pure JAX, usable under jit / eval_shape / lower):

  init_params(key, cfg)            -> params pytree
  params_logical(cfg)              -> matching pytree of logical-axis tuples
  train_forward(params, cfg, batch)-> (loss, metrics)
  prefill(params, cfg, batch)      -> (last_logits (B, V), cache)
  decode_step(params, cfg, cache, tokens) -> (logits (B, V), cache)
  init_cache(cfg, B, S)            -> zeroed cache pytree
  cache_logical(cfg, B?)           -> logical-axis tuples for the cache

Layers are stacked and scanned (one compiled body regardless of depth);
remat policy per cfg.remat.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import encdec, hybrid
from repro.models.attention import (attention_decode, attention_fwd, init_attention,
                                    init_mla, mla_decode, mla_fwd)
from repro.models.common import (chunked_cross_entropy, dtype_of, embed_tokens,
                                 init_embedding, init_mlp, init_rmsnorm,
                                 logits_from_hidden, mlp, rmsnorm)
from repro.models.moe import init_moe, moe_ffn
from repro.models.ssm import init_mamba2, mamba2_decode, mamba2_fwd
from repro.parallel.sharding import shard


# ----------------------------------------------------------------------
# generic helpers
def capture_logical(init_fn, key):
    """Trace ``init_fn`` (no FLOPs) and capture its logical-axis tree."""
    box = {}

    def f(k):
        p, lg = init_fn(k)
        box["lg"] = lg
        return p

    jax.eval_shape(f, key)
    return box["lg"]


def stacked_init(init_fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_fn(k)[0])(keys)


def stacked_logical(init_fn, key):
    lg = capture_logical(init_fn, key)
    return jax.tree.map(lambda axes: ("layers",) + axes, lg,
                        is_leaf=lambda x: isinstance(x, tuple))


def scan_or_unroll(cfg, body, carry, xs):
    """lax.scan when cfg.scan_layers else a python-unrolled loop.

    The unrolled path exists for the roofline: XLA's cost_analysis counts a
    while-loop body ONCE (not x trip-count), so per-layer marginal FLOPs /
    bytes / collective-bytes are measured from unrolled L=1 vs L=2 compiles
    and extrapolated to full depth (see benchmarks/roofline.py).
    """
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


def maybe_remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def default_positions(cfg, B, S, offset=0):
    pos = jnp.arange(S, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.mrope:
        return jnp.broadcast_to(pos[..., None], (B, S, 3))
    return pos


# ----------------------------------------------------------------------
# decoder-only layer (dense / MoE / MLA / VLM share this)
def _init_dec_layer(key, cfg):
    ks = jax.random.split(key, 4)
    p, lg = {}, {}
    if cfg.mla is not None:
        p["attn"], lg["attn"] = init_mla(ks[0], cfg)
    else:
        p["attn"], lg["attn"] = init_attention(ks[0], cfg)
    p["ln1"], lg["ln1"] = init_rmsnorm(cfg.d_model, None)
    p["ln2"], lg["ln2"] = init_rmsnorm(cfg.d_model, None)
    if cfg.moe is not None:
        p["ffn"], lg["ffn"] = init_moe(ks[1], cfg)
    else:
        p["ffn"], lg["ffn"] = init_mlp(ks[1], cfg)
    return p, lg


def _dec_layer_fwd(cfg, lp, h, positions):
    # Megatron-SP choreography: ONE bf16 all-gather of the normed input per
    # sublayer (q/k/v and mlp dots reuse it), and sublayer outputs are
    # constrained seq-sharded BEFORE the residual add so the row-parallel
    # all-reduce lowers to a reduce-scatter (attributed from HLO: the naive
    # placement gathered the f32 residual 3x per layer and used ARs).
    a_in = jax.lax.optimization_barrier(
        shard(rmsnorm(lp["ln1"], h, cfg.norm_eps), "batch", "act_seq", None))
    if cfg.mla is not None:
        a, kv = mla_fwd(lp["attn"], cfg, a_in, positions, causal=cfg.causal)
    else:
        a, kv = attention_fwd(lp["attn"], cfg, a_in, positions,
                              causal=cfg.causal)
    a = shard(a, "batch", "residual_seq", None)
    h = shard(h + a, "batch", "residual_seq", None)
    f_in = jax.lax.optimization_barrier(
        shard(rmsnorm(lp["ln2"], h, cfg.norm_eps), "batch", "act_seq", None))
    if cfg.moe is not None:
        f, aux = moe_ffn(lp["ffn"], cfg, f_in, use_pallas=cfg.use_pallas)
    else:
        f, aux = mlp(lp["ffn"], f_in), jnp.float32(0.0)
    f = shard(f, "batch", "residual_seq", None)
    return h + f, aux, kv


def _merge_vision(cfg, h, batch):
    ve = batch.get("vision_embeds")
    if ve is None or cfg.num_frontend_tokens == 0:
        return h
    n = ve.shape[1]
    return jnp.concatenate([ve.astype(h.dtype), h[:, n:, :]], axis=1)


def _dec_backbone(params, cfg, batch, collect_cache: bool):
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = embed_tokens(params["embed"], cfg, tokens)
    if cfg.family == "vlm":
        h = _merge_vision(cfg, h, batch)
    positions = batch.get("positions")
    if positions is None:
        positions = default_positions(cfg, B, S)

    def body(carry, lp):
        hh, aux_acc = carry
        hh, aux, kv = _dec_layer_fwd(cfg, lp, hh, positions)
        hh = shard(hh, "batch", "residual_seq", None)
        return (hh, aux_acc + aux), kv if collect_cache else None

    h = shard(h, "batch", "residual_seq", None)
    body = maybe_remat(cfg, body)
    (h, aux), kvs = scan_or_unroll(cfg, body, (h, jnp.float32(0.0)),
                                   params["layers"])
    h = shard(h, "batch", "act_seq", None)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return h, aux / cfg.num_layers, kvs


def _dec_train_forward(params, cfg, batch):
    h, aux, _ = _dec_backbone(params, cfg, batch, collect_cache=False)
    loss, cnt = chunked_cross_entropy(
        lambda hc: logits_from_hidden(params["embed"], cfg, hc),
        h, batch["labels"], cfg, batch.get("loss_mask"))
    total = loss + aux
    return total, {"loss": loss, "aux_loss": aux, "tokens": cnt}


def _dec_prefill(params, cfg, batch, cache_len: Optional[int] = None):
    h, _, kvs = _dec_backbone(params, cfg, batch, collect_cache=True)
    B, S = batch["tokens"].shape
    logits = logits_from_hidden(params["embed"], cfg, h[:, -1:, :])[:, 0]
    if cfg.mla is not None:
        ckv, kpe = kvs
        cache = {"ckv": _pad_seq(ckv, 2, cache_len),
                 "kpe": _pad_seq(kpe, 2, cache_len),
                 "len": jnp.full((B,), S, jnp.int32)}
        cache["ckv"] = shard(cache["ckv"], None, "batch", "kv_seq", None)
        cache["kpe"] = shard(cache["kpe"], None, "batch", "kv_seq", None)
    else:
        k, v = kvs
        cache = {"k": _pad_seq(k, 2, cache_len), "v": _pad_seq(v, 2, cache_len),
                 "len": jnp.full((B,), S, jnp.int32)}
        cache["k"] = shard(cache["k"], None, "batch", "kv_seq", "kv_heads", None)
        cache["v"] = shard(cache["v"], None, "batch", "kv_seq", "kv_heads", None)
    return logits, cache


def _pad_seq(x, axis, target: Optional[int]):
    if target is None or target <= x.shape[axis]:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pad)


def cache_read(stack, i):
    return jax.lax.dynamic_index_in_dim(stack, i, 0, keepdims=False)


def cache_write(stack, val, i):
    return jax.lax.dynamic_update_index_in_dim(
        stack, val.astype(stack.dtype)[None], i, 0)


def _dec_decode(params, cfg, cache, tokens):
    """Caches are scan CARRIES updated in place with dynamic_update_index:
    passing them as scan xs/ys makes XLA double-buffer the full stack (and
    hoist a full-stack f32 dot-operand convert on CPU) — observed ~12 GB of
    avoidable copies on the 88-layer decode cell."""
    B = tokens.shape[0]
    h = embed_tokens(params["embed"], cfg, tokens)          # (B,1,D)
    pos = cache["len"]
    idx = jnp.arange(cfg.num_layers)

    if cfg.mla is not None:
        def body(carry, xs):
            hh, ckvs, kpes = carry
            lp, i = xs
            ckv, kpe = cache_read(ckvs, i), cache_read(kpes, i)
            a_in = rmsnorm(lp["ln1"], hh, cfg.norm_eps)
            a, ckv, kpe = mla_decode(lp["attn"], cfg, a_in, pos, ckv, kpe,
                                     cache["len"])
            hh = hh + a
            f_in = rmsnorm(lp["ln2"], hh, cfg.norm_eps)
            f = (moe_ffn(lp["ffn"], cfg, f_in)[0] if cfg.moe is not None
                 else mlp(lp["ffn"], f_in))
            return (hh + f, cache_write(ckvs, ckv, i),
                    cache_write(kpes, kpe, i)), None

        (h, ckvs, kpes), _ = scan_or_unroll(
            cfg, body, (h, cache["ckv"], cache["kpe"]),
            (params["layers"], idx))
        new_cache = {"ckv": ckvs, "kpe": kpes, "len": cache["len"] + 1}
    else:
        int8 = cfg.kv_cache_dtype == "int8"

        def body(carry, xs):
            hh, ks, vs, kss, vss = carry
            lp, i = xs
            kc, vc = cache_read(ks, i), cache_read(vs, i)
            scales = ((cache_read(kss, i), cache_read(vss, i))
                      if int8 else None)
            a_in = rmsnorm(lp["ln1"], hh, cfg.norm_eps)
            a, kc, vc, scales = attention_decode(
                lp["attn"], cfg, a_in, pos, kc, vc, cache["len"],
                scales=scales)
            hh = hh + a
            f_in = rmsnorm(lp["ln2"], hh, cfg.norm_eps)
            f = (moe_ffn(lp["ffn"], cfg, f_in)[0] if cfg.moe is not None
                 else mlp(lp["ffn"], f_in))
            if int8:
                kss = cache_write(kss, scales[0], i)
                vss = cache_write(vss, scales[1], i)
            return (hh + f, cache_write(ks, kc, i),
                    cache_write(vs, vc, i), kss, vss), None

        dummy = jnp.zeros((cfg.num_layers, 1), jnp.float32)
        (h, ks, vs, kss, vss), _ = scan_or_unroll(
            cfg, body,
            (h, cache["k"], cache["v"],
             cache.get("k_scale", dummy), cache.get("v_scale", dummy)),
            (params["layers"], idx))
        new_cache = {"k": ks, "v": vs, "len": cache["len"] + 1}
        if int8:
            new_cache["k_scale"] = kss
            new_cache["v_scale"] = vss
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = logits_from_hidden(params["embed"], cfg, h)[:, 0]
    return logits, new_cache


def _dec_init_params(key, cfg):
    ks = jax.random.split(key, 3)
    p = {"embed": init_embedding(ks[0], cfg)[0],
         "layers": stacked_init(lambda k: _init_dec_layer(k, cfg), ks[1],
                                cfg.num_layers),
         "final_norm": init_rmsnorm(cfg.d_model, None)[0]}
    return p


def _dec_params_logical(cfg):
    key = jax.random.PRNGKey(0)
    return {"embed": capture_logical(lambda k: init_embedding(k, cfg), key),
            "layers": stacked_logical(lambda k: _init_dec_layer(k, cfg), key),
            "final_norm": capture_logical(
                lambda k: init_rmsnorm(cfg.d_model, None), key)}


def _dec_init_cache(cfg, B, S, dtype=jnp.bfloat16):
    L = cfg.num_layers
    if cfg.mla is not None:
        m = cfg.mla
        return {"ckv": jnp.zeros((L, B, S, m.kv_lora_rank), dtype),
                "kpe": jnp.zeros((L, B, S, m.qk_rope_head_dim), dtype),
                "len": jnp.zeros((B,), jnp.int32)}
    if cfg.kv_cache_dtype == "int8":
        KV = cfg.padded_kv
        return {"k": jnp.zeros((L, B, S, KV, cfg.head_dim), jnp.int8),
                "v": jnp.zeros((L, B, S, KV, cfg.head_dim), jnp.int8),
                "k_scale": jnp.zeros((L, B, S, KV), jnp.float32),
                "v_scale": jnp.zeros((L, B, S, KV), jnp.float32),
                "len": jnp.zeros((B,), jnp.int32)}
    return {"k": jnp.zeros((L, B, S, cfg.padded_kv, cfg.head_dim), dtype),
            "v": jnp.zeros((L, B, S, cfg.padded_kv, cfg.head_dim), dtype),
            "len": jnp.zeros((B,), jnp.int32)}


def _dec_cache_logical(cfg):
    if cfg.mla is not None:
        return {"ckv": ("layers", "batch", "kv_seq", None),
                "kpe": ("layers", "batch", "kv_seq", None),
                "len": ("noshard",)}
    lg = {"k": ("layers", "batch", "kv_seq", "kv_heads", None),
          "v": ("layers", "batch", "kv_seq", "kv_heads", None),
          "len": ("noshard",)}
    if cfg.kv_cache_dtype == "int8":
        lg["k_scale"] = ("layers", "batch", "kv_seq", "kv_heads")
        lg["v_scale"] = ("layers", "batch", "kv_seq", "kv_heads")
    return lg


# ----------------------------------------------------------------------
# public dispatch
_DEC_FAMILIES = ("dense", "moe", "vlm")


def init_params(key, cfg):
    if cfg.family in _DEC_FAMILIES:
        return _dec_init_params(key, cfg)
    if cfg.family in ("ssm", "hybrid"):
        return hybrid.init_params(key, cfg)
    if cfg.family == "encdec":
        return encdec.init_params(key, cfg)
    raise ValueError(cfg.family)


def params_logical(cfg):
    if cfg.family in _DEC_FAMILIES:
        return _dec_params_logical(cfg)
    if cfg.family in ("ssm", "hybrid"):
        return hybrid.params_logical(cfg)
    if cfg.family == "encdec":
        return encdec.params_logical(cfg)
    raise ValueError(cfg.family)


def train_forward(params, cfg, batch):
    if cfg.family in _DEC_FAMILIES:
        return _dec_train_forward(params, cfg, batch)
    if cfg.family in ("ssm", "hybrid"):
        return hybrid.train_forward(params, cfg, batch)
    if cfg.family == "encdec":
        return encdec.train_forward(params, cfg, batch)
    raise ValueError(cfg.family)


def prefill(params, cfg, batch, cache_len=None):
    if cfg.family in _DEC_FAMILIES:
        return _dec_prefill(params, cfg, batch, cache_len)
    if cfg.family in ("ssm", "hybrid"):
        return hybrid.prefill(params, cfg, batch, cache_len)
    if cfg.family == "encdec":
        return encdec.prefill(params, cfg, batch, cache_len)
    raise ValueError(cfg.family)


def decode_step(params, cfg, cache, tokens):
    if cfg.family in _DEC_FAMILIES:
        return _dec_decode(params, cfg, cache, tokens)
    if cfg.family in ("ssm", "hybrid"):
        return hybrid.decode_step(params, cfg, cache, tokens)
    if cfg.family == "encdec":
        return encdec.decode_step(params, cfg, cache, tokens)
    raise ValueError(cfg.family)


def init_cache(cfg, B, S, dtype=jnp.bfloat16):
    if cfg.family in _DEC_FAMILIES:
        return _dec_init_cache(cfg, B, S, dtype)
    if cfg.family in ("ssm", "hybrid"):
        return hybrid.init_cache(cfg, B, S, dtype)
    if cfg.family == "encdec":
        return encdec.init_cache(cfg, B, S, dtype)
    raise ValueError(cfg.family)


def cache_logical(cfg):
    if cfg.family in _DEC_FAMILIES:
        return _dec_cache_logical(cfg)
    if cfg.family in ("ssm", "hybrid"):
        return hybrid.cache_logical(cfg)
    if cfg.family == "encdec":
        return encdec.cache_logical(cfg)
    raise ValueError(cfg.family)


def build_model(cfg):
    """Convenience bundle of partials bound to cfg."""
    return {
        "init": functools.partial(init_params, cfg=cfg),
        "logical": functools.partial(params_logical, cfg=cfg),
        "train_forward": functools.partial(train_forward, cfg=cfg),
        "prefill": functools.partial(prefill, cfg=cfg),
        "decode_step": functools.partial(decode_step, cfg=cfg),
        "init_cache": functools.partial(init_cache, cfg=cfg),
        "cache_logical": functools.partial(cache_logical, cfg=cfg),
    }
