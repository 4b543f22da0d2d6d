"""MiniCPM3 (hf:openbmb/MiniCPM3-4B): a dense decoder with multi-head
latent attention (MLA, a query bottleneck of rank ``q_lora_rank``) and
SwiGLU, served by the program's ``dense`` family with an ``MLAConfig``.
See ``chipbench/archs/__init__.py`` for the interface."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.flops import request_flops  # noqa: F401  (the interface)
from chipbench.refs.minicpm3 import logits_at  # noqa: F401  (the interface)

#: the keys that cut the configuration to a size the CPU runs in seconds
SMOKE = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, intermediate_size=128, vocab_size=512,
             q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
             qk_rope_head_dim=8, v_head_dim=8)


def model_config(conf: dict, name: str = "served"):
    """The program's ``ModelConfig`` for the configuration's keys."""
    from repro.configs.base import MLAConfig, ModelConfig
    return ModelConfig(
        name=name, family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["v_head_dim"], d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        mla=MLAConfig(q_lora_rank=conf["q_lora_rank"],
                      kv_lora_rank=conf["kv_lora_rank"],
                      qk_nope_head_dim=conf["qk_nope_head_dim"],
                      qk_rope_head_dim=conf["qk_rope_head_dim"],
                      v_head_dim=conf["v_head_dim"]),
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        dtype=conf["torch_dtype"]).resolve(tp=1)


def _fan_in(path: str, shape) -> int:
    """Fan-in of a per-layer matrix (the layer axis dropped)."""
    if path.endswith("attn/wo"):                  # (H, v, D)
        return shape[0] * shape[1]
    return shape[0]


def draw(key, path: str, sd):
    """Matrices N(0, 1/fan_in), the embedding N(0, 0.02^2), norm scales
    1 + N(0, 0.1^2)."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("scale", "q_norm", "kv_norm"):
        x = 1.0 + 0.1 * jax.random.normal(key, sd.shape, jnp.float32)
        return x.astype(sd.dtype)
    if leaf == "tok":
        std = 0.02
    else:
        per_layer = sd.shape[1:] if path.startswith("layers") else sd.shape
        std = _fan_in(path, per_layer) ** -0.5
    return (jax.random.normal(key, sd.shape, sd.dtype)
            * jnp.asarray(std, sd.dtype))
