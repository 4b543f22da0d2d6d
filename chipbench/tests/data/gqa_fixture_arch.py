"""A second served architecture for the tests, plugged in from new files
only: a GQA decoder (``num_key_value_heads`` shared key/value heads,
SwiGLU, tied embedding) on the program's ``attention_fwd`` /
``attention_decode`` and its k/v cache.  The tests register it as
``chipbench.archs.gqa_fixture`` and its reference
(``gqa_fixture_ref.py``) as ``chipbench.refs.gqa_fixture``, as a later
change would add them as files."""
import jax
import jax.numpy as jnp

from chipbench.refs.gqa_fixture import logits_at  # noqa: F401

SMOKE = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=128,
             vocab_size=512)


def model_config(conf, name="served"):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=name, family="dense", num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        dtype=conf["torch_dtype"]).resolve(tp=1)


def draw(key, path, sd):
    """Matrices N(0, 1/fan_in), the embedding N(0, 0.02^2), norm scales
    1 + N(0, 0.1^2)."""
    if path.endswith("/scale"):
        x = 1.0 + 0.1 * jax.random.normal(key, sd.shape, jnp.float32)
        return x.astype(sd.dtype)
    shape = sd.shape[1:] if path.startswith("layers") else sd.shape
    fan_in = shape[0] * shape[1] if path.endswith("attn/wo") else shape[0]
    std = 0.02 if path == "embed/tok" else fan_in ** -0.5
    return jax.random.normal(key, sd.shape, sd.dtype) \
        * jnp.asarray(std, sd.dtype)


def request_flops(conf, prompt_len, new_tokens):
    """Prefill of the prompt under a causal mask, then one decode step a
    further token; matrix products and attention, and the logits."""
    D, L, F = conf["hidden_size"], conf["num_hidden_layers"], \
        conf["intermediate_size"]
    H, KV, dh = conf["num_attention_heads"], conf["num_key_value_heads"], \
        conf["head_dim"]
    mm = 2 * (2 * D * H * dh + 2 * D * KV * dh + 3 * D * F)
    lg = 2 * D * conf["vocab_size"]
    p = prompt_len
    total = L * (p * mm + 4 * H * dh * p * (p + 1) // 2) + lg
    return total + sum(L * (mm + 4 * H * dh * (p + k)) + lg
                       for k in range(1, new_tokens))
