"""Plain float32 reference of a GQA decoder, the second served
architecture of the tests (``gqa_fixture_arch.py``), independent of the
program.  Blocks ``h += Attn(rms(h) ln1)``, ``h += W_o (silu(x W_g) *
(x W_i))`` with ``x = rms(h) ln2``; attention over ``num_key_value_heads``
shared key/value heads, RoPE (rotate-half), scale ``head_dim^-1/2``, a
causal mask; logits from the final RMS-normed state against the tied
embedding.  Float32 at ``HIGHEST``; ``fp8=True`` rounds each product's
operands to float8 e4m3 (weights per tensor, activations per row)."""
import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _mm(spec, a, b, fp8, a_axes=(-1,), b_axes=None):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = _fp8(a, a_axes), _fp8(b, b_axes)
    return jnp.einsum(spec, a, b, precision=HI)


def _fp8(x, axes):
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """x (B, S, H, d) at positions 0..S-1."""
    d = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        / theta ** (jnp.arange(d, dtype=jnp.float32) / d)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    return jnp.concatenate([x[..., :d] * c - x[..., d:] * s,
                            x[..., :d] * s + x[..., d:] * c], -1)


def logits_at(params, conf, tokens, rows, cols, fp8=False):
    """Float32 logits (n, V) at the positions ``(rows[i], cols[i])`` of
    ``tokens`` (B, S): the logits there predict the token at
    ``cols[i] + 1``."""
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    h = jnp.take(params["embed"]["tok"], jnp.asarray(tokens), 0)
    h = h.astype(jnp.float32)
    S = h.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    for i in range(conf["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        at, ff = lp["attn"], lp["ffn"]
        x = _rms(h, lp["ln1"]["scale"], eps)
        q = _rope(_mm("bsd,dhk->bshk", x, at["wq"], fp8), theta)
        k = _rope(_mm("bsd,dhk->bshk", x, at["wk"], fp8), theta)
        v = _mm("bsd,dhk->bshk", x, at["wv"], fp8)
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
        s = _mm("bqhk,bshk->bhqs", q, k, fp8, (-1,), (-1,)) \
            * q.shape[-1] ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        o = _mm("bhqs,bshk->bqhk", p, v, fp8, (-1,), (1,))
        h = h + _mm("bqhk,hkd->bqd", o, at["wo"], fp8, (-2, -1))
        x = _rms(h, lp["ln2"]["scale"], eps)
        g = _mm("bsd,df->bsf", x, ff["wg"], fp8)
        u = _mm("bsd,df->bsf", x, ff["wi"], fp8)
        h = h + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, ff["wo"], fp8)
    x = _rms(h[jnp.asarray(rows), jnp.asarray(cols)],
             params["final_norm"]["scale"], eps)
    table = params["embed"]["tok"][:conf["vocab_size"]]
    return _mm("nd,vd->nv", x, table, fp8)
