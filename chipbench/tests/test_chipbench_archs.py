"""The architecture modules against the code that each replaced, and the
program's counters as a served run records them.

``_old_*`` below are copies of what ``kinds/serve.py`` and ``smoke.py``
hard-coded for MiniCPM3 before ``archs/minicpm3.py`` took it over; the
module has to give the same configuration, the same weights bit for
bit, the same FLOPs and the same smoke size.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import archs, flops, harness
from chipbench.archs import minicpm3
from chipbench.kinds import serve
from chipbench.smoke import DEVICE, small
from chipbench.trace import Tracer

_OLD_SERVE_SMALL = dict(num_hidden_layers=2, hidden_size=64,
                        num_attention_heads=4, num_key_value_heads=4,
                        intermediate_size=128, vocab_size=512,
                        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
                        qk_rope_head_dim=8, v_head_dim=8)


def _old_model_config(conf, name="served"):
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


def _old_fan_in(path, shape):
    if path.endswith("attn/wo"):
        return shape[0] * shape[1]
    return shape[0]


def _old_one(key, path, sd):
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("scale", "q_norm", "kv_norm"):
        x = 1.0 + 0.1 * jax.random.normal(key, sd.shape, jnp.float32)
        return x.astype(sd.dtype)
    if leaf == "tok":
        std = 0.02
    else:
        per_layer = sd.shape[1:] if path.startswith("layers") else sd.shape
        std = _old_fan_in(path, per_layer) ** -0.5
    return (jax.random.normal(key, sd.shape, sd.dtype)
            * jnp.asarray(std, sd.dtype))


def _leaves(mcfg):
    """(path, ShapeDtypeStruct) of every weight the program makes."""
    from repro.models import model as M
    shapes = jax.eval_shape(lambda k: M.init_params(k, mcfg),
                            jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return [("/".join(str(getattr(k, "key", k)) for k in p), sd)
            for p, sd in flat]


def _old_make_weights(mcfg, seed):
    flat = _leaves(mcfg)

    @jax.jit
    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        return [_old_one(jax.random.fold_in(key, i), p, sd)
                for i, (p, sd) in enumerate(flat)]
    s = int(seed)
    return build(np.uint32(s & 0xFFFFFFFF), np.uint32((s >> 32) & 0xFFFFFFFF))


@pytest.fixture(scope="module")
def full():
    return harness.load_cell("serve-chat-tail").config


@pytest.fixture(scope="module")
def smoke():
    return small("serve-chat-tail").config


def test_the_served_configuration_names_its_module(full):
    assert archs.of(full) is minicpm3
    assert "reference" not in full
    assert all(hasattr(minicpm3, k) for k in archs.INTERFACE)


@pytest.mark.parametrize("size", ["full", "smoke"])
def test_model_config_is_the_hard_coded_one(request, size):
    conf = request.getfixturevalue(size)
    assert minicpm3.model_config(conf, "x") == _old_model_config(conf, "x")
    assert serve.model_config(conf) == _old_model_config(conf)


def test_smoke_is_the_old_serve_small(full, smoke):
    assert minicpm3.SMOKE == _OLD_SERVE_SMALL
    assert smoke == dict(full, **_OLD_SERVE_SMALL,
                         fleet=dict(full["fleet"],
                                    slowdowns_s=[0.0, 0.002, 0.004]))


def test_every_weight_path_is_drawn_as_before_at_published_size(full):
    """The same traced draw for every leaf of the 62-layer model (nothing
    is computed: the programs are compared)."""
    key = jax.random.PRNGKey(0)
    leaves = _leaves(_old_model_config(full))
    assert len(leaves) == 14
    for path, sd in leaves:
        new = jax.make_jaxpr(lambda k: minicpm3.draw(k, path, sd))(key)
        old = jax.make_jaxpr(lambda k: _old_one(k, path, sd))(key)
        assert str(new) == str(old), path


@pytest.mark.parametrize("seed", [7, 2 ** 33 + 11])
def test_weights_are_bit_identical_at_smoke_size(smoke, seed):
    mcfg = serve.model_config(smoke)
    new = jax.tree_util.tree_leaves(serve.make_weights(mcfg, seed))
    old = _old_make_weights(mcfg, seed)
    assert len(new) == len(old) == len(_leaves(mcfg))
    for a, b in zip(new, old):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a.view(jnp.uint16)
                                                 if a.dtype == jnp.bfloat16
                                                 else a),
                                      np.asarray(b.view(jnp.uint16)
                                                 if b.dtype == jnp.bfloat16
                                                 else b))


@pytest.mark.parametrize("p,n", [(1020, 1), (1020, 129), (16, 8), (7, 512)])
def test_request_flops_is_the_flops_module(full, smoke, p, n):
    for conf in (full, smoke):
        assert minicpm3.request_flops(conf, p, n) == \
            flops.request_flops(conf, p, n)


# ----------------------------------------------------------------------
# the program's counters in the record
def test_counters_differ_by_one_engines_busy_over_a_wave(smoke):
    from repro.serving.engine import Request, ServingEngine
    mcfg = serve.model_config(smoke)
    params = serve.make_weights(mcfg, 3)
    engines = [ServingEngine(mcfg, params, node=f"n{i}", max_batch=2,
                             max_seq=24) for i in range(3)]
    before = serve.counters(engines)
    assert before["busy_s"] == 0.0 and before["max_batch"] == 6.0
    busy0 = engines[1].busy_s
    engines[1].submit(Request(rid=0, tokens=np.arange(16, dtype=np.int32),
                              max_new_tokens=3))
    engines[1].step_wave()
    after = serve.counters(engines)
    assert after["busy_s"] - before["busy_s"] == engines[1].busy_s - busy0
    assert after["busy_s"] > 0
    assert {k: v for k, v in after.items() if k != "busy_s"} == \
        {k: v for k, v in before.items() if k != "busy_s"}
    # private attributes and non-numbers stay out
    assert "_tok_count" not in after and "node" not in after


@pytest.mark.parametrize("trace", [False, True])
def test_record_marks_the_window_and_the_trace(trace):
    cell = small("serve-chat-tail")
    out = serve.run(cell, seed=2 ** 33 + 21, seconds=2.0,
                    tracer=Tracer(trace), t0=time.perf_counter(),
                    devs=[DEVICE])
    rec = out.record
    marks = rec["marks"]
    want = ["start", "trace_start", "trace_stop", "end"] if trace \
        else ["start", "end"]
    assert list(marks) == want
    ts = [marks[k]["t"] for k in want]
    assert ts == sorted(ts)
    # the span's busy seconds are the engines' counter between its marks
    stop = "trace_stop" if trace else "end"
    assert rec["span"]["busy"] == (marks[stop]["engines"]["busy_s"]
                                   - marks["start"]["engines"]["busy_s"])
    assert rec["span"]["busy"] > 0
    # the program's spans: every route call of the window is counted
    routes = (marks["end"]["spans"]["route"][0]
              - marks["start"]["spans"].get("route", (0, 0.0))[0])
    assert routes == len(rec["route_s"])
    # each request's wave start lies between its due time and its end
    started, done = rec["t_start_s"], rec["done_at_s"]
    assert np.all(np.isfinite(started))
    assert np.all(started <= done)
