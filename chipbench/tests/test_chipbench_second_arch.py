"""A second served architecture plugs in from new files only.

The fixture under ``data/`` is such a set of files: a GQA decoder's
configuration (``gqa_fixture.json``), its architecture module
(``gqa_fixture_arch.py``) and its plain reference
(``gqa_fixture_ref.py``).  The tests register the two modules under the
names a later change would give them as files, ``chipbench.archs.*`` and
``chipbench.refs.*``, and run the served cell through the harness with
no other change: set-up, window, the check against the reference, the
metric readers (``serve.model_mfu.tail`` counts the fixture's FLOPs).
"""
import importlib.util
import os
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import archs, harness
from chipbench.kinds import serve
from chipbench.smoke import DEVICE, cut
from chipbench.trace import Tracer

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAME = "gqa_fixture"


@pytest.fixture(scope="module")
def arch():
    with pytest.MonkeyPatch.context() as mp:
        for pkg, file in (("refs", "_ref.py"), ("archs", "_arch.py")):
            name = f"chipbench.{pkg}.{NAME}"
            spec = importlib.util.spec_from_file_location(
                name, os.path.join(DATA, NAME + file))
            mod = importlib.util.module_from_spec(spec)
            mp.setitem(sys.modules, name, mod)
            spec.loader.exec_module(mod)
        yield archs.load(NAME)


def cell():
    """The fixture's served cell at smoke size, with the chat traffic and
    the metrics of the benchmark's served cell."""
    like = harness.load_cell("serve-chat-tail")
    return cut(harness.Cell(
        name="gqa-fixture-chat", chips=1, config_name=NAME,
        config=harness.load_json(os.path.join(DATA, NAME + ".json")),
        traffic=like.traffic, end_to_end=like.end_to_end,
        per_layer=like.per_layer))


def test_the_fixture_has_the_interface(arch):
    assert all(hasattr(arch, k) for k in archs.INTERFACE)
    c = cell().config
    assert archs.of(c) is arch
    mcfg = serve.model_config(c)
    assert mcfg.mla is None and mcfg.num_kv_heads < mcfg.num_heads


@pytest.mark.parametrize("trace", [False, True])
def test_the_fixture_runs_end_to_end_through_run_cell(arch, trace):
    c = cell()
    line = harness.run_cell(c, seed=2 ** 33 + 3, seconds=2.0, trace=trace,
                            t0=time.perf_counter(), devs=[DEVICE])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert float(line["checks"]["served_logit_gap"]["value"]) <= \
        line["checks"]["served_logit_gap"]["limit"]
    if not trace:
        assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}
    else:
        want = {m["name"] for m in c.per_layer
                if m["source"] != "device_trace"}
        assert set(line["metrics"]) == want
        assert line["metrics"]["serve.model_mfu.tail"]["value"] > 0


@pytest.mark.parametrize("seed", [5, 2 ** 33 + 9])
def test_the_fixtures_float8_control_fails_the_limit(arch, seed):
    c = cell()
    out = serve.run(c, seed=seed, seconds=2.0, tracer=Tracer(False),
                    t0=time.perf_counter(), devs=[DEVICE], control=True)
    checks = {k.name: k for k in out.checks}
    assert checks["served_logit_gap"].ok
    assert not checks["control_served_logit_gap"].ok


def test_the_fixtures_flops_match_its_weights(arch):
    """Twice the per-layer matrix entries, the logits and attention over
    one position: a prompt of one token."""
    import jax
    from repro.models import model as M
    conf = harness.load_json(os.path.join(DATA, NAME + ".json"))
    mcfg = serve.model_config(conf)
    shapes = jax.eval_shape(lambda k: M.init_params(k, mcfg),
                            jax.random.PRNGKey(0))
    per_layer = sum(x.size // x.shape[0]
                    for x in jax.tree.leaves(shapes["layers"])
                    if x.ndim >= 3)
    L, D, V = conf["num_hidden_layers"], conf["hidden_size"], \
        conf["vocab_size"]
    attn = 4 * conf["num_attention_heads"] * conf["head_dim"]
    assert arch.request_flops(conf, 1, 1) == \
        L * (2 * per_layer + attn) + 2 * D * V


def test_the_reference_reads_the_program_weights(arch):
    """The reference and the program's prefill agree on the first
    served token's logits at smoke size."""
    from repro.serving.engine import jit_prefill
    c = cell().config
    mcfg = serve.model_config(c)
    params = serve.make_weights(mcfg, 4)
    toks = np.random.default_rng(4).integers(0, mcfg.vocab_size, (2, 16))
    logits, _ = jit_prefill(params, cfg=mcfg,
                            batch={"tokens": jnp.asarray(toks, jnp.int32)},
                            cache_len=24)
    ref = arch.logits_at(params, c, toks, [0, 1], [15, 15])
    got = np.asarray(logits[:, :mcfg.vocab_size], np.float32)
    assert np.argmax(got, -1).tolist() == np.argmax(ref, -1).tolist()
    np.testing.assert_allclose(got, ref, atol=0.05 * float(ref.std()))
