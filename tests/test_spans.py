"""Spans (``core/telemetry.span``): the recorder's naming and totals, the
compiled pass's steps in ``LAST_PHASES``, the served engine's spans and
wave start, and the names the device programs carry."""
import re
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import simcore
from repro.core.campaign import LAST_PHASES, run_scenario
from repro.core.telemetry import PhaseTimer, snapshot, span

RUN_STEPS = ("lower", "put", "kernel", "fetch", "summarize")
SMALL = dict(n_nodes=12, n_replicas_per_app=4, n_requests=40, n_trials=2)


def _diff(before, after):
    """(count, seconds) of what ran between two snapshots."""
    return {k: (c - before.get(k, (0, 0.0))[0], s - before.get(k, (0, 0.0))[1])
            for k, (c, s) in after.items()
            if c != before.get(k, (0, 0.0))[0]}


# ----------------------------------------------------------------------
# the recorder
def test_nested_spans_are_named_by_path_and_land_in_the_phase_timer():
    timer = PhaseTimer()
    before = snapshot()
    with timer.phase("run:x"):
        with span("lower"):
            pass
        for _ in range(3):
            with span("kernel"):
                with span("sync"):
                    pass
    assert set(timer.summary()) == {"run:x", "run:x/lower", "run:x/kernel",
                                    "run:x/kernel/sync"}
    assert timer.count == {"run:x": 1, "run:x/lower": 1, "run:x/kernel": 3,
                           "run:x/kernel/sync": 3}
    w = timer.wall
    assert w["run:x/kernel/sync"] <= w["run:x/kernel"] <= w["run:x"]
    assert w["run:x/lower"] + w["run:x/kernel"] <= w["run:x"]
    assert snapshot() == before          # a timer's spans stay its own


def test_spans_outside_any_timer_go_to_the_process_totals():
    before = snapshot()
    for _ in range(4):
        with span("probe-route"):
            with span("predict"):
                pass
    got = _diff(before, snapshot())
    assert set(got) == {"probe-route", "probe-route/predict"}
    assert got["probe-route"][0] == got["probe-route/predict"][0] == 4
    assert 0 <= got["probe-route/predict"][1] <= got["probe-route"][1]


def test_a_phase_keeps_its_name_inside_another_span():
    timer = PhaseTimer()
    with span("probe-outer"):
        with timer.phase("build"):
            with span("step"):
                pass
    assert set(timer.summary()) == {"build", "build/step"}


def test_span_as_a_decorator_and_on_an_exception():
    timer = PhaseTimer()

    @span("work")
    def work(fail):
        if fail:
            raise ValueError("planted")
        return 7

    with timer.phase("p"):
        assert work(False) == 7
        with pytest.raises(ValueError):
            work(True)
        with span("after"):
            pass
    # the failed call is timed and closed: the next span is not its child
    assert timer.count == {"p": 1, "p/work": 2, "p/after": 1}


def test_threads_keep_their_own_open_spans():
    timer = PhaseTimer()
    names = []

    def other():
        before = snapshot()
        with span("probe-thread"):
            pass
        names.extend(_diff(before, snapshot()))

    with timer.phase("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    assert names == ["probe-thread"]
    assert set(timer.summary()) == {"main"}


# ----------------------------------------------------------------------
# the compiled pass
@pytest.mark.parametrize("scenario,policy", [("churn", "perf_aware"),
                                             ("baseline", "least_conn")])
def test_compiled_call_records_its_five_steps(scenario, policy):
    """... and the prediction noise's draw inside ``lower``, only where
    the pass reads predictions (``least_conn`` reads none)."""
    run_scenario(scenario, policies=[policy], seeds=(0, 1),
                 include_oracle=False, backend="compiled", **SMALL)
    top = f"run:{policy}"
    steps = {f"{top}/{s}" for s in RUN_STEPS}
    children = {f"{top}/lower/pred_noise"} if policy == "perf_aware" \
        else set()
    assert set(LAST_PHASES) == {"build", top} | steps | children
    assert sum(LAST_PHASES[s] for s in steps) <= LAST_PHASES[top]
    for c in children:
        assert LAST_PHASES[c] <= LAST_PHASES[f"{top}/lower"]


def test_prepare_compiled_records_the_same_steps():
    from repro.core.scenarios import get_scenario
    from repro.core.simulator import _build_cluster
    cfg = get_scenario("baseline").compile(seed=0, **SMALL)
    timer = PhaseTimer()
    with timer.phase("run"):
        fn = simcore.prepare_compiled(_build_cluster(cfg), "least_conn")
        fn()
    assert set(timer.summary()) == {"run"} | {f"run/{s}" for s in RUN_STEPS}


def test_summaries_are_bit_identical_with_the_profiler_on(tmp_path):
    kw = dict(policies=["perf_aware"], seeds=(0, 1), include_oracle=False,
              backend="compiled", **SMALL)
    off = run_scenario("churn", **kw)["perf_aware"].per_seed
    jax.profiler.start_trace(str(tmp_path))
    try:
        on = run_scenario("churn", **kw)["perf_aware"].per_seed
    finally:
        jax.profiler.stop_trace()
    assert set(on) == set(off)
    for k in off:
        np.testing.assert_array_equal(on[k], off[k])


# ----------------------------------------------------------------------
# the served path
@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs.base import get_config
    from repro.models import model as M
    cfg = get_config("deepseek-67b", smoke=True).resolve(tp=1)
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


def test_engine_marks_wave_start(tiny_model):
    from repro.monitoring.metrics import SimClock
    from repro.serving.engine import Request, ServingEngine
    cfg, params = tiny_model
    clock = SimClock()
    eng = ServingEngine(cfg, params, max_batch=3, max_seq=32, clock=clock,
                        slowdown=0.01)
    rng = np.random.default_rng(5)
    lens = [2, 5, 3, 4]
    reqs = [Request(rid=i, tokens=rng.integers(0, 100, size=6),
                    max_new_tokens=n) for i, n in enumerate(lens)]
    for r in reqs:
        eng.submit(r)
    clock.advance(0.5)
    eng.step_wave()                       # 3 slots x 5 tokens
    eng.step_wave()                       # 1 slot x 4 tokens
    for r in reqs:
        assert r.t_enqueue <= r.t_start <= r.t_done
    assert reqs[0].t_start == reqs[2].t_start < reqs[3].t_start
    # the second wave waited for the first's four decode steps
    assert reqs[3].t_start - reqs[3].t_enqueue >= 0.5 + 4 * 0.01


def test_drain_spans_name_the_engine_steps(tiny_model):
    from repro.monitoring.metrics import SimClock
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.router import MorpheusRouter
    cfg, params = tiny_model
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=2,
                          max_seq=32, clock=clock, slowdown=0.01)
            for i in range(2)]
    router = MorpheusRouter(reps, policy="perf_aware")
    router.kb.put("serve", "n0", 0.0, 0.1)
    router.kb.put("serve", "n1", 0.0, 0.2)
    rng = np.random.default_rng(6)
    before = snapshot()
    for i in range(3):
        router.route(Request(rid=i, tokens=rng.integers(0, 100, size=6),
                             max_new_tokens=4))
    router.drain()
    got = _diff(before, snapshot())
    waves = sum(-(-router.routed.count(i) // 2) for i in range(2))
    assert got["route"][0] == got["route/predict"][0] \
        == got["route/pick"][0] == got["route/scrape"][0] == 3
    assert got["drain"][0] == 1
    assert got["drain/prefill"][0] == waves
    assert got["drain/decode"][0] == got["drain/slowdown"][0] == 3 * waves
    assert got["drain/scrape"][0] == 4 * waves    # each step and the end
    inner = sum(got[f"drain/{s}"][1]
                for s in ("prefill", "decode", "slowdown", "scrape"))
    assert inner <= got["drain"][1]


def test_served_rows_carry_the_measured_queue_wait(tiny_model):
    from repro.core.telemetry import TRACE_IDX
    from repro.monitoring.metrics import SimClock
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.router import MorpheusRouter
    cfg, params = tiny_model
    clock = SimClock()
    rep = ServingEngine(cfg, params, node="n0", max_batch=1, max_seq=32,
                        clock=clock, slowdown=0.25)
    router = MorpheusRouter([rep], policy="round_robin")
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, tokens=rng.integers(0, 100, size=6),
                    max_new_tokens=3) for i in range(3)]
    for r in reqs:
        router.route(r)
    router.drain()
    d = router.trace()["data"][0]
    qw = d[:, TRACE_IDX["queue_wait"]]
    np.testing.assert_allclose(qw, [r.t_start - r.t_enqueue for r in reqs])
    # waves of one: each request waits for the ones before it
    np.testing.assert_allclose(qw, [0.0, 0.5, 1.0])
    np.testing.assert_allclose(
        qw + d[:, TRACE_IDX["service_base"]], d[:, TRACE_IDX["response"]])


# ----------------------------------------------------------------------
# names of the device work, in the lowered programs
def _lowered_text(fn, *args, **kwargs):
    return fn.lower(*args, **kwargs).as_text(debug_info=True)


def test_model_steps_carry_their_scope_names(tiny_model):
    from repro.models import model as M
    from repro.serving.engine import jit_decode, jit_prefill
    cfg, params = tiny_model
    toks = jnp.zeros((2, 6), jnp.int32)
    text = _lowered_text(jit_prefill, params, cfg=cfg,
                         batch={"tokens": toks}, cache_len=16)
    assert "jit(prefill)/prefill/" in text
    cache = M.prefill(params, cfg, {"tokens": toks}, cache_len=16)[1]
    text = _lowered_text(jit_decode, params, cfg=cfg, cache=cache,
                         tokens=toks[:, :1])
    assert "jit(decode_step)/decode_step/" in text


def test_scan_step_and_segment_sum_carry_their_names(monkeypatch):
    from repro.core.campaign import stack_clusters
    from repro.core.rng import rng_seed
    from repro.core.scenarios import get_scenario
    from repro.core.simulator import _build_cluster
    monkeypatch.setattr(simcore, "_SEGSUM_BACKEND", "interpret")
    cfg = get_scenario("churn").compile(seed=0, **SMALL)
    cluster = stack_clusters([_build_cluster(cfg)])
    st, consts, xs, carry0, _ = simcore._lower(
        cluster, "perf_aware", [(rng_seed(0, "policy"), SMALL["n_trials"])])
    with jax.enable_x64():
        text = _lowered_text(jax.jit(simcore._build_kernel(st)),
                             consts, xs, carry0)
    # the recount at a churn resync, inside the step
    assert re.search(r'"sim_step/[^"]*segment_sum/pallas_call"', text)
