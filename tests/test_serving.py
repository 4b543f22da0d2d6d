"""Serving engine + Morpheus router."""
import numpy as np
import pytest

import jax

from repro.configs.base import get_config
from repro.core.capacity import CapacityConfig
from repro.models import model as M
from repro.monitoring.metrics import SimClock
from repro.serving.engine import Request, ServingEngine
from repro.serving.router import MorpheusRouter

from repro.testing import make_store, make_trained_predictor


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = get_config("deepseek-67b", smoke=True).resolve(tp=1)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(n, rng):
    return [Request(rid=i, tokens=rng.integers(0, 100, size=8),
                    max_new_tokens=4) for i in range(n)]


def test_engine_serves_wave(tiny_setup):
    cfg, params = tiny_setup
    clock = SimClock()
    eng = ServingEngine(cfg, params, max_batch=3, max_seq=32, clock=clock)
    rng = np.random.default_rng(0)
    for r in _reqs(5, rng):
        eng.submit(r)
    done = eng.step_wave()
    assert len(done) == 3
    assert eng.pending() == 2
    for r in done:
        assert r.output is not None and len(r.output) == 4
        assert r.rtt is not None and r.rtt >= 0


def test_engines_share_one_compiled_prefill_and_decode(tiny_setup):
    """Replicas of one model at one max_seq compile prefill and decode
    once between them, not once per engine."""
    from repro.serving.engine import jit_decode, jit_prefill

    cfg, params = tiny_setup
    engines = [ServingEngine(cfg, params, max_batch=2, max_seq=48,
                             clock=SimClock()) for _ in range(3)]
    rng = np.random.default_rng(0)
    before = jit_prefill._cache_size(), jit_decode._cache_size()
    for eng in engines:
        for r in _reqs(2, rng):
            eng.submit(r)
        eng.step_wave()
    assert (jit_prefill._cache_size() - before[0],
            jit_decode._cache_size() - before[1]) == (1, 1)


def test_engine_exports_metrics(tiny_setup):
    cfg, params = tiny_setup
    clock = SimClock()
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=32, clock=clock,
                        slowdown=0.01)
    rng = np.random.default_rng(1)
    for r in _reqs(2, rng):
        eng.submit(r)
    eng.step_wave()
    names = eng.store.names
    assert "queue_depth" in names and "token_rate" in names


def test_router_perf_aware_avoids_slow_replica(tiny_setup):
    cfg, params = tiny_setup
    clock = SimClock()
    fast = ServingEngine(cfg, params, node="fast", max_batch=2, max_seq=32,
                         clock=clock, slowdown=0.0)
    slow = ServingEngine(cfg, params, node="slow", max_batch=2, max_seq=32,
                         clock=clock, slowdown=0.5)
    router = MorpheusRouter([fast, slow], policy="perf_aware")
    router.kb.put("serve", "fast", 0.0, 0.1)
    router.kb.put("serve", "slow", 0.0, 5.0)
    rng = np.random.default_rng(2)
    for r in _reqs(4, rng):
        router.route(r)
    assert router.routed.count(0) >= 3       # mostly the fast replica


def test_router_predicted_rtts_is_one_plane_call(tiny_setup):
    """The perf-aware sweep must be ONE batched plane dispatch feeding the
    policy, not a per-replica serial predict loop (DESIGN.md §9)."""
    cfg, params = tiny_setup
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=2,
                          max_seq=32, clock=clock) for i in range(3)]
    store = make_store()
    preds = {f"n{i}": make_trained_predictor("serve", store, "lr",
                                             seed=500 + i, node=f"n{i}")
             for i in range(3)}
    router = MorpheusRouter(reps, policy="perf_aware", predictors=preds)
    calls = []
    orig = router.plane.predict_all

    def counted(keys=None):
        calls.append(keys)
        return orig(keys)

    router.plane.predict_all = counted
    rtts = router._predicted_rtts()
    assert len(calls) == 1 and len(calls[0]) == 3
    assert np.isfinite(rtts).all()
    # plane outputs match each predictor's serial path and land in the kb
    for i in range(3):
        serial = preds[f"n{i}"].predict().rtt_pred
        assert rtts[i] == pytest.approx(serial, rel=1e-5, abs=1e-5)
        assert router.kb.latest("serve", f"n{i}") == pytest.approx(rtts[i])


def test_router_falls_back_without_trained_predictors(tiny_setup):
    cfg, params = tiny_setup
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=2,
                          max_seq=32, clock=clock) for i in range(2)]
    router = MorpheusRouter(reps, policy="perf_aware")
    router.kb.put("serve", "n0", 0.0, 2.5)
    rtts = router._predicted_rtts()
    assert rtts[0] == 2.5                      # knowledge-base fallback
    assert rtts[1] == 1.0 + reps[1].pending()  # queue-depth proxy


def test_router_keyed_sweep_honors_outage_window(tiny_setup):
    """Regression (ISSUE 4): ``predict_all`` applied outage caching only
    to full-fleet calls, so the router's keyed sweep re-queried the
    store straight through an ``add_outage`` window.  Subset calls must
    now serve the frozen snapshot too."""
    cfg, params = tiny_setup
    store = make_store()
    clock = store.clock
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=2,
                          max_seq=32, clock=clock) for i in range(3)]
    preds = {f"n{i}": make_trained_predictor("serve", store, "lr",
                                             seed=900 + i, node=f"n{i}")
             for i in range(3)}
    router = MorpheusRouter(reps, policy="perf_aware", predictors=preds)
    now = clock.now()
    router.plane.add_outage(now + 5.0, now + 500.0)
    before = router._predicted_rtts()
    d0 = router.plane.dispatches
    clock.advance(10.0)                      # inside the outage window
    rng = np.random.default_rng(0)
    for _ in range(20):                      # the source keeps changing...
        store.scrape({n: float(v) * 100.0 for n, v in
                      zip(store.names, rng.standard_normal(10))})
    during = router._predicted_rtts()
    assert router.plane.dispatches == d0     # ...but no re-query happens
    np.testing.assert_array_equal(during, before)
    clock.advance(600.0)                     # outage over: fresh compute
    after = router._predicted_rtts()
    assert router.plane.dispatches > d0
    assert not np.array_equal(after, before)


def test_router_falls_back_to_least_conn_below_viability(tiny_setup):
    """The DESIGN.md §11 fallback rule: once the rolling accuracy of the
    routed predictions drops below the threshold, requests are PICKED by
    least_conn — but predictions keep being computed and reconciled, so
    a retrained fleet can win the route back."""
    cfg, params = tiny_setup
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=2,
                          max_seq=32, clock=clock) for i in range(2)]
    router = MorpheusRouter(reps, policy="perf_aware",
                            fallback_threshold=0.6)
    router.kb.put("serve", "n0", 0.0, 0.1)
    router.kb.put("serve", "n1", 0.0, 5.0)
    rng = np.random.default_rng(4)
    assert router.predictions_viable()
    router.route(Request(rid=0, tokens=rng.integers(0, 100, size=8)))
    assert router.fallbacks == 0
    # accuracy collapses (e.g. the workload drifted under the fleet)
    for _ in range(router.accuracy.min_count):
        router.accuracy.update(np.array([0.9, 0.9]))
    assert not router.predictions_viable()
    before = len(router.routed)
    inflight_before = len(router._inflight)
    router.route(Request(rid=1, tokens=rng.integers(0, 100, size=8)))
    assert router.fallbacks == 1
    assert len(router.routed) == before + 1
    # still tracking predictions while fallen back: the tracker can see
    # a hot-swapped fleet recover, so the fallback is not permanent
    assert len(router._inflight) == inflight_before + 1
    good = np.zeros(2)
    for _ in range(router.accuracy.window):
        router.accuracy.update(good)
    assert router.predictions_viable()         # the route is won back
    router.route(Request(rid=2, tokens=rng.integers(0, 100, size=8)))
    assert router.fallbacks == 1               # no new fallback


def test_router_drain_settles_accuracy_tracker(tiny_setup):
    cfg, params = tiny_setup
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=2,
                          max_seq=32, clock=clock, slowdown=0.01)
            for i in range(2)]
    store = make_store()
    preds = {f"n{i}": make_trained_predictor("serve", store, "lr",
                                             seed=950 + i, node=f"n{i}")
             for i in range(2)}
    router = MorpheusRouter(reps, policy="perf_aware", predictors=preds)
    rng = np.random.default_rng(5)
    for r in _reqs(4, rng):
        router.route(r)
    assert len(router._inflight) == 4
    assert router.accuracy.count.sum() == 0
    router.drain()
    assert len(router._inflight) == 0
    assert router.accuracy.count.sum() == 4   # every completion settled


def test_router_capacity_pool_masks_drained_engines(tiny_setup):
    """The serving-side capacity mirror (DESIGN.md §12): a fixed pool
    smaller than the engine count keeps the standby engines drained —
    the policy can never pick them — and the ledger reports the
    provisioned/busy/waste triple."""
    cfg, params = tiny_setup
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=2,
                          max_seq=32, clock=clock, slowdown=0.01)
            for i in range(4)]
    cap = CapacityConfig(autoscaler="fixed", initial_replicas=2,
                         decide_every_s=1.0)
    router = MorpheusRouter(reps, policy="round_robin", capacity=cap)
    assert [e.active for e in reps] == [True, True, False, False]
    rng = np.random.default_rng(6)
    for r in _reqs(6, rng):
        clock.advance(0.1)
        assert router.route(r) in (0, 1)
    done = router.drain()
    assert len(done) == 6
    led = router.pool.ledger()
    assert led["provisioned_s"] > 0
    assert led["busy_s"] > 0
    assert 0.0 <= led["waste"] <= 1.0
    assert led["shed"] == 0


def test_router_capacity_admission_sheds(tiny_setup):
    """The admission hook: once every active engine's estimated wait
    exceeds the limit, route() returns -1 and records the shed request
    instead of queueing unboundedly."""
    cfg, params = tiny_setup
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=1,
                          max_seq=32, clock=clock) for i in range(2)]
    cap = CapacityConfig(autoscaler="fixed", initial_replicas=2,
                         admission_limit_s=0.5)
    router = MorpheusRouter(reps, policy="least_conn", capacity=cap)
    router.pool.note_prediction(10.0)     # each queued wave ~10s of wait
    rng = np.random.default_rng(7)
    results = [router.route(r) for r in _reqs(6, rng)]
    assert -1 in results                  # deep queues -> shed
    assert router.pool.shed == results.count(-1) == len(router.shed)
    served = [i for i in results if i >= 0]
    assert len(router.drain()) == len(served)


def test_router_capacity_scales_up_reactively(tiny_setup):
    """Queue pressure grows the active set on the decision cadence."""
    cfg, params = tiny_setup
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=1,
                          max_seq=32, clock=clock) for i in range(3)]
    cap = CapacityConfig(autoscaler="reactive", initial_replicas=1,
                         min_replicas=1, decide_every_s=1.0,
                         cooldown_s=0.0, hi_util=0.5)
    router = MorpheusRouter(reps, policy="least_conn", capacity=cap)
    assert sum(e.active for e in reps) == 1
    rng = np.random.default_rng(8)
    for r in _reqs(8, rng):
        router.route(r)
        clock.advance(1.1)                # queues stay busy -> util 1.0
    assert sum(e.active for e in reps) > 1
    assert any(d > 0 for _, d in router.pool.scale_events)


def test_pool_ledger_pays_drain_tails(tiny_setup):
    """Scale-down with queued work: the drained engines' remaining
    serving time is still provisioned, so busy_s can never exceed
    provisioned_s (waste stays a true fraction, not a clipped 0)."""
    cfg, params = tiny_setup
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=1,
                          max_seq=32, clock=clock, slowdown=0.02)
            for i in range(3)]
    cap = CapacityConfig(autoscaler="fixed", initial_replicas=3,
                         decide_every_s=1.0)
    router = MorpheusRouter(reps, policy="round_robin", capacity=cap)
    rng = np.random.default_rng(10)
    for r in _reqs(6, rng):
        router.route(r)
    # operator forces a scale-down while every engine holds queued work
    for e in reps[1:]:
        e.active = False
    router.drain()                       # inactive engines still drain
    clock.advance(0.5)
    led = router.pool.ledger()
    assert led["busy_s"] <= led["provisioned_s"] + 1e-9, led
    assert led["waste"] >= 0.0


def test_engine_accumulates_busy_seconds(tiny_setup):
    cfg, params = tiny_setup
    clock = SimClock()
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=32, clock=clock,
                        slowdown=0.01)
    assert eng.busy_s == 0.0
    rng = np.random.default_rng(9)
    for r in _reqs(2, rng):
        eng.submit(r)
    eng.step_wave()
    assert eng.busy_s > 0.0


def test_router_round_robin_spreads(tiny_setup):
    cfg, params = tiny_setup
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=2,
                          max_seq=32, clock=clock) for i in range(3)]
    router = MorpheusRouter(reps, policy="round_robin")
    rng = np.random.default_rng(3)
    for r in _reqs(6, rng):
        router.route(r)
    assert router.routed == [0, 1, 2, 0, 1, 2]
    done = router.drain()
    assert len(done) == 6


# ----------------------------------------------------------------------
# flight recorder: the serving T=1 mirror (DESIGN.md §16)

def _trace_sum_err(data):
    """Max |signed component sum - response| over served rows."""
    from repro.core.telemetry import COMPONENTS, DISP_SERVED, TRACE_IDX
    served = data[..., TRACE_IDX["disposition"]] == DISP_SERVED
    comp = sum(data[..., TRACE_IDX[c]] for c in COMPONENTS
               if c != "hedge_s") - data[..., TRACE_IDX["hedge_s"]]
    err = np.abs(comp - data[..., TRACE_IDX["response"]])[served]
    return float(err.max()) if err.size else 0.0


def test_router_trace_schema_and_sum_rule(tiny_setup):
    """One row per routed request, simulator-identical schema, and the
    decomposition sums to the measured response on every served row."""
    from repro.core.telemetry import (DISP_SERVED, TRACE_FIELDS,
                                     TRACE_IDX)
    cfg, params = tiny_setup
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=2,
                          max_seq=32, clock=clock, slowdown=0.01)
            for i in range(3)]
    router = MorpheusRouter(reps, policy="round_robin")
    rng = np.random.default_rng(20)
    for r in _reqs(6, rng):
        router.route(r)
    router.drain()
    blk = router.trace()
    assert blk["fields"] == list(TRACE_FIELDS)
    assert blk["sample_every"] == 1
    d = blk["data"]
    assert d.shape == (1, 6, len(TRACE_FIELDS))
    assert (d[0, :, TRACE_IDX["disposition"]] == DISP_SERVED).all()
    np.testing.assert_array_equal(d[0, :, TRACE_IDX["rep"]],
                                  [0, 1, 2, 0, 1, 2])
    assert np.isfinite(d[0, :, TRACE_IDX["response"]]).all()
    assert _trace_sum_err(d) < 1e-6
    # reactive policy: no prediction at the pick
    assert np.isnan(d[0, :, TRACE_IDX["predicted"]]).all()
    assert np.isfinite(d[0, :, TRACE_IDX["score"]]).all()


def test_router_trace_perf_aware_captures_decision(tiny_setup):
    """perf_aware rows carry the prediction and score the pick saw, and
    the spelled-out pick matches Policy.pick bit-for-bit (routed)."""
    from repro.core.telemetry import TRACE_IDX
    cfg, params = tiny_setup
    clock = SimClock()
    fast = ServingEngine(cfg, params, node="fast", max_batch=2,
                         max_seq=32, clock=clock, slowdown=0.0)
    slow = ServingEngine(cfg, params, node="slow", max_batch=2,
                         max_seq=32, clock=clock, slowdown=0.5)
    router = MorpheusRouter([fast, slow], policy="perf_aware")
    router.kb.put("serve", "fast", 0.0, 0.1)
    router.kb.put("serve", "slow", 0.0, 5.0)
    rng = np.random.default_rng(21)
    for r in _reqs(4, rng):
        router.route(r)
    router.drain()
    d = router.trace()["data"]
    assert np.isfinite(d[0, :, TRACE_IDX["predicted"]]).all()
    np.testing.assert_array_equal(d[0, :, TRACE_IDX["rep"]],
                                  router.routed)
    # the recorded score is the chosen replica's (the row minimum
    # among routable candidates)
    assert (d[0, :, TRACE_IDX["score"]] <= 5.0 + 1e-9).all()
    assert _trace_sum_err(d) < 1e-6


def test_router_trace_shed_rows(tiny_setup):
    """Admission sheds close immediately: disposition SHED, rep -1,
    NaN response — and the registry counters agree."""
    from repro.core.telemetry import DISP_SERVED, DISP_SHED, TRACE_IDX
    cfg, params = tiny_setup
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=1,
                          max_seq=32, clock=clock) for i in range(2)]
    cap = CapacityConfig(autoscaler="fixed", initial_replicas=2,
                         admission_limit_s=0.5)
    router = MorpheusRouter(reps, policy="least_conn", capacity=cap)
    router.pool.note_prediction(10.0)
    rng = np.random.default_rng(22)
    results = [router.route(r) for r in _reqs(6, rng)]
    router.drain()
    d = router.trace()["data"]
    assert d.shape[1] == 6                      # shed rows are rows too
    disp = d[0, :, TRACE_IDX["disposition"]]
    assert (disp == DISP_SHED).sum() == results.count(-1) > 0
    shed_rows = d[0, disp == DISP_SHED]
    assert (shed_rows[:, TRACE_IDX["rep"]] == -1).all()
    assert np.isnan(shed_rows[:, TRACE_IDX["response"]]).all()
    served_rows = d[0, disp == DISP_SERVED]
    assert np.isfinite(served_rows[:, TRACE_IDX["response"]]).all()
    exp = router.registry.collect()
    assert exp["router_requests_total"] == 6.0
    assert exp["router_shed_total"] == float(results.count(-1))
    assert exp["router_rtt_seconds_count"] == float(
        6 - results.count(-1))
    assert exp["router_inflight"] == 0.0        # all settled at drain


def test_router_trace_timeout_and_retry_rows(tiny_setup):
    """Every ATTEMPT is a row: a client timeout closes its row with
    disposition TIMEOUT (NaN response, the client never saw one) and
    the retry re-entering route() opens a fresh row."""
    from repro.core.telemetry import (DISP_SERVED, DISP_TIMEOUT,
                                     TRACE_IDX)
    from repro.core.resilience import ResilienceConfig
    cfg, params = tiny_setup
    clock = SimClock()
    reps = [ServingEngine(cfg, params, node="n0", max_batch=2,
                          max_seq=32, clock=clock, slowdown=5.0)]
    res = ResilienceConfig(timeout_s=0.5, max_retries=1)
    router = MorpheusRouter(reps, policy="round_robin", resilience=res)
    rng = np.random.default_rng(23)
    n = 2
    for r in _reqs(n, rng):
        router.route(r)
    router.drain()
    assert len(router.timeouts) == n            # every attempt blew 0.5s
    d = router.trace()["data"]
    disp = d[0, :, TRACE_IDX["disposition"]]
    # n primaries + n retries, all timed out
    assert d.shape[1] == 2 * n
    assert (disp == DISP_TIMEOUT).all()
    assert np.isnan(d[0, :, TRACE_IDX["response"]]).all()
    assert (d[0, :, TRACE_IDX["rep"]] == -1).all()
    exp = router.registry.collect()
    assert exp["router_retries_total"] == float(n)
    assert exp["router_timeouts_total"] == float(n)
    assert exp["router_inflight"] == 0.0
    assert (disp == DISP_SERVED).sum() == 0


def test_router_trace_hedge_effect(tiny_setup):
    """A winning hedge shows up as hedge_s > 0 on its primary's row and
    the sum rule still closes: qw + base - hedge_s == response."""
    from repro.core.telemetry import DISP_SERVED, TRACE_IDX
    cfg, params = tiny_setup
    clock = SimClock()
    # the hedged duplicate lands on an idle twin and wins the race
    slow = ServingEngine(cfg, params, node="slow", max_batch=1,
                         max_seq=32, clock=clock, slowdown=0.3)
    twin = ServingEngine(cfg, params, node="twin", max_batch=1,
                         max_seq=32, clock=clock, slowdown=0.0)
    router = MorpheusRouter([slow, twin], policy="perf_aware",
                            hedge_factor=1.0)
    router.kb.put("serve", "slow", 0.0, 1.0)
    router.kb.put("serve", "twin", 0.0, 1.0)
    rng = np.random.default_rng(24)
    for r in _reqs(3, rng):
        router.route(r)
    router.drain()
    d = router.trace()["data"]
    hs = d[0, :, TRACE_IDX["hedge_s"]]
    if router.hedged:                           # a duplicate was issued
        assert float(router.registry.collect()["router_hedges_total"]) \
            == len(router.hedged)
    assert (hs[np.isfinite(hs)] >= 0).all()
    assert (d[0, :, TRACE_IDX["disposition"]] == DISP_SERVED).all()
    assert _trace_sum_err(d) < 1e-6


def test_router_registry_rides_metrics_store(tiny_setup):
    """With a MetricsStore attached the registry scrapes into the same
    columnar plane the predictors read (Prometheus-style export)."""
    cfg, params = tiny_setup
    store = make_store()
    clock = store.clock
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=2,
                          max_seq=32, clock=clock) for i in range(2)]
    router = MorpheusRouter(reps, policy="round_robin",
                            metrics_store=store)
    rng = np.random.default_rng(25)
    for r in _reqs(4, rng):
        router.route(r)
    router.drain()
    clock.advance(0.05)
    router.registry.scrape()
    arr, _ = store.query_window(
        ["router_requests_total", "router_rtt_seconds_count"], 0.2,
        fast=True)
    np.testing.assert_array_equal(arr[:, -1], [4.0, 4.0])
