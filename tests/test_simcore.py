"""Compiled scan kernel (DESIGN.md §13): compiled-vs-serial parity for
every registered scenario, the single-device fallback, the shard_map
path, and the support-matrix guards.

The serial :class:`SimStepper` is the reference semantics; these tests
pin the ``lax.scan`` kernel to it within 1e-5 relative drift (in
practice the paths differ only by floating-point reassociation,
<= 1e-12).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.campaign import (DEFAULT_POLICIES, SUMMARY_STATS,
                                 compiled_coverage, run_campaign,
                                 run_campaign_serial, run_scenario)
from repro.core.capacity import CapacityConfig
from repro.core.scenarios import scenario_names
from repro.core.simcore import (fleet_throughput, run_compiled,
                                run_sim_compiled, supports)
from repro.core.simulator import SimConfig, _build_cluster, run_sim

SMALL = dict(seeds=(0, 1, 2, 3), n_trials=4, n_requests=50)
STATS = SUMMARY_STATS + ("hedged",)


def assert_parity(compiled, serial, label, rtol=1e-5):
    for pol in serial:
        for k in STATS:
            a = np.asarray(compiled[pol].per_seed[k], float)
            b = np.asarray(serial[pol].per_seed[k], float)
            both_nan = np.isnan(a) & np.isnan(b)
            np.testing.assert_allclose(
                np.where(both_nan, 0.0, a), np.where(both_nan, 0.0, b),
                rtol=rtol, atol=1e-7, err_msg=f"{label}/{pol}/{k}")
        assert compiled[pol].n_hedged == serial[pol].n_hedged, \
            f"{label}/{pol}/n_hedged"


# ----------------------------------------------------------------------
# the acceptance gate: every registered scenario, every default policy
# (+ oracle), compiled == serial
@pytest.mark.parametrize("name", scenario_names())
def test_compiled_matches_serial_per_scenario(name):
    serial = run_scenario(name, backend="serial", **SMALL)
    compiled = run_scenario(name, backend="auto", **SMALL)
    assert_parity(compiled, serial, name)


@pytest.mark.parametrize("name", ("tier-drift", "app-drift",
                                  "colocation-drift"))
def test_drift_crossing_compiled_matches_serial(name):
    """The registry-wide test's shrunken horizon ends before t_drift;
    this one compresses the timeline so the drift transition happens
    inside the run — the kernel's masked pre/post regime switch must
    track the serial stepper through the crossing."""
    kw = dict(seeds=(0, 1, 2), n_trials=3, n_requests=80,
              arrival_rate=2.0, t_drift=20.0)
    serial = run_scenario(name, backend="serial", **kw)
    compiled = run_scenario(name, backend="auto", **kw)
    assert_parity(compiled, serial, name)


def test_drift_fallback_crossing_compiled_matches_serial():
    """drift-fallback adds the closed-loop fleet: warmup, several
    retrains, the drift onset, and accuracy-triggered fallback all
    inside the horizon."""
    kw = dict(seeds=(0, 1, 2), n_trials=3, n_requests=80,
              arrival_rate=2.0, online_warmup_s=8.0, retrain_every_s=6.0,
              t_drift=20.0)
    serial = run_scenario("drift-fallback", backend="serial", **kw)
    compiled = run_scenario("drift-fallback", backend="auto", **kw)
    assert_parity(compiled, serial, "drift-fallback")


@pytest.mark.parametrize("name", ("flash-crowd-autoscale",
                                  "scale-to-zero-idle",
                                  "spot-preemption"))
def test_capacity_timeline_crossing(name):
    """Autoscaler epochs / preemption windows land inside the shrunken
    horizon: the kernel's masked membership updates (activation times,
    cold-start multipliers, admission sheds) must match the serial
    CapacityController event loop."""
    kw = dict(seeds=(0, 1), n_trials=3, n_requests=120, arrival_rate=4.0)
    serial = run_scenario(name, backend="serial", **kw)
    compiled = run_scenario(name, backend="auto", **kw)
    assert_parity(compiled, serial, name)


def test_hedged_compiled_matches_serial():
    # aggressive threshold + load so the hedge fires hundreds of times
    # inside the shrunken horizon (n_hedged == 0 would test nothing)
    kw = dict(hedge_factor=0.5, arrival_rate=8.0, **SMALL)
    serial = run_scenario("baseline", backend="serial", **kw)
    compiled = run_scenario("baseline", backend="auto", **kw)
    assert_parity(compiled, serial, "baseline+hedge")
    assert serial["perf_aware"].n_hedged > 0  # the hedge actually fired


# ----------------------------------------------------------------------
# property: the scan core never routes to a drained / inactive replica
@pytest.mark.parametrize("name", ("flash-crowd-autoscale",
                                  "scale-to-zero-idle",
                                  "spot-preemption"))
@pytest.mark.parametrize("policy", ("perf_aware", "least_conn"))
def test_never_routes_to_inactive_replica(name, policy):
    from repro.core.scenarios import get_scenario
    cfg = get_scenario(name).compile(seed=0, n_trials=6, n_requests=150,
                                     arrival_rate=4.0)
    summary = run_compiled(_build_cluster(cfg), policy)
    assert summary["capacity"]["routed_inactive"] == 0


def test_churn_avoids_drained_node():
    """During the downtime window the failed node's replicas carry the
    churn busy-bump.  The kernel must make the exact same routing
    decisions as the serial stepper, and — replaying occupancy from its
    own outputs — may land on a drained replica only when no live
    candidate was strictly less loaded (the bump makes that the
    least-loaded choice only when every alternative queues past the
    node's wake time)."""
    cfg = SimConfig(n_trials=6, n_requests=120, churn=(5.0, 30.0),
                    arrival_rate=1.0, seed=3)
    cluster = _build_cluster(cfg)
    compiled = run_compiled(cluster, "least_conn")
    serial = run_sim(cfg, "least_conn")
    np.testing.assert_array_equal(compiled["chosen"], serial["chosen"])

    chosen = np.asarray(compiled["chosen"], int)         # (T, J)
    resp = np.asarray(compiled["rtts"], float)
    node_of = np.asarray(cluster.node_of)                # (T, R)
    failed = np.asarray(cluster.failed_node)
    t_fail, downtime = cfg.churn
    t_up = t_fail + downtime
    K = cfg.n_replicas_per_app
    T = cfg.n_trials
    busy = np.zeros_like(node_of, float)
    bumped = False
    n_drained_picks = 0
    for j in range(cfg.n_requests):
        now = float(cluster.req_t[j])
        if not bumped and now >= t_fail:
            down = node_of == failed[:, None]
            busy = np.where(down, np.maximum(busy, t_up), busy)
            bumped = True
        a = int(cluster.req_app[j])
        cand = slice(a * K, (a + 1) * K)
        for t in range(T):
            pick = chosen[t, j]
            on_failed = node_of[t, pick] == failed[t]
            if bumped and now < t_up and on_failed:
                n_drained_picks += 1
                assert busy[t, cand].min() >= busy[t, pick], \
                    f"req {j} trial {t}: drained pick beaten by a " \
                    f"live candidate"
            busy[t, pick] = now + resp[t, j]
    # the window must actually exercise avoidance: most in-window
    # requests with a live alternative route around the failed node
    assert n_drained_picks < 0.2 * cfg.n_requests * T


# ----------------------------------------------------------------------
# single-device fallback + shard_map
def test_single_device_fallback_identical():
    """With one visible device the dispatcher must take the plain jit
    path, and forcing it explicitly must be a no-op on the numbers."""
    cfg = SimConfig(n_trials=4, n_requests=60, seed=1)
    auto = run_sim_compiled(cfg, "perf_aware")
    forced = run_sim_compiled(cfg, "perf_aware", force_single=True)
    assert forced["simcore_backend"] == "jit"
    if len(__import__("jax").devices()) == 1:
        assert auto["simcore_backend"] == "jit"
    for k in ("mean_rtt", "p99_rtt", "hedged_per_trial"):
        np.testing.assert_array_equal(auto[k], forced[k])


_SHARD_SNIPPET = """
import numpy as np
from repro.core.simulator import SimConfig, _build_cluster, run_sim
from repro.core.simcore import run_compiled
cfg = SimConfig(n_trials=8, n_requests=40, seed=0)
summary = run_compiled(_build_cluster(cfg), "perf_aware")
assert summary["simcore_backend"] == "shard_map", summary["simcore_backend"]
ref = run_sim(cfg, "perf_aware")
for k in ("mean_rtt", "p99_rtt"):
    np.testing.assert_allclose(summary[k], ref[k], rtol=1e-5, atol=1e-7)
print("SHARD_OK")
"""


_SHARD_UNEVEN_SNIPPET = """
import numpy as np
from repro.core.simulator import SimConfig, _build_cluster, run_sim
from repro.core.simcore import run_compiled
# 6 trials on a 4-device mesh: the dispatcher pads to 8 by replicating
# the last trial and slices the outputs back — this used to silently
# fall back to single-device jit
cfg = SimConfig(n_trials=6, n_requests=40, seed=0)
summary = run_compiled(_build_cluster(cfg), "perf_aware")
assert summary["simcore_backend"] == "shard_map", summary["simcore_backend"]
ref = run_sim(cfg, "perf_aware")
for k in ("mean_rtt", "p99_rtt"):
    assert np.asarray(summary[k]).shape == np.asarray(ref[k]).shape
    np.testing.assert_allclose(summary[k], ref[k], rtol=1e-5, atol=1e-7)
print("SHARD_OK")
"""


def _run_shard_subprocess(snippet):
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"),
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    out = subprocess.run([sys.executable, "-c", snippet], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "SHARD_OK" in out.stdout


@pytest.mark.slow
def test_shard_map_parity_subprocess():
    """Real multi-device dispatch: 4 XLA host devices in a subprocess,
    trial axis sharded, numerics still match the serial stepper."""
    _run_shard_subprocess(_SHARD_SNIPPET)


@pytest.mark.slow
def test_shard_map_uneven_trials_subprocess():
    """T=6 on a 4-device mesh: pad-and-mask keeps the shard_map path
    (and its numerics) instead of falling back to single-device jit."""
    _run_shard_subprocess(_SHARD_UNEVEN_SNIPPET)


# ----------------------------------------------------------------------
# support matrix + dispatcher guards
def test_supports_rejects_unknown_policy():
    assert "unknown policy" in supports(SimConfig(), "nope")


def test_supports_rejects_unlowered_policy():
    from repro.core.balancer import POLICIES, Policy

    class _Weird(Policy):
        name = "weird-test-only"
        requires = ()
        scan_lowered = False

        def select(self, state):  # pragma: no cover
            return 0

    POLICIES[_Weird.name] = _Weird
    try:
        assert "no in-kernel score lowering" in \
            supports(SimConfig(), _Weird.name)
    finally:
        del POLICIES[_Weird.name]


#: the only intentionally-unsupported rows left in the support matrix:
#: policy-level rejections.  Every SimConfig feature combination is
#: lowered; pin the reason strings so a wording change (which the
#: campaign dispatcher and bench gate match on) is a loud failure.
_REASON_UNKNOWN = "unknown policy"
_REASON_UNLOWERED = "no in-kernel score lowering"


@pytest.mark.parametrize("name", scenario_names())
@pytest.mark.parametrize("policy", DEFAULT_POLICIES + ("oracle",))
def test_supports_every_registered_scenario(name, policy):
    """100% compiled coverage: no (registered scenario, default policy)
    pair may fall back to the serial stepper under backend='auto'."""
    from repro.core.scenarios import get_scenario
    cfg = get_scenario(name).compile(seed=0)
    assert supports(cfg, policy) is None


def test_compiled_coverage_helper_empty():
    assert compiled_coverage() == []


def test_supports_formerly_rejected_combos():
    """The PR-6 support matrix kicked these back to serial; they are
    lowered now and must stay that way."""
    assert supports(SimConfig(churn=(5.0, 10.0),
                              capacity=CapacityConfig()),
                    "least_conn") is None
    assert supports(SimConfig(closed_loop=True,
                              capacity=CapacityConfig()),
                    "perf_aware") is None
    assert supports(SimConfig(hedge_factor=1.5), "oracle") is None


def _register_weird():
    from repro.core.balancer import POLICIES, Policy

    class _Weird(Policy):
        name = "weird-test-only"
        requires = ()
        scan_lowered = False

        def select(self, state):  # pragma: no cover
            return 0

    POLICIES[_Weird.name] = _Weird
    return _Weird.name


def test_backend_compiled_raises_on_unsupported():
    from repro.core.balancer import POLICIES
    name = _register_weird()
    try:
        with pytest.raises(ValueError, match="backend='compiled'"):
            run_scenario("baseline", policies=[name],
                         include_oracle=False, backend="compiled",
                         **SMALL)
    finally:
        del POLICIES[name]


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown backend"):
        run_scenario("baseline", policies=["least_conn"],
                     include_oracle=False, backend="warp", **SMALL)


def test_run_compiled_raises_on_unsupported():
    from repro.core.balancer import POLICIES
    name = _register_weird()
    try:
        with pytest.raises(ValueError, match="simcore cannot run"):
            run_compiled(_build_cluster(SimConfig()), name)
    finally:
        del POLICIES[name]


# ----------------------------------------------------------------------
# kernel cache: LRU-bounded across a campaign sweep
def test_fn_cache_bounded_over_full_campaign():
    """A full 19-scenario x default-policy sweep must stay inside the
    LRU bound (the PR-6 cache grew one pinned entry per distinct
    kernel, forever)."""
    from repro.core import simcore
    run_campaign(backend="auto", seeds=(0, 1), n_trials=2,
                 n_requests=50)
    stats = simcore.cache_stats()
    assert stats["size"] <= stats["max"]
    assert stats["misses"] >= 1


def test_fn_cache_lru_eviction(monkeypatch):
    from collections import OrderedDict

    from repro.core import simcore
    monkeypatch.setattr(simcore, "_FN_CACHE", OrderedDict())
    monkeypatch.setattr(simcore, "_FN_CACHE_MAX", 2)
    monkeypatch.setattr(simcore, "_FN_STATS",
                        {"hits": 0, "misses": 0, "evictions": 0})
    cfg = SimConfig(n_trials=2, n_requests=10, seed=0)
    for pol in ("least_conn", "round_robin", "random"):
        run_sim_compiled(cfg, pol, force_single=True)
    stats = simcore.cache_stats()
    assert stats["size"] <= 2
    assert stats["misses"] == 3 and stats["evictions"] == 1
    # most-recently-used survives: re-running it is a hit, not a miss
    run_sim_compiled(cfg, "random", force_single=True)
    assert simcore.cache_stats()["hits"] == 1


# ----------------------------------------------------------------------
# Pallas segment-sum backend: the count-resync / snapshot reductions
# through the kernel (in interpret mode here) must match the XLA plan
@pytest.mark.parametrize("kw", (dict(churn=(5.0, 10.0)),
                                dict(prediction_lag_s=2.0)))
def test_pallas_segsum_backend_parity(monkeypatch, kw):
    from repro.core import simcore
    cfg = SimConfig(n_trials=3, n_requests=60, arrival_rate=2.0, seed=0,
                    **kw)
    ref = run_sim(cfg, "perf_aware")
    monkeypatch.setattr(simcore, "_SEGSUM_BACKEND", "interpret")
    got = run_sim_compiled(cfg, "perf_aware", force_single=True)
    for k in ("mean_rtt", "p99_rtt"):
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-5, atol=1e-7)


# ----------------------------------------------------------------------
# fleet-scale entry point
def test_fleet_throughput_smoke():
    eps, stats = fleet_throughput(n_requests=200, n_nodes=12,
                                  n_replicas_per_app=6, n_apps=3,
                                  n_trials=2, arrival_rate=50.0)
    assert eps > 0
    assert np.isfinite(stats["mean_rtt"]) and stats["mean_rtt"] > 0
    assert np.isfinite(stats["p99_rtt"])
    assert stats["n_replicas"] == 18
