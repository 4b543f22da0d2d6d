"""The deferred prediction noise (``_Cluster.pred_noise``): its one reader,
``_Cluster.z_pred_block``, returns bit for bit the candidates' part of an
eager ``standard_normal((T, J, R))`` after ``z_rtt``, and a cluster draws
it only when a pass reads predictions — once, under one ``pred_noise``
span, never as the (T, J, R) tensor."""
import tracemalloc
from dataclasses import replace

import numpy as np
import pytest

from repro.core import simcore
from repro.core.balancer import make_policy
from repro.core.campaign import stack_clusters
from repro.core.rng import rng_stream
from repro.core.scenarios import get_scenario
from repro.core.simulator import (SimConfig, SimStepper, _arrival_times,
                                  _build_cluster)
from repro.core.telemetry import snapshot

#: five apps, so a request's candidates are a fifth of the replicas and
#: the candidate block a fifth of the full tensor
SHAPE = dict(n_nodes=12, n_replicas_per_app=40, n_requests=150)


def _eager(cfg: SimConfig) -> np.ndarray:
    """The (T, J, R) prediction noise drawn eagerly, in ``_build_cluster``'s
    order: the arrival stream (when it shares the generator), ``z_rtt``,
    then the noise."""
    T, J = cfg.n_trials, cfg.n_requests
    R = len(cfg.apps) * cfg.n_replicas_per_app
    if cfg.stream_seed is None:
        rng = rng_stream(cfg.seed, "noise")
        rng.integers(0, len(cfg.apps), size=J)
        _arrival_times(cfg, rng)
    else:
        rng = rng_stream(cfg.seed, "noise_streamed")
    rng.standard_normal((T, J))
    return rng.standard_normal((T, J, R))


def _eager_block(cluster, z_pred: np.ndarray) -> np.ndarray:
    """(J, T, K) gather of an eager ``z_pred`` at each request's
    candidates, as ``simcore._lower`` took it before the deferral."""
    K = cluster.cfg.n_replicas_per_app
    cand = cluster.req_app.astype(np.int64)[:, None] * K + np.arange(K)
    return np.take_along_axis(z_pred.transpose(1, 0, 2),
                              cand[:, None, :], axis=2)


def _drawn(cluster) -> bool:
    return "z_pred_block" in vars(cluster)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_block_is_the_eager_draw_for_one_seed(seed):
    cfg = SimConfig(seed=seed, n_trials=3, **SHAPE)
    assert cfg.stream_seed is None       # arrivals share the generator
    cluster = _build_cluster(cfg)
    assert not _drawn(cluster)           # nothing drawn at build
    block = cluster.z_pred_block
    np.testing.assert_array_equal(block, _eager_block(cluster, _eager(cfg)))
    assert cluster.z_pred_block is block  # drawn once, then kept


def test_block_of_three_seeds_with_unequal_trials():
    """Each seed's trials are drawn from its own generator; the blocks
    need not be of one size (``stack_clusters`` itself asks for equal
    configs)."""
    spec = get_scenario("churn")
    cfgs = [spec.compile(seed=s, n_trials=t, **SHAPE)
            for s, t in ((3, 2), (11, 5), (40, 1))]
    parts = [_build_cluster(c) for c in cfgs]
    merged = replace(parts[0],
                     node_of=np.concatenate([p.node_of for p in parts]),
                     pred_noise=sum((p.pred_noise for p in parts), ()))
    assert [b.trials for b in merged.pred_noise] == [2, 5, 1]
    want = np.concatenate([_eager(c) for c in cfgs])
    np.testing.assert_array_equal(merged.z_pred_block,
                                  _eager_block(merged, want))


def test_stacked_clusters_keep_the_blocks_of_their_seeds():
    cfgs = [get_scenario("baseline").compile(seed=s, n_trials=2, **SHAPE)
            for s in (0, 1, 2)]
    stacked = stack_clusters([_build_cluster(c) for c in cfgs])
    assert len(stacked.pred_noise) == 3
    want = np.concatenate([_eager(c) for c in cfgs])
    np.testing.assert_array_equal(stacked.z_pred_block,
                                  _eager_block(stacked, want))


@pytest.mark.parametrize("scenario", ["baseline", "churn"])
def test_block_row_is_the_serial_steppers_candidates(scenario):
    """Row ``j`` of the block is the eager noise of request ``j``'s
    app's candidates (``app_prep(a).candidates``), as the serial stepper
    read it per app before the deferral."""
    cfgs = [get_scenario(scenario).compile(seed=s, n_trials=3, **SHAPE)
            for s in (5, 6)]
    stacked = stack_clusters([_build_cluster(c) for c in cfgs])
    z_pred = np.concatenate([_eager(c) for c in cfgs])
    block = stacked.z_pred_block
    for j, a in enumerate(stacked.req_app):
        cand = stacked.app_prep(int(a)).candidates
        np.testing.assert_array_equal(block[j], z_pred[:, j, cand])


def _lower_peak(cluster, policy):
    """(xs, peak bytes) of one ``simcore._lower`` under tracemalloc."""
    tracemalloc.start()
    try:
        _, _, xs, _, _ = simcore._lower(cluster, policy, None)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    return xs, peak


def _noise_spans(before):
    return {k: c - before.get(k, (0, 0.0))[0]
            for k, (c, _) in snapshot().items()
            if k.endswith("pred_noise") and c != before.get(k, (0, 0.0))[0]}


@pytest.mark.parametrize("scenario,policy,hedge,drawn", [
    ("baseline", "least_conn", None, False),
    ("churn", "round_robin", None, False),
    ("churn", "perf_aware", None, True),
    ("stale-predictions", "perf_aware", None, True),
    ("baseline", "perf_aware", 1.5, True),
])
def test_lowering_draws_only_the_block_a_pass_reads(scenario, policy,
                                                    hedge, drawn):
    cfg = get_scenario(scenario).compile(seed=4, n_trials=4,
                                         hedge_factor=hedge, **SHAPE)
    cluster = _build_cluster(cfg)
    full = cluster.node_of.size * len(cluster.req_t) * 8    # (T, J, R)
    before = snapshot()
    xs, peak = _lower_peak(cluster, policy)
    assert peak < full, (peak, full)     # no (T, J, R) array, ever
    assert _drawn(cluster) == drawn
    if drawn:
        np.testing.assert_array_equal(xs["zp"],
                                      _eager_block(cluster, _eager(cfg)))
        assert _noise_spans(before) == {"pred_noise": 1}
    else:
        assert "zp" not in xs
        assert _noise_spans(before) == {}


@pytest.mark.parametrize("policy,count", [("least_conn", 0),
                                          ("perf_aware", 1)])
def test_compiled_passes_draw_the_block_once_per_cluster(policy, count):
    """Two predictive passes over one cluster (``run_compiled`` then
    ``prepare_compiled``) share one draw; reactive passes make none."""
    cfg = get_scenario("churn").compile(seed=1, n_trials=2, n_nodes=10,
                                        n_replicas_per_app=6,
                                        n_requests=40)
    cluster = _build_cluster(cfg)
    before = snapshot()
    simcore.run_compiled(cluster, policy)
    simcore.prepare_compiled(cluster, policy)()
    want = {"lower/pred_noise": count} if count else {}
    assert _noise_spans(before) == want
    assert _drawn(cluster) == bool(count)


@pytest.mark.parametrize("policy,drawn", [("least_conn", False),
                                          ("perf_aware", True)])
def test_serial_pass_draws_the_block_only_to_read_it(policy, drawn):
    cfg = get_scenario("churn").compile(seed=2, n_trials=2, n_nodes=10,
                                        n_replicas_per_app=6,
                                        n_requests=40)
    cluster = _build_cluster(cfg)
    SimStepper(cluster, make_policy(policy, seed=0)).run()
    assert _drawn(cluster) == drawn
    if drawn:
        np.testing.assert_array_equal(cluster.z_pred_block,
                                      _eager_block(cluster, _eager(cfg)))
