"""§6 load-balancing simulation (paper Fig. 11), vectorised across trials.

Heterogeneous nodes (acceleration factor), empirically-shaped interference
matrix, log-normal RTT (Eqs. 10-11), noisy predictions (Eq. 12).  Policies
are NOT implemented here: every request is routed through the shared
policy engine (``repro.core.balancer.POLICIES``), the same classes the
live router and the benchmarks dispatch through (DESIGN.md §8).
Parameters are derived from the paper's own tables (Table 4 RMSE range,
Table 5 CoV range, Fig. 11 axes) since the exact repo parameters are not
in the paper text — documented in DESIGN.md §7.

All trials advance request-by-request in lockstep so every step is a
vectorised numpy op over (n_trials, n_candidates) arrays.  The loop is
split into three parts: cluster construction (:func:`_build_cluster`),
the per-request policy step (:class:`SimStepper` — reused by
``repro.core.campaign`` over a stack of per-seed clusters, DESIGN.md
§10), and metrics accumulation (:class:`_Metrics` — mean, p50/p95/p99
tails, per-app breakdown, resource-seconds).

Beyond the seed scenarios, the simulator supports:
  * every registered policy, including ``least_conn``;
  * prediction-guided hedging (``SimConfig.hedge_factor``);
  * stale predictions (``SimConfig.prediction_lag_s``): the predictor's
    view of cluster occupancy refreshes only every ``lag`` seconds, so
    interference-driven RTT shifts are seen late (paper §4's collection
    cycles are periodic, not per-request);
  * node failure / churn (``SimConfig.churn``): one random node per
    trial goes down at ``t_fail`` for ``downtime`` seconds — its
    replicas stop accepting work and policies must route around it;
  * non-Poisson arrivals (``arrival_process``: bursty on/off cycles,
    diurnal rate modulation, flash crowds);
  * discrete hardware tiers (``node_tiers``) on top of the continuous
    heterogeneity draw, and a ``hotspot`` interference profile where one
    app dominates co-location noise;
  * predictor cold start (``cold_start_s``): until the knowledge base
    has trained, predictions carry only the app-mean RTT — no occupancy
    or node-speed signal;
  * metric outages (``outage``): the predictor's occupancy snapshot is
    frozen for the whole window, however stale it gets (the
    ``PeriodicRefresh`` outage hook shared with the prediction plane);
  * closed-loop prediction (``closed_loop``, DESIGN.md §11): instead of
    the synthetic Eq. 12 accuracy draw, ``predicted`` comes from an
    :class:`~repro.core.online.OnlineFleet` of per-(trial, app)
    predictors trained on the RTTs the simulation itself observes,
    scoring the same (stale, outage-frozen) occupancy snapshot —
    prediction quality can now degrade under drift and recover with
    retraining, and ``fallback_threshold`` routes trials whose rolling
    accuracy drops below the viability floor via least-connections;
  * mid-run workload drift (``t_drift`` + ``drift_interference`` /
    ``drift_rtt_factor`` / ``drift_tier_shuffle``): at ``t_drift`` the
    interference matrix is redrawn, per-app mean RTTs are rescaled,
    and/or node speeds are reshuffled — the regime shifts the paper's
    §7 adaptability argument is about;
  * the capacity plane (``SimConfig.capacity``, DESIGN.md §12): an
    elastic per-trial active-replica set driven by a predictive or
    reactive autoscaler (``repro.core.capacity``), scale-up warm-up
    (cold replicas serve degraded RTT), scale-down draining, spot
    preemption (``preempt``), admission control (requests are SHED when
    even the active set cannot bound queue wait), and resource-waste
    accounting — every summary now reports replica-seconds provisioned
    vs busy, the idle-provisioned ``waste`` fraction, ``shed_rate``,
    and ``slo_violation_s``.  Node failure (``churn``), preemption, and
    autoscaler epochs all ride one membership-event timeline.

The declarative layer over these knobs lives in
``repro.core.scenarios`` (ScenarioSpec -> SimConfig).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, replace
from functools import cached_property
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.balancer import (BUSY_PENALTY, ClusterState, PerfAware,
                                 POLICIES, make_policy)
from repro.core.capacity import (CapacityConfig, CapacityController,
                                 DEFAULT_SLO_S, MembershipEvent,
                                 membership_timeline)
from repro.core.online import OnlineFleet
from repro.core.resilience import (backoff_delay, BreakerBoard,
                                   ResilienceConfig)
from repro.core.rng import rng_resume, rng_seed, rng_stream
from repro.core.telemetry import (compose_row, DISP_FAIL_FAST, DISP_SERVED,
                                  DISP_SHED, DISP_TIMEOUT, FlightRecorder,
                                  span, TraceConfig)
from repro.monitoring.metrics import PeriodicRefresh

# SPA app profiles: (mean RTT s, cpu cores/req, mem GB/req) — scaled from
# the paper's app set (upload / MotionCor2 / FFT mock / gCTF / ctffind4).
APPS = {
    "upload": (20.0, 0.5, 1.0),
    "motioncor2": (5.0, 2.0, 4.0),
    "fft_mock": (10.0, 1.0, 2.0),
    "gctf": (5.0, 2.0, 3.0),
    "ctffind4": (3.0, 1.0, 1.0),
}

ARRIVAL_PROCESSES = ("poisson", "bursty", "diurnal", "flash_crowd", "ramp")


@dataclass
class SimConfig:
    n_nodes: int = 10
    n_replicas_per_app: int = 4
    apps: Tuple[str, ...] = tuple(APPS)
    n_requests: int = 400           # per trial (all apps interleaved)
    n_trials: int = 200
    accuracy: float = 0.8           # p in Eq. 12
    heterogeneity: float = 0.3      # std of node acceleration factors
    interference_strength: float = 0.5
    arrival_rate: float = 2.0       # requests/s entering the cluster
    seed: int = 0
    # -- beyond-seed scenarios (defaults reproduce the seed behaviour) --
    hedge_factor: Optional[float] = None    # PerfAware hedging threshold
    prediction_lag_s: float = 0.0           # stale-prediction refresh lag
    churn: Optional[Tuple[float, float]] = None  # (t_fail_s, downtime_s)
    # -- scenario-engine knobs (DESIGN.md §10) --------------------------
    #: separate RNG stream for the request arrivals.  When set, configs
    #: differing only in ``seed`` share one arrival stream (paired
    #: comparison across seeds) — which is also what lets the campaign
    #: runner advance a stack of per-seed clusters in one lockstep pass.
    stream_seed: Optional[int] = None
    arrival_process: str = "poisson"
    #: per-process shape, () selects defaults:
    #:   bursty      (burst_factor, on_s, off_s)
    #:   diurnal     (period_s, amplitude<1)
    #:   flash_crowd (t_start_s, duration_s, factor)
    arrival_params: Tuple[float, ...] = ()
    #: discrete tier speed offsets cycled over nodes (e.g. (-0.4, 0, 1.0)
    #: = fast/standard/slow thirds); heterogeneity noise adds on top
    node_tiers: Optional[Tuple[float, ...]] = None
    interference_profile: str = "uniform"   # or "hotspot"
    cold_start_s: float = 0.0               # untrained-predictor window
    outage: Optional[Tuple[float, float]] = None  # (t_start_s, duration_s)
    # -- closed-loop online prediction (core/online.py, DESIGN.md §11) --
    #: ``predicted`` comes from per-(trial, app) online predictors
    #: trained on observed RTTs instead of the synthetic Eq. 12 draw
    closed_loop: bool = False
    online_warmup_s: float = 20.0     # observe-only window before 1st train
    retrain_every_s: float = 0.0      # 0 -> train once at warmup, frozen
    online_window: int = 400          # rolling observation window (requests)
    fallback_threshold: float = 0.0   # accuracy floor; 0 disables fallback
    accuracy_window: int = 40         # rolling accuracy tracker length
    # -- mid-run workload drift (DESIGN.md §11) -------------------------
    t_drift: Optional[float] = None               # drift onset (s)
    drift_interference: Optional[float] = None    # redraw imat, new strength
    drift_rtt_factor: Optional[Tuple[float, ...]] = None  # per-app factors
    drift_tier_shuffle: bool = False              # permute node speeds
    # -- capacity plane (core/capacity.py, DESIGN.md §12) ---------------
    #: elastic replica set + autoscaler + admission control; None keeps
    #: the fixed-membership behaviour (and its goldens) bit-identical
    capacity: Optional[CapacityConfig] = None
    #: spot preemption: (t_start_s, duration_s) — one node per trial is
    #: reclaimed for the window (requires ``capacity``)
    preempt: Optional[Tuple[float, float]] = None
    # -- resilience plane (core/resilience.py, DESIGN.md §14) -----------
    #: fault timeline (gray failure / correlated outage / staleness
    #: storm) + client-side timeout / retry / circuit-breaker semantics;
    #: None keeps every earlier scenario bit-identical
    resilience: Optional[ResilienceConfig] = None
    # -- flight recorder (core/telemetry.py, DESIGN.md §16) -------------
    #: per-request decision traces + additive RTT decomposition, emitted
    #: identically by the serial stepper and the compiled kernel; None
    #: keeps untraced runs (and their goldens) byte-identical
    trace: Optional[TraceConfig] = None


def _interference_matrix(apps: Sequence[str], strength: float,
                         rng) -> np.ndarray:
    """I[a, b]: relative RTT-std increase on app a per co-located busy b."""
    n = len(apps)
    base = rng.uniform(0.05, 0.35, size=(n, n))
    return strength * (base + base.T) / 2.0


def _apply_interference_profile(imat: np.ndarray, profile: str,
                                n_apps: int) -> np.ndarray:
    """Shape the raw interference draw: ``hotspot`` amplifies one heavy
    interferer's row AND column (the paper's MotionCor2-style app)."""
    if profile == "hotspot":
        h = min(1, n_apps - 1)
        imat = imat.copy()
        imat[h, :] *= 3.0
        imat[:, h] *= 3.0
    elif profile != "uniform":
        raise ValueError(f"unknown interference_profile {profile!r}")
    return imat


def _rate_factor(cfg: SimConfig, t: float) -> float:
    """Instantaneous arrival-rate multiplier at time t."""
    kind, p = cfg.arrival_process, cfg.arrival_params
    if kind == "bursty":
        factor, on_s, off_s = p or (6.0, 10.0, 30.0)
        return factor if (t % (on_s + off_s)) < on_s else 1.0
    if kind == "diurnal":
        period_s, amplitude = p or (240.0, 0.8)
        return 1.0 + amplitude * np.sin(2.0 * np.pi * t / period_s)
    if kind == "flash_crowd":
        t_start, duration, factor = p or (60.0, 30.0, 8.0)
        return factor if t_start <= t < t_start + duration else 1.0
    if kind == "ramp":
        # triangular overload: 1 -> peak over [t0, tp], peak -> 1 over
        # [tp, t1] — exercises the autoscaler in BOTH directions (scale
        # up under rising demand, release capacity as it recedes)
        t0, tp, t1, peak = p or (30.0, 80.0, 140.0, 5.0)
        if t <= t0 or t >= t1:
            return 1.0
        if t <= tp:
            return 1.0 + (peak - 1.0) * (t - t0) / max(tp - t0, 1e-9)
        return 1.0 + (peak - 1.0) * (t1 - t) / max(t1 - tp, 1e-9)
    raise ValueError(f"unknown arrival_process {kind!r}; "
                     f"one of {ARRIVAL_PROCESSES}")


def _arrival_times(cfg: SimConfig, rng) -> np.ndarray:
    """Request arrival times.  Poisson keeps the seed's exact draw; the
    modulated processes rescale unit-exponential gaps by the local rate
    (time-rescaling construction of an inhomogeneous Poisson process)."""
    if cfg.arrival_process == "poisson":
        return np.cumsum(rng.exponential(1.0 / cfg.arrival_rate,
                                         size=cfg.n_requests))
    gaps = rng.exponential(1.0, size=cfg.n_requests)
    out = np.empty(cfg.n_requests)
    t = 0.0
    for i, e in enumerate(gaps):
        t += e / max(cfg.arrival_rate * _rate_factor(cfg, t), 1e-9)
        out[i] = t
    return out


@dataclass
class _AppPrep:
    """Per-app tensors that do not change across steps, hoisted out of
    the per-request loop (built once, reused J times per policy)."""
    candidates: np.ndarray    # (C,) replica indices serving the app
    cand_flat: np.ndarray     # (T*C,) flat (trial, node) index per candidate
    weight: np.ndarray        # (T, R) interference weight per busy replica
    speed: np.ndarray         # (T, C) 1 + accel of each candidate's node
    log_rbar: float           # log of the app's mean RTT


@dataclass(frozen=True)
class _NoiseBlock:
    """One seed's prediction noise, not yet drawn: the state of its
    generator where the (trials, J, R) draw starts."""
    state: dict
    trials: int


@dataclass
class _Cluster:
    """Static per-run arrays: topology, request stream, pre-drawn noise.

    The prediction noise is the exception: ``pred_noise`` records where
    each seed's block lies in its generator's stream, and
    :attr:`z_pred_block` draws the candidates' part of it at its first
    read, bit for bit what an eager draw would give.

    ``imat`` is (A, A) for a single-seed cluster; the campaign's stacked
    clusters carry a per-trial (T, A, A) matrix because each seed drew
    its own interference mix.  The ``*_post`` arrays are the post-drift
    regime (active once ``now >= cfg.t_drift``): a None field keeps its
    pre-drift counterpart.
    """
    cfg: SimConfig
    app_of: np.ndarray        # (R,) app index per replica
    mean_rtt: np.ndarray      # (A,)
    cpu_req: np.ndarray       # (A,)
    mem_req: np.ndarray       # (A,)
    imat: np.ndarray          # (A, A) or (T, A, A) interference matrix
    node_of: np.ndarray       # (T, R) node per replica per trial
    accel: np.ndarray         # (T, N) node acceleration factors
    req_app: np.ndarray       # (J,) app index per request
    req_t: np.ndarray         # (J,) arrival time per request
    z_rtt: np.ndarray         # (T, J) RTT noise
    pred_noise: Tuple[_NoiseBlock, ...]  # (T, J, R) noise, per-seed blocks
    failed_node: Optional[np.ndarray] = None   # (T,) churn target
    imat_post: Optional[np.ndarray] = None     # post-drift interference
    accel_post: Optional[np.ndarray] = None    # post-drift node speeds
    mean_rtt_post: Optional[np.ndarray] = None  # post-drift app means
    preempted_node: Optional[np.ndarray] = None  # (T,) spot-preempt target
    # resilience plane (DESIGN.md §14): replicas on the gray node / in
    # the correlated-outage group, and the pre-drawn backoff jitter
    gray_rep: Optional[np.ndarray] = None      # (T, R) bool
    group_rep: Optional[np.ndarray] = None     # (T, R) bool
    z_jitter: Optional[np.ndarray] = None      # (T, J, max_retries) U[0,1)

    def __post_init__(self):
        self._prep: Dict[Tuple[int, bool], _AppPrep] = {}
        # flat (trial * n_nodes + node) index of every replica, for the
        # bincount node-bucket accumulation in rtt_draw
        T = len(self.node_of)
        N = self.cfg.n_nodes
        self._tn = T * N
        self._trial = np.arange(T)
        self._flat_nodes = (self._trial[:, None] * N
                            + self.node_of).ravel()
        # per-event scratch (DESIGN.md §13): the busy mask and the
        # weighted-occupancy product are recomputed every request, so the
        # serial reference path reuses ONE pair of (T, R) buffers instead
        # of allocating two fresh arrays per event
        self._busy_mask = np.empty(self.node_of.shape, bool)
        self._busy_w = np.empty(self.node_of.shape, float)

    @cached_property
    def z_pred_block(self) -> np.ndarray:
        """(J, T, K) prediction noise of each request's K candidates,
        drawn at its first read and kept: bit for bit the candidate
        slice of an eager (T, J, R) draw, made a trial at a time in one
        (J, R) scratch buffer, so the full tensor never exists.  Row
        ``j`` is what both engines read for request ``j``; a pass that
        scores no predictions never draws it."""
        with span("pred_noise"):
            J, T, A = len(self.req_t), len(self.node_of), len(self.mean_rtt)
            R = len(self.app_of)
            out = np.empty((J, T, R // A))
            scratch = np.empty((J, R))
            # app_of is np.repeat(arange(A), K): app a's candidates are
            # the contiguous replicas a*K .. a*K + K - 1
            by_app = scratch.reshape(J, A, R // A)
            rows = np.arange(J)
            t = 0
            for b in self.pred_noise:
                rng = rng_resume(b.state)
                for _ in range(b.trials):
                    rng.standard_normal(out=scratch)
                    out[:, t, :] = by_app[rows, self.req_app]
                    t += 1
            return out

    def in_drift(self, now: float) -> bool:
        return self.cfg.t_drift is not None and now >= self.cfg.t_drift

    def app_prep(self, a: int, post: bool = False) -> _AppPrep:
        key = (a, bool(post))
        prep = self._prep.get(key)
        if prep is None:
            imat = self.imat_post if post and self.imat_post is not None \
                else self.imat
            accel = self.accel_post if post and self.accel_post is not None \
                else self.accel
            mean_rtt = self.mean_rtt_post \
                if post and self.mean_rtt_post is not None else self.mean_rtt
            cand = np.flatnonzero(self.app_of == a)
            nodes = self.node_of[:, cand]                       # (T, C)
            T = len(self.node_of)
            if imat.ndim == 3:
                weight = imat[:, a, :][:, self.app_of]          # (T, R)
            else:
                weight = np.broadcast_to(imat[a][self.app_of],
                                         self.node_of.shape)
            trial = self._trial          # hoisted: no np.arange rebuild
            prep = _AppPrep(
                candidates=cand,
                cand_flat=(trial[:, None] * self.cfg.n_nodes
                           + nodes).ravel(),
                weight=weight,
                speed=1.0 + accel[trial[:, None], nodes],
                log_rbar=float(np.log(mean_rtt[a])))
            self._prep[key] = prep
        return prep

    def _node_buckets(self, p: _AppPrep, busy_until: np.ndarray,
                      now: float) -> np.ndarray:
        """(T*N,) summed interference weight of busy replicas per
        (trial, node) bucket — the shared core of :meth:`rtt_draw` and
        :meth:`rtt_draw_at`.  One bincount is O(T*R) instead of the
        O(T*C*R) mask product; each candidate then gathers its bucket.
        The mask and the product land in preallocated scratch buffers
        (``__post_init__``) — zero per-event allocations on this path."""
        busy = np.greater(busy_until, now, out=self._busy_mask)  # (T, R)
        w = np.multiply(busy, p.weight, out=self._busy_w)
        return np.bincount(self._flat_nodes, weights=w.ravel(),
                           minlength=self._tn)

    @staticmethod
    def _lognormal(log_rbar: float, inter: np.ndarray,
                   z: np.ndarray) -> np.ndarray:
        """Log-normal moment matching with s = rbar * (0.1 + inter):
        mu = log(rbar) - u/2, sigma = sqrt(u), u = log(1 + (s/rbar)^2)."""
        v = 0.1 + inter
        u = np.log1p(v * v)
        return np.exp(log_rbar - 0.5 * u + np.sqrt(u) * z)

    def rtt_draw(self, j: int, a: int, busy_until: np.ndarray,
                 now: float) -> np.ndarray:
        """True RTT per candidate under the given occupancy snapshot
        (log-normal with co-location interference, Eqs. 10-11)."""
        p = self.app_prep(a, self.in_drift(now))
        g = self._node_buckets(p, busy_until, now)
        inter = g[p.cand_flat].reshape(p.speed.shape)            # (T, C)
        x = self._lognormal(p.log_rbar, inter, self.z_rtt[:, j, None])
        return x * p.speed                                       # Eq. 10

    def rtt_draw_at(self, j: int, a: int, busy_until: np.ndarray,
                    now: float, picks: np.ndarray) -> np.ndarray:
        """The column of :meth:`rtt_draw` each trial actually picked,
        without materialising the other candidates.  Every op is
        elementwise in the candidate axis, so values are bit-identical
        to ``rtt_draw(...)[trial, picks]`` — the fast path for policies
        that never read the full RTT/prediction matrix
        (``tests/test_capacity.py`` pins the equivalence)."""
        p = self.app_prep(a, self.in_drift(now))
        g = self._node_buckets(p, busy_until, now)
        T = len(self.node_of)
        flat = p.cand_flat.reshape(T, -1)[self._trial, picks]
        x = self._lognormal(p.log_rbar, g[flat], self.z_rtt[:, j])
        return x * p.speed[self._trial, picks]


def _build_cluster(cfg: SimConfig) -> _Cluster:
    """Topology + request stream; same RNG order as the seed simulator so
    the default scenarios stay statistically unchanged."""
    rng = rng_stream(cfg.seed, "topology")
    T = cfg.n_trials
    A = len(cfg.apps)
    R = A * cfg.n_replicas_per_app
    imat = _apply_interference_profile(
        _interference_matrix(cfg.apps, cfg.interference_strength, rng),
        cfg.interference_profile, A)
    # per-trial random placement (isolate policy effect, as in the paper)
    node_of = rng.integers(0, cfg.n_nodes, size=(T, R))
    accel = np.clip(rng.normal(0.0, cfg.heterogeneity, size=(T, cfg.n_nodes)),
                    -0.8, 2.0)
    if cfg.node_tiers is not None:
        tiers = np.asarray(cfg.node_tiers, float)
        tier_of = np.arange(cfg.n_nodes) % len(tiers)
        accel = np.clip(tiers[tier_of][None, :] + accel, -0.8, 4.0)
    # request stream: same per policy for paired comparison.  With
    # stream_seed set, arrivals come from their own generator so configs
    # differing only in `seed` share one stream (campaign lockstep);
    # the named streams keep every consumer independent even when the
    # integer seeds collide (core/rng.py pins the legacy identities).
    if cfg.stream_seed is None:
        stream_rng = noise_rng = rng_stream(cfg.seed, "noise")
    else:
        stream_rng = rng_stream(cfg.stream_seed, "arrival")
        noise_rng = rng_stream(cfg.seed, "noise_streamed")
    req_app = stream_rng.integers(0, A, size=cfg.n_requests)
    req_t = _arrival_times(cfg, stream_rng)
    z_rtt = noise_rng.standard_normal((T, cfg.n_requests))
    # the (T, J, R) prediction noise is this generator's last draw: keep
    # where it starts, and draw it only when a pass reads it
    pred_noise = (_NoiseBlock(noise_rng.bit_generator.state, T),)
    failed_node = None
    if cfg.churn is not None:
        failed_node = rng_stream(cfg.seed, "churn").integers(
            0, cfg.n_nodes, size=T)
    preempted_node = None
    if cfg.preempt is not None:
        if cfg.capacity is None:
            raise ValueError("preempt requires a CapacityConfig (the "
                             "elastic replica set handles the takeback)")
        preempted_node = rng_stream(cfg.seed, "preempt").integers(
            0, cfg.n_nodes, size=T)
    # resilience plane (DESIGN.md §14): one "fault" stream, fixed draw
    # order (gray target -> outage-group start -> backoff jitter) so
    # adding a later fault never moves an earlier one
    gray_rep = group_rep = z_jitter = None
    res = cfg.resilience
    if res is not None:
        if cfg.hedge_factor is not None and res.client_side:
            raise ValueError(
                "hedge_factor and resilience timeouts are mutually "
                "exclusive (a hedged duplicate has no attempt identity "
                "for the timeout/breaker state machine)")
        fault_rng = rng_stream(cfg.seed, "fault")
        if res.gray is not None:
            gray_node = fault_rng.integers(0, cfg.n_nodes, size=T)
            gray_rep = node_of == gray_node[:, None]
        if res.outage_group is not None:
            n_down = min(int(res.outage_group[2]), cfg.n_nodes)
            start = fault_rng.integers(0, cfg.n_nodes, size=T)
            off = (node_of - start[:, None]) % cfg.n_nodes
            group_rep = off < n_down     # contiguous group, wrap mod N
        if res.client_side:
            z_jitter = fault_rng.random((T, cfg.n_requests,
                                         res.max_retries))
    mean_rtt = np.array([APPS[a][0] for a in cfg.apps])
    # post-drift regime: redrawn interference mix, reshuffled node
    # speeds, rescaled app means — all from drift-salted generators so
    # the pre-drift draws (and every non-drift config) stay untouched
    imat_post = accel_post = mean_rtt_post = None
    if cfg.t_drift is not None:
        drift_rng = rng_stream(cfg.seed, "drift")
        if cfg.drift_interference is not None:
            imat_post = _apply_interference_profile(
                _interference_matrix(cfg.apps, cfg.drift_interference,
                                     drift_rng),
                cfg.interference_profile, A)
        if cfg.drift_tier_shuffle:
            perm = np.argsort(drift_rng.random((T, cfg.n_nodes)), axis=1)
            accel_post = np.take_along_axis(accel, perm, axis=1)
        if cfg.drift_rtt_factor is not None:
            factor = np.broadcast_to(
                np.asarray(cfg.drift_rtt_factor, float), (A,))
            mean_rtt_post = mean_rtt * factor
    return _Cluster(
        cfg=cfg,
        app_of=np.repeat(np.arange(A), cfg.n_replicas_per_app),
        mean_rtt=mean_rtt,
        cpu_req=np.array([APPS[a][1] for a in cfg.apps]),
        mem_req=np.array([APPS[a][2] for a in cfg.apps]),
        imat=imat, node_of=node_of, accel=accel,
        req_app=req_app, req_t=req_t, z_rtt=z_rtt, pred_noise=pred_noise,
        failed_node=failed_node, imat_post=imat_post,
        accel_post=accel_post, mean_rtt_post=mean_rtt_post,
        preempted_node=preempted_node,
        gray_rep=gray_rep, group_rep=group_rep, z_jitter=z_jitter)


class _Metrics:
    """Per-trial accumulation: full RTT matrix (for tail percentiles and
    the per-app breakdown), resource-seconds, assignments, and the
    capacity plane's waste / shed / SLO accounting (DESIGN.md §12).

    Shed and timed-out requests carry NaN in the RTT matrix and -1 in
    ``chosen``; RTT stats then become nan-aware.  The guard is the
    CONFIG — *can this config drop a request at all* (capacity with
    admission control, OR a resilience plane with a client timeout) —
    never the data, so batched and serial campaign runs always take the
    same code path even when a particular seed happens to shed nothing.
    """

    def __init__(self, cfg: SimConfig):
        T, J = cfg.n_trials, cfg.n_requests
        self.cfg = cfg
        self.rtts = np.zeros((T, J))
        self.cpu_s = np.zeros(T)
        self.mem_s = np.zeros(T)
        self.chosen = np.zeros((T, J), dtype=np.int64)
        self.n_hedged = 0
        self.hedged = np.zeros(T, dtype=np.int64)   # per-trial hedge count
        # capacity-plane accounting (reported for EVERY run: without a
        # CapacityConfig the whole pool counts as provisioned and the
        # accounting SLO defaults to DEFAULT_SLO_S)
        self.slo = cfg.capacity.slo_target_s if cfg.capacity is not None \
            else DEFAULT_SLO_S
        can_shed = cfg.capacity is not None \
            and cfg.capacity.admission_limit_s is not None
        can_timeout = cfg.resilience is not None \
            and cfg.resilience.client_side
        self._nan_stats = can_shed or can_timeout
        self.busy_s = np.zeros(T)           # replica-seconds of service
        self.slo_violation_s = np.zeros(T)  # response time above the SLO
        self.shed = np.zeros((T, J), bool)
        self.n_fallback = 0                 # least_conn-fallback routings
        # resilience-plane accounting (DESIGN.md §14)
        self.timeout = np.zeros((T, J), bool)  # all attempts timed out
        self.fail_fast = np.zeros((T, J), bool)  # timed out, 0 dispatches
        self.attempts = np.zeros(T)            # dispatched attempts
        self.wasted_s = np.zeros(T)            # timed-out attempts' work

    def add(self, j: int, response: np.ndarray, cpu: np.ndarray,
            mem: np.ndarray, rep: np.ndarray, rtt: np.ndarray,
            shed: Optional[np.ndarray] = None,
            timeout: Optional[np.ndarray] = None,
            fail_fast: Optional[np.ndarray] = None):
        self.rtts[:, j] = response
        self.cpu_s += cpu
        self.mem_s += mem
        if shed is None and timeout is None:
            self.chosen[:, j] = rep
            self.busy_s += rtt
            self.slo_violation_s += np.maximum(response - self.slo, 0.0)
        else:
            fail = np.zeros(len(response), bool)
            if shed is not None:
                self.shed[:, j] = shed
                fail |= shed
            if timeout is not None:
                self.timeout[:, j] = timeout
                fail |= timeout
            if fail_fast is not None:
                self.fail_fast[:, j] = fail_fast
            served = ~fail
            self.chosen[:, j] = np.where(fail, -1, rep)
            self.busy_s += np.where(served, rtt, 0.0)
            self.slo_violation_s += np.where(
                served, np.maximum(response - self.slo, 0.0), 0.0)

    def _stat_fns(self):
        if not self._nan_stats:
            return np.mean, np.percentile
        return np.nanmean, np.nanpercentile

    def summary(self, cluster: _Cluster,
                busy_until: Optional[np.ndarray] = None,
                capacity: Optional[CapacityController] = None
                ) -> Dict[str, np.ndarray]:
        mean_fn, pct_fn = self._stat_fns()
        with warnings.catch_warnings():
            # all-shed slices legitimately yield NaN stats
            warnings.simplefilter("ignore", RuntimeWarning)
            p50, p95, p99 = pct_fn(self.rtts, [50, 95, 99], axis=1)
            per_app = {}
            for i, name in enumerate(self.cfg.apps):
                mask = cluster.req_app == i
                if mask.any():
                    per_app[name] = mean_fn(self.rtts[:, mask], axis=1)
            mean_rtt = mean_fn(self.rtts, axis=1)
        # replica-seconds provisioned: the capacity ledger when elastic,
        # else the full pool over the per-trial horizon (which covers
        # every completion, so waste = idle fraction stays in [0, 1])
        t_end = float(cluster.req_t[-1])
        if busy_until is not None:
            t_end = np.maximum(t_end, busy_until.max(axis=1))
        if capacity is not None:
            capacity.finalize(t_end)
            provisioned = capacity.prov_s.copy()
        else:
            provisioned = len(cluster.app_of) * np.asarray(t_end, float) \
                * np.ones(len(self.rtts))
        waste = np.clip(1.0 - self.busy_s / np.maximum(provisioned, 1e-9),
                        0.0, 1.0)
        out = {"mean_rtt": mean_rtt,
               "p50_rtt": p50, "p95_rtt": p95, "p99_rtt": p99,
               "per_app": per_app,
               "cpu_s": self.cpu_s, "mem_s": self.mem_s,
               "chosen": self.chosen, "n_hedged": self.n_hedged,
               "hedged_per_trial": self.hedged,
               # capacity-plane accounting (DESIGN.md §12)
               "provisioned_s": provisioned, "busy_s": self.busy_s,
               "waste": waste,
               "shed_rate": self.shed.mean(axis=1),
               "n_shed": int(self.shed.sum()),
               "slo_violation_s": self.slo_violation_s,
               "n_fallback": self.n_fallback,
               # resilience-plane accounting (DESIGN.md §14): goodput is
               # the fraction of requests that completed at all — shed
               # and timed-out requests both count against it
               "goodput": 1.0 - (self.shed | self.timeout).mean(axis=1),
               "timeout_rate": self.timeout.mean(axis=1),
               "n_timeouts": int(self.timeout.sum()),
               # NaN-disposition split: admission shed vs client timeout
               # vs breaker/drain fail-fast (timed out with 0 dispatched
               # attempts).  fail_fast ⊂ timeout, so the three resolved
               # buckets are shed / (timeout & ~fail_fast) / fail_fast.
               "n_client_timeout": int((self.timeout
                                        & ~self.fail_fast).sum()),
               "n_fail_fast": int(self.fail_fast.sum()),
               "client_timeout_rate": (self.timeout
                                       & ~self.fail_fast).mean(axis=1),
               "fail_fast_rate": self.fail_fast.mean(axis=1),
               "attempts_per_req": self.attempts / self.rtts.shape[1],
               "wasted_work_s": self.wasted_s,
               # raw per-request views (windowed analyses, e.g. the
               # post-drift recovery metric in benchmarks/bench_online)
               "rtts": self.rtts, "req_t": cluster.req_t}
        if capacity is not None:
            out["capacity"] = capacity.telemetry()
        return out


class SimStepper:
    """Advance all trials one request at a time — the reusable core of
    :func:`run_sim`.

    The stepper owns the mutable state (occupancy, metrics, the stale /
    outage snapshot, the churn latch); the cluster stays read-only, so
    one cluster can be re-stepped under many policies.  Because every
    step is already a vectorised op over the (T, C) trial axis — the
    same batch axis the policy engine's ``score(state)`` takes — the
    campaign runner batches a whole seed grid simply by handing in a
    cluster whose trial axis stacks per-seed clusters (DESIGN.md §10).
    """

    def __init__(self, cluster: _Cluster, policy):
        cfg = cluster.cfg
        self.cluster = cluster
        self.cfg = cfg
        self.pol = policy
        self.hedging = isinstance(policy, PerfAware) \
            and cfg.hedge_factor is not None
        # reactive policies never read predicted/actual: skip building
        # the full per-candidate RTT matrix and draw only the pick
        self.reactive = not self.hedging and not policy.requires
        # only prediction-consuming policies pay for the predicted
        # matrix (the oracle reads state.actual, never state.predicted)
        self.needs_pred = self.hedging or "predicted" in policy.requires
        T = cfg.n_trials
        self.trial = np.arange(T)
        self.busy_until = np.zeros((T, len(cluster.app_of)))
        self.metrics = _Metrics(cfg)
        # flight recorder (DESIGN.md §16): per-request decision traces +
        # additive RTT decomposition, sampled every trace.sample_every
        self.recorder: Optional[FlightRecorder] = None
        tr = cfg.trace
        if tr is not None:
            self.recorder = FlightRecorder(cfg.n_requests, T,
                                           tr.sample_every)
        # closed-loop mode: per-(trial, app) online predictors trained
        # on the RTTs this run observes (DESIGN.md §11)
        self.fleet = None
        if cfg.closed_loop and self.needs_pred:
            self.fleet = OnlineFleet(
                cluster.node_of, cluster.app_of, cfg.n_nodes,
                len(cfg.apps), cluster.mean_rtt,
                warmup_s=cfg.online_warmup_s,
                retrain_every_s=cfg.retrain_every_s,
                window=cfg.online_window,
                accuracy_window=cfg.accuracy_window)
        # stale-prediction state: the predictor's occupancy snapshot
        # refreshes on the plane's periodic-collection cadence (shared
        # PeriodicRefresh), not per request; an outage freezes it for
        # the whole window regardless of the cadence.  A resilience
        # staleness storm is one more outage window on the same hook
        # (with lag 0 the snapshot is live outside the storm and frozen
        # inside it).
        res = cfg.resilience
        outages = ()
        if cfg.outage is not None:
            t0, duration = cfg.outage
            outages = ((t0, t0 + duration),)
        if res is not None and res.staleness is not None:
            s0, sdur = res.staleness
            outages = outages + ((s0, s0 + sdur),)
        self.snapshot = PeriodicRefresh(cfg.prediction_lag_s, outages) \
            if (cfg.prediction_lag_s > 0 or outages) else None
        # resilience plane (DESIGN.md §14)
        self.res = res
        self.breaker: Optional[BreakerBoard] = None
        if res is not None and res.breaker_threshold is not None:
            self.breaker = BreakerBoard(
                len(cluster.app_of), res.breaker_threshold,
                res.breaker_cooldown_s, res.timeout_s, n_trials=T)
        self.capacity: Optional[CapacityController] = None
        if cfg.capacity is not None:
            self.capacity = CapacityController(
                cfg.capacity, cluster.app_of, cluster.node_of,
                cluster.mean_rtt, cluster.req_app, cluster.req_t,
                cluster.preempted_node)
        # membership-event timeline (DESIGN.md §12): node churn, spot
        # preemption, and autoscaler epochs ride ONE precomputed
        # timeline in exact heap pop order — event times are
        # data-independent, so `membership_timeline` materialises the
        # sequence up front and this stepper walks it with a pointer
        # (the compiled scan core lowers the same timeline to masked
        # per-step updates, DESIGN.md §13)
        self._timeline: List[MembershipEvent] = membership_timeline(
            float(cluster.req_t[-1]), churn=cfg.churn,
            capacity=cfg.capacity, preempt=cfg.preempt,
            outage_group=None if res is None else res.outage_group)
        self._ev_ptr = 0

    def _advance_membership(self, now: float):
        """Apply every timeline event with ``t <= now``: the churn
        busy-bump (numerically identical to the old one-shot latch),
        spot preemption windows, and autoscaler epochs."""
        while self._ev_ptr < len(self._timeline) \
                and self._timeline[self._ev_ptr].t <= now:
            ev = self._timeline[self._ev_ptr]
            self._ev_ptr += 1
            if ev.kind == "churn":
                down = self.cluster.node_of \
                    == self.cluster.failed_node[:, None]         # (T, R)
                t_up = self.cfg.churn[0] + self.cfg.churn[1]
                self.busy_until = np.where(
                    down, np.maximum(self.busy_until, t_up),
                    self.busy_until)
            elif ev.kind == "scale":
                self.capacity.decide(ev.t, self.busy_until)
            elif ev.kind == "group_down":
                # correlated outage: the whole node group drops at once
                # (churn's busy-bump, group-wide — DESIGN.md §14)
                g0, gdur, _ = self.res.outage_group
                self.busy_until = np.where(
                    self.cluster.group_rep,
                    np.maximum(self.busy_until, g0 + gdur),
                    self.busy_until)
            elif ev.kind == "preempt_down":
                self.capacity.preempt(ev.t, self.busy_until)
            elif ev.kind == "preempt_up":
                self.capacity.restore(ev.t)

    def _gray_mult(self, now: float,
                   candidates: np.ndarray) -> Optional[np.ndarray]:
        """(T, C) gray-failure RTT multiplier inside the gray window,
        else None.  Applied to the TRUE RTT only — the prediction basis
        keeps the healthy view the replica still advertises."""
        res = self.res
        if res is None or res.gray is None:
            return None
        g0, gdur, gslow = res.gray
        if not g0 <= now < g0 + gdur:
            return None
        return np.where(self.cluster.gray_rep[:, candidates], gslow, 1.0)

    def step(self, j: int):
        cluster, cfg = self.cluster, self.cfg
        a = int(cluster.req_app[j])
        now = float(cluster.req_t[j])

        self._advance_membership(now)
        busy_until, trial = self.busy_until, self.trial

        prep = cluster.app_prep(a)
        candidates = prep.candidates

        # capacity plane: wake scale-from-zero apps, evaluate admission,
        # and expose the routable mask + cold-replica degradation
        capacity = self.capacity
        active = cold = shed = served = None
        if capacity is not None:
            capacity.wake(a, now)
            shed = capacity.shed_mask(candidates, busy_until, now)
            served = None if shed is None else ~shed
            active = capacity.active_for(candidates)
            cold = capacity.cold_mult(candidates, now)

        graym = self._gray_mult(now, candidates)
        # tracing a sampled request replaces pick() with its exact
        # decomposition (score -> masked argmin -> update) so the
        # winning score can be recorded without disturbing any policy
        # RNG stream — bitwise-identical picks either way
        rec = self.recorder
        tracing = rec is not None and rec.wants(j)
        tr_scores = raw = None
        predicted = fleet_X = fleet_pred = None
        if self.reactive:
            state = ClusterState(now=now,
                                 busy_until=busy_until[:, candidates],
                                 active=active)
            if tracing:
                tr_scores = self.pol.score(state)
                picks = np.argmin(state.mask_inactive(tr_scores), axis=1)
                self.pol.update(state, picks)
            else:
                picks = self.pol.pick(state)
            rep = candidates[picks]
            rtt = cluster.rtt_draw_at(j, a, busy_until, now, picks)
            raw = rtt                       # pre cold/gray service draw
            if cold is not None:
                rtt = rtt * cold[trial, picks]
            if graym is not None:
                rtt = rtt * graym[trial, picks]
        else:
            actual = cluster.rtt_draw(j, a, busy_until, now)
            actual_raw = actual             # pre cold/gray service draws
            if cold is not None:
                actual = actual * cold      # cold replicas serve degraded
            if self.fleet is not None:
                # closed loop: the fleet folds completed observations,
                # retrains on its cadence, and scores the same (stale,
                # outage-frozen) occupancy snapshot the Eq. 12 path
                # would have used (DESIGN.md §11)
                self.fleet.fold_pending(now)
                self.fleet.maybe_retrain(now)
                stale_busy = busy_until
                if self.snapshot is not None:
                    stale_busy = self.snapshot.get(now, busy_until.copy)
                fleet_X = self.fleet.features(a, candidates, stale_busy,
                                              now)
                fleet_pred = self.fleet.predict(a, fleet_X)
                predicted = fleet_pred
                if cfg.fallback_threshold > 0:
                    # non-viable trials fall back to least_conn: zeroing
                    # the prediction leaves score = queue wait exactly
                    ok = self.fleet.viable(a, cfg.fallback_threshold)
                    predicted = np.where(ok[:, None], fleet_pred, 0.0)
                    self.metrics.n_fallback += int((~ok).sum())
            elif self.needs_pred:
                # predicted RTT: Eq. 12 with eps = (1 - p) * actual,
                # computed on the (possibly stale) occupancy snapshot the
                # predictor last saw.  Before cold_start_s no predictor
                # has trained yet: the basis is the bare app-mean RTT
                # (no occupancy / node-speed signal).
                if now < cfg.cold_start_s:
                    pred_basis = np.broadcast_to(
                        cluster.mean_rtt[a], actual.shape).copy()
                elif self.snapshot is not None:
                    stale_busy = self.snapshot.get(now, busy_until.copy)
                    pred_basis = cluster.rtt_draw(j, a, stale_busy, now)
                else:
                    pred_basis = actual
                if cold is not None and pred_basis is not actual:
                    # the predictor knows membership state: cold
                    # replicas are predicted slow too ("actual" already
                    # carries the factor)
                    pred_basis = pred_basis * cold
                eps = (1.0 - cfg.accuracy) * pred_basis
                predicted = pred_basis + eps * cluster.z_pred_block[j]
            if graym is not None:
                # AFTER the prediction basis is fixed: the multiply makes
                # a fresh array, so a ``pred_basis is actual`` alias keeps
                # the healthy view while the oracle / served RTT see the
                # gray truth
                actual = actual * graym

            state = ClusterState(now=now,
                                 busy_until=busy_until[:, candidates],
                                 predicted=predicted, actual=actual,
                                 active=active)
            if self.hedging:
                scores = self.pol.score(state)  # reused by hedge_plan
                picks = np.argmin(state.mask_inactive(scores), axis=1)
                self.pol.update(state, picks)
                tr_scores = scores
            elif tracing:
                tr_scores = self.pol.score(state)
                picks = np.argmin(state.mask_inactive(tr_scores), axis=1)
                self.pol.update(state, picks)
            else:
                picks = self.pol.pick(state)
            rep = candidates[picks]
            rtt = actual[trial, picks]
            raw = actual_raw[trial, picks]
        if tracing:
            # pre-commit queue wait on the chosen replica (busy_until is
            # overwritten by _settle / the hedge-duplicate commit below)
            tr_qwait = np.maximum(busy_until[trial, rep] - now, 0.0)
        finish = np.maximum(now, busy_until[trial, rep]) + rtt
        if self.fleet is not None:
            # the routed request is the training signal: picked
            # candidate's features, its true RTT, and when it completes
            # (shed trials contribute nothing)
            self.fleet.observe(a, fleet_X[trial, picks], rtt, finish,
                               fleet_pred[trial, picks], served=served)
        if capacity is not None:
            # feed the autoscaler's signals: the drained-replica
            # invariant, and the service-RTT estimate (route-time fleet
            # forecast when predictions exist, completion-folded
            # observations otherwise — never clairvoyant)
            capacity.check_routed(rep, served)
            if fleet_pred is not None:
                capacity.note_prediction(a, fleet_pred[trial, picks],
                                         served)
            elif predicted is not None:
                capacity.note_prediction(a, predicted[trial, picks],
                                         served)
            else:
                capacity.note_completion(a, rtt, finish, served)
        cpu = cluster.cpu_req[a] * rtt
        mem = cluster.mem_req[a] * rtt

        if self.hedging:
            second, mask = self.pol.hedge_plan(state, picks, scores)
            if served is not None:
                mask = mask & served
            rep2 = candidates[second]
            rtt2 = actual[trial, second]
            finish2 = np.maximum(now, busy_until[trial, rep2]) + rtt2
            response = np.where(mask, np.minimum(finish, finish2),
                                finish) - now
            response, cpu, mem = self._settle(served, response, finish,
                                              rep, cpu, mem)
            hm = np.flatnonzero(mask)
            busy_until[hm, rep2[hm]] = finish2[hm]    # duplicate occupies
            cpu = cpu + mask * cluster.cpu_req[a] * rtt2   # resource waste
            mem = mem + mask * cluster.mem_req[a] * rtt2
            self.metrics.busy_s += mask * rtt2
            self.metrics.n_hedged += int(mask.sum())
            self.metrics.hedged += mask
        else:
            response, cpu, mem = self._settle(served, finish - now,
                                              finish, rep, cpu, mem)

        self.metrics.add(j, response, cpu, mem, rep, rtt, shed)
        if tracing:
            if self.hedging:
                hedge_s = np.where(mask,
                                   finish - np.minimum(finish, finish2),
                                   0.0)
            else:
                hedge_s = 0.0
            # zero-interference service draw on the chosen replica's
            # (possibly post-drift) tier: same z, same speed, inter = 0
            p = cluster.app_prep(a, cluster.in_drift(now))
            base = _Cluster._lognormal(p.log_rbar, 0.0,
                                       cluster.z_rtt[:, j]) \
                * p.speed[trial, picks]
            disp = np.zeros(len(rep)) if shed is None \
                else np.where(shed, DISP_SHED, DISP_SERVED)
            rec.record(j, compose_row(
                rep=rep,
                predicted=(predicted[trial, picks]
                           if predicted is not None else np.nan),
                score=tr_scores[trial, picks],
                queue_wait=tr_qwait, raw=raw, base=base,
                cold_mult=cold[trial, picks] if cold is not None else 1.0,
                gray_mult=(graym[trial, picks]
                           if graym is not None else 1.0),
                retry_s=0.0, hedge_s=hedge_s, disposition=disp,
                response=response))

    def _settle(self, served, response, finish, rep, cpu, mem):
        """Commit the routed request's occupancy and mask the shed
        trials out of the response/resource accounting (one place, both
        the hedged and plain paths)."""
        if served is None:
            self.busy_until[self.trial, rep] = finish
            return response, cpu, mem
        ok = np.flatnonzero(served)
        self.busy_until[ok, rep[ok]] = finish[ok]
        return (np.where(served, response, np.nan),
                np.where(served, cpu, 0.0), np.where(served, mem, 0.0))

    def step_res(self, j: int):
        """One request under the client-side resilience plane
        (DESIGN.md §14): per-request timeout, bounded retries with
        exponential backoff + jitter, per-replica circuit breaker.  A
        statically unrolled attempt loop (1 + max_retries) replaces
        :meth:`step`'s single dispatch.

        RTT noise and the interference snapshot are REQUEST-scoped: the
        true-RTT matrix is drawn once at arrival occupancy and each
        attempt gathers its pick's column; occupancy feedback between
        attempts flows through queue wait only.  A dispatched attempt
        occupies the server for its full service time whether or not
        the client waits for the answer — the retry-amplification
        mechanism.  The compiled kernel lowers the identical unroll
        (``tests/test_resilience.py`` pins the parity).
        """
        cluster, cfg, res = self.cluster, self.cfg, self.res
        a = int(cluster.req_app[j])
        now = float(cluster.req_t[j])

        self._advance_membership(now)
        busy_until, trial = self.busy_until, self.trial
        T = len(trial)

        prep = cluster.app_prep(a)
        candidates = prep.candidates
        C = len(candidates)

        # capacity plane: admission is evaluated ONCE at arrival — a
        # shed request never dispatches an attempt
        capacity = self.capacity
        active = cold = shed = None
        if capacity is not None:
            capacity.wake(a, now)
            shed = capacity.shed_mask(candidates, busy_until, now)
            active = capacity.active_for(candidates)
            cold = capacity.cold_mult(candidates, now)

        # the once-per-request true-RTT matrix at ARRIVAL occupancy
        actual = cluster.rtt_draw(j, a, busy_until, now)
        actual_raw = actual                 # pre cold/gray service draws
        if cold is not None:
            actual = actual * cold
        pol = self.pol
        predicted = fleet_X = fleet_pred = None
        if self.fleet is not None:
            self.fleet.fold_pending(now)
            self.fleet.maybe_retrain(now)
            stale_busy = busy_until
            if self.snapshot is not None:
                stale_busy = self.snapshot.get(now, busy_until.copy)
            fleet_X = self.fleet.features(a, candidates, stale_busy, now)
            fleet_pred = self.fleet.predict(a, fleet_X)
            predicted = fleet_pred
            if cfg.fallback_threshold > 0:
                ok = self.fleet.viable(a, cfg.fallback_threshold)
                predicted = np.where(ok[:, None], fleet_pred, 0.0)
                self.metrics.n_fallback += int((~ok).sum())
        elif self.needs_pred:
            if now < cfg.cold_start_s:
                pred_basis = np.broadcast_to(cluster.mean_rtt[a],
                                             actual.shape).copy()
            elif self.snapshot is not None:
                stale_busy = self.snapshot.get(now, busy_until.copy)
                pred_basis = cluster.rtt_draw(j, a, stale_busy, now)
            else:
                pred_basis = actual
            if cold is not None and pred_basis is not actual:
                pred_basis = pred_basis * cold
            eps = (1.0 - cfg.accuracy) * pred_basis
            predicted = pred_basis + eps * cluster.z_pred_block[j]
        graym = self._gray_mult(now, candidates)
        if graym is not None:
            actual = actual * graym   # fresh array: the prediction
            # basis above keeps the healthy (advertised) view

        # the rng-consuming scoring inputs are drawn ONCE per request
        # (same draw counts as the non-resilient path, so the campaign's
        # seed_blocks replay stays exact); retries rescore statelessly
        # at their own per-trial attempt times
        draws = None
        if pol.name == "random":
            if pol._blocks is not None:
                draws = np.concatenate(
                    [rng.random((n, C)) for rng, n in pol._blocks],
                    axis=0)
            else:
                draws = pol.rng.random((T, C))
        cursor = None
        if pol.name == "round_robin":
            pol._ensure(T)
            cursor = pol._cursor

        timeout = res.timeout_s
        shed_m = np.zeros(T, bool) if shed is None else shed
        success = np.zeros(T, bool)
        t_att = np.full(T, now)
        rep_fin = np.zeros(T, np.int64)
        picks_fin = np.zeros(T, np.int64)
        rtt_fin = np.zeros(T)
        fin_fin = np.zeros(T)
        disp_work = np.zeros(T)        # ALL dispatched service time
        n_att = np.zeros(T)
        rec = self.recorder
        tracing = rec is not None and rec.wants(j)
        if tracing:
            # successful-attempt captures for the trace row: winning
            # score, attempt start time, queue wait at dispatch
            sc_fin = np.zeros(T)
            t_att_fin = np.zeros(T)
            qw_fin = np.zeros(T)

        for i in range(1 + res.max_retries):
            alive = ~success & ~shed_m
            if not alive.any():
                break
            mask = np.ones((T, C), bool) if active is None \
                else active.copy()
            if self.breaker is not None:
                mask &= ~self.breaker.open_mask(t_att)[:, candidates]
            dispatch = alive & mask.any(axis=1)

            busy_c = busy_until[:, candidates]
            wait = np.maximum(busy_c - t_att[:, None], 0.0)
            if pol.name in ("perf_aware", "oracle"):
                sc = wait + (actual if pol.name == "oracle"
                             else predicted)
            elif pol.name == "least_conn":
                sc = busy_c - t_att[:, None]
            elif pol.name == "round_robin":
                dist = (np.arange(C)[None, :] - cursor[:, None]) % C
                sc = np.where(busy_c <= t_att[:, None],
                              dist.astype(float), BUSY_PENALTY + wait)
            else:   # random
                sc = np.where(busy_c <= t_att[:, None], draws,
                              BUSY_PENALTY + wait)
            picks = np.argmin(np.where(mask, sc, np.inf), axis=1)
            rep = candidates[picks]
            rtt_i = actual[trial, picks]
            b_pick = busy_until[trial, rep]
            resp_i = np.maximum(b_pick - t_att, 0.0) + rtt_i
            ok_i = dispatch & (resp_i <= timeout)
            tmo_i = dispatch & ~ok_i

            # the server does the work whether or not the client is
            # still listening
            finish_i = np.maximum(t_att, b_pick) + rtt_i
            d = np.flatnonzero(dispatch)
            busy_until[d, rep[d]] = finish_i[d]
            disp_work += np.where(dispatch, rtt_i, 0.0)
            n_att += dispatch
            if cursor is not None:
                cursor = np.where(dispatch, (picks + 1) % C, cursor)
            if self.breaker is not None:
                self.breaker.record(t_att, rep, ok_i, tmo_i)

            ok = np.flatnonzero(ok_i)
            rep_fin[ok] = rep[ok]
            picks_fin[ok] = picks[ok]
            rtt_fin[ok] = rtt_i[ok]
            fin_fin[ok] = t_att[ok] + resp_i[ok]
            if tracing:
                sc_fin[ok] = sc[ok, picks[ok]]
                t_att_fin[ok] = t_att[ok]
                qw_fin[ok] = np.maximum(b_pick[ok] - t_att[ok], 0.0)
            success |= ok_i

            if i < res.max_retries:
                delay = backoff_delay(res, i, cluster.z_jitter[:, j, i])
                # a failed DISPATCH is learned only at the timeout; a
                # fail-fast attempt (no routable candidate — breaker
                # open or replica set drained) goes straight to backoff.
                # That asymmetry is why breakers arrest retry storms.
                t_att = np.where(dispatch, t_att + timeout + delay,
                                 t_att + delay)
        if cursor is not None:
            pol._cursor = cursor

        timed_out = ~success & ~shed_m
        response = np.where(success, fin_fin - now, np.nan)
        if self.fleet is not None:
            # only completed requests train the predictor or count
            # against rolling accuracy — a timed-out request has no
            # observed RTT (DESIGN.md §14)
            self.fleet.observe(a, fleet_X[trial, picks_fin], rtt_fin,
                               fin_fin, fleet_pred[trial, picks_fin],
                               served=success)
        if capacity is not None:
            capacity.check_routed(rep_fin, success)
            if fleet_pred is not None:
                capacity.note_prediction(a, fleet_pred[trial, picks_fin],
                                         success)
            elif predicted is not None:
                capacity.note_prediction(a, predicted[trial, picks_fin],
                                         success)
            else:
                capacity.note_completion(a, rtt_fin, fin_fin, success)
        cpu = np.where(success, cluster.cpu_req[a] * rtt_fin, 0.0)
        mem = np.where(success, cluster.mem_req[a] * rtt_fin, 0.0)
        fail_fast = timed_out & (n_att == 0)
        self.metrics.add(j, response, cpu, mem, rep_fin, rtt_fin,
                         shed=shed, timeout=timed_out,
                         fail_fast=fail_fast)
        if tracing:
            p = cluster.app_prep(a, cluster.in_drift(now))
            base = _Cluster._lognormal(p.log_rbar, 0.0,
                                       cluster.z_rtt[:, j]) \
                * p.speed[trial, picks_fin]
            disp = np.where(
                shed_m, DISP_SHED,
                np.where(fail_fast, DISP_FAIL_FAST,
                         np.where(timed_out, DISP_TIMEOUT, DISP_SERVED)))
            rec.record(j, compose_row(
                rep=rep_fin,
                predicted=(predicted[trial, picks_fin]
                           if predicted is not None else np.nan),
                score=sc_fin, queue_wait=qw_fin,
                raw=actual_raw[trial, picks_fin], base=base,
                cold_mult=(cold[trial, picks_fin]
                           if cold is not None else 1.0),
                gray_mult=(graym[trial, picks_fin]
                           if graym is not None else 1.0),
                retry_s=t_att_fin - now, hedge_s=0.0,
                disposition=disp, response=response))
        # all dispatched-but-timed-out attempts still burned server time
        # (add() booked only the successful attempt's work)
        extra = disp_work - np.where(success, rtt_fin, 0.0)
        self.metrics.busy_s += extra
        self.metrics.cpu_s += cluster.cpu_req[a] * extra
        self.metrics.mem_s += cluster.mem_req[a] * extra
        self.metrics.wasted_s += extra
        self.metrics.attempts += n_att

    def run(self) -> Dict[str, np.ndarray]:
        step = self.step_res if (self.res is not None
                                 and self.res.client_side) else self.step
        for j in range(self.cfg.n_requests):
            step(j)
        summary = self.metrics.summary(self.cluster, self.busy_until,
                                       self.capacity)
        if self.fleet is not None:
            self.fleet.fold_pending(np.inf)   # everything has completed
            summary["online"] = self.fleet.stats()
        if self.recorder is not None:
            summary["trace"] = self.recorder.block()
        return summary


def run_sim(cfg: SimConfig, policy: str = "perf_aware"):
    """Simulate cfg.n_trials trials under one registered policy.

    Returns the :class:`_Metrics` summary dict: per-trial mean RTT,
    p50/p95/p99 RTT, per-app mean RTT, cpu/mem resource-seconds, the
    assignment matrix, and the hedged-request count.
    """
    cluster = _build_cluster(cfg)
    pol = make_policy(policy, seed=rng_seed(cfg.seed, "policy"),
                      hedge_factor=cfg.hedge_factor)
    return SimStepper(cluster, pol).run()


def scheduling_inefficiency(cfg: SimConfig, policy: str) -> Dict[str, float]:
    """Performance loss vs the oracle LB (paper's metric), in %."""
    res = run_sim(cfg, policy)
    ora = run_sim(cfg, "oracle")
    ineff = (res["mean_rtt"] - ora["mean_rtt"]) / ora["mean_rtt"] * 100.0
    tail = (res["p99_rtt"] - ora["p99_rtt"]) \
        / np.maximum(ora["p99_rtt"], 1e-9) * 100.0
    waste_cpu = (res["cpu_s"] - ora["cpu_s"]) \
        / np.maximum(ora["cpu_s"], 1e-9) * 100.0
    return {"inefficiency_pct": float(np.mean(ineff)),
            "inefficiency_std": float(np.std(ineff)),
            "p99_inefficiency_pct": float(np.mean(tail)),
            "resource_waste_pct": float(np.mean(waste_cpu))}


def sweep_accuracy(base: SimConfig, accuracies=np.linspace(0, 1, 11)):
    """Fig. 11 subplot 1."""
    out = []
    for p in accuracies:
        cfg = replace(base, accuracy=float(p))
        out.append((float(p),
                    scheduling_inefficiency(cfg, "perf_aware")))
    return out


def sweep_replicas(base: SimConfig, counts=(1, 2, 3, 4, 6, 8, 10),
                   policies=("perf_aware", "least_conn", "round_robin",
                             "random")):
    """Fig. 11 subplots 2-3."""
    out = {}
    for pol in policies:
        rows = []
        for c in counts:
            cfg = replace(base, n_replicas_per_app=int(c))
            rows.append((int(c), scheduling_inefficiency(cfg, pol)))
        out[pol] = rows
    return out


def sweep_heterogeneity(base: SimConfig, hs=(0.0, 0.15, 0.3, 0.5, 0.75, 1.0),
                        policies=("perf_aware", "least_conn", "round_robin",
                                  "random")):
    """Fig. 11 subplot 4."""
    out = {}
    for pol in policies:
        rows = []
        for h in hs:
            cfg = replace(base, heterogeneity=float(h))
            rows.append((float(h), scheduling_inefficiency(cfg, pol)))
        out[pol] = rows
    return out
