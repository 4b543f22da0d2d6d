"""Compiled simulator core (DESIGN.md §13): the §6 event loop as one
fixed-shape ``lax.scan`` kernel.

The serial :class:`~repro.core.simulator.SimStepper` pays a Python
iteration per request; this module lowers the SAME per-request update to
a jitted scan over the request grid, with every piece of mutable state
held as dense arrays carried through the scan:

* replica occupancy ``busy_until`` as a dense ``(T, R)`` carry (serial
  semantics: never decreases per replica), plus an INCREMENTAL
  per-(node, app) busy-count carry for the predictive policies: instead
  of re-reducing all R replicas per step, the kernel delta-updates a
  dense ``(A, T, N)`` count tensor on dispatch and pops completed
  replicas through an amortized ``lax.while_loop`` expiry sweep
  (``counted`` tracks membership in the counts, so every pop is an
  O(T·N) masked one-hot update — scatter-free);
* membership events — node churn, autoscaler epochs, spot preemption —
  from the :func:`~repro.core.capacity.membership_timeline` lowered to
  masked time-indexed updates (with a capacity plane, churn is an event
  kind walked by the same in-kernel pointer + ``lax.while_loop`` as the
  autoscaler epochs, so it interleaves in exact heap order; without
  one, it stays an idempotent per-step ``max`` bump — either way the
  count carry resyncs from a full bucket reduction at the churn step);
* policy scoring reuses the exact arithmetic of the vectorized
  ``Policy.score`` batch axis (``BUSY_PENALTY``, argmin-first tie
  break, ``mask_inactive``) — in-kernel, per step;
* the capacity plane (decide / wake / preempt / admission / ledger) and
  the closed-loop :class:`~repro.core.online.OnlineFleet` (ridge
  retrains via ``_ridge_solve``, rolling-accuracy fallback) are
  carried as dense per-trial state with the serial update order
  preserved step for step.

**Serial-reference contract**: the serial stepper is the semantics; the
kernel must agree with it to <= 1e-5 relative on every summary stat for
every supported config (``tests/test_simcore.py`` gates all registered
scenarios).  All float state runs under ``jax.enable_x64``
so the only divergence from the numpy path is libm/XLA ulp noise.
Pre-drawn noise (``_Cluster.z_rtt`` / ``z_pred`` / the RandomChoice
stream) is fed in as scan inputs, so compiled and serial runs consume
bit-identical randomness.

**Dispatch**: with multiple devices and a supported config the trial
axis is sharded via ``shard_map`` (trials are embarrassingly parallel
for everything except the capacity plane's global ledger scalars, which
therefore force the single-device path); one device — CPU CI — takes a
plain ``jit`` with identical numerics.  ``force_single=True`` pins the
fallback for tests.

**Throughput mode** (:func:`fleet_throughput`): for scale demos the
pre-drawn ``(T, J, R)`` noise tensors are infeasible; the kernel can
instead draw noise in-kernel from a JAX PRNG (``native_noise``).  That
path makes no bit-parity claim against the serial stepper — it is the
same model with a different random stream — and is only used by
``benchmarks/bench_simcore.py``'s fleet-scale demo.

Buffer reuse: the scan carry is updated in place by XLA (double
buffering at worst); input buffers are deliberately NOT donated because
CPU ``device_put`` of numpy arrays can alias host memory, and donating
an alias would corrupt the caller's plan arrays.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import lu_solve
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.balancer import BUSY_PENALTY, POLICIES
from repro.core.capacity import CapacityConfig, membership_timeline
from repro.core.resilience import ResilienceConfig
from repro.core.rng import rng_from_key, rng_key, rng_seed, rng_stream
from repro.core.simulator import SimConfig, _build_cluster, _Cluster, _Metrics
from repro.core.telemetry import (DISP_FAIL_FAST, DISP_SERVED, DISP_SHED,
                                  DISP_TIMEOUT, TRACE_FIELDS, trace_block)
from repro.monitoring.metrics import PeriodicRefresh

__all__ = ["supports", "run_compiled", "run_sim_compiled",
           "fleet_throughput", "cache_stats"]

_EV_KIND = {"scale": 0, "preempt_down": 1, "preempt_up": 2, "churn": 3,
            "group_down": 4}

#: segment-sum path for the from-scratch bucket reductions (count
#: resyncs at churn, snapshot refreshes): None picks the Pallas kernel
#: on TPU and the XLA sort plan elsewhere; tests pin "xla", "pallas"
#: (compiled for the TPU) or "interpret" (the kernel in Pallas
#: interpret mode, on any backend).
_SEGSUM_BACKEND: Optional[str] = None


def _segsum_backend() -> str:
    if _SEGSUM_BACKEND is not None:
        return _SEGSUM_BACKEND
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ----------------------------------------------------------------------
# static kernel specialisation
@dataclass(frozen=True)
class _Static:
    """Everything the kernel builder branches on at trace time.  Hashable
    -> one compiled kernel per distinct feature combination (shapes are
    handled by jit's own cache)."""
    policy: str
    n_apps: int
    k: int                       # replicas per app (candidate count)
    n_nodes: int
    hedge: Optional[float]
    accuracy: float
    reactive: bool               # policy reads neither predicted nor actual
    needs_pred: bool             # Eq. 12 / fleet predictions consumed
    closed_loop: bool            # OnlineFleet active (needs_pred implied)
    snapshot: bool               # stale/outage occupancy snapshot carried
    cold_start: bool
    churn: Optional[Tuple[float, float]]
    drift: bool
    capacity: Optional[CapacityConfig]
    preempt: bool
    admission: bool
    pending: bool                # completion-EWMA ring (capacity, no preds)
    fallback_threshold: float
    obs_window: int              # fleet observation ring length (Wn)
    acc_window: int              # rolling-accuracy ring length (Wa)
    lam: float = 1e-3
    min_obs: int = 8
    min_count: int = 8
    native_noise: bool = False
    resilience: Optional[ResilienceConfig] = None
    trace_every: int = 0         # flight-recorder sampling stride; 0 off

    @property
    def hedging(self) -> bool:
        return self.hedge is not None and self.k >= 2

    @property
    def fallback(self) -> bool:
        return self.closed_loop and self.fallback_threshold > 0

    @property
    def res_client(self) -> bool:
        """Client-side timeout/retry/breaker plane armed (DESIGN.md
        §14): the step lowers to the unrolled attempt loop."""
        return self.resilience is not None and self.resilience.client_side

    @property
    def res_breaker(self) -> bool:
        return self.resilience is not None \
            and self.resilience.breaker_threshold is not None


def supports(cfg: SimConfig, policy: str) -> Optional[str]:
    """None when ``run_compiled`` reproduces the serial stepper for this
    (config, policy); otherwise the human-readable reason it cannot.

    Every SimConfig feature combination is lowered — churn interleaves
    with autoscaler/preemption epochs through the shared membership
    timeline, and the closed-loop fleet composes with the capacity
    plane and the oracle hedger — so the only rejections left are
    policies the kernel has no score lowering for."""
    cls = POLICIES.get(policy)
    if cls is None:
        return f"unknown policy {policy!r}"
    if not getattr(cls, "scan_lowered", False):
        return f"policy {policy!r} has no in-kernel score lowering"
    return None


def _static_for(cfg: SimConfig, policy: str) -> _Static:
    cls = POLICIES[policy]
    hedge = cfg.hedge_factor if policy in ("perf_aware", "oracle") else None
    hedging = hedge is not None
    reactive = not hedging and not cls.requires
    needs_pred = hedging or "predicted" in cls.requires
    closed = bool(cfg.closed_loop and needs_pred)
    res = cfg.resilience
    # a staleness storm is one more outage window on the PeriodicRefresh
    # hook: it forces the snapshot carry exactly like a plane outage
    outages = cfg.outage is not None \
        or (res is not None and res.staleness is not None)
    snapshot = (cfg.prediction_lag_s > 0 or outages) \
        and (needs_pred or closed)
    return _Static(
        policy=policy, n_apps=len(cfg.apps), k=cfg.n_replicas_per_app,
        n_nodes=cfg.n_nodes, hedge=hedge, accuracy=cfg.accuracy,
        reactive=reactive, needs_pred=needs_pred, closed_loop=closed,
        snapshot=snapshot, cold_start=cfg.cold_start_s > 0,
        churn=cfg.churn, drift=cfg.t_drift is not None,
        capacity=cfg.capacity, preempt=cfg.preempt is not None,
        admission=cfg.capacity is not None
        and cfg.capacity.admission_limit_s is not None,
        pending=cfg.capacity is not None and not needs_pred,
        fallback_threshold=cfg.fallback_threshold if closed else 0.0,
        obs_window=max(1, min(cfg.online_window, cfg.n_requests)),
        acc_window=max(1, int(cfg.accuracy_window)),
        resilience=cfg.resilience,
        trace_every=0 if cfg.trace is None
        else int(cfg.trace.sample_every))


def _count_flags(st: _Static) -> Tuple[bool, bool, bool]:
    """(full_actual, need_live, need_snap): which occupancy sources the
    kernel draws full-K interference from, hence which incremental
    count carries exist.  ``need_live`` counts track the live ``busy``
    occupancy; ``need_snap`` counts track the stale snapshot."""
    if st.reactive:
        return False, False, False
    full_actual = st.policy != "perf_aware" \
        or (not st.closed_loop and not st.snapshot)
    need_live = full_actual or (st.closed_loop and not st.snapshot)
    return full_actual, need_live, st.snapshot


def _needs_plan(st: _Static) -> bool:
    """True when the kernel still performs a from-scratch bucket
    reduction (count resync at a busy-bump step — churn or a correlated
    group outage; snapshot refresh without a live count carry to copy
    from)."""
    _, need_live, need_snap = _count_flags(st)
    group = st.resilience is not None \
        and st.resilience.outage_group is not None
    return (need_live and (st.churn is not None or group)) \
        or (need_snap and not need_live)


# ----------------------------------------------------------------------
# host-side schedule precomputation (data-independent per-step flags)
def _refresh_schedule(cfg: SimConfig, req_t: np.ndarray,
                      call_mask: np.ndarray) -> np.ndarray:
    """(J,) bool: steps where the snapshot recomputes.  Drives the REAL
    :class:`PeriodicRefresh` with the serial call pattern, so cadence +
    outage-freeze semantics cannot drift from the reference."""
    outages = ()
    if cfg.outage is not None:
        t0, duration = cfg.outage
        outages = ((t0, t0 + duration),)
    res = cfg.resilience
    if res is not None and res.staleness is not None:
        s0, sdur = res.staleness
        outages = outages + ((s0, s0 + sdur),)
    pr = PeriodicRefresh(cfg.prediction_lag_s, outages)
    out = np.zeros(len(req_t), bool)
    for j, now in enumerate(req_t):
        if not call_mask[j]:
            continue
        token = object()
        out[j] = pr.get(float(now), lambda: token) is token
    return out


def _retrain_schedule(cfg: SimConfig, req_t: np.ndarray) -> np.ndarray:
    """(J,) bool retrain flags replicating ``OnlineFleet.maybe_retrain``:
    first at warmup_s, then every retrain_every_s (0 -> once, frozen)."""
    out = np.zeros(len(req_t), bool)
    nxt = float(cfg.online_warmup_s)
    for j, now in enumerate(req_t):
        if now < nxt:
            continue
        out[j] = True
        if cfg.retrain_every_s > 0:
            while nxt <= now:
                nxt += cfg.retrain_every_s
        else:
            nxt = np.inf
    return out


def _policy_draws(J: int, T: int, K: int, seed: int,
                  seed_blocks) -> np.ndarray:
    """(J, T, K) RandomChoice draws, bit-identical to J sequential
    ``rng.random((T, K))`` calls (PCG64 fills row-major)."""
    if seed_blocks is None:
        return rng_from_key(seed).random((J, T, K))
    parts = [rng_from_key(s).random((J, int(n), K))
             for s, n in seed_blocks]
    return np.concatenate(parts, axis=1)


def _rate_at(cap: CapacityConfig, req_t: np.ndarray, cum: np.ndarray,
             t: float) -> np.ndarray:
    """(A,) trailing arrival rate — same float ops as
    ``CapacityController.rate`` (shared across trials)."""
    win = min(cap.rate_window_s, max(t, 1e-9))
    hi = np.searchsorted(req_t, t, side="right")
    lo = np.searchsorted(req_t, t - win, side="right")
    return (cum[hi] - cum[lo]) / win


def _bucket_plan(key: np.ndarray, n_buckets: int):
    """Static gather plan for per-trial bucket sums over the replica
    axis.

    XLA's scatter (segment_sum / bincount) serializes on CPU, so the
    kernel reduces buckets as sort -> prefix-sum -> two static gathers
    instead: ``perm`` sorts each trial's replicas by bucket key, and
    ``[start, end)`` brackets each bucket in that order — all
    host-precomputed constants (topology is static per trial)."""
    T = key.shape[0]
    perm = np.argsort(key, axis=1, kind="stable").astype(np.int32)
    cnt = np.zeros((T, n_buckets), np.int64)
    np.add.at(cnt, (np.arange(T)[:, None], key), 1)
    end = np.cumsum(cnt, axis=1).astype(np.int32)
    start = (end - cnt).astype(np.int32)
    return perm, start, end


def _mates_plan(node_of: np.ndarray, n_nodes: int):
    """Static co-location table: ``idx[t, n, :]`` lists the replicas
    placed on node ``n`` in trial ``t`` (clamped pad entries, padded
    width ``B`` = the fattest node).

    Placement never changes mid-run, so interference draws gather only
    the O(B) replicas sharing the candidate's node instead of reducing
    all R replicas per step; pad slots are masked out in-kernel via the
    companion ``pad`` table."""
    T, R = node_of.shape
    trial = np.arange(T)[:, None]
    counts = np.zeros((T, n_nodes), np.int64)
    np.add.at(counts, (trial, node_of), 1)
    B = max(int(counts.max()), 1)
    order = np.argsort(node_of, axis=1, kind="stable")   # (T, R)
    sorted_nodes = np.take_along_axis(node_of, order, axis=1)
    starts = np.cumsum(counts, axis=1) - counts          # (T, n_nodes)
    slot = np.arange(R)[None, :] \
        - np.take_along_axis(starts, sorted_nodes, axis=1)
    idx = np.zeros((T, n_nodes, B), np.int32)
    pad = np.ones((T, n_nodes, B), bool)
    idx[trial, sorted_nodes, slot] = order
    pad[trial, sorted_nodes, slot] = False
    return idx, pad


# ----------------------------------------------------------------------
# lowering: cluster -> (static, consts, xs, carry0, aux)
def _lower(cluster: _Cluster, policy: str, seed_blocks=None):
    cfg = cluster.cfg
    st = _static_for(cfg, policy)
    T, J = cfg.n_trials, cfg.n_requests
    A, K, N = st.n_apps, st.k, st.n_nodes
    R = A * K
    expected = np.repeat(np.arange(A), K)
    if not np.array_equal(cluster.app_of, expected):
        raise ValueError("simcore requires the contiguous app layout "
                         "_build_cluster produces (app_of = repeat)")

    req_t = np.asarray(cluster.req_t, float)
    req_app = np.asarray(cluster.req_app, np.int32)
    trial = np.arange(T)


    def regime(imat, accel, mean_rtt):
        """Per-app (imat row, speed, cand_node, log_rbar) tensors for one
        interference/speed/mean regime — the ``_AppPrep`` inputs.

        The serial path materialises a dense per-replica weight matrix
        ``imat_row[app_of]`` (T, R); the kernel instead folds the busy
        mask into per-(node, app) counts and contracts them with the raw
        (T, A) imat row, so the per-step traffic stays O(T·R) once, not
        once per tensor (same sum, reassociated — rounding-level drift
        only)."""
        speed = np.empty((A, T, K))
        cand_node = np.empty((A, T, K), np.int32)
        log_rbar = np.empty(A)
        irow = np.empty((A, T, A))
        for a in range(A):
            cand = np.arange(a * K, (a + 1) * K)
            nodes = cluster.node_of[:, cand]
            irow[a] = imat[:, a, :] if imat.ndim == 3 \
                else np.broadcast_to(imat[a], (T, A))
            speed[a] = 1.0 + accel[trial[:, None], nodes]
            cand_node[a] = nodes
            log_rbar[a] = float(np.log(mean_rtt[a]))
        return irow, speed, cand_node, log_rbar

    ir_pre, sp_pre, cand_node, lr_pre = regime(
        cluster.imat, cluster.accel, cluster.mean_rtt)
    mate_idx, mate_pad = _mates_plan(np.asarray(cluster.node_of), N)
    mate_app = cluster.app_of[mate_idx].astype(np.int32)  # (T, N, B)

    consts: Dict[str, np.ndarray] = {
        "node_of": np.asarray(cluster.node_of, np.int32),
        "mate_idx": mate_idx, "mate_app": mate_app, "mate_pad": mate_pad,
        "imat_pre": ir_pre,
        "speed_pre": sp_pre,
        "cand_node": cand_node, "log_rbar_pre": lr_pre,
        "mean_rtt": np.asarray(cluster.mean_rtt, float),
    }
    full_actual, need_live, need_snap = _count_flags(st)
    if _needs_plan(st):
        # the remaining from-scratch bucket reductions (count resync at
        # the churn step, snapshot refresh without a live carry): the
        # Pallas segment-sum kernel on TPU, else the XLA sort plan
        na_key = np.asarray(cluster.node_of) * A \
            + cluster.app_of[None, :]
        if _segsum_backend() != "xla":
            consts["na_key"] = na_key.astype(np.int32)
        else:
            perm, bstart, bend = _bucket_plan(na_key, N * A)
            consts.update(perm=perm, bstart=bstart, bend=bend)
    if st.drift:
        imat_p = cluster.imat_post if cluster.imat_post is not None \
            else cluster.imat
        accel_p = cluster.accel_post if cluster.accel_post is not None \
            else cluster.accel
        mean_p = cluster.mean_rtt_post \
            if cluster.mean_rtt_post is not None else cluster.mean_rtt
        ir_po, sp_po, _, lr_po = regime(imat_p, accel_p, mean_p)
        consts.update(speed_post=sp_po, log_rbar_post=lr_po,
                      imat_post=ir_po)
    if st.churn is not None:
        consts["down"] = cluster.node_of == cluster.failed_node[:, None]
    if st.pending or st.fallback:
        consts["req_app"] = req_app

    xs: Dict[str, np.ndarray] = {
        "j": np.arange(J, dtype=np.int32),
        "app": req_app,
        "t": req_t,
    }
    if not st.native_noise:
        xs["z"] = np.ascontiguousarray(cluster.z_rtt.T)        # (J, T)
        if st.needs_pred and not st.closed_loop:
            # pre-gather each step's candidate block: (J, T, K), the
            # only slice of z_pred the kernel ever reads
            cand_idx = req_app.astype(np.int64)[:, None] * K \
                + np.arange(K)[None, :]                        # (J, K)
            xs["zp"] = np.take_along_axis(
                cluster.z_pred.transpose(1, 0, 2),
                cand_idx[:, None, :], axis=2)                  # (J, T, K)
        if st.policy == "random":
            xs["draw"] = _policy_draws(J, T, K,
                                       rng_seed(cfg.seed, "policy"),
                                       seed_blocks)
    res = cfg.resilience
    grp = None if res is None else res.outage_group
    if st.churn is not None and st.capacity is None:
        # no event walk to ride: churn stays a masked max-bump
        xs["churnflag"] = req_t >= st.churn[0]
    if grp is not None and st.capacity is None:
        # ... and so does the correlated group outage
        xs["gflag"] = req_t >= grp[0]
    bumps = [st.churn[0]] if st.churn is not None else []
    if grp is not None:
        bumps.append(grp[0])
    if bumps and need_live:
        # one-hot flag at each busy-bump step: the count carry resyncs
        # from a full bucket reduction right after the bump
        resync = np.zeros(J, bool)
        for t0 in bumps:
            cf = req_t >= t0
            edge = cf.copy()
            edge[1:] &= ~cf[:-1]
            resync |= edge
        xs["resync"] = resync
    if res is not None:
        if res.gray is not None:
            g0, gdur, _ = res.gray
            consts["grayrep"] = np.asarray(cluster.gray_rep, bool)
            xs["grayflag"] = (req_t >= g0) & (req_t < g0 + gdur)
        if grp is not None:
            consts["gdown"] = np.asarray(cluster.group_rep, bool)
        if res.client_side and res.max_retries > 0:
            xs["zj"] = np.ascontiguousarray(
                cluster.z_jitter.transpose(1, 0, 2))       # (J, T, m)
    if st.drift:
        xs["driftflag"] = req_t >= cfg.t_drift
    if st.cold_start:
        xs["coldflag"] = req_t < cfg.cold_start_s
    if st.snapshot:
        if st.closed_loop:
            call = np.ones(J, bool)
        else:                    # Eq. 12 consults it only past cold start
            call = req_t >= cfg.cold_start_s if st.cold_start \
                else np.ones(J, bool)
        xs["refresh"] = _refresh_schedule(cfg, req_t, call)
    if st.closed_loop:
        xs["retrain"] = _retrain_schedule(cfg, req_t)

    carry0: Dict[str, np.ndarray] = {"busy": np.zeros((T, R))}
    if st.policy == "round_robin":
        carry0["cursor"] = np.zeros(T, np.int64)
    if st.snapshot:
        carry0["snap"] = np.zeros((T, R))
    # incremental occupancy counts: nothing is busy at t=0
    if need_live:
        carry0["cnt"] = np.zeros((A, T, N), np.int32)
        carry0["counted"] = np.zeros((T, R), bool)
    if need_snap:
        carry0["snap_cnt"] = np.zeros((A, T, N), np.int32)
        carry0["snap_counted"] = np.zeros((T, R), bool)
    if st.res_breaker:
        # per-replica breaker FSM as int/float/bool carries (closed /
        # open / half-open — BreakerBoard's fail/open_until/tripped)
        carry0["br_fail"] = np.zeros((T, R), np.int64)
        carry0["br_open"] = np.zeros((T, R))
        carry0["br_trip"] = np.zeros((T, R), bool)

    aux: Dict[str, object] = {"st": st}
    cap = st.capacity
    if cap is not None:
        events = membership_timeline(float(req_t[-1]), churn=cfg.churn,
                                     capacity=cap, preempt=cfg.preempt,
                                     outage_group=grp)
        ev_t = np.array([ev.t for ev in events])
        ev_kind = np.array([_EV_KIND[ev.kind] for ev in events], np.int32)
        ev_step = np.searchsorted(req_t, ev_t, side="left").astype(np.int32)
        cum = np.zeros((J + 1, A))
        np.add.at(cum, (np.arange(J) + 1, cluster.req_app), 1.0)
        cum = np.cumsum(cum, axis=0)
        ev_rate = np.stack([
            _rate_at(cap, req_t, cum, t) if k == _EV_KIND["scale"]
            else np.zeros(A)
            for t, k in zip(ev_t, ev_kind)]) if len(events) \
            else np.zeros((0, A))
        consts.update(ev_t=ev_t, ev_kind=ev_kind, ev_step=ev_step,
                      ev_rate=ev_rate)
        if st.preempt:
            consts["hit"] = cluster.node_of \
                == cluster.preempted_node[:, None]
        active0 = np.zeros((T, R), bool)
        for a in range(A):
            n0 = min(cap.initial, K)
            active0[:, a * K:a * K + n0] = True
        carry0.update(
            active=active0, allowed=np.ones((T, R), bool),
            warm=np.full((T, R), -np.inf), paid=np.zeros((T, R)),
            prov=np.zeros(T), last_t=np.float64(0.0),
            s_hat=np.broadcast_to(cluster.mean_rtt, (T, A)).copy(),
            last_scale=np.full((T, A), -np.inf),
            util_sum=np.zeros(T), ev_ptr=np.int64(0),
            s_ups=np.zeros(T, np.int64), s_dns=np.zeros(T, np.int64),
            wakeups=np.zeros(T, np.int64),
            routed_inactive=np.int64(0))
        if st.pending:
            carry0.update(pend_rtt=np.zeros((J, T)),
                          pend_fin=np.full((J, T), np.inf),
                          folded=np.zeros((J, T), bool))
        aux["decisions"] = int((ev_kind == _EV_KIND["scale"]).sum())
    if st.closed_loop:
        Wn, D = st.obs_window, N + A
        carry0.update(
            W=np.zeros((T, A, D)), trained=np.zeros((T, A), bool),
            obs_X=np.zeros((Wn, T, D)), obs_y=np.zeros((Wn, T)),
            obs_fin=np.full((Wn, T), np.inf),
            obs_app=np.zeros(Wn, np.int32),
            obs_valid=np.zeros(Wn, bool))
        if st.fallback:
            Wa = st.acc_window
            carry0.update(
                tr_ring=np.zeros((A, Wa, T)),
                tr_pos=np.zeros((A, T), np.int64),
                tr_cnt=np.zeros((A, T), np.int64),
                pd_err=np.zeros((J, T)), pd_fin=np.full((J, T), np.inf),
                pd_done=np.zeros((J, T), bool),
                n_fallback=np.int64(0))
        aux["retrain_steps"] = np.flatnonzero(xs["retrain"])
    if st.trace_every:
        # flight recorder (DESIGN.md §16): the trace rides the CARRY —
        # a (J_s, T, F) slot buffer written by dynamic_update_slice at
        # slot j // sample_every — so the ys contract (and the shard
        # out_specs) stays untouched in both sampled and full modes
        k = st.trace_every
        carry0["trace"] = np.full(
            (-(-J // k), T, len(TRACE_FIELDS)), np.nan)
        xs["tr_slot"] = (np.arange(J) // k).astype(np.int32)
        xs["tr_keep"] = (np.arange(J) % k) == 0
    return st, consts, xs, carry0, aux


# ----------------------------------------------------------------------
# in-kernel helpers (jnp mirrors of capacity._take_lowest/_take_highest)
def _take_lo(elig, k):
    cs = jnp.cumsum(elig.astype(jnp.int64), axis=1)
    return elig & (cs <= k[:, None])


def _take_hi(elig, k):
    cs = jnp.cumsum(elig[:, ::-1].astype(jnp.int64), axis=1)[:, ::-1]
    return elig & (cs <= k[:, None])


def _ridge_solve(G, b):
    """Batched float64 solve of ``G x = b`` for the closed-loop ridge
    retrain: a float32 LU factorisation plus two float64
    iterative-refinement steps.  XLA:TPU has no float64 LU, so this one
    formulation serves every backend.  On the ridge systems of the
    large drift-fallback cell (condition number up to ~2e4) two steps
    bring the error against the float64 serial solve to ~1e-13."""
    lu, piv, _ = lax.linalg.lu(G.astype(jnp.float32))

    def solve32(r):
        x = lu_solve((lu, piv), r.astype(jnp.float32)[..., None])
        return x[..., 0].astype(G.dtype)

    x = solve32(b)
    for _ in range(2):
        x = x + solve32(b - jnp.einsum("...de,...e->...d", G, x))
    return x


# ----------------------------------------------------------------------
# kernel builder
def _build_kernel(st: _Static):
    cap = st.capacity
    res = st.resilience
    grp = None if res is None else res.outage_group
    A, K, N = st.n_apps, st.k, st.n_nodes
    R = A * K
    PEN = BUSY_PENALTY
    D = N + A
    Wn, Wa = st.obs_window, st.acc_window
    full_actual, need_live, need_snap = _count_flags(st)
    seg_backend = _segsum_backend()

    def run(c, xs, carry0):
        T = c["node_of"].shape[0]
        J = xs["t"].shape[0]
        trial = jnp.arange(T)
        if st.closed_loop:
            eye_n = jnp.eye(N, dtype=jnp.float64)

        def bucket_sum(values, perm, bstart, bend):
            """Per-trial bucket sums of ``values`` (T, R) -> (T, B) via
            the host-precomputed sort plan: gather into bucket order,
            exclusive prefix-sum, difference the bucket brackets.  Pure
            gather/cumsum — no scatter (see bucket_plan)."""
            s = jnp.take_along_axis(values, perm, axis=1)
            cs = jnp.concatenate(
                [jnp.zeros((T, 1), values.dtype), jnp.cumsum(s, axis=1)],
                axis=1)
            return jnp.take_along_axis(cs, bend, axis=1) \
                - jnp.take_along_axis(cs, bstart, axis=1)

        def per_app(name, a):
            return lax.dynamic_index_in_dim(c[name], a, 0, keepdims=False)

        def col(m, a):
            return lax.dynamic_index_in_dim(m, a, 1, keepdims=False)

        def set_col(m, v, a):
            return lax.dynamic_update_slice_in_dim(m, v[:, None], a, axis=1)

        def sl(m, a0):
            return lax.dynamic_slice_in_dim(m, a0, K, axis=1)

        def unsl(m, v, a0):
            return lax.dynamic_update_slice_in_dim(m, v, a0, axis=1)

        if not st.reactive:
            def recount(busy_src, now):
                """From-scratch (A, T, N) busy counts + (T, R) counted
                mask — the full bucket reduction, amortized to count
                resyncs (churn) and snapshot refreshes.  Pallas
                segment-sum on TPU, XLA sort plan elsewhere."""
                busyb = busy_src > now
                if seg_backend != "xla":
                    # 0/1 masks summing to <= R: exact in float32, the
                    # kernel's dtype on the TPU
                    from repro.kernels.segment_sum import segment_sum
                    flat = segment_sum(busyb.astype(jnp.float32),
                                       c["na_key"], N * A,
                                       interpret=seg_backend == "interpret")
                else:
                    flat = bucket_sum(busyb.astype(jnp.float64),
                                      c["perm"], c["bstart"], c["bend"])
                return (flat.reshape(-1, N, A).transpose(2, 0, 1)
                        .astype(jnp.int32), busyb)

            # sub-blocks per app for the expiry pops; _SUB=2 would
            # double the pops one round can retire but also doubles
            # every scatter's index arrays, which measured strictly
            # worse (4.2-4.5 vs 3.7-3.8 ms/step at the large config)
            _SUB = 1
            _NB = A * _SUB                  # sub-blocks per trial
            _KB = K // _SUB                 # replicas per sub-block

            def expire(cnt_, counted_, busy_src, now):
                """Incremental count expiry: pop replicas whose
                ``busy_until`` fell to <= now — the first AND last
                expired of every app block, so up to 2·A per trial
                per round.  Total pops are bounded by total
                dispatches (~1/trial/step), so one unrolled round
                retires everything on almost every step and the
                while_loop behind it is entered only on burst tails.
                Each round locates its pops with two iota min/max
                reductions over the (T, NB, KB) expiry mask — measured
                ~1.3 ms/step cheaper than bool argmax + a flipped-copy
                argmax + any on XLA CPU — plus O(T·A)-element
                scatters; never a dense (T, N) one-hot or a (T, R)
                gather+cumsum bucket reduction (XLA's CPU cumsum alone
                costs more than this whole loop).  The f64 expiry
                compare is hoisted out and the expired mask rides the
                carry, so rounds touch only bool masks."""
                expm = busy_src <= now                       # (T, R)
                blk = jnp.arange(_NB)[None, :]               # (1, NB)
                base = (blk // _SUB) * K + (blk % _SUB) * _KB
                t2 = trial[:, None]                          # (T, 1)

                def cond(s):
                    return s[2].any()

                def body(s):
                    cnt__, cted__, ex = s
                    exv = ex.reshape(T, _NB, _KB)
                    kio = jnp.arange(_KB, dtype=jnp.int32)[None, None, :]
                    k1 = jnp.where(exv, kio, _KB).min(2)     # first hit
                    k2 = jnp.where(exv, kio, -1).max(2)      # last hit
                    hasb = k2 >= 0                           # (T, NB)
                    k1 = jnp.where(hasb, k1, 0)
                    has2 = hasb & (k2 != k1)                 # 2nd pop
                    i1 = base + k1                           # replica ids
                    i2 = base + k2
                    n1 = c["node_of"][t2, i1]                # (T, NB)
                    n2 = c["node_of"][t2, i2]
                    app = blk // _SUB
                    # one scatter per carry (each costs a buffer copy)
                    aa = jnp.concatenate([app + 0 * k1, app + 0 * k2], 1)
                    tt = jnp.concatenate([t2 + 0 * k1, t2 + 0 * k2], 1)
                    nn = jnp.concatenate([n1, n2], 1)
                    dec = jnp.concatenate([hasb, has2], 1)
                    cnt__ = cnt__.at[aa, tt, nn].add(
                        -dec.astype(cnt__.dtype))
                    ii = jnp.concatenate([jnp.where(hasb, i1, R),
                                          jnp.where(has2, i2, R)], 1)
                    cted__ = cted__.at[tt, ii].set(False, mode="drop")
                    return cnt__, cted__, expm & cted__
                # first round unrolled: it runs on ~every step (some
                # trial always has an expiry), and outside the loop XLA
                # fuses it into the step instead of paying while-loop
                # carry boundaries
                first = body((cnt_, counted_, expm & counted_))
                out = lax.while_loop(cond, body, first)
                return out[0], out[1]

            def gather_counts(counts, nodes):
                """(A, T, N) counts at candidate nodes -> (A, T, K)."""
                idx = jnp.broadcast_to(nodes[None], (A,) + nodes.shape)
                return jnp.take_along_axis(counts, idx, axis=2)

        def _lognormal(inter, lr, z):
            v = 0.1 + inter
            u = jnp.log1p(v * v)
            return jnp.exp(lr - 0.5 * u + jnp.sqrt(u) * z)

        def rtt_full(a, drift_on, counts, z):
            """In-kernel ``_Cluster.rtt_draw`` over the app's whole
            candidate row (T, K) from the carried per-(node, app)
            occupancy ``counts`` (A, T, N), contracted with the raw
            imat row.  The interference score depends only on the
            candidate's *node*, so the app-axis contraction is done
            once per node — an (A,T,N)×(T,A) pre-contraction — and the
            (T, K) candidate row is a cheap gather from the (T, N)
            result instead of an (A,T,K) gather + einsum.  The serial
            bincount of ``busy · imat_row[app_of]`` is the same sum
            reassociated — rounding-level drift only (counts are
            integer-exact)."""
            iw = per_app("imat_pre", a)                    # (T, A)
            lr = per_app("log_rbar_pre", a)
            sp = per_app("speed_pre", a)
            if st.drift:
                iw = jnp.where(drift_on, per_app("imat_post", a), iw)
                lr = jnp.where(drift_on, per_app("log_rbar_post", a), lr)
                sp = jnp.where(drift_on, per_app("speed_post", a), sp)
            nodes = per_app("cand_node", a)                # (T, K)
            # unrolled (A is tiny, static): XLA's CPU lowering of the
            # equivalent "atn,ta->tn" einsum is ~3x slower than five
            # fused broadcast multiply-adds
            w_cnt = counts[0] * iw[:, 0:1]                 # (T, N)
            for a_ in range(1, A):
                w_cnt = w_cnt + counts[a_] * iw[:, a_:a_ + 1]
            inter = jnp.take_along_axis(w_cnt, nodes, axis=1)
            return _lognormal(inter, lr, z[:, None]) * sp

        def rtt_at(a, drift_on, busy_src, now, z, cand):
            """Pick-only ``rtt_draw`` at candidate slots ``cand``
            (T, Kq): gather the O(B) co-located replicas from the static
            mates table instead of reducing the full replica axis.  The
            mate's interference weight is the app's (T, A) imat-row
            entry for the mate's app, gathered in-kernel — no (A,T,N,B)
            weight tensor on the host, no per-regime rebuild under
            drift; the summed set is identical to the serial bincount
            (reassociated)."""
            iw = per_app("imat_pre", a)                    # (T, A)
            lr = per_app("log_rbar_pre", a)
            sp = per_app("speed_pre", a)                   # (T, K)
            if st.drift:
                iw = jnp.where(drift_on, per_app("imat_post", a), iw)
                lr = jnp.where(drift_on, per_app("log_rbar_post", a), lr)
                sp = jnp.where(drift_on, per_app("speed_post", a), sp)
            nodes = jnp.take_along_axis(per_app("cand_node", a), cand,
                                        axis=1)            # (T, Kq)
            sp = jnp.take_along_axis(sp, cand, axis=1)
            mi = jnp.take_along_axis(c["mate_idx"], nodes[:, :, None],
                                     axis=1)               # (T, Kq, B)
            ma = jnp.take_along_axis(c["mate_app"], nodes[:, :, None],
                                     axis=1)               # (T, Kq, B)
            mp = jnp.take_along_axis(c["mate_pad"], nodes[:, :, None],
                                     axis=1)               # (T, Kq, B)
            w = jnp.take_along_axis(iw, ma.reshape(T, -1),
                                    axis=1).reshape(ma.shape)
            bg = jnp.take_along_axis(busy_src, mi.reshape(T, -1),
                                     axis=1).reshape(mi.shape)
            inter = jnp.where((bg > now) & ~mp, w, 0.0).sum(-1)
            return _lognormal(inter, lr, z[:, None]) * sp

        # -------------------------------------------------------------
        # flight recorder (DESIGN.md §16): decomposition helpers.  The
        # trace rides the carry as a (J_s, T, F) slot buffer; a step
        # whose tr_keep flag is off writes its slot's previous contents
        # back (pure, shape-stable — sampled and full modes share one
        # kernel structure).
        if st.trace_every:
            def trace_base(a, drift_on, z, picks):
                """Zero-interference service draw on the chosen
                replica's tier: serial ``_lognormal(log_rbar, 0, z) *
                speed[trial, picks]`` with the same drift selection as
                rtt_full/rtt_at."""
                lr = per_app("log_rbar_pre", a)
                sp = per_app("speed_pre", a)
                if st.drift:
                    lr = jnp.where(drift_on,
                                   per_app("log_rbar_post", a), lr)
                    sp = jnp.where(drift_on,
                                   per_app("speed_post", a), sp)
                sp_p = jnp.take_along_axis(sp, picks[:, None],
                                           axis=1)[:, 0]
                return _lognormal(0.0, lr, z) * sp_p

            def trace_row(rep, pred_p, score, qwait, raw, base, cm, gm,
                          retry_s, hedge_s, disp, resp):
                """(T, F) row in TRACE_FIELDS order — the jnp mirror of
                telemetry.compose_row."""
                disp = disp.astype(jnp.float64)
                dropped = disp != DISP_SERVED

                def nanm(v):
                    return jnp.where(dropped, jnp.nan, v)
                cols = [
                    jnp.where(dropped, -1.0, rep.astype(jnp.float64)),
                    nanm(pred_p), nanm(score), nanm(qwait), nanm(base),
                    nanm(raw - base), nanm(raw * (cm - 1.0)),
                    nanm(raw * cm * (gm - 1.0)),
                    nanm(retry_s), nanm(hedge_s), disp, nanm(resp),
                ]
                return jnp.stack(cols, axis=-1)

            def trace_commit(buf, x, tr):
                slot = x["tr_slot"]
                zero = jnp.zeros((), slot.dtype)
                return lax.dynamic_update_slice(buf, tr[None],
                                                (slot, zero, zero))

            def trace_emit(buf, x, row_fn):
                """Commit ``row_fn()`` into the slot buffer.  Full mode
                (k == 1) writes unconditionally; sampled mode branches
                on the per-step keep flag with ``lax.cond`` so the
                ~(k-1)/k skipped steps pay for NO row computation at
                all — the flag is a replicated scalar (xs, trial axis
                None), so the cond stays a genuine branch, not a
                select."""
                if st.trace_every == 1:
                    return trace_commit(buf, x, row_fn())
                return lax.cond(
                    x["tr_keep"],
                    lambda b: trace_commit(b, x, row_fn()),
                    lambda b: b, buf)

        # -------------------------------------------------------------
        # capacity-event machinery (fires inside a while_loop per step)
        if cap is not None:
            E = c["ev_t"].shape[0]
            al = cap.ewma_alpha

            def fold_completions(t_ev, j, s_hat, folded, pend_rtt,
                                 pend_fin):
                if not st.pending:
                    return s_hat, folded

                def body(s, fs):
                    s_hat_, folded_ = fs
                    ap = c["req_app"][s]
                    m = (s < j) & (~folded_[s]) & (pend_fin[s] <= t_ev)
                    cur = col(s_hat_, ap)
                    new = jnp.where(m, (1.0 - al) * cur
                                    + al * pend_rtt[s], cur)
                    return (set_col(s_hat_, new, ap),
                            folded_.at[s].set(folded_[s] | m))
                return lax.fori_loop(0, J, body, (s_hat, folded))

            def decide(t_ev, rate, j, busy, pend_rtt, pend_fin, cv):
                (active, allowed, warm, paid, prov, last_t, s_hat,
                 last_scale, folded, util_sum, s_ups, s_dns) = cv
                s_hat, folded = fold_completions(t_ev, j, s_hat, folded,
                                                 pend_rtt, pend_fin)
                dt = jnp.maximum(t_ev - last_t, 0.0)
                prov = prov + active.sum(1) * dt
                last_t = jnp.maximum(last_t, t_ev)
                # pass 1: targets from the PRE-change active set
                tgts = []
                for a_ in range(A):
                    s_ = slice(a_ * K, (a_ + 1) * K)
                    act = active[:, s_]
                    cur = act.sum(1)
                    if cap.autoscaler == "predictive":
                        need = jnp.ceil(rate[a_] * s_hat[:, a_]
                                        / cap.rho_target).astype(jnp.int64)
                    elif cap.autoscaler == "reactive":
                        busy_c = (busy[:, s_] > t_ev) & act
                        util = jnp.where(
                            cur > 0,
                            busy_c.sum(1) / jnp.maximum(cur, 1), 0.0)
                        cooled = t_ev - last_scale[:, a_] >= cap.cooldown_s
                        need = cur + jnp.where(
                            cooled & (util > cap.hi_util), 1,
                            jnp.where(cooled & (util < cap.lo_util),
                                      -1, 0))
                    else:
                        need = jnp.full((T,), cap.initial, jnp.int64)
                    hi0 = K if cap.max_replicas is None \
                        else min(cap.max_replicas, K)
                    hi = jnp.minimum(hi0, allowed[:, s_].sum(1))
                    tgts.append(jnp.clip(need, cap.min_replicas, hi))
                # pass 2: apply (activate lowest standby, drain highest
                # idle first, busy only to cover the rest)
                util_acc = jnp.zeros((T,))
                for a_ in range(A):
                    s_ = slice(a_ * K, (a_ + 1) * K)
                    act = active[:, s_]
                    cur = act.sum(1)
                    busy_c = (busy[:, s_] > t_ev) & act
                    util_acc = util_acc + jnp.where(
                        cur > 0, busy_c.sum(1) / jnp.maximum(cur, 1), 0.0)
                    want = tgts[a_]
                    k_up = jnp.maximum(want - cur, 0)
                    k_dn = jnp.maximum(cur - want, 0)
                    changed = (k_up > 0) | (k_dn > 0)
                    grow = _take_lo(~act & allowed[:, s_], k_up)
                    overlap = jnp.where(
                        grow, jnp.maximum(paid[:, s_] - t_ev, 0.0), 0.0)
                    prov = prov - overlap.sum(1)
                    warm = warm.at[:, s_].set(
                        jnp.where(grow, t_ev + cap.warmup_s, warm[:, s_]))
                    active = active.at[:, s_].set(act | grow)
                    s_ups = s_ups + grow.sum(1)
                    idle = act & ~busy_c
                    drop = _take_hi(idle, k_dn)
                    rem = k_dn - drop.sum(1)
                    drop = drop | _take_hi(act & busy_c & ~drop, rem)
                    tail = jnp.where(
                        drop, jnp.maximum(busy[:, s_] - t_ev, 0.0), 0.0)
                    prov = prov + tail.sum(1)
                    paid = paid.at[:, s_].set(
                        jnp.where(drop, t_ev + tail, paid[:, s_]))
                    active = active.at[:, s_].set(active[:, s_] & ~drop)
                    s_dns = s_dns + drop.sum(1)
                    last_scale = last_scale.at[:, a_].set(
                        jnp.where(changed, t_ev, last_scale[:, a_]))
                util_sum = util_sum + util_acc / max(A, 1)
                return (active, allowed, warm, paid, prov, last_t, s_hat,
                        last_scale, folded, util_sum, s_ups, s_dns)

            def pre_down(t_ev, busy, cv):
                (active, allowed, warm, paid, prov, last_t, s_hat,
                 last_scale, folded, util_sum, s_ups, s_dns) = cv
                dt = jnp.maximum(t_ev - last_t, 0.0)
                prov = prov + active.sum(1) * dt
                last_t = jnp.maximum(last_t, t_ev)
                hit = c["hit"]
                allowed = allowed & ~hit
                m = hit & active
                tail = jnp.where(m, jnp.maximum(busy - t_ev, 0.0), 0.0)
                prov = prov + tail.sum(1)
                paid = jnp.where(m, t_ev + tail, paid)
                active = active & ~m
                return (active, allowed, warm, paid, prov, last_t, s_hat,
                        last_scale, folded, util_sum, s_ups, s_dns)

            def pre_up(cv):
                (active, allowed, warm, paid, prov, last_t, s_hat,
                 last_scale, folded, util_sum, s_ups, s_dns) = cv
                allowed = allowed | c["hit"]
                return (active, allowed, warm, paid, prov, last_t, s_hat,
                        last_scale, folded, util_sum, s_ups, s_dns)

            def apply_events(j, busy, pend_rtt, pend_fin, ptr, cv):
                """Walk every membership event with ``t <= now`` in heap
                order.  ``busy`` rides the loop carry because the churn
                event bumps it mid-walk, and later autoscaler epochs in
                the same step must see the post-churn occupancy (exact
                serial interleaving)."""
                if E == 0:
                    return ptr, busy, cv

                def cond(s):
                    p = s[0]
                    return (p < E) \
                        & (c["ev_step"][jnp.minimum(p, E - 1)] <= j)

                def ev_scale(t_ev, rate, s_):
                    b = s_[0]
                    return (b,) + decide(t_ev, rate, j, b, pend_rtt,
                                         pend_fin, s_[1:])

                def body(s):
                    p = s[0]
                    bcv = s[1:]
                    t_ev = c["ev_t"][p]
                    rate = c["ev_rate"][p]
                    if st.preempt or st.churn is not None \
                            or grp is not None:
                        ident = lambda s_: s_
                        branches = [
                            lambda s_: ev_scale(t_ev, rate, s_),
                            (lambda s_: (s_[0],) + pre_down(t_ev, s_[0],
                                                            s_[1:]))
                            if st.preempt else ident,
                            (lambda s_: (s_[0],) + pre_up(s_[1:]))
                            if st.preempt else ident,
                            (lambda s_: (jnp.where(
                                c["down"],
                                jnp.maximum(s_[0], st.churn[0]
                                            + st.churn[1]), s_[0]),)
                             + s_[1:])
                            if st.churn is not None else ident,
                            # correlated outage: churn's busy-bump,
                            # group-wide (DESIGN.md §14)
                            (lambda s_: (jnp.where(
                                c["gdown"],
                                jnp.maximum(s_[0], grp[0] + grp[1]),
                                s_[0]),) + s_[1:])
                            if grp is not None else ident,
                        ]
                        bcv = lax.switch(c["ev_kind"][p], branches, bcv)
                    else:
                        bcv = ev_scale(t_ev, rate, bcv)
                    return (p + 1,) + bcv
                out = lax.while_loop(cond, body, (ptr, busy) + cv)
                return out[0], out[1], out[2:]

        # -------------------------------------------------------------
        if st.closed_loop:
            def viable_mask(a, ring, pos, cnt):
                cnt_a = lax.dynamic_index_in_dim(cnt, a, 0,
                                                 keepdims=False)   # (T,)
                ring_a = lax.dynamic_index_in_dim(ring, a, 0,
                                                  keepdims=False)  # (Wa,T)
                filled = jnp.minimum(cnt_a, Wa)
                valid = jnp.arange(Wa)[:, None] < filled[None, :]
                esum = jnp.where(valid, ring_a, 0.0).sum(0)
                acc = 1.0 - esum / jnp.maximum(filled, 1)
                acc = jnp.where(filled > 0, acc, 1.0)
                return (cnt_a < st.min_count) \
                    | (acc >= st.fallback_threshold)

        def step(cr, x):
            busy = cr["busy"]
            j, a, now = x["j"], x["app"], x["t"]
            a0 = a * K
            ncr = dict(cr)

            # membership: without a capacity plane churn is an
            # idempotent masked max-bump (busy never decreases per
            # replica, so re-applying is a no-op); with one it rides
            # the event walk below so it interleaves with autoscaler
            # epochs in exact heap order
            if st.churn is not None and cap is None:
                t_up = st.churn[0] + st.churn[1]
                busy = jnp.where(x["churnflag"] & c["down"],
                                 jnp.maximum(busy, t_up), busy)
            if grp is not None and cap is None:
                busy = jnp.where(x["gflag"] & c["gdown"],
                                 jnp.maximum(busy, grp[0] + grp[1]),
                                 busy)

            served = jnp.ones((T,), bool)
            shed = jnp.zeros((T,), bool)
            act_c = coldm = None
            if cap is not None:
                cv = (cr["active"], cr["allowed"], cr["warm"], cr["paid"],
                      cr["prov"], cr["last_t"], cr["s_hat"],
                      cr["last_scale"],
                      cr["folded"] if st.pending else jnp.zeros((), bool),
                      cr["util_sum"], cr["s_ups"], cr["s_dns"])
                ptr, busy, cv = apply_events(
                    j, busy,
                    cr["pend_rtt"] if st.pending else None,
                    cr["pend_fin"] if st.pending else None,
                    cr["ev_ptr"], cv)
                (active, allowed, warm, paid, prov, last_t, s_hat,
                 last_scale, folded, util_sum, s_ups, s_dns) = cv
                # wake (scale-from-zero), serial call order preserved
                act_c = sl(active, a0)
                alw_c = sl(allowed, a0)
                empty = ~act_c.any(1)
                g_ = empty.any()
                dt = jnp.maximum(now - last_t, 0.0)
                prov = prov + jnp.where(g_, active.sum(1) * dt, 0.0)
                last_t = jnp.where(g_, jnp.maximum(last_t, now), last_t)
                first = _take_lo(alw_c, empty.astype(jnp.int64))
                none = ~first.any(1) & empty
                first = first | _take_lo(jnp.ones_like(first),
                                         none.astype(jnp.int64))
                paid_c = sl(paid, a0)
                overlap = jnp.where(first,
                                    jnp.maximum(paid_c - now, 0.0), 0.0)
                prov = prov - overlap.sum(1)
                warm_c = jnp.where(first, now + cap.warmup_s,
                                   sl(warm, a0))
                act_c = act_c | first
                active = unsl(active, act_c, a0)
                warm = unsl(warm, warm_c, a0)
                wakeups = cr["wakeups"] + empty
                busy_c = sl(busy, a0)
                wait_c = jnp.maximum(busy_c - now, 0.0)
                if st.admission:
                    aw = jnp.where(act_c, wait_c, jnp.inf).min(1)
                    shed = aw > cap.admission_limit_s
                    served = ~shed
                coldm = jnp.where(now < warm_c, cap.cold_rtt_factor, 1.0)
            else:
                busy_c = sl(busy, a0)
                wait_c = jnp.maximum(busy_c - now, 0.0)

            # gray failure: (T, K) multiplier on the TRUE RTT inside the
            # window; the prediction basis keeps the healthy view the
            # replica still advertises (DESIGN.md §14)
            graym = None
            if res is not None and res.gray is not None:
                graym = jnp.where(x["grayflag"] & sl(c["grayrep"], a0),
                                  res.gray[2], 1.0)

            # incremental occupancy counts: resync once at the churn
            # bump, then expire completions amortized per step
            if need_live:
                cnt, counted = cr["cnt"], cr["counted"]
                if st.churn is not None or grp is not None:
                    cnt, counted = lax.cond(
                        x["resync"], lambda s: recount(busy, now),
                        lambda s: s, (cnt, counted))
                cnt, counted = expire(cnt, counted, busy, now)
            if st.snapshot:
                snap = jnp.where(x["refresh"], busy, cr["snap"])
                ncr["snap"] = snap
                if need_snap:
                    s_cnt, s_cted = cr["snap_cnt"], cr["snap_counted"]
                    if need_live:
                        # at refresh snap == busy, so the snapshot
                        # counts are a copy of the live carry
                        s_cnt = jnp.where(x["refresh"], cnt, s_cnt)
                        s_cted = jnp.where(x["refresh"], counted, s_cted)
                    else:
                        s_cnt, s_cted = lax.cond(
                            x["refresh"], lambda s: recount(busy, now),
                            lambda s: s, (s_cnt, s_cted))
                    s_cnt, s_cted = expire(s_cnt, s_cted, snap, now)
                    ncr.update(snap_cnt=s_cnt, snap_counted=s_cted)
            drift_on = x["driftflag"] if st.drift else False
            if st.native_noise:
                kj = jax.random.fold_in(c["key"], j)
                z = jax.random.normal(kj, (T,), jnp.float64)
            else:
                z = x["z"]

            hmask = jnp.zeros((T,), bool)
            rtt2 = jnp.zeros((T,))
            predicted = None
            if st.reactive and not st.res_client:
                idle = busy_c <= now
                if st.policy == "round_robin":
                    dist = jnp.mod(jnp.arange(K)[None, :]
                                   - cr["cursor"][:, None],
                                   K).astype(jnp.float64)
                    sc = jnp.where(idle, dist, PEN + wait_c)
                elif st.policy == "random":
                    if st.native_noise:
                        draw = jax.random.uniform(
                            jax.random.fold_in(kj, 2), (T, K),
                            jnp.float64)
                    else:
                        draw = x["draw"]
                    sc = jnp.where(idle, draw, PEN + wait_c)
                else:                                    # least_conn
                    sc = busy_c - now
                sc_m = jnp.where(act_c, sc, jnp.inf) \
                    if cap is not None else sc
                picks = jnp.argmin(sc_m, axis=1)
                if st.policy == "round_robin":
                    ncr["cursor"] = (picks + 1) % K
                rtt_pick = rtt_at(a, drift_on, busy, now, z,
                                  picks[:, None])[:, 0]
                raw_pick = rtt_pick         # pre cold/gray service draw
                if cap is not None:
                    rtt_pick = rtt_pick * coldm[trial, picks]
                if graym is not None:
                    rtt_pick = rtt_pick * graym[trial, picks]
            else:
                # the full-K actual draw is needed only when it scores
                # (oracle) or seeds the Eq. 12 basis; otherwise the
                # pick-only draw after argmin replaces it.  The client
                # plane is request-scoped (serial step_res draws the
                # matrix once at arrival occupancy and every attempt
                # gathers its pick's column), so it always needs the
                # full row — from the count carry when one exists, from
                # the mates table otherwise (snapshot / reactive
                # configs; same sum reassociated).
                actual = None
                if full_actual or st.res_client:
                    if need_live:
                        actual = rtt_full(a, drift_on, cnt, z)
                    else:
                        allk = jnp.broadcast_to(
                            jnp.arange(K)[None, :], (T, K))
                        actual = rtt_at(a, drift_on, busy, now, z, allk)
                    actual_raw = actual     # pre cold/gray service draws
                    if cap is not None:
                        actual = actual * coldm
                if st.closed_loop:
                    # serial order: fold trackers -> retrain -> features
                    if st.fallback:
                        def tr_body(s, tv):
                            ring, pos, cnt, done = tv
                            ap = c["req_app"][s]
                            m = (s < j) & (~done[s]) \
                                & (cr["pd_fin"][s] <= now)
                            err = jnp.minimum(jnp.abs(cr["pd_err"][s]),
                                              1.0)
                            pos_a = lax.dynamic_index_in_dim(
                                pos, ap, 0, keepdims=False)       # (T,)
                            ring_a = lax.dynamic_index_in_dim(
                                ring, ap, 0, keepdims=False)      # (Wa,T)
                            hit_w = (jnp.arange(Wa)[:, None]
                                     == pos_a[None, :]) & m[None, :]
                            ring_a = jnp.where(hit_w, err[None, :],
                                               ring_a)
                            ring = lax.dynamic_update_slice_in_dim(
                                ring, ring_a[None], ap, axis=0)
                            pos_a = jnp.where(m, (pos_a + 1) % Wa, pos_a)
                            pos = lax.dynamic_update_slice_in_dim(
                                pos, pos_a[None], ap, axis=0)
                            cnt_a = lax.dynamic_index_in_dim(
                                cnt, ap, 0, keepdims=False) + m
                            cnt = lax.dynamic_update_slice_in_dim(
                                cnt, cnt_a[None], ap, axis=0)
                            done = done.at[s].set(done[s] | m)
                            return ring, pos, cnt, done
                        tr_ring, tr_pos, tr_cnt, tr_done = lax.fori_loop(
                            0, J, tr_body,
                            (cr["tr_ring"], cr["tr_pos"], cr["tr_cnt"],
                             cr["pd_done"]))
                        ncr.update(tr_ring=tr_ring, tr_pos=tr_pos,
                                   tr_cnt=tr_cnt, pd_done=tr_done)

                    def train(wt):
                        W_, tr_ = wt
                        for a_ in range(A):
                            msl = cr["obs_valid"] & (cr["obs_app"] == a_)
                            mm = (msl[:, None]
                                  & (cr["obs_fin"] <= now)).astype(
                                      jnp.float64)
                            n_eff = mm.sum(0)
                            Xw = cr["obs_X"] * mm[:, :, None]
                            G = jnp.einsum("wtd,wte->tde", Xw,
                                           cr["obs_X"]) \
                                + st.lam * jnp.eye(D, dtype=jnp.float64)
                            b = jnp.einsum("wtd,wt->td", Xw, cr["obs_y"])
                            Wa_ = _ridge_solve(G, b)
                            okm = n_eff >= st.min_obs
                            W_ = W_.at[:, a_].set(
                                jnp.where(okm[:, None], Wa_, W_[:, a_]))
                            tr_ = tr_.at[:, a_].set(tr_[:, a_] | okm)
                        return W_, tr_
                    W, trained = lax.cond(x["retrain"], train,
                                          lambda wt: wt,
                                          (cr["W"], cr["trained"]))
                    ncr.update(W=W, trained=trained)
                    counts_src = s_cnt if st.snapshot else cnt
                    nodes = per_app("cand_node", a)
                    onehot = jnp.take(eye_n, nodes, axis=0)   # (T, K, N)
                    cand_counts = gather_counts(
                        counts_src, nodes).transpose(1, 2, 0)  # (T, K, A)
                    cand_counts = cand_counts.astype(jnp.float64)
                    X = jnp.concatenate([onehot, cand_counts], axis=-1)
                    W_a = lax.dynamic_index_in_dim(W, a, 1,
                                                   keepdims=False)
                    y = jnp.maximum(
                        jnp.einsum("tkd,td->tk", X, W_a), 1e-3)
                    tr_a = lax.dynamic_index_in_dim(trained, a, 1,
                                                    keepdims=False)
                    fleet_pred = jnp.where(tr_a[:, None], y,
                                           c["mean_rtt"][a])
                    predicted = fleet_pred
                    if st.fallback:
                        ok = viable_mask(a, tr_ring, tr_pos, tr_cnt)
                        predicted = jnp.where(ok[:, None], fleet_pred,
                                              0.0)
                        ncr["n_fallback"] = cr["n_fallback"] \
                            + (~ok).sum()
                elif st.needs_pred:
                    mean_b = jnp.broadcast_to(c["mean_rtt"][a], (T, K))
                    cold_on = x["coldflag"] if st.cold_start else False
                    if st.snapshot:
                        stale = rtt_full(a, drift_on, s_cnt, z)
                        basis = jnp.where(cold_on, mean_b, stale) \
                            if st.cold_start else stale
                        if cap is not None:
                            basis = basis * coldm
                    elif st.cold_start:
                        other = mean_b * coldm if cap is not None \
                            else mean_b
                        basis = jnp.where(cold_on, other, actual)
                    else:
                        basis = actual
                    if st.native_noise:
                        zc = jax.random.normal(
                            jax.random.fold_in(kj, 1), (T, K),
                            jnp.float64)
                    else:
                        zc = x["zp"]
                    eps = (1.0 - st.accuracy) * basis
                    predicted = basis + eps * zc
                if graym is not None and actual is not None:
                    # AFTER the prediction basis is fixed: the oracle /
                    # served RTT see the gray truth, Eq. 12 keeps the
                    # advertised (healthy) view
                    actual = actual * graym

                if st.res_client:
                    # ---- client plane (DESIGN.md §14): statically
                    # unrolled attempt loop, argmin for argmin with the
                    # serial step_res.  The true-RTT matrix above is
                    # request-scoped; occupancy feedback between
                    # attempts flows through queue wait only, and every
                    # dispatched attempt occupies its server for the
                    # full service time whether or not the client is
                    # still listening (retry amplification).
                    timeout = res.timeout_s
                    colK = jnp.arange(K)[None, :]
                    if st.res_breaker:
                        fail_c = sl(cr["br_fail"], a0)
                        open_c = sl(cr["br_open"], a0)
                        trip_c = sl(cr["br_trip"], a0)
                    if st.policy == "round_robin":
                        cursor = cr["cursor"]
                    success = jnp.zeros((T,), bool)
                    t_att = jnp.zeros((T,)) + now
                    picks_fin = jnp.zeros((T,), jnp.int64)
                    rtt_fin = jnp.zeros((T,))
                    fin_fin = jnp.zeros((T,))
                    disp_work = jnp.zeros((T,))
                    n_att = jnp.zeros((T,))
                    if st.trace_every:
                        # successful-attempt captures for the trace row
                        sc_fin = jnp.zeros((T,))
                        ta_fin = jnp.zeros((T,))
                        qw_fin = jnp.zeros((T,))
                    busy_c_i = busy_c
                    for i in range(1 + res.max_retries):
                        alive = ~success & ~shed
                        mask = act_c if cap is not None \
                            else jnp.ones((T, K), bool)
                        if st.res_breaker:
                            # open = tripped and still cooling; a
                            # half-open probe stays routable
                            mask = mask & ~(trip_c
                                            & (t_att[:, None] < open_c))
                        dispatch = alive & mask.any(1)
                        wait_i = jnp.maximum(
                            busy_c_i - t_att[:, None], 0.0)
                        if st.policy in ("perf_aware", "oracle"):
                            sc = wait_i + (predicted
                                           if st.policy == "perf_aware"
                                           else actual)
                        elif st.policy == "least_conn":
                            sc = busy_c_i - t_att[:, None]
                        elif st.policy == "round_robin":
                            dist = jnp.mod(colK - cursor[:, None],
                                           K).astype(jnp.float64)
                            sc = jnp.where(busy_c_i <= t_att[:, None],
                                           dist, PEN + wait_i)
                        else:                            # random
                            sc = jnp.where(busy_c_i <= t_att[:, None],
                                           x["draw"], PEN + wait_i)
                        picks = jnp.argmin(
                            jnp.where(mask, sc, jnp.inf), axis=1)
                        rtt_i = actual[trial, picks]
                        b_pick = busy_c_i[trial, picks]
                        resp_i = jnp.maximum(b_pick - t_att, 0.0) + rtt_i
                        ok_i = dispatch & (resp_i <= timeout)
                        tmo_i = dispatch & ~ok_i
                        # the server does the work whether or not the
                        # client waited for the answer
                        finish_i = jnp.maximum(t_att, b_pick) + rtt_i
                        selp = colK == picks[:, None]
                        busy_c_i = jnp.where(selp & dispatch[:, None],
                                             finish_i[:, None], busy_c_i)
                        disp_work = disp_work + jnp.where(dispatch,
                                                          rtt_i, 0.0)
                        n_att = n_att + dispatch
                        if st.policy == "round_robin":
                            cursor = jnp.where(dispatch, (picks + 1) % K,
                                               cursor)
                        if need_live:
                            nodes_row = per_app("cand_node", a)
                            np1 = nodes_row[trial, picks]
                            r1 = a0 + picks
                            add1 = dispatch & ~counted[trial, r1]
                            cnt = cnt.at[a, trial, np1].add(
                                add1.astype(cnt.dtype))
                            counted = counted.at[
                                trial, jnp.where(dispatch, r1, R)].set(
                                    True, mode="drop")
                        if st.res_breaker:
                            # BreakerBoard.record: success resets, a
                            # timeout increments and trips at the
                            # threshold — or instantly on a half-open
                            # probe (pre-update state decides)
                            was_half = trip_c \
                                & (t_att[:, None] >= open_c)
                            okm = selp & ok_i[:, None]
                            tm = selp & tmo_i[:, None]
                            fail_c = jnp.where(okm, 0, fail_c + tm)
                            tripped_now = tm & (
                                (fail_c >= res.breaker_threshold)
                                | was_half)
                            trip_c = jnp.where(okm, False,
                                               trip_c | tripped_now)
                            open_c = jnp.where(
                                tripped_now,
                                t_att[:, None] + timeout
                                + res.breaker_cooldown_s, open_c)
                        picks_fin = jnp.where(ok_i, picks, picks_fin)
                        rtt_fin = jnp.where(ok_i, rtt_i, rtt_fin)
                        fin_fin = jnp.where(ok_i, t_att + resp_i,
                                            fin_fin)
                        if st.trace_every:
                            sc_fin = jnp.where(ok_i, sc[trial, picks],
                                               sc_fin)
                            ta_fin = jnp.where(ok_i, t_att, ta_fin)
                            qw_fin = jnp.where(
                                ok_i, jnp.maximum(b_pick - t_att, 0.0),
                                qw_fin)
                        success = success | ok_i
                        if i < res.max_retries:
                            # a failed DISPATCH is learned only at the
                            # timeout; a fail-fast attempt (breaker open
                            # / replica set drained) goes straight to
                            # backoff — the asymmetry that lets breakers
                            # arrest retry storms
                            delay = res.backoff_base_s \
                                * res.backoff_mult ** i \
                                * (1.0 + res.backoff_jitter
                                   * x["zj"][:, i])
                            t_att = jnp.where(dispatch,
                                              t_att + timeout + delay,
                                              t_att + delay)
                    busy = unsl(busy, busy_c_i, a0)
                    ncr["busy"] = busy
                    if st.policy == "round_robin":
                        ncr["cursor"] = cursor
                    if st.res_breaker:
                        ncr["br_fail"] = unsl(cr["br_fail"], fail_c, a0)
                        ncr["br_open"] = unsl(cr["br_open"], open_c, a0)
                        ncr["br_trip"] = unsl(cr["br_trip"], trip_c, a0)
                    if need_live:
                        ncr["cnt"] = cnt
                        ncr["counted"] = counted
                    timed_out = ~success & ~shed
                    rep = a0 + picks_fin
                    resp = jnp.where(success, fin_fin - now, jnp.nan)
                    if st.closed_loop:
                        # only completed requests train the predictor or
                        # count against rolling accuracy — a timed-out
                        # request has no observed RTT
                        fin_obs = jnp.where(success, fin_fin, jnp.inf)
                        slot = jnp.mod(j, Wn)
                        ncr["obs_X"] = cr["obs_X"].at[slot].set(
                            X[trial, picks_fin])
                        ncr["obs_y"] = cr["obs_y"].at[slot].set(rtt_fin)
                        ncr["obs_fin"] = cr["obs_fin"].at[slot].set(
                            fin_obs)
                        ncr["obs_app"] = cr["obs_app"].at[slot].set(a)
                        ncr["obs_valid"] = cr["obs_valid"].at[slot].set(
                            True)
                        if st.fallback:
                            perr = jnp.abs(fleet_pred[trial, picks_fin]
                                           - rtt_fin) \
                                / jnp.maximum(rtt_fin, 1e-9)
                            ncr["pd_err"] = cr["pd_err"].at[j].set(perr)
                            ncr["pd_fin"] = cr["pd_fin"].at[j].set(
                                fin_obs)
                            ncr["pd_done"] = ncr["pd_done"].at[j].set(
                                ~success)
                    if cap is not None:
                        ok_r = active[trial, rep] | ~success
                        ncr["routed_inactive"] = cr["routed_inactive"] \
                            + (~ok_r).sum()
                        if predicted is not None:
                            pred_src = fleet_pred if st.closed_loop \
                                else predicted
                            pred_pick = pred_src[trial, picks_fin]
                            cur = col(s_hat, a)
                            upd = (1.0 - al) * cur + al * pred_pick
                            s_hat = set_col(
                                s_hat, jnp.where(success, upd, cur), a)
                        elif st.pending:
                            fin_eff = jnp.where(success, fin_fin,
                                                jnp.inf)
                            ncr["pend_rtt"] = cr["pend_rtt"].at[j].set(
                                rtt_fin)
                            ncr["pend_fin"] = cr["pend_fin"].at[j].set(
                                fin_eff)
                        ncr.update(active=active, allowed=allowed,
                                   warm=warm, paid=paid, prov=prov,
                                   last_t=last_t, s_hat=s_hat,
                                   last_scale=last_scale,
                                   util_sum=util_sum, ev_ptr=ptr,
                                   s_ups=s_ups, s_dns=s_dns,
                                   wakeups=wakeups)
                        if st.pending:
                            ncr["folded"] = folded
                    if st.trace_every:
                        def res_row():
                            disp = jnp.where(
                                shed, DISP_SHED,
                                jnp.where(timed_out & (n_att == 0),
                                          DISP_FAIL_FAST,
                                          jnp.where(timed_out,
                                                    DISP_TIMEOUT,
                                                    DISP_SERVED)))
                            return trace_row(
                                rep, (predicted[trial, picks_fin]
                                      if predicted is not None
                                      else jnp.full((T,), jnp.nan)),
                                sc_fin, qw_fin,
                                actual_raw[trial, picks_fin],
                                trace_base(a, drift_on, z, picks_fin),
                                (coldm[trial, picks_fin]
                                 if cap is not None else 1.0),
                                (graym[trial, picks_fin]
                                 if graym is not None else 1.0),
                                ta_fin - now, jnp.zeros((T,)), disp,
                                resp)
                        ncr["trace"] = trace_emit(cr["trace"], x,
                                                  res_row)
                    ys = {"resp": resp, "rtt": rtt_fin,
                          "rep": rep.astype(jnp.int32), "shed": shed,
                          "hmask": hmask, "rtt2": rtt2,
                          "tout": timed_out, "att": n_att,
                          "bwork": disp_work}
                    return ncr, ys

                sig = predicted if st.policy == "perf_aware" else actual
                sc = wait_c + sig
                sc_m = jnp.where(act_c, sc, jnp.inf) \
                    if cap is not None else sc
                picks = jnp.argmin(sc_m, axis=1)
                if full_actual:
                    rtt_pick = actual[trial, picks]
                    if st.trace_every:
                        raw_pick = actual_raw[trial, picks]
                else:
                    rtt_pick = rtt_at(a, drift_on, busy, now, z,
                                      picks[:, None])[:, 0]
                    raw_pick = rtt_pick     # pre cold/gray service draw
                    if cap is not None:
                        rtt_pick = rtt_pick * coldm[trial, picks]
                    if graym is not None:
                        rtt_pick = rtt_pick * graym[trial, picks]
                if st.hedging:
                    s2 = sc_m.at[trial, picks].set(jnp.inf)
                    second = jnp.argmin(s2, axis=1)
                    completion = wait_c + sig
                    bc = jnp.where(busy_c > now, completion, jnp.inf)
                    if cap is not None:
                        bc = jnp.where(act_c, bc, jnp.inf)
                    ref = bc.min(1)
                    hmask = sig[trial, picks] > st.hedge * ref
                    if cap is not None:
                        hmask = hmask & act_c[trial, second]
                    if st.admission:
                        hmask = hmask & served

            # commits only touch the app's K-column block, so the write
            # is a masked block update, never a row-indexed scatter
            # (XLA CPU scatter serializes over trials)
            rep = a0 + picks
            b_pick = busy_c[trial, picks]
            if st.trace_every:
                # the trace's score column, recomputed at the pick from
                # b_pick rather than gathered out of ``sc``: a gather
                # from the score matrix keeps it alive past the argmin,
                # forcing XLA to materialize (T, K) scores every step
                # (measured ~2x whole-kernel on the large bench cell).
                # Each expression is the element-at-pick of its
                # policy's score branch, bitwise.  Placement matters:
                # hoisting this gather above the rtt draw re-triggers
                # the same materialization, so it stays down here next
                # to ``b_pick``.
                wait_pick = jnp.maximum(b_pick - now, 0.0)
                if not (st.reactive and not st.res_client):
                    score_pick = wait_pick + sig[trial, picks]
                elif st.policy == "round_robin":
                    score_pick = jnp.where(
                        b_pick <= now,
                        jnp.mod(picks - cr["cursor"],
                                K).astype(jnp.float64),
                        PEN + wait_pick)
                elif st.policy == "random":
                    score_pick = jnp.where(b_pick <= now,
                                           draw[trial, picks],
                                           PEN + wait_pick)
                else:                                    # least_conn
                    score_pick = b_pick - now
            finish = jnp.maximum(now, b_pick) + rtt_pick
            colK = jnp.arange(K)[None, :]
            new_c = jnp.where((colK == picks[:, None]) & served[:, None],
                              finish[:, None], busy_c)
            if st.hedging:
                if full_actual:
                    rtt2 = actual[trial, second]
                else:
                    rtt2 = rtt_at(a, drift_on, busy, now, z,
                                  second[:, None])[:, 0]
                    if cap is not None:
                        rtt2 = rtt2 * coldm[trial, second]
                    if graym is not None:
                        rtt2 = rtt2 * graym[trial, second]
                b2 = busy_c[trial, second]
                finish2 = jnp.maximum(now, b2) + rtt2
                resp = jnp.where(hmask, jnp.minimum(finish, finish2),
                                 finish) - now
                new_c = jnp.where(
                    (colK == second[:, None]) & hmask[:, None],
                    finish2[:, None], new_c)
            else:
                resp = finish - now
            busy = unsl(busy, new_c, a0)
            if st.admission:
                resp = jnp.where(served, resp, jnp.nan)
            ncr["busy"] = busy
            if need_live:
                # delta-update the count carry exactly as the busy
                # commit: +1 per newly-busy replica (a pick that
                # already had queued work stays counted, no increment).
                # One dispatch per trial -> a T-element scatter, never
                # a dense (T, N) one-hot.
                nodes_row = per_app("cand_node", a)        # (T, K)
                np1 = nodes_row[trial, picks]
                r1 = a0 + picks                            # replica ids
                add1 = served & ~counted[trial, r1]
                dt = cnt.dtype
                cnt = cnt.at[a, trial, np1].add(add1.astype(dt))
                counted = counted.at[
                    trial, jnp.where(served, r1, R)].set(True,
                                                         mode="drop")
                if st.hedging:
                    np2 = nodes_row[trial, second]
                    r2 = a0 + second
                    add2 = hmask & ~counted[trial, r2]
                    cnt = cnt.at[a, trial, np2].add(add2.astype(dt))
                    counted = counted.at[
                        trial, jnp.where(hmask, r2, R)].set(True,
                                                            mode="drop")
                ncr["cnt"] = cnt
                ncr["counted"] = counted

            if st.closed_loop:
                # the fleet's finish mask mirrors serial observe():
                # shed requests never complete (inf keeps them out of
                # the training window and the accuracy fold)
                fin_obs = jnp.where(served, finish, jnp.inf)
                slot = jnp.mod(j, Wn)
                ncr["obs_X"] = cr["obs_X"].at[slot].set(X[trial, picks])
                ncr["obs_y"] = cr["obs_y"].at[slot].set(rtt_pick)
                ncr["obs_fin"] = cr["obs_fin"].at[slot].set(fin_obs)
                ncr["obs_app"] = cr["obs_app"].at[slot].set(a)
                ncr["obs_valid"] = cr["obs_valid"].at[slot].set(True)
                if st.fallback:
                    perr = jnp.abs(fleet_pred[trial, picks] - rtt_pick) \
                        / jnp.maximum(rtt_pick, 1e-9)
                    ncr["pd_err"] = cr["pd_err"].at[j].set(perr)
                    ncr["pd_fin"] = cr["pd_fin"].at[j].set(fin_obs)
                    if st.admission:
                        ncr["pd_done"] = ncr["pd_done"].at[j].set(~served)
            if cap is not None:
                ok_r = active[trial, rep]
                if st.admission:
                    ok_r = ok_r | ~served
                ncr["routed_inactive"] = cr["routed_inactive"] \
                    + (~ok_r).sum()
                if predicted is not None:
                    # serial note_prediction feeds the RAW fleet
                    # prediction (fallback may have zeroed `predicted`
                    # for scoring, but the capacity EWMA never sees 0s)
                    pred_src = fleet_pred if st.closed_loop else predicted
                    pred_pick = pred_src[trial, picks]
                    cur = col(s_hat, a)
                    upd = (1.0 - al) * cur + al * pred_pick
                    s_hat = set_col(s_hat,
                                    jnp.where(served, upd, cur), a)
                elif st.pending:
                    fin_eff = jnp.where(served, finish, jnp.inf)
                    ncr["pend_rtt"] = cr["pend_rtt"].at[j].set(rtt_pick)
                    ncr["pend_fin"] = cr["pend_fin"].at[j].set(fin_eff)
                ncr.update(active=active, allowed=allowed, warm=warm,
                           paid=paid, prov=prov, last_t=last_t,
                           s_hat=s_hat, last_scale=last_scale,
                           util_sum=util_sum, ev_ptr=ptr, s_ups=s_ups,
                           s_dns=s_dns, wakeups=wakeups)
                if st.pending:
                    ncr["folded"] = folded

            if st.trace_every:
                def tail_row():
                    if st.hedging:
                        hsave = jnp.where(
                            hmask, finish - jnp.minimum(finish, finish2),
                            0.0)
                    else:
                        hsave = jnp.zeros((T,))
                    return trace_row(
                        rep, (predicted[trial, picks]
                              if predicted is not None
                              else jnp.full((T,), jnp.nan)),
                        score_pick, jnp.maximum(b_pick - now, 0.0),
                        raw_pick, trace_base(a, drift_on, z, picks),
                        coldm[trial, picks] if cap is not None else 1.0,
                        graym[trial, picks]
                        if graym is not None else 1.0,
                        jnp.zeros((T,)), hsave,
                        jnp.where(shed, DISP_SHED, DISP_SERVED), resp)
                ncr["trace"] = trace_emit(cr["trace"], x, tail_row)
            ys = {"resp": resp, "rtt": rtt_pick,
                  "rep": rep.astype(jnp.int32), "shed": shed,
                  "hmask": hmask, "rtt2": rtt2}
            return ncr, ys

        return lax.scan(step, carry0, xs)

    return run


# ----------------------------------------------------------------------
# dispatch: shard_map over trials, or plain jit
_T_AXIS = {
    # consts
    "node_of": 0, "down": 0, "hit": 0, "perm": 0, "bstart": 0, "bend": 0,
    "na_key": 0, "mate_idx": 0, "mate_app": 0, "mate_pad": 0,
    "grayrep": 0, "gdown": 0,
    "imat_pre": 1, "imat_post": 1,
    "speed_pre": 1, "speed_post": 1, "cand_node": 1, "log_rbar_pre": None,
    "log_rbar_post": None, "mean_rtt": None, "app_of": None,
    "req_app": None, "ev_t": None, "ev_kind": None, "ev_step": None,
    "ev_rate": None, "key": None,
    # xs
    "j": None, "app": None, "t": None, "z": 1, "zp": 1, "draw": 1, "zj": 1,
    "refresh": None, "coldflag": None, "driftflag": None,
    "churnflag": None, "gflag": None, "grayflag": None, "resync": None,
    "retrain": None,
    # carry / ys
    "busy": 0, "cursor": 0, "snap": 0,
    "cnt": 1, "counted": 0, "snap_cnt": 1, "snap_counted": 0,
    "br_fail": 0, "br_open": 0, "br_trip": 0,
    "resp": 1, "rtt": 1, "rep": 1, "shed": 1, "hmask": 1, "rtt2": 1,
    "tout": 1, "att": 1, "bwork": 1,
    # flight recorder (DESIGN.md §16): (J_s, T, F) carry + slot xs
    "trace": 1, "tr_slot": None, "tr_keep": None,
}


def _spec_tree(tree):
    out = {}
    for k in tree:
        ax = _T_AXIS[k]                 # KeyError = unshardable state
        out[k] = P() if ax is None else P(*([None] * ax + ["trials"]))
    return out


def _shardable(st: _Static) -> bool:
    # the capacity ledger carries global scalars (last_t, event pointer,
    # routed_inactive) and the closed-loop fleet a global fallback
    # counter: both force the single-device path
    return st.capacity is None and not st.closed_loop


# LRU-bounded kernel cache.  A campaign sweep builds one entry per
# distinct (_Static, dispatch mode) pair — the 19-scenario grid times
# the default policies lands well under 128 — but an unbounded dict
# would pin every jitted callable (and its compiled executables) for
# the life of the process across repeated ad-hoc sweeps.
_FN_CACHE_MAX = 128
_FN_CACHE: "OrderedDict[Tuple, object]" = OrderedDict()
_FN_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def cache_stats() -> Dict[str, int]:
    """Kernel-cache telemetry: current size, bound, hit/miss/eviction
    counters (cumulative over the process)."""
    return {"size": len(_FN_CACHE), "max": _FN_CACHE_MAX,
            **_FN_STATS}


def _get_fn(st: _Static, mode: str, ndev: int, trees=None):
    # the segment-sum backend is trace-time state (_segsum_backend() is
    # read inside _build_kernel), so it must be part of the cache key
    # or a test flipping _SEGSUM_BACKEND would get a stale kernel
    key = (st, mode, ndev, _segsum_backend())
    fn = _FN_CACHE.get(key)
    if fn is not None:
        _FN_STATS["hits"] += 1
        _FN_CACHE.move_to_end(key)
        return fn
    _FN_STATS["misses"] += 1
    run = _build_kernel(st)
    if mode == "shard":
        consts, xs, carry0, ys_keys = trees
        mesh = Mesh(np.array(jax.devices()), axis_names=("trials",))
        cr_spec = _spec_tree(carry0)
        fn = jax.jit(jax.shard_map(
            run, mesh=mesh,
            in_specs=(_spec_tree(consts), _spec_tree(xs), cr_spec),
            out_specs=(cr_spec, _spec_tree(ys_keys)),
            check_vma=False))
    else:
        fn = jax.jit(run)
    _FN_CACHE[key] = fn
    while len(_FN_CACHE) > _FN_CACHE_MAX:
        _FN_CACHE.popitem(last=False)
        _FN_STATS["evictions"] += 1
    return fn


def _ys_keys(st: _Static) -> Dict[str, None]:
    """Per-step output keys the kernel emits for this specialisation
    (the shard-map out_specs need them before tracing)."""
    keys = {"resp": None, "rtt": None, "rep": None, "shed": None,
            "hmask": None, "rtt2": None}
    if st.res_client:
        keys.update(tout=None, att=None, bwork=None)
    return keys


def _pad_trials(tree, T, Tp):
    """Pad every trial-sharded array from T to Tp trials by replicating
    the last trial.  Replication (vs zeros) keeps the padded rows on
    the same code path as real ones — no special-casing in-kernel —
    and their outputs are simply sliced off afterwards.  Safe because
    trials are independent on every shardable config (``_shardable``
    already excludes the global-ledger features)."""
    out = {}
    for k, v in tree.items():
        ax = _T_AXIS[k]
        if ax is None or v.shape[ax] != T:
            out[k] = v
            continue
        idx = np.concatenate(
            [np.arange(T), np.full(Tp - T, T - 1, np.int64)])
        out[k] = np.take(v, idx, axis=ax)
    return out


def _execute(st, consts, xs, carry0, force_single=False):
    ndev = jax.device_count()
    T = carry0["busy"].shape[0]
    use_shard = not force_single and ndev > 1 and _shardable(st)
    Tp = -(-T // ndev) * ndev if use_shard else T
    with jax.enable_x64():
        if Tp != T:
            consts = _pad_trials(consts, T, Tp)
            xs = _pad_trials(xs, T, Tp)
            carry0 = _pad_trials(carry0, T, Tp)
        cj = {k: jnp.asarray(v) for k, v in consts.items()}
        xj = {k: jnp.asarray(v) for k, v in xs.items()}
        crj = {k: jnp.asarray(v) for k, v in carry0.items()}
        if use_shard:
            fn = _get_fn(st, "shard", ndev, (cj, xj, crj, _ys_keys(st)))
        else:
            fn = _get_fn(st, "jit", 1)
        final, ys = fn(cj, xj, crj)
        final = {k: np.asarray(v) for k, v in final.items()}
        ys = {k: np.asarray(v) for k, v in ys.items()}
    if Tp != T:
        def _cut(tree):
            out = {}
            for k, v in tree.items():
                ax = _T_AXIS[k]
                if ax is not None and v.ndim > ax and v.shape[ax] == Tp:
                    out[k] = np.take(v, np.arange(T), axis=ax)
                else:
                    out[k] = v
            return out
        final, ys = _cut(final), _cut(ys)
    return final, ys, ("shard_map" if use_shard else "jit")


# ----------------------------------------------------------------------
# host-side summary (reuses _Metrics so percentile / nan / per-app
# semantics are the serial code's, not a reimplementation)
class _CompiledLedger:
    """Duck-typed stand-in for CapacityController inside
    ``_Metrics.summary`` (finalize + prov_s + telemetry)."""

    def __init__(self, final, decisions: int):
        self.prov_s = np.array(final["prov"], float)
        self._last_t = float(final["last_t"])
        self._active = np.asarray(final["active"], bool)
        self._final = final
        self._decisions = decisions

    def finalize(self, t_end):
        t_end = np.asarray(t_end, float)
        self.prov_s += self._active.sum(axis=1) \
            * np.maximum(t_end - self._last_t, 0.0)
        self._last_t = float(np.max(t_end))

    def telemetry(self):
        f = self._final
        return {
            "decisions": self._decisions,
            "scale_ups": np.array(f["s_ups"]),
            "scale_downs": np.array(f["s_dns"]),
            "wakeups": np.array(f["wakeups"]),
            "routed_inactive": int(f["routed_inactive"]),
            "mean_util": np.asarray(f["util_sum"])
            / max(self._decisions, 1),
            "active_final": self._active.sum(axis=1),
        }


def _online_summary(cluster: _Cluster, st: _Static, final, aux):
    """Mirror of ``OnlineFleet.stats()`` from the final carry.  Accuracy
    trackers are only maintained in-kernel when they can steer routing
    (``fallback_threshold > 0``); otherwise ``accuracy`` is None."""
    cfg = cluster.cfg
    J = cfg.n_requests
    steps = np.asarray(aux["retrain_steps"], int)
    versions = np.zeros(st.n_apps, np.int64)
    for j in steps:
        lo = max(0, j - st.obs_window)
        present = np.unique(cluster.req_app[lo:j])
        versions[present] += 1
    out = {
        "versions": versions,
        "retrain_times": [float(cluster.req_t[j]) for j in steps],
        "trained_frac": float(np.asarray(final["trained"]).mean()),
        "accuracy": None,
    }
    if st.fallback:
        Wa = st.acc_window
        ring = np.array(final["tr_ring"])            # (A, Wa, T)
        pos = np.array(final["tr_pos"])
        cnt = np.array(final["tr_cnt"])
        done = np.array(final["pd_done"])
        err_all = np.asarray(final["pd_err"])
        fin_all = np.asarray(final["pd_fin"])
        for s in range(J):                   # final fold at now = inf
            m = ~done[s] & (fin_all[s] <= np.inf)
            if not m.any():
                continue
            a = int(cluster.req_app[s])
            err = np.minimum(np.abs(err_all[s]), 1.0)
            idx = np.flatnonzero(m)
            ring[a][pos[a, idx], idx] = err[idx]
            pos[a, idx] = (pos[a, idx] + 1) % Wa
            cnt[a, idx] += 1
            done[s] |= m
        filled = np.minimum(cnt, Wa)                 # (A, T)
        valid = np.arange(Wa)[None, :, None] < filled[:, None, :]
        esum = np.where(valid, ring, 0.0).sum(axis=1)
        acc = 1.0 - esum / np.maximum(filled, 1)
        out["accuracy"] = np.where(filled > 0, acc, 1.0)
    return out


def _summarize(cluster: _Cluster, st: _Static, final, ys, aux,
               backend: str):
    cfg = cluster.cfg
    m = _Metrics(cfg)
    resp = ys["resp"].T                              # (T, J)
    rtt = ys["rtt"].T
    rep = ys["rep"].T.astype(np.int64)
    shed = ys["shed"].T
    hmask = ys["hmask"].T
    rtt2 = ys["rtt2"].T
    served = ~shed
    cpu_a = cluster.cpu_req[cluster.req_app][None, :]     # (1, J)
    mem_a = cluster.mem_req[cluster.req_app][None, :]
    m.rtts = resp
    m.chosen = np.where(shed, -1, rep)
    m.shed = shed
    m.busy_s = (np.where(served, rtt, 0.0) + hmask * rtt2).sum(axis=1)
    m.cpu_s = (np.where(served, cpu_a * rtt, 0.0)
               + hmask * cpu_a * rtt2).sum(axis=1)
    m.mem_s = (np.where(served, mem_a * rtt, 0.0)
               + hmask * mem_a * rtt2).sum(axis=1)
    with np.errstate(invalid="ignore"):
        over = resp - m.slo
    m.slo_violation_s = np.where(served, np.maximum(over, 0.0),
                                 0.0).sum(axis=1)
    if st.res_client:
        # client-plane accounting (serial step_res booked the successful
        # attempt's work in add() and every other dispatched attempt as
        # extra): total dispatched work IS the busy/cpu/mem integral,
        # the shortfall vs the served RTT is the wasted work
        tout = ys["tout"].T
        bwork = ys["bwork"].T                          # (T, J)
        ok = served & ~tout
        m.timeout = tout
        m.fail_fast = tout & (ys["att"].T == 0)
        m.chosen = np.where(shed | tout, -1, rep)
        m.busy_s = bwork.sum(axis=1)
        m.cpu_s = (cpu_a * bwork).sum(axis=1)
        m.mem_s = (mem_a * bwork).sum(axis=1)
        m.wasted_s = (bwork - np.where(ok, rtt, 0.0)).sum(axis=1)
        m.attempts = ys["att"].T.sum(axis=1)
        m.slo_violation_s = np.where(ok, np.maximum(over, 0.0),
                                     0.0).sum(axis=1)
    m.n_hedged = int(hmask.sum())
    m.hedged = hmask.sum(axis=1).astype(np.int64)
    m.n_fallback = int(final.get("n_fallback", 0))
    ledger = None
    if cfg.capacity is not None:
        ledger = _CompiledLedger(final, int(aux["decisions"]))
    summary = m.summary(cluster, busy_until=np.asarray(final["busy"]),
                        capacity=ledger)
    if st.closed_loop:
        summary["online"] = _online_summary(cluster, st, final, aux)
    if st.trace_every:
        summary["trace"] = trace_block(final["trace"], cfg.n_requests,
                                       st.trace_every)
    summary["simcore_backend"] = backend
    return summary


# ----------------------------------------------------------------------
# public entry points
def run_compiled(cluster: _Cluster, policy: str, *, seed_blocks=None,
                 force_single: bool = False) -> Dict[str, np.ndarray]:
    """Run one (cluster, policy) pass through the compiled scan kernel.

    Drop-in for ``SimStepper(cluster, make_policy(...)).run()`` on
    supported configs (see :func:`supports`); raises ValueError on an
    unsupported one.  ``seed_blocks`` mirrors RandomChoice's campaign
    blocks; ``force_single`` pins the single-device jit path even when
    multiple devices are visible (fallback regression tests).
    """
    reason = supports(cluster.cfg, policy)
    if reason is not None:
        raise ValueError(f"simcore cannot run this config: {reason}")
    st, consts, xs, carry0, aux = _lower(cluster, policy, seed_blocks)
    final, ys, backend = _execute(st, consts, xs, carry0, force_single)
    return _summarize(cluster, st, final, ys, aux, backend)


def prepare_compiled(cluster: _Cluster, policy: str, *,
                     seed_blocks=None):
    """Lower + jit once, return a zero-arg callable that reruns the hot
    kernel on device-resident inputs.

    ``run_compiled`` pays fresh-cell costs on every call (re-lowering,
    host-side xs rebuild — including the (J, T, K) noise pre-gather);
    the callable returned here pays only the kernel plus summary, which
    is the compiled engine's warm steady-state and the number the
    benchmark's warm-ratio gate compares against the serial stepper's
    hot-cache reruns.  Single-device jit path only."""
    reason = supports(cluster.cfg, policy)
    if reason is not None:
        raise ValueError(f"simcore cannot run this config: {reason}")
    st, consts, xs, carry0, aux = _lower(cluster, policy, seed_blocks)
    with jax.enable_x64():
        cj = {k: jnp.asarray(v) for k, v in consts.items()}
        xj = {k: jnp.asarray(v) for k, v in xs.items()}
        crj = {k: jnp.asarray(v) for k, v in carry0.items()}
        fn = _get_fn(st, "jit", 1)

    def run() -> Dict[str, np.ndarray]:
        with jax.enable_x64():
            final, ys = fn(cj, xj, crj)
            final_np = {k: np.asarray(v) for k, v in final.items()}
            ys_np = {k: np.asarray(v) for k, v in ys.items()}
        return _summarize(cluster, st, final_np, ys_np, aux, "jit")

    return run


def run_sim_compiled(cfg: SimConfig, policy: str = "perf_aware",
                     force_single: bool = False):
    """Compiled mirror of :func:`~repro.core.simulator.run_sim`."""
    return run_compiled(_build_cluster(cfg), policy,
                        force_single=force_single)


def fleet_throughput(n_requests: int = 1_000_000, n_nodes: int = 250,
                     n_replicas_per_app: int = 200, n_apps: int = 5,
                     n_trials: int = 4, policy: str = "perf_aware",
                     seed: int = 0, arrival_rate: float = 2000.0):
    """Fleet-scale demo: million-request x thousand-replica runs with
    in-kernel noise (no (T, J, R) host tensors, no serial-parity claim).

    Returns (events_per_second, stats_dict).  Used by
    ``benchmarks/bench_simcore.py`` to demonstrate the ROADMAP-scale
    configuration runs in seconds.
    """
    import time

    from repro.core.simulator import APPS

    apps = tuple(APPS)[:n_apps]
    cfg = SimConfig(n_nodes=n_nodes, n_replicas_per_app=n_replicas_per_app,
                    apps=apps, n_requests=n_requests, n_trials=n_trials,
                    seed=seed, arrival_rate=arrival_rate)
    from dataclasses import replace as _dc_replace
    st = _dc_replace(_static_for(cfg, policy), native_noise=True)

    rng = rng_stream(seed, "fleet-demo")
    T, A, K, N = n_trials, n_apps, n_replicas_per_app, n_nodes
    R = A * K
    mean_rtt = np.array([APPS[a][0] for a in apps])
    imat = 0.5 * rng.uniform(0.05, 0.35, size=(A, A))
    node_of = rng.integers(0, N, size=(T, R)).astype(np.int32)
    accel = np.clip(rng.normal(0.0, 0.3, size=(T, N)), -0.8, 2.0)
    app_of = np.repeat(np.arange(A), K)
    req_app = rng.integers(0, A, size=n_requests).astype(np.int32)
    req_t = np.cumsum(rng.exponential(1.0 / arrival_rate,
                                      size=n_requests))
    trial = np.arange(T)
    speed = np.empty((A, T, K))
    cand_node = np.empty((A, T, K), np.int32)
    log_rbar = np.log(mean_rtt)
    for a in range(A):
        nodes = node_of[:, a * K:(a + 1) * K]
        speed[a] = 1.0 + accel[trial[:, None], nodes]
        cand_node[a] = nodes
    mate_idx, mate_pad = _mates_plan(node_of, N)
    mate_app = app_of[mate_idx].astype(np.int32)         # (T, N, B)
    irow = np.broadcast_to(imat[:, None, :], (A, T, A)).copy()
    consts = {"node_of": node_of, "mate_idx": mate_idx,
              "mate_app": mate_app, "mate_pad": mate_pad,
              "imat_pre": irow,
              "speed_pre": speed, "cand_node": cand_node,
              "log_rbar_pre": log_rbar, "mean_rtt": mean_rtt,
              "key": rng_key(seed, "fleet-demo-noise")}
    xs = {"j": np.arange(n_requests, dtype=np.int32), "app": req_app,
          "t": req_t}
    carry0 = {"busy": np.zeros((T, R))}
    if policy == "round_robin":
        carry0["cursor"] = np.zeros(T, np.int64)
    _, need_live, _ = _count_flags(st)
    if need_live:
        carry0["cnt"] = np.zeros((A, T, N), np.int32)
        carry0["counted"] = np.zeros((T, R), bool)

    t0 = time.perf_counter()
    final, ys, backend = _execute(st, consts, xs, carry0)
    wall = time.perf_counter() - t0
    resp = ys["resp"]
    stats = {"mean_rtt": float(resp.mean()),
             "p99_rtt": float(np.percentile(resp, 99)),
             "n_requests": n_requests, "n_replicas": R,
             "n_trials": T, "wall_s": wall, "backend": backend,
             "events_per_s": n_requests * T / wall}
    return stats["events_per_s"], stats
