"""Morpheus-integrated request router (the paper's Fig. 1 load balancer).

Routes each incoming request to one replica per the configured policy.
The router implements NO policy logic of its own: it builds a 1-trial
:class:`~repro.core.balancer.ClusterState` from its replicas and
dispatches through the same ``POLICIES`` engine the §6 simulator and the
benchmarks use (DESIGN.md §8), so the served policy and the simulated
policy cannot diverge.

For ``perf_aware`` the router serves every replica's RTT estimate from
the :class:`~repro.core.prediction_plane.PredictionPlane` in ONE
``predict_all`` call (beyond-paper: the paper computes one prediction per
request per replica; the plane batches state retrieval across replicas
and runs one jitted inference per model bucket — DESIGN.md §9) and
models each replica's queue wait as ``pending waves x predicted wave
RTT``.  Prediction-guided hedging doubles as straggler mitigation: when
``hedge_factor`` is set the policy may also queue the request on the
runner-up replica (see ``PerfAware.hedge_candidates``).

The router shares the online adaptation plane's viability rule
(DESIGN.md §11): every routed prediction is reconciled against the
request's measured RTT at ``drain`` time through the same
:class:`~repro.core.online.RollingAccuracy` tracker the closed-loop
simulator uses, and when the fleet's rolling accuracy drops below
``fallback_threshold`` the router serves requests via ``least_conn``
until retraining (e.g. an ``OnlineAdapter`` hot-swap) restores it.

It also mirrors the capacity plane (DESIGN.md §12): with a
:class:`~repro.core.capacity.CapacityConfig` the router manages its
replicas through an :class:`~repro.core.capacity.EnginePool` — the
autoscaler grows/shrinks the active engine set on the same decision
rules the simulator uses, drained engines are masked out of the
policy's ClusterState (they still serve their queues), the admission
hook sheds requests the active set cannot bound (``route`` returns
-1), and ``pool.ledger()`` reports the serving-side (provisioned,
busy, waste, shed) accounting.

And it mirrors the resilience plane (DESIGN.md §14): with a
:class:`~repro.core.resilience.ResilienceConfig` the router applies the
same client-side rules the simulator's ``step_res`` uses — replicas
whose circuit breaker is OPEN are masked out of candidate scoring
(half-open probes stay routable), a completed request whose measured
RTT exceeds ``timeout_s`` counts as a client timeout (the server still
did the work), timed-out attempts feed the shared
:class:`~repro.core.resilience.BreakerBoard` (T=1) and are retried
through ``route`` while attempts remain, and — the tracker hygiene rule
— timed-out requests NEVER enter the rolling-accuracy reconciliation:
a blown deadline says nothing about how wrong the prediction was.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.balancer import ClusterState, PerfAware, POLICIES, make_policy
from repro.core.capacity import CapacityConfig, EnginePool
from repro.core.knowledge import KnowledgeBase
from repro.core.online import RollingAccuracy
from repro.core.prediction_plane import PredictionPlane
from repro.core.resilience import BreakerBoard, ResilienceConfig
from repro.core.telemetry import (DISP_SERVED, DISP_SHED, DISP_TIMEOUT,
                                  MetricsRegistry, TRACE_FIELDS, compose_row,
                                  span, trace_block)
from repro.serving.engine import Request, ServingEngine


class MorpheusRouter:
    def __init__(self, replicas: Sequence[ServingEngine], policy: str = "perf_aware",
                 kb: Optional[KnowledgeBase] = None,
                 predictors: Optional[dict] = None,
                 plane: Optional[PredictionPlane] = None,
                 hedge_factor: Optional[float] = None, seed: int = 0,
                 fallback_threshold: float = 0.0,
                 accuracy_window: int = 40,
                 capacity: Optional[CapacityConfig] = None,
                 resilience: Optional[ResilienceConfig] = None,
                 metrics_store=None):
        if hedge_factor is not None and resilience is not None \
                and resilience.client_side:
            raise ValueError("hedging and client-side resilience (timeout/"
                             "retry) are mutually exclusive — same rule as "
                             "the simulator")
        self.replicas = list(replicas)
        self.policy_name = policy
        self.policy = make_policy(policy, seed=seed, hedge_factor=hedge_factor)
        self.kb = kb or KnowledgeBase()
        self.predictors = predictors or {}
        self.plane = plane or PredictionPlane()
        self.hedge_factor = hedge_factor
        self.routed: List[int] = []
        self.hedged: List[int] = []
        self._hedge_pairs: List[tuple] = []   # (primary, duplicate) requests
        # per-replica rolling prediction accuracy (the same tracker the
        # closed-loop simulator's OnlineFleet uses) + the fallback policy
        # served while predictions are non-viable
        self.fallback_threshold = float(fallback_threshold)
        self.accuracy = RollingAccuracy(accuracy_window, n=len(self.replicas))
        self.fallbacks = 0                    # requests routed via fallback
        self._fallback_policy = make_policy("least_conn", seed=seed)
        self._inflight: List[Tuple[Request, int, float]] = []
        # capacity plane (DESIGN.md §12): elastic engine pool + admission
        self.pool = None if capacity is None \
            else EnginePool(self.replicas, capacity)
        self.shed: List[Request] = []         # admission-rejected requests
        # resilience plane (DESIGN.md §14): T=1 breaker board + the
        # timeout/retry ledger drained by _settle_resilience()
        self.resilience = resilience
        self.breaker = None
        if resilience is not None and resilience.breaker_threshold is not None:
            self.breaker = BreakerBoard(
                len(self.replicas), resilience.breaker_threshold,
                resilience.breaker_cooldown_s, resilience.timeout_s)
        self.timeouts: List[Request] = []     # exhausted every attempt
        self.retries = 0                      # re-entries through route()
        self._attempt: Dict[int, int] = {}    # rid -> retries already issued
        self._res_pending: List[Tuple[Request, int]] = []
        self._timeout_ids: set = set()        # attempt objects that timed out
        # flight recorder (core/telemetry.py, DESIGN.md §16): the T=1
        # serving mirror is always on — one trace row per routed attempt
        # (retries and hedge duplicates are attempts of their primary),
        # opened at pick time with the score/prediction/queue-wait the
        # decision actually saw and finalized at drain/settle time.  The
        # Prometheus-style registry rides the columnar MetricsStore when
        # one is supplied (same storage model as the prediction signals).
        self._trace_open: Dict[int, dict] = {}     # id(req) -> open row
        self._trace_done: List[Tuple[int, np.ndarray]] = []
        self._trace_seq = 0
        self._hedge_saved: Dict[int, float] = {}   # id(primary) -> saved s
        self.registry = MetricsRegistry(store=metrics_store)
        self.m_requests = self.registry.counter("router_requests_total")
        self.m_shed = self.registry.counter("router_shed_total")
        self.m_retries = self.registry.counter("router_retries_total")
        self.m_timeouts = self.registry.counter("router_timeouts_total")
        self.m_hedges = self.registry.counter("router_hedges_total")
        self.m_fallbacks = self.registry.counter("router_fallbacks_total")
        self.m_inflight = self.registry.gauge("router_inflight")
        self.m_rtt = self.registry.histogram("router_rtt_seconds")

    # ------------------------------------------------------------------
    def _predicted_rtts(self) -> np.ndarray:
        """One batched plane sweep across replicas.

        Retrained predictors are re-exported first (version check, no-op
        when unchanged), then the whole fleet is served by a single
        ``PredictionPlane.predict_all`` — O(model buckets) jitted
        dispatches, one batched state query per store — instead of the
        seed's per-replica serial ``RTTPredictor.predict`` loop.
        Replicas without a trained predictor fall back to the knowledge
        base, then to a queue-depth proxy.
        """
        key_of = {}
        for i, rep in enumerate(self.replicas):
            p = self.predictors.get(rep.node)
            if p is not None:
                self.plane.register_predictor(p)
                key_of[(p.app, p.node)] = i
        recs = self.plane.predict_all(list(key_of)) if key_of else {}
        preds = np.full(len(self.replicas), np.inf)
        for key, rec in recs.items():
            i = key_of[key]
            self.kb.put("serve", self.replicas[i].node, rec.t, rec.rtt_pred)
            self.predictors[self.replicas[i].node].predictions.append(rec)
            preds[i] = rec.rtt_pred
        for i, rep in enumerate(self.replicas):
            if np.isfinite(preds[i]):
                continue
            p = self.predictors.get(rep.node)
            if p is not None and p.choice is not None:
                # trained but not plane-exportable (e.g. a test double
                # without inference_params): serial path still serves it
                rec = p.predict()
                if rec is not None:
                    self.kb.put("serve", rep.node, rec.t, rec.rtt_pred)
                    preds[i] = rec.rtt_pred
                    continue
            v = self.kb.latest("serve", rep.node)
            preds[i] = v if v is not None else 1.0 + rep.pending()
        return preds

    def _queue_proxy(self) -> np.ndarray:
        return np.array([r.pending() for r in self.replicas], float)

    def predictions_viable(self) -> bool:
        """The fallback rule (DESIGN.md §11): serve perf_aware only while
        the mean rolling accuracy of the replicas with enough evidence
        stays at or above ``fallback_threshold``."""
        if self.fallback_threshold <= 0:
            return True
        tracked = self.accuracy.count >= self.accuracy.min_count
        if not tracked.any():
            return True            # no evidence of non-viability yet
        return float(self.accuracy.accuracy()[tracked].mean()) \
            >= self.fallback_threshold

    def cluster_state(self, needs_pred: Optional[bool] = None
                      ) -> ClusterState:
        """The router's observable state as a 1-trial ClusterState.

        Queue wait is estimated as pending waves x predicted wave RTT
        when predictions are needed; reactive policies see zero wait
        plus the raw queue depths (classic least-connections / RR)."""
        queue = self._queue_proxy()
        predicted = None
        wait_est = np.zeros(len(self.replicas))
        if needs_pred is None:
            needs_pred = isinstance(self.policy, PerfAware)
        if needs_pred:
            with span("predict"):
                predicted = self._predicted_rtts()
            waves = np.ceil(queue
                            / np.array([r.max_batch for r in self.replicas]))
            wait_est = predicted * waves
            if self.pool is not None and np.isfinite(predicted).any():
                self.pool.note_prediction(
                    float(predicted[np.isfinite(predicted)].mean()))
        active = None if self.pool is None \
            else self.pool.active_mask()[None, :]
        return ClusterState(now=0.0, busy_until=wait_est[None, :],
                            queue_depth=queue[None, :],
                            predicted=None if predicted is None
                            else predicted[None, :], active=active)

    @span("route")
    def route(self, req: Request) -> int:
        """Route one request; returns the replica index, or -1 when the
        capacity plane's admission control sheds it (the request is
        recorded in ``self.shed`` and not enqueued anywhere)."""
        if self.pool is not None:
            # capacity epoch: scale decisions ride the request clock,
            # wake from zero, then gate admission
            now = self.pool.clock.now()
            self.pool.on_request(now)
            if not self.pool.admit(now):
                self.shed.append(req)
                self.m_requests.inc()
                self.m_shed.inc()
                # closed immediately: no pick ever happened
                self._trace_done.append((self._trace_seq, compose_row(
                    rep=-1.0, predicted=np.nan, score=np.nan,
                    queue_wait=np.nan, raw=np.nan, base=np.nan,
                    cold_mult=1.0, gray_mult=1.0, retry_s=np.nan,
                    hedge_s=np.nan, disposition=DISP_SHED,
                    response=np.nan)))
                self._trace_seq += 1
                return -1
        use_pred = isinstance(self.policy, PerfAware)
        fell_back = use_pred and not self.predictions_viable()
        # predictions are still computed and reconciled while fallen
        # back — otherwise the tracker would never see a retrained
        # fleet recover and the fallback would be permanent
        state = self.cluster_state(needs_pred=use_pred)
        if self.breaker is not None:
            # OPEN breakers leave candidate scoring entirely; half-open
            # probes stay routable.  When everything is open the request
            # routes anyway (failing fast would starve the probes).
            now = self.replicas[0].clock.now() if self.replicas else 0.0
            open_m = self.breaker.open_mask(np.array([now]))
            if not open_m.all():
                act = ~open_m if state.active is None \
                    else state.active & ~open_m
                state = ClusterState(now=state.now,
                                     busy_until=state.busy_until,
                                     queue_depth=state.queue_depth,
                                     predicted=state.predicted, active=act)
        # pick == argmin(mask_inactive(score)) + update, spelled out so
        # the flight recorder sees the scores the decision was made on
        # (bit-identical to Policy.pick — same single score() call)
        with span("pick"):
            if fell_back:
                self.fallbacks += 1
                self.m_fallbacks.inc()
                reactive = ClusterState(
                    now=0.0, busy_until=np.zeros((1, len(self.replicas))),
                    queue_depth=self._queue_proxy()[None, :],
                    active=state.active)
                tr_scores = self._fallback_policy.score(reactive)
                i = int(np.argmin(reactive.mask_inactive(tr_scores),
                                  axis=1)[0])
                self._fallback_policy.update(reactive, np.array([i]))
            else:
                tr_scores = self.policy.score(state)
                i = int(np.argmin(state.mask_inactive(tr_scores), axis=1)[0])
                self.policy.update(state, np.array([i]))
        self.replicas[i].submit(req)
        self.routed.append(i)
        self.m_requests.inc()
        self.m_inflight.inc()
        self._trace_open[id(req)] = {
            "seq": self._trace_seq, "req": req, "rep": i,
            "predicted": (float(state.predicted[0, i])
                          if state.predicted is not None else np.nan),
            "score": float(tr_scores[0, i]),
        }
        self._trace_seq += 1
        if self.resilience is not None and self.resilience.client_side:
            self._attempt.setdefault(req.rid, 0)
            self._res_pending.append((req, i))
        if use_pred and state.predicted is not None \
                and np.isfinite(state.predicted[0, i]):
            # predicted COMPLETION (queue-wait estimate + service RTT):
            # drain() reconciles it against the measured enqueue->done
            # latency, which includes the queue wait too
            self._inflight.append(
                (req, i,
                 float(state.predicted[0, i] + state.busy_until[0, i])))
        if self.hedge_factor is not None and use_pred and not fell_back \
                and state.predicted is not None:
            second, mask = self.policy.hedge_plan(state, np.array([i]))
            if bool(mask[0]):
                # submit a DUPLICATE object, not the same request: both
                # engines mutate t_done/output on completion, and drain()
                # reconciles the pair so the earlier completion wins
                j = int(second[0])
                dup = Request(rid=req.rid, tokens=req.tokens,
                              max_new_tokens=req.max_new_tokens)
                self.replicas[j].submit(dup)
                self._hedge_pairs.append((req, dup))
                self.hedged.append(j)
                self.m_hedges.inc()
        return i

    # ------------------------------------------------------------------
    @span("drain")
    def drain(self) -> List[Request]:
        """Serve every queued request to completion (round over replicas).

        Hedged duplicates are reconciled here: the primary request takes
        the earlier of the two completions and the duplicate is dropped
        from the finished list (each routed request appears once).
        Completed requests also settle the rolling accuracy tracker:
        each routed prediction is compared against the measured RTT, so
        the fallback rule sees prediction quality as it actually
        happened.

        With a client-side resilience plane each serve round is followed
        by a settlement pass: attempts whose measured RTT blew
        ``timeout_s`` are retried through ``route`` (retry re-entry is
        real load) and the loop continues until no retry was issued.
        Timed-out attempts are dropped from the finished list — the
        request either reappears as a successful retry or lands in
        ``self.timeouts``."""
        finished: List[Request] = []
        while True:
            progress = True
            while progress:
                progress = False
                for rep in self.replicas:
                    out = rep.step_wave()
                    if out:
                        finished.extend(out)
                        progress = True
            if not self._settle_resilience():
                break
        dup_ids = {id(d) for _, d in self._hedge_pairs}
        for primary, dup in self._hedge_pairs:
            if dup.t_done is not None and (
                    primary.t_done is None or dup.t_done < primary.t_done):
                if primary.t_done is not None:
                    # time the winning duplicate saved, captured before
                    # the overwrite — this is the trace row's hedge_s
                    self._hedge_saved[id(primary)] = \
                        primary.t_done - dup.t_done
                primary.t_done = dup.t_done
                primary.output = dup.output
        finished = [r for r in finished if id(r) not in dup_ids
                    and id(r) not in self._timeout_ids]
        self._hedge_pairs.clear()
        # finalize served trace rows (post hedge reconciliation, so the
        # response is the winning completion).  queue_wait is the
        # measured wait from enqueue to the wave that took the request,
        # service_base the rest of its own attempt, so the sum rule
        # holds by construction: qw + base - hedge_s == response.
        for rid in [k for k, v in self._trace_open.items()
                    if v["req"].t_done is not None]:
            row = self._trace_open.pop(rid)
            req = row["req"]
            resp = float(req.rtt)
            hs = float(self._hedge_saved.pop(rid, 0.0))
            qw = float(req.t_start - req.t_enqueue)
            self.m_inflight.dec()
            self.m_rtt.observe(resp)
            self._trace_done.append((row["seq"], compose_row(
                rep=float(row["rep"]), predicted=row["predicted"],
                score=row["score"], queue_wait=qw,
                raw=resp - qw + hs, base=resp - qw + hs,
                cold_mult=1.0, gray_mult=1.0, retry_s=0.0, hedge_s=hs,
                disposition=DISP_SERVED, response=resp)))
        still_inflight = []
        for req, i, pred in self._inflight:
            rtt = req.rtt
            if rtt is None:
                still_inflight.append((req, i, pred))
                continue
            if id(req) in self._timeout_ids:
                # the client gave up on this attempt: its measured RTT
                # says nothing about prediction quality, so the rolling
                # accuracy tracker never sees it (DESIGN.md §14)
                continue
            err = np.zeros(len(self.replicas))
            mask = np.zeros(len(self.replicas), bool)
            err[i] = abs(pred - rtt) / max(rtt, 1e-9)
            mask[i] = True
            self.accuracy.update(err, mask)
        self._inflight = still_inflight
        self._timeout_ids.clear()
        return finished

    def _settle_resilience(self) -> bool:
        """Classify completed attempts (DESIGN.md §14).

        A measured RTT above ``timeout_s`` means the CLIENT gave up —
        the server still did the full work (the wave already ran), which
        is exactly the wasted-work half of retry amplification.  Each
        verdict feeds the breaker at the attempt's DISPATCH time (the
        client learns of a timeout ``timeout_s`` after dispatch, which
        is what ``BreakerBoard.record`` encodes), and a timed-out
        request re-enters ``route`` while attempts remain.  Returns True
        when at least one retry was issued (the drain loop must serve
        another round)."""
        res = self.resilience
        if res is None or not res.client_side:
            return False
        still: List[Tuple[Request, int]] = []
        retried = False
        for req, i in self._res_pending:
            if req.t_done is None:
                still.append((req, i))
                continue
            timed_out = bool(req.rtt > res.timeout_s)
            if self.breaker is not None:
                self.breaker.record(
                    np.array([req.t_enqueue]), np.array([i]),
                    np.array([not timed_out]), np.array([timed_out]))
            if not timed_out:
                continue
            self._timeout_ids.add(id(req))
            row = self._trace_open.pop(id(req), None)
            if row is not None:
                # the attempt's row closes as a client timeout (NaN
                # response — the client never saw one); a retry opens
                # its own row through route()
                self.m_inflight.dec()
                self._trace_done.append((row["seq"], compose_row(
                    rep=-1.0, predicted=np.nan, score=np.nan,
                    queue_wait=np.nan, raw=np.nan, base=np.nan,
                    cold_mult=1.0, gray_mult=1.0, retry_s=np.nan,
                    hedge_s=np.nan, disposition=DISP_TIMEOUT,
                    response=np.nan)))
            attempt = self._attempt.get(req.rid, 0)
            if attempt < res.max_retries:
                self._attempt[req.rid] = attempt + 1
                self.retries += 1
                self.m_retries.inc()
                retry = Request(rid=req.rid, tokens=req.tokens,
                                max_new_tokens=req.max_new_tokens)
                if self.route(retry) >= 0:
                    retried = True
            else:
                self.timeouts.append(req)
                self.m_timeouts.inc()
        self._res_pending = still
        return retried

    # ------------------------------------------------------------------
    def trace(self) -> Dict:
        """Finalized trace rows in route order, packaged as the same
        ``"trace"`` block the serial and compiled simulators emit
        (T=1, ``sample_every=1``); attempts still in flight are not
        included until a ``drain`` settles them."""
        rows = [r for _, r in sorted(self._trace_done,
                                     key=lambda kv: kv[0])]
        data = (np.stack(rows)[:, None, :] if rows
                else np.empty((0, 1, len(TRACE_FIELDS))))
        return trace_block(data, len(rows), 1)
