"""Flight recorder: per-request decision traces and RTT decomposition.

Every traced request carries one fixed-width row (:data:`TRACE_FIELDS`)
recording the routing decision (chosen replica, score at pick time,
predicted RTT) and an *additive* decomposition of the observed response
time::

    queue_wait + service_base + interference_s + cold_s + gray_s
        + retry_s - hedge_s  ==  response        (served requests)

where ``service_base`` is the replica's intrinsic service draw (the
lognormal RTT draw at zero interference on the chosen replica's
hardware tier), ``interference_s`` is the colocation inflation of that
draw, ``cold_s``/``gray_s`` are the cold-start and gray-failure
multiplier surcharges, ``retry_s`` is time burned on failed attempts +
backoff before the successful dispatch, and ``hedge_s`` is the time
*saved* by a winning hedge duplicate (subtracted, so the identity
holds).  Dropped requests keep ``rep = -1``, a non-zero
:data:`disposition <DISP_SHED>` code and NaN components.

The same schema is emitted by all three execution paths:

* the serial ``SimStepper`` (via :class:`FlightRecorder`),
* the compiled ``lax.scan`` kernel (a ``(J_s, T, F)`` carry buffer,
  sampled every ``TraceConfig.sample_every`` requests so the tensor
  stays bounded), and
* the ``MorpheusRouter`` serving mirror (T=1, always-on), which also
  exports a Prometheus-style counter/gauge/histogram registry riding
  the columnar ``MetricsStore``.

This module imports only numpy so the serial path and the router stay
jax-free; :func:`span` imports ``jax.profiler`` at its first use.
"""
from __future__ import annotations

import functools
import math
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# Trace schema
# ---------------------------------------------------------------------------

#: Column order of every trace row.  ``rep`` is the chosen replica id
#: (-1 when dropped), ``predicted`` the predictor's RTT estimate as
#: scored (NaN for policies that never consult one), ``score`` the
#: winning policy score, ``disposition`` a :data:`DISP_SERVED` code and
#: ``response`` the end-to-end response time (NaN when dropped).  The
#: seven middle columns are the additive decomposition.
TRACE_FIELDS = (
    "rep", "predicted", "score",
    "queue_wait", "service_base", "interference_s", "cold_s", "gray_s",
    "retry_s", "hedge_s",
    "disposition", "response",
)

#: field name -> column index
TRACE_IDX = {name: i for i, name in enumerate(TRACE_FIELDS)}

#: Decomposition components (sum rule: their signed sum == response).
COMPONENTS = ("queue_wait", "service_base", "interference_s", "cold_s",
              "gray_s", "retry_s", "hedge_s")

DISP_SERVED = 0        #: request completed
DISP_SHED = 1          #: dropped by admission control
DISP_TIMEOUT = 2       #: client-side timeout after >=1 dispatched attempt
DISP_FAIL_FAST = 3     #: breaker/drain failed fast: 0 attempts dispatched

DISPOSITIONS = {
    DISP_SERVED: "served",
    DISP_SHED: "shed",
    DISP_TIMEOUT: "client_timeout",
    DISP_FAIL_FAST: "fail_fast",
}


@dataclass(frozen=True)
class TraceConfig:
    """Flight-recorder knob on ``SimConfig``.

    ``sample_every = k`` records every k-th request (request indices
    ``0, k, 2k, ...``); ``1`` is full tracing.  The compiled kernel
    allocates a ``(ceil(J / k), T, F)`` carry, so the default keeps
    trace memory ~6% of a full (T, J) ys column."""
    sample_every: int = 16


def trace_block(data, n_requests: int, sample_every: int) -> Dict:
    """Package a ``(J_s, T, F)`` slot-major buffer as the summary's
    ``"trace"`` block (trial-major ``(T, J_s, F)``), identically for
    the serial recorder and the compiled ``_summarize``."""
    data = np.asarray(data)
    return {
        "fields": list(TRACE_FIELDS),
        "sample_every": int(sample_every),
        "requests": np.arange(0, int(n_requests), int(sample_every)),
        "data": np.transpose(data, (1, 0, 2)),
    }


def compose_row(*, rep, predicted, score, queue_wait, raw, base,
                cold_mult, gray_mult, retry_s, hedge_s, disposition,
                response) -> np.ndarray:
    """Assemble one (T, F) trace row from pick-time quantities.

    ``raw`` is the undecorated service draw on the chosen replica
    (pre cold-start / gray multipliers); ``base`` the zero-interference
    draw on the same tier; the multiplier surcharges are attributed
    multiplicatively-in, additively-out: ``cold_s = raw * (cm - 1)``,
    ``gray_s = raw * cm * (gm - 1)`` so that
    ``base + interference + cold_s + gray_s == raw * cm * gm`` exactly.
    Rows whose disposition is non-zero are NaN-masked with ``rep = -1``.
    """
    rep = np.asarray(rep, np.float64)
    disposition = np.asarray(disposition, np.float64)
    dropped = disposition != DISP_SERVED
    raw = np.asarray(raw, np.float64)
    cm = np.asarray(cold_mult, np.float64)
    gm = np.asarray(gray_mult, np.float64)
    cols = {
        "rep": np.where(dropped, -1.0, rep),
        "predicted": np.asarray(predicted, np.float64),
        "score": np.asarray(score, np.float64),
        "queue_wait": np.asarray(queue_wait, np.float64),
        "service_base": np.asarray(base, np.float64),
        "interference_s": raw - base,
        "cold_s": raw * (cm - 1.0),
        "gray_s": raw * cm * (gm - 1.0),
        "retry_s": np.asarray(retry_s, np.float64),
        "hedge_s": np.asarray(hedge_s, np.float64),
        "disposition": disposition,
        "response": np.asarray(response, np.float64),
    }
    out = np.empty(rep.shape + (len(TRACE_FIELDS),), np.float64)
    for name, i in TRACE_IDX.items():
        col = np.broadcast_to(cols[name], rep.shape)
        if name not in ("rep", "disposition"):
            col = np.where(dropped, np.nan, col)
        out[..., i] = col
    return out


class FlightRecorder:
    """Serial-side trace sink: a ``(J_s, T, F)`` slot-major buffer
    mirroring the compiled kernel's carry layout."""

    def __init__(self, n_requests: int, n_trials: int, sample_every: int):
        if sample_every < 1:
            raise ValueError("sample_every must be >= 1")
        self.sample_every = int(sample_every)
        self.n_requests = int(n_requests)
        n_slots = -(-self.n_requests // self.sample_every)
        self.buf = np.full(
            (n_slots, int(n_trials), len(TRACE_FIELDS)), np.nan)

    def wants(self, j: int) -> bool:
        return j % self.sample_every == 0

    def record(self, j: int, row: np.ndarray):
        """Store the (T, F) row for request ``j`` (no-op off-sample)."""
        if self.wants(j):
            self.buf[j // self.sample_every] = row

    def block(self) -> Dict:
        return trace_block(self.buf, self.n_requests, self.sample_every)


# ---------------------------------------------------------------------------
# Tail attribution
# ---------------------------------------------------------------------------

def tail_attribution(trace: Dict,
                     quantiles: Sequence[float] = (0.99, 0.999)) -> Dict:
    """Attribute response-time tails to decomposition components.

    For each quantile q, selects the served rows at or above the q-th
    response percentile (across all trials) and reports the mean of
    each component over those rows plus its share of the mean tail
    response (``hedge_s`` enters negatively, so shares sum to ~1).
    """
    data = np.asarray(trace["data"], np.float64).reshape(
        -1, len(TRACE_FIELDS))
    resp = data[:, TRACE_IDX["response"]]
    disp = data[:, TRACE_IDX["disposition"]]
    served = (disp == DISP_SERVED) & np.isfinite(resp)
    out: Dict[str, Dict] = {
        "n_rows": int(data.shape[0]),
        "n_served": int(served.sum()),
        "dispositions": {
            name: int(np.sum(disp == code))
            for code, name in DISPOSITIONS.items()},
    }
    rows = data[served]
    rr = rows[:, TRACE_IDX["response"]] if rows.size else np.empty(0)
    for q in quantiles:
        key = "p" + ("%g" % (100 * q)).replace(".", "_")
        if rr.size == 0:
            out[key] = None
            continue
        cut = np.quantile(rr, q)
        tail = rows[rr >= cut]
        tresp = float(tail[:, TRACE_IDX["response"]].mean())
        comp = {}
        for name in COMPONENTS:
            v = float(tail[:, TRACE_IDX[name]].mean())
            signed = -v if name == "hedge_s" else v
            comp[name] = {
                "mean_s": v,
                "share": signed / tresp if tresp else 0.0,
            }
        out[key] = {
            "cut_s": float(cut),
            "n_tail": int(tail.shape[0]),
            "mean_response_s": tresp,
            "components": comp,
        }
    return out


# ---------------------------------------------------------------------------
# Prometheus-style registry riding the columnar MetricsStore
# ---------------------------------------------------------------------------

class Counter:
    """Monotone counter (exported as a single cumulative series)."""

    def __init__(self, name: str):
        self.name, self.value = name, 0.0

    def inc(self, amount: float = 1.0):
        if amount < 0:
            raise ValueError("counters only go up")
        self.value += amount

    def export(self) -> Dict[str, float]:
        return {self.name: self.value}


class Gauge:
    """Set-to-current-value metric."""

    def __init__(self, name: str):
        self.name, self.value = name, 0.0

    def set(self, value: float):
        self.value = float(value)

    def inc(self, amount: float = 1.0):
        self.value += amount

    def dec(self, amount: float = 1.0):
        self.value -= amount

    def export(self) -> Dict[str, float]:
        return {self.name: self.value}


class Histogram:
    """Fixed-bucket cumulative histogram, Prometheus ``le`` semantics:
    one series per bucket plus ``_sum`` and ``_count``."""

    DEFAULT_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

    def __init__(self, name: str,
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        self.name = name
        self.buckets = tuple(sorted(buckets))
        self.counts = np.zeros(len(self.buckets) + 1, np.int64)
        self.sum = 0.0

    def observe(self, value: float):
        self.counts[np.searchsorted(self.buckets, value, side="left")] += 1
        self.sum += float(value)

    @property
    def count(self) -> int:
        return int(self.counts.sum())

    def quantile(self, q: float) -> float:
        """Bucket-interpolated quantile (inf bucket clamps to top le)."""
        total = self.count
        if total == 0:
            return math.nan
        target = q * total
        cum = np.cumsum(self.counts)
        i = int(np.searchsorted(cum, target, side="left"))
        if i >= len(self.buckets):
            return self.buckets[-1]
        lo = 0.0 if i == 0 else self.buckets[i - 1]
        lo_cum = 0 if i == 0 else cum[i - 1]
        frac = (target - lo_cum) / max(self.counts[i], 1)
        return lo + (self.buckets[i] - lo) * min(max(frac, 0.0), 1.0)

    def export(self) -> Dict[str, float]:
        out = {}
        cum = 0
        for le, c in zip(self.buckets, self.counts[:-1]):
            cum += int(c)
            out[f"{self.name}_bucket_le_{le:g}"] = float(cum)
        out[f"{self.name}_bucket_le_inf"] = float(self.count)
        out[f"{self.name}_sum"] = self.sum
        out[f"{self.name}_count"] = float(self.count)
        return out


class MetricsRegistry:
    """Counter/gauge/histogram registry whose scrape lands in the
    columnar ``MetricsStore`` (one 200 ms column per scrape), so the
    serving plane's telemetry rides the same storage and retrieval
    model as the prediction-plane signals."""

    def __init__(self, store=None):
        self.store = store
        self._metrics: Dict[str, object] = {}

    def _add(self, metric):
        if metric.name in self._metrics:
            raise ValueError(f"duplicate metric {metric.name}")
        self._metrics[metric.name] = metric
        if self.store is not None:
            self.store.register(list(metric.export()))
        return metric

    def counter(self, name: str) -> Counter:
        return self._add(Counter(name))

    def gauge(self, name: str) -> Gauge:
        return self._add(Gauge(name))

    def histogram(self, name: str, buckets=Histogram.DEFAULT_BUCKETS
                  ) -> Histogram:
        return self._add(Histogram(name, buckets))

    def collect(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for m in self._metrics.values():
            out.update(m.export())
        return out

    def scrape(self, t: Optional[float] = None):
        """Write one column of current values into the store."""
        if self.store is not None:
            self.store.scrape(self.collect(), t=t)


# ---------------------------------------------------------------------------
# Spans: campaign phases, the compiled pass's steps, the served path
# ---------------------------------------------------------------------------

class PhaseTimer:
    """Wall time and count per span name, for the spans it owns.

    ``phase(name)`` opens a span owned by this timer; every span opened
    inside it (:func:`span`) is named ``<phase>/<child>`` and recorded
    here too, so a caller's timer collects the steps of the code it
    calls.  A phase's own name is never prefixed, so a timer's top-level
    names do not depend on what its caller has open."""

    def __init__(self):
        self.wall: Dict[str, float] = {}
        self.count: Dict[str, int] = {}
        self._lock = threading.Lock()

    def phase(self, name: str):
        return span(name, timer=self)

    def add(self, name: str, seconds: float):
        with self._lock:
            self.wall[name] = self.wall.get(name, 0.0) + seconds
            self.count[name] = self.count.get(name, 0) + 1

    def summary(self) -> Dict[str, float]:
        return dict(sorted(self.wall.items()))


#: totals of the spans that no timer owns (opened outside any phase)
_PROCESS = PhaseTimer()
#: ``open``: this thread's open spans, outermost first, as (full name,
#: owning timer), so that spans of different threads never nest
_LOCAL = threading.local()
#: ``jax.profiler.TraceAnnotation`` (a no-op context without jax),
#: imported at the first span so that this module stays jax-free
_ANNOTATION = None


def _annotation(name: str):
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation as _ANNOTATION
        except ImportError:  # pragma: no cover - jax-free installs
            from contextlib import nullcontext as _ANNOTATION
    return _ANNOTATION(name)


class span:
    """A timed host-side span, also a profiler ``TraceAnnotation`` of its
    full name, so that a traced run places it on the device trace's
    clock.  Inside another span it is named ``<parent>/<name>`` and is
    recorded by the parent's timer; outside every timer it goes to the
    process-wide totals (:func:`snapshot`).  A context manager entered
    once, or a decorator that opens a new one per call.  Host code only:
    never open one inside traced (jitted) code."""

    __slots__ = ("name", "timer", "_ann", "_t0")

    def __init__(self, name: str, timer: Optional[PhaseTimer] = None):
        self.name = name
        self.timer = timer

    def __enter__(self):
        stack = _LOCAL.__dict__.setdefault("open", [])
        if self.timer is None:
            if stack:
                parent, self.timer = stack[-1]
                self.name = f"{parent}/{self.name}"
            else:
                self.timer = _PROCESS
        stack.append((self.name, self.timer))
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self.timer.add(self.name, dt)
        _LOCAL.open.pop()

    def __call__(self, fn):
        name, timer = self.name, self.timer

        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with span(name, timer):
                return fn(*args, **kwargs)
        return spanned


def snapshot() -> Dict[str, Tuple[int, float]]:
    """(count, seconds) by full name of every span no timer owned, since
    the process started; the difference of two snapshots covers what
    ran between them."""
    with _PROCESS._lock:
        return {k: (_PROCESS.count[k], _PROCESS.wall[k])
                for k in sorted(_PROCESS.wall)}
