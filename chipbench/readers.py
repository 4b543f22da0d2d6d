"""Arithmetic shared by the metric readers in ``metrics/``.

Each reader takes the run's record and returns a number, or None where
the run recorded nothing for it to read.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from chipbench import archs, flops


def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank percentile of every value, failures (inf) included."""
    v = np.asarray(values, float)
    if not len(v):
        return None
    return float(np.percentile(v, q, method="inverted_cdf"))


def idle_share(record) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device, averaged over the chips."""
    tr = record.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def phase_mean(record, name: str) -> Optional[float]:
    calls = record.get("calls")
    if not calls:
        return None
    return float(np.mean([c["phases"][name] for c in calls]))


def tokens_per_s(new_tokens, done_at, seconds: float) -> Optional[float]:
    """Output tokens of every completed request over the time from the
    window's start until the last of them completed, or the close where
    that is later: the requests due in the window are waited for, so a
    request that straddles the close counts, and so does its time.
    ``done_at`` is NaN or inf for a request that never completed."""
    done_at = np.asarray(done_at, float)
    done = np.isfinite(done_at)
    if not done.any():
        return None
    return float(np.asarray(new_tokens)[done].sum()) \
        / max(float(seconds), float(done_at[done].max()))


# The served per-layer metrics read ``span``: the window from its start
# up to the trace's stop in a traced run (stopping a trace blocks the
# loop, and a request that waits it out would count the stall), the
# whole window otherwise.
def route_ms(record) -> Optional[float]:
    """Mean duration of the route calls in the span, in ms."""
    if "span" not in record or not record["span"]["routes"]:
        return None
    return 1e3 * float(np.mean(record["route_s"][:record["span"]["routes"]]))


def route_rtt_share(record) -> Optional[float]:
    """Route time of the requests routed and completed in the span over
    their summed RTT, in percent (the paper bounds it at 10%)."""
    if "span" not in record:
        return None
    n = record["span"]["routes"]
    rtt = np.asarray(record["rtt_s"][:n], float)
    ok = np.asarray(record["done_at_s"][:n], float) <= record["span"]["t"]
    if not ok.any() or rtt[ok].sum() <= 0:
        return None
    return 100.0 * float(np.sum(record["route_s"][:n][ok])) \
        / float(rtt[ok].sum())


def engine_busy_share(record) -> Optional[float]:
    """The replicas' summed ``busy_s`` in the span over its length, in
    percent."""
    span = record.get("span")
    if not span or span["t"] <= 0:
        return None
    return 100.0 * span["busy"] / span["t"]


def model_mfu(record) -> Optional[float]:
    """Model FLOPs of the requests completed in the span (their prefill
    and their own decode steps, as the configuration's architecture
    module counts them) over the span at the chip's peak bf16 rate, in
    percent."""
    span = record.get("span")
    if not span or span["t"] <= 0:
        return None
    conf = record["config"]
    request_flops = archs.of(conf).request_flops
    done = np.flatnonzero(record["done_at_s"] <= span["t"])
    total = sum(request_flops(conf, int(record["prompt_len"][i]),
                              int(record["new_tokens"][i]))
                for i in done)
    peak = flops.peaks(record["device_kind"])["bf16_flops"]
    return 100.0 * total / (span["t"] * peak)
