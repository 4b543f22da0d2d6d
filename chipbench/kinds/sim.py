"""Cells of the compiled fleet simulator.

The window calls ``campaign.run_scenario(..., backend="compiled")`` back
to back, each call on one batch of ``S`` campaign seeds (``S`` seeds per
call).  Without ``seed_batches`` in the traffic, every call runs the one
batch ``S n .. S n + S - 1`` of ``--seed n``.  With ``seed_batches: B``,
the calls cycle through a pool that is the same for every seed: batch
``b`` holds the campaign seeds ``S b .. S b + S - 1``, ``b < B``, and
``--seed`` draws only the order of the batches and of the seeds in each,
so that every seed does the same work.  Set-up makes one call of each
batch, which compiles or loads every program the window runs.

The check: three of the seeds that the window ran, the first, the last
and one between them drawn from ``--seed``, are simulated again by the
plain reference (the serial stepper, ``refs/simserial``), and every
call's per-seed summary statistics for those seeds are held to it by
their largest relative gap.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict

import numpy as np

from chipbench.gen import rng_for
from chipbench.harness import Cell, Check, Outcome, memory_peak_bytes

#: the summary statistics compared (``campaign.SUMMARY_STATS``)
STATS = ("mean_rtt", "p50_rtt", "p95_rtt", "p99_rtt", "cpu_s", "mem_s",
         "waste", "shed_rate", "slo_violation_s", "goodput",
         "timeout_rate")


def drift(got: Dict[str, float], ref: Dict[str, float]) -> float:
    """Largest relative gap over ``STATS``; a NaN on one side only is an
    infinite gap, NaN on both sides none."""
    worst = 0.0
    for k in STATS:
        x, y = float(got[k]), float(ref[k])
        if np.isnan(x) and np.isnan(y):
            continue
        if np.isnan(x) or np.isnan(y):
            return float("inf")
        worst = max(worst, abs(x - y) / max(abs(y), 1e-9))
    return worst


def shape(cell: Cell) -> dict:
    """``run_scenario`` overrides: every scenario gets replicas per app
    so that the fleet holds ``replicas_total`` replicas."""
    from repro.core.scenarios import get_scenario
    c = cell.config
    scen = cell.traffic["scenario"]
    over = dict(n_nodes=c["n_nodes"], n_requests=c["n_requests"],
                n_trials=c["trials_per_seed"])
    n_apps = len(get_scenario(scen).compile(seed=0, **over).apps)
    over["n_replicas_per_app"] = c["replicas_total"] // n_apps
    return over


def campaign_seeds(cell: Cell, seed: int):
    k = cell.config["seeds_per_call"]
    return tuple(k * int(seed) + i for i in range(k))


def batches(cell: Cell, seed: int):
    """The batches of campaign seeds that a run's calls cycle through,
    in the run's order (module docstring)."""
    n = cell.traffic.get("seed_batches")
    if not n:
        return [campaign_seeds(cell, seed)]
    k = cell.config["seeds_per_call"]
    rng = rng_for(seed, "sim-pool")
    return [tuple(k * int(b) + int(i) for i in rng.permutation(k))
            for b in rng.permutation(int(n))]


def checked(n: int, seed: int):
    """Positions of the call's seeds that the check compares: the first,
    the last, and one between them drawn from ``--seed``."""
    if n <= 2:
        return list(range(n))
    return [0, 1 + int(rng_for(seed, "sim-check").integers(n - 2)), n - 1]


def reference(cell: Cell, seed: int) -> Dict[str, float]:
    from chipbench.refs.simserial import run_seed
    return run_seed(cell.traffic["scenario"], cell.traffic["policy"], seed,
                    **shape(cell))


@contextlib.contextmanager
def float32_state():
    """The compiled simulator with its float64 state computed in float32:
    ``jax.enable_x64`` made a no-op while the block runs (the control)."""
    import jax
    saved = jax.enable_x64
    jax.enable_x64 = lambda *a, **k: contextlib.nullcontext()
    try:
        yield
    finally:
        jax.enable_x64 = saved


def run(cell: Cell, *, seed: int, seconds: float, tracer, t0: float,
        devs, control: bool = False) -> Outcome:
    """One run; ``control`` also reads the float32 control's drift on
    the same seeds (calibration only)."""
    from repro.core.campaign import LAST_PHASES, run_scenario
    scen, pol = cell.traffic["scenario"], cell.traffic["policy"]
    order = batches(cell, seed)
    kw = dict(policies=[pol], include_oracle=False, backend="compiled",
              **shape(cell))

    def call(b):
        return run_scenario(scen, seeds=order[b], **kw)[pol]

    for b in range(len(order)):               # compiles or loads
        call(b)
    t_w = time.perf_counter()
    setup_s = t_w - t0
    calls = []
    tracer.start()
    while not calls or calls[-1]["end"] < seconds:
        b = len(calls) % len(order)
        t_c = time.perf_counter()
        res = call(b)
        calls.append({"start": t_c - t_w, "end": time.perf_counter() - t_w,
                      "batch": b, "phases": dict(LAST_PHASES),
                      "per_seed": {k: np.asarray(res.per_seed[k])
                                   for k in STATS}})
        tracer.stop()                         # the first call is traced
    peak = memory_peak_bytes(devs)

    ran = [(b, j) for b in range(min(len(order), len(calls)))
           for j in range(len(order[b]))]
    picks = [ran[i] for i in checked(len(ran), seed)]
    refs = {(b, j): reference(cell, order[b][j]) for b, j in picks}
    worst = max(drift({k: c["per_seed"][k][j] for k in STATS}, refs[b, j])
                for c in calls for b, j in picks if c["batch"] == b)
    cfg = cell.config
    checks = [Check("sim_stats_drift", worst,
                    cfg["limits"]["sim_stats_drift"])]
    if control:
        with float32_state():
            low = call(0).per_seed
        checks.append(Check("control_sim_stats_drift", max(drift(
            {k: low[k][j] for k in STATS}, refs[b, j])
            for b, j in picks if b == 0),
            cfg["limits"]["sim_stats_drift"]))
    record = {
        "calls": calls, "policy": pol,
        "requests_per_call": len(order[0]) * cfg["trials_per_seed"]
        * cfg["n_requests"],
        "segsum": {"pattern": cfg["segsum_op"],
                   "T": len(order[0]) * cfg["trials_per_seed"],
                   "R": cfg["replicas_total"],
                   "segments": cfg["n_nodes"]
                   * (cfg["replicas_total"]
                      // kw["n_replicas_per_app"])},
        "device_kind": devs[0].device_kind,
    }
    return Outcome(setup_s=setup_s, attempted=len(calls) * len(order[0]),
                   failed=0,
                   checks=checks, memory_peak_bytes=peak, record=record)
