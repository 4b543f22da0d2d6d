"""Campaign runner: batched scenario x policy x seed grid vs looping
serial ``run_sim`` (DESIGN.md §10).

The serial path pays the per-request stepping loop (and the cluster
build) once per grid cell; the batched path builds each scenario's
per-seed clusters once and advances the whole seed axis in ONE lockstep
pass per (scenario, policy) through the policy engine's (T, C) batch
axis.  Reported: wall time for both paths over the full registered
scenario matrix, the speedup, the max relative drift between batched and
serial per-seed stats (the parity guard CI's smoke mode enforces), and
the scenario x policy result table EXPERIMENTS.md embeds.

Run:  PYTHONPATH=src python benchmarks/bench_campaign.py \
          [--seeds 12] [--smoke] [--no-artifact]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

try:
    from benchmarks.run import manifest
except ImportError:          # script mode: benchmarks/ is sys.path[0]
    from run import manifest
from repro.core.campaign import (DEFAULT_POLICIES, LAST_PHASES,
                                 SUMMARY_STATS, campaign_table,
                                 run_campaign, run_campaign_serial)
from repro.core.scenarios import scenario_names
from repro.launch.compile_cache import enable_compile_cache

PARITY_TOL = 1e-5
#: compiled backends only: the scan kernel's in-kernel ridge retrain
#: reproduces the serial numpy solve to float reassociation, not
#: bit-for-bit — over full campaign horizons (500+ requests) a
#: near-tie argmin can flip O(1) pick per ~1e3 decisions (measured:
#: 2 of 4480 on tier-drift seed 5, mean_rtt damage 1.3e-6), which
#: jumps empirical percentiles by O(1e-3).  Closed-loop cells
#: therefore gate at this looser bound; the test suite still pins
#: them at 1e-5 on shrunken horizons where no flip occurs.
CLOSED_LOOP_TOL = 1e-2
ARTIFACT = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "artifacts", "campaign.json")


def parity_drift(batched, serial):
    """Max relative per-seed-stat drift between the two grids, split
    into (exact-parity cells, closed-loop cells) — see
    CLOSED_LOOP_TOL for why closed-loop cells get their own bound
    under compiled backends."""
    from repro.core.scenarios import get_scenario
    worst = {False: 0.0, True: 0.0}
    for scen, cell in batched.items():
        closed = bool(get_scenario(scen).compile(seed=0).closed_loop)
        for pol, r in cell.items():
            s = serial[scen][pol]
            for k in SUMMARY_STATS:
                d = np.max(np.abs(r.per_seed[k] - s.per_seed[k])
                           / np.maximum(np.abs(s.per_seed[k]), 1e-9))
                worst[closed] = max(worst[closed], float(d))
    return worst[False], worst[True]


def bench(scenarios, policies, seeds, repeats: int = 1,
          backend: str = "serial", **overrides):
    """(results, serial_s, batched_s, drift) over the given grid.

    ``backend`` is forwarded to :func:`run_campaign`: ``"serial"`` is
    the PR-3 batched stepper, ``"auto"`` routes every supported cell
    through the compiled scan kernel (DESIGN.md §13) and falls back to
    the stepper elsewhere — the parity drift below then doubles as a
    registry-wide compiled-vs-serial gate."""
    kw = dict(scenarios=scenarios, policies=policies, seeds=seeds,
              backend=backend, **overrides)
    run_campaign(**{**kw, "seeds": seeds[:2],
                    "n_trials": 2, "n_requests": 10})   # warm-up
    t_b, batched = _best_of(lambda: run_campaign(**kw), repeats)
    t_s, serial = _best_of(lambda: run_campaign_serial(
        **{k: v for k, v in kw.items() if k != "backend"}), repeats)
    return batched, t_s, t_b, *parity_drift(batched, serial)


def _best_of(fn, repeats: int):
    """(best wall seconds, last result) — the grids are deterministic,
    so the last result stands for every repeat."""
    best, result = float("inf"), None
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        result = fn()
        best = min(best, time.perf_counter() - t0)
    return best, result


def _kernel_cache_stats():
    """PR-7 kernel-cache counters, None under a serial-only run (the
    compiled core was never imported, so there is nothing to report)."""
    import sys
    simcore = sys.modules.get("repro.core.simcore")
    return None if simcore is None else simcore.cache_stats()


def _write_artifact(results, t_s, t_b, drift, drift_cl, seeds,
                    backend="serial"):
    os.makedirs(os.path.dirname(ARTIFACT), exist_ok=True)
    payload = {
        "manifest": manifest(),
        "seeds": list(seeds), "backend": backend,
        "serial_s": t_s, "batched_s": t_b,
        "speedup_x": t_s / max(t_b, 1e-12), "parity_drift": drift,
        "parity_drift_closed_loop": drift_cl,
        # per-phase wall breakdown of the LAST run_scenario pass (build
        # + one run:<policy> entry each) — the campaign-runner
        # observability hook (DESIGN.md §16)
        "phases_last_scenario": dict(LAST_PHASES),
        "kernel_cache": _kernel_cache_stats(),
        "table": {
            scen: {pol: {
                "p50_rtt": r.stat("p50_rtt"),
                "p95_rtt": r.stat("p95_rtt"),
                "p99_rtt": r.stat("p99_rtt"),
                "inefficiency_pct": r.inefficiency_pct,
                "inefficiency_std": r.inefficiency_std,
                "p99_inefficiency_pct": r.p99_inefficiency_pct,
                "resource_waste_pct": r.resource_waste_pct,
                "waste": r.stat("waste"),
                "shed_rate": r.stat("shed_rate"),
                "slo_violation_s": r.stat("slo_violation_s"),
                # capacity-plane fleet telemetry (None off-plane) —
                # surfaced instead of dropped at the campaign layer
                "telemetry": r.telemetry,
            } for pol, r in cell.items() if pol != "oracle"}
            for scen, cell in results.items()},
    }
    with open(ARTIFACT, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {os.path.abspath(ARTIFACT)}")


def run(seeds=tuple(range(12)), repeats: int = 2):
    """Harness contract (benchmarks/run.py): CSV rows for the full grid."""
    results, t_s, t_b, drift, drift_cl = bench(
        scenario_names(), DEFAULT_POLICIES, tuple(seeds),
        repeats=repeats)
    drift = max(drift, drift_cl)   # serial backend: both exact
    n_runs = len(results) * len(next(iter(results.values()))) * len(seeds)
    return [
        ("campaign_serial", t_s / n_runs * 1e6,
         f"grid_runs={n_runs};wall_s={t_s:.2f}"),
        ("campaign_batched", t_b / n_runs * 1e6,
         f"wall_s={t_b:.2f};speedup_x={t_s / max(t_b, 1e-12):.1f};"
         f"parity_drift={drift:.2e}"),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=12,
                    help="seeds per scenario (>=8 for the headline grid)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="small grid + hard parity/speedup gate (CI)")
    ap.add_argument("--backend", choices=("serial", "compiled", "auto"),
                    default="serial",
                    help="grid engine: 'serial' = PR-3 batched stepper, "
                         "'auto' = compiled scan kernel where supported "
                         "(re-baselines campaign.json on the compiled "
                         "core)")
    ap.add_argument("--no-artifact", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        scenarios = ("baseline", "flash-crowd", "stale-predictions")
        results, t_s, t_b, drift, drift_cl = bench(
            scenarios, ("perf_aware", "least_conn", "random"),
            tuple(range(12)), repeats=2, backend=args.backend,
            n_trials=6, n_requests=80)
    else:
        scenarios = scenario_names()
        results, t_s, t_b, drift, drift_cl = bench(
            scenarios, DEFAULT_POLICIES, tuple(range(args.seeds)),
            repeats=args.repeats, backend=args.backend)

    speedup = t_s / max(t_b, 1e-12)
    n_cells = len(results) * (len(next(iter(results.values()))))
    print(f"grid: {len(results)} scenarios x "
          f"{len(next(iter(results.values())))} policies (incl. oracle) x "
          f"{args.seeds if not args.smoke else 12} seeds")
    print(f"serial  {t_s:7.2f}s   ({n_cells} independent run_sim loops)")
    print(f"batched {t_b:7.2f}s   speedup {speedup:.1f}x   "
          f"parity_drift {drift:.2e} "
          f"(closed-loop cells {drift_cl:.2e})")
    print()
    print(campaign_table(results))
    print()
    print("phases (last scenario): "
          + ", ".join(f"{k}={v:.2f}s" for k, v in LAST_PHASES.items()))
    print(f"kernel cache: {_kernel_cache_stats()}")
    tele_cells = [f"{scen}/{pol}" for scen, cell in results.items()
                  for pol, r in cell.items() if r.telemetry is not None]
    print(f"capacity telemetry: {len(tele_cells)} cells"
          + (f" ({', '.join(tele_cells[:4])}{'...' if len(tele_cells) > 4 else ''})"
             if tele_cells else ""))

    if not args.smoke and not args.no_artifact:
        _write_artifact(results, t_s, t_b, drift, drift_cl,
                        tuple(range(args.seeds)), backend=args.backend)

    assert drift <= PARITY_TOL, \
        f"batched/serial drift {drift:.2e} exceeds {PARITY_TOL}"
    cl_tol = PARITY_TOL if args.backend == "serial" else CLOSED_LOOP_TOL
    assert drift_cl <= cl_tol, \
        f"closed-loop cell drift {drift_cl:.2e} exceeds {cl_tol}"
    floor = 3.0 if args.smoke else 5.0   # CI runners are noisy
    assert speedup >= floor, \
        f"batched campaign only {speedup:.1f}x serial (need >={floor}x)"
    print(f"\nOK: parity<= {PARITY_TOL}, speedup {speedup:.1f}x "
          f">= {floor}x")


if __name__ == "__main__":
    main()
