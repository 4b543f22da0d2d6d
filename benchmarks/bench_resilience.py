"""Resilience plane: the metastable-collapse study (DESIGN.md §14).

Three client configurations ride the SAME 10x overload ramp
(``retry-storm``'s arrival timeline: baseline until t=30s, peak at
t=80s, offered load back to baseline by t=130s):

* **no-retry**          — 25s timeout, no retries: the backlog hurts,
  then drains (the pure-queueing reference).
* **naive-retries**     — 25s timeout, 3 retries with exponential
  backoff + jitter, no breaker: every timed-out request re-enters the
  router up to 3 more times, and a timed-out attempt still occupies its
  server for the full service time.  The amplified load keeps the fleet
  saturated AFTER the offered load recedes — the metastable collapse.
* **breaker-admission** — the same retry budget behind per-replica
  circuit breakers plus admission control over the fixed full-size
  pool: breakers fail fast instead of dispatching doomed attempts,
  admission sheds the excess, the fleet recovers with the load.

The headline metric is **recovery goodput**: the completed fraction of
requests arriving at t >= 160s, 30s after the offered load returned to
a level the fleet served at ~1.0 goodput before the ramp.  The
acceptance gate (ISSUE 8): both variants start healthy
(pre-ramp goodput >= 0.95), naive retries stay collapsed in the
recovery window, and breaker-admission recovers
(>= naive + ``GATE_MARGIN`` and >= 0.9 absolute).  Writes
experiments/artifacts/resilience.json (rendered into EXPERIMENTS.md
§Resilience by experiments/generate_experiments.py).

Run:  PYTHONPATH=src python benchmarks/bench_resilience.py \
          [--seeds 12] [--smoke] [--no-artifact]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from dataclasses import replace

import numpy as np

from repro.core.balancer import make_policy
from repro.core.campaign import stack_clusters
from repro.core.rng import rng_seed
from repro.core.scenarios import get_scenario
from repro.core.simulator import SimStepper, _build_cluster
from repro.launch.compile_cache import enable_compile_cache

VARIANTS = ("no-retry", "naive-retries", "breaker-admission")
#: variant -> (scenario, resilience override applied to the spec)
_SPEC_OF = {"no-retry": ("retry-storm", dict(max_retries=0)),
            "naive-retries": ("retry-storm", None),
            "breaker-admission": ("breaker-saves-retry-storm", None)}
#: the ramp timeline (scenarios._RETRY_STORM): baseline before PRE_T,
#: offered load back to baseline at RECEDE_T, recovery window beyond
PRE_T, RECEDE_T, RECOVERY_T = 30.0, 130.0, 160.0
WINDOW_S = 40.0
GATE_MARGIN = 0.15
ARTIFACT = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "artifacts", "resilience.json")


def run_cell(variant: str, seeds, policy: str = "perf_aware", **overrides):
    """One variant over the stacked seed grid (serial reference path —
    the compiled kernel agrees to <= 1e-5, tests/test_resilience.py)."""
    name, res_patch = _SPEC_OF[variant]
    spec = get_scenario(name)
    if res_patch:
        overrides = dict(overrides,
                         resilience=replace(spec.resilience, **res_patch))
    cfgs = [spec.compile(seed=s, **overrides) for s in seeds]
    stacked = stack_clusters([_build_cluster(c) for c in cfgs])
    pol = make_policy(policy, seed=rng_seed(cfgs[0].seed, "policy"),
                      seed_blocks=[(rng_seed(c.seed, "policy"), c.n_trials)
                                   for c in cfgs])
    s = SimStepper(stacked, pol).run()
    ok = np.isfinite(s["rtts"])          # completed within its deadline
    t = s["req_t"]
    pre, rec = t < PRE_T, t >= RECOVERY_T
    timeline = []
    for lo in np.arange(0.0, float(t.max()) + WINDOW_S, WINDOW_S):
        m = (t >= lo) & (t < lo + WINDOW_S)
        if m.any():
            timeline.append([float(lo), int(m.sum()),
                             float(ok[:, m].mean())])
    return {
        "goodput": float(s["goodput"].mean()),
        "pre_goodput": float(ok[:, pre].mean()),
        "recovery_goodput": float(ok[:, rec].mean()),
        "timeout_rate": float(s["timeout_rate"].mean()),
        "shed_rate": float(s["shed_rate"].mean()),
        "attempts_per_req": float(s["attempts_per_req"].mean()),
        "wasted_work_s": float(s["wasted_work_s"].mean()),
        "p95_rtt": float(np.nanmean(s["p95_rtt"])),
        "n_recovery": int(rec.sum()),
        "timeline": timeline,
    }


def collapse_prevented(cells: dict, margin: float = GATE_MARGIN) -> bool:
    """The study's claim, as a predicate: both clients start healthy,
    naive retries stay collapsed after the load recedes, breakers +
    admission recover."""
    naive, brk = cells["naive-retries"], cells["breaker-admission"]
    healthy_start = min(naive["pre_goodput"], brk["pre_goodput"]) >= 0.95
    recovers = brk["recovery_goodput"] >= 0.9
    separated = brk["recovery_goodput"] \
        >= naive["recovery_goodput"] + margin
    return healthy_start and recovers and separated


def smoke_parity(rtol: float = 1e-5):
    """The smoke gate's parity half: the compiled kernel must track the
    serial reference through the storm scenarios on a reduced grid
    (the full registry sweep lives in tests/test_resilience.py)."""
    from repro.core.campaign import SUMMARY_STATS, run_scenario
    kw = dict(seeds=(0, 1), n_trials=2, n_requests=60,
              policies=("perf_aware", "least_conn"), include_oracle=False)
    for name in ("retry-storm", "breaker-saves-retry-storm"):
        serial = run_scenario(name, backend="serial", **kw)
        compiled = run_scenario(name, backend="auto", **kw)
        for pol in serial:
            for k in SUMMARY_STATS:
                a = np.asarray(compiled[pol].per_seed[k], float)
                b = np.asarray(serial[pol].per_seed[k], float)
                both_nan = np.isnan(a) & np.isnan(b)
                np.testing.assert_allclose(
                    np.where(both_nan, 0.0, a), np.where(both_nan, 0.0, b),
                    rtol=rtol, atol=1e-7, err_msg=f"{name}/{pol}/{k}")


def bench(seeds, **overrides):
    t0 = time.perf_counter()
    cells = {v: run_cell(v, seeds, **overrides) for v in VARIANTS}
    return cells, time.perf_counter() - t0


def table(cells) -> str:
    rows = [("variant", "pre", "overall", "recovery", "tout", "shed",
             "att/req", "wasted s")]
    for v in VARIANTS:
        r = cells[v]
        rows.append((v, f"{r['pre_goodput']:.3f}", f"{r['goodput']:.3f}",
                     f"{r['recovery_goodput']:.3f}",
                     f"{r['timeout_rate']:.3f}", f"{r['shed_rate']:.3f}",
                     f"{r['attempts_per_req']:.2f}",
                     f"{r['wasted_work_s']:.0f}"))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    return "\n".join("  ".join(c.ljust(w) for c, w in zip(r, widths))
                     for r in rows)


def _write_artifact(cells, seeds, wall_s):
    os.makedirs(os.path.dirname(ARTIFACT), exist_ok=True)
    payload = {"seeds": list(seeds), "wall_s": wall_s,
               "gate_margin": GATE_MARGIN,
               "windows": {"pre_t": PRE_T, "recede_t": RECEDE_T,
                           "recovery_t": RECOVERY_T,
                           "window_s": WINDOW_S},
               "table": cells,
               "collapse_prevented": collapse_prevented(cells)}
    with open(ARTIFACT, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {os.path.abspath(ARTIFACT)}")


def run(seeds=tuple(range(12))):
    """Harness contract (benchmarks/run.py): CSV rows per variant."""
    cells, wall = bench(tuple(seeds))
    return [(f"resilience_{v}", cells[v]["recovery_goodput"],
             f"goodput={cells[v]['goodput']:.3f};"
             f"att={cells[v]['attempts_per_req']:.2f}")
            for v in VARIANTS]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced grid + hard collapse gate (CI)")
    ap.add_argument("--no-artifact", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        seeds, overrides = tuple(range(4)), dict(n_trials=4)
        smoke_parity()
        print("parity: compiled == serial within 1e-5 on the storm "
              "scenarios")
    else:
        seeds, overrides = tuple(range(args.seeds)), {}
    cells, wall = bench(seeds, **overrides)

    print(f"retry-storm study: {{{', '.join(VARIANTS)}}} x "
          f"{len(seeds)} seeds ({wall:.1f}s, one stacked lockstep pass "
          f"per variant)")
    print(table(cells))
    naive, brk = cells["naive-retries"], cells["breaker-admission"]
    print(f"\nrecovery window (t >= {RECOVERY_T:.0f}s, offered load back "
          f"to baseline at {RECEDE_T:.0f}s, n={naive['n_recovery']}):")
    print(f"  naive retries stay at {naive['recovery_goodput']:.3f} "
          f"goodput; breakers + admission at "
          f"{brk['recovery_goodput']:.3f}")

    if not args.smoke and not args.no_artifact:
        _write_artifact(cells, seeds, wall)

    assert collapse_prevented(cells), (
        f"collapse-vs-recovery gate failed: pre="
        f"({naive['pre_goodput']:.3f}, {brk['pre_goodput']:.3f}), "
        f"recovery naive={naive['recovery_goodput']:.3f} "
        f"breaker={brk['recovery_goodput']:.3f} "
        f"(need breaker >= 0.9 and >= naive + {GATE_MARGIN})")
    print("\nOK: naive retries collapse after the load recedes; "
          "breakers + admission control prevent it")


if __name__ == "__main__":
    main()
