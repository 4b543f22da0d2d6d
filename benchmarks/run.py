"""Benchmark harness: one bench per paper table/figure + kernels + roofline.

Prints ``name,us_per_call,derived`` CSV rows (per the repo contract).
Every artifact a bench writes is stamped with :func:`manifest` so the
perf trajectory (``BENCH_*.json``, ``campaign.json``,
``telemetry.json``) stays reconstructible across PRs.
"""
from __future__ import annotations

import sys
import time
import traceback


def manifest() -> dict:
    """Provenance stamp for bench artifacts: commit, UTC timestamp, jax
    version, and device topology.  Degrades field-by-field (no git, no
    jax) rather than failing the bench."""
    import datetime
    import os
    import subprocess
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__))).stdout.strip()
    except OSError:
        commit = ""
    out = {
        "commit": commit or None,
        "timestamp": datetime.datetime.now(datetime.timezone.utc)
                     .isoformat(timespec="seconds"),
    }
    try:
        import jax
        devs = jax.devices()
        out.update(jax_version=jax.__version__,
                   backend=devs[0].platform,
                   device_count=len(devs),
                   devices=[str(d) for d in devs])
    except Exception:
        out.update(jax_version=None, backend=None, device_count=0,
                   devices=[])
    return out


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_adaptation, bench_binning, bench_breakdown,
                            bench_campaign, bench_capacity,
                            bench_correlations, bench_covariability,
                            bench_kernels, bench_load_balancing,
                            bench_online, bench_overhead,
                            bench_prediction_plane, bench_resilience,
                            bench_selection, bench_simcore,
                            bench_state_scaling, bench_telemetry)
    from benchmarks import roofline

    benches = [
        ("fig4", bench_correlations.run),
        ("fig5", bench_selection.run),
        ("fig6/table4", bench_adaptation.run),
        ("fig7", bench_overhead.run),
        ("fig8", bench_binning.run),
        ("fig9", bench_breakdown.run),
        ("fig10", bench_state_scaling.run),
        ("plane", bench_prediction_plane.run),
        ("fig11", bench_load_balancing.run),
        ("campaign", bench_campaign.run),
        ("simcore", bench_simcore.run),
        ("online", bench_online.run),
        ("capacity", bench_capacity.run),
        ("resilience", bench_resilience.run),
        ("telemetry", bench_telemetry.run),
        ("table5", bench_covariability.run),
        ("kernels", bench_kernels.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for label, fn in benches:
        t0 = time.time()
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{label}_FAILED,0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
        print(f"_elapsed[{label}],{(time.time()-t0)*1e6:.0f},wall",
              flush=True)

    # roofline rows (from the dry-run artifact, if present)
    try:
        for r in roofline.full_table():
            dom_s = {"compute": r["compute_s"], "memory": r["memory_s"],
                     "collective": r["collective_s"]}[r["dominant"]]
            print(f"roofline[{r['arch']}|{r['shape']}],{dom_s*1e6:.0f},"
                  f"dominant={r['dominant']};useful={r['useful_ratio']:.3f};"
                  f"mfu_bound={r['mfu_bound']:.3f}")
    except FileNotFoundError:
        print("roofline_SKIPPED,0,run repro.launch.dryrun first")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
