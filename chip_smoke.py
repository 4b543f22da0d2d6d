"""Smoke run of the system's two device paths on a TPU.

    python chip_smoke.py              # one chip: simulator, then serving
    python chip_smoke.py --chips 4    # four chips: the trial-sharded path

One chip:

1. **Simulator.**  Three campaign cells at ``bench_simcore.LARGE``
   (250 nodes, 5 apps x 200 replicas, 1000 requests), 8 seeds x 32
   trials, through ``run_scenario(..., backend="compiled")``:
   ``baseline``/``least_conn``, ``churn``/``perf_aware`` (the Pallas
   recount) and ``drift-fallback``/``perf_aware`` (the closed-loop ridge
   solve; its 4 apps get 250 replicas each, so R = 1000 there too).
   Each is checked against the serial ``SimStepper`` on seed 0.
2. **Serving.**  ``minicpm3-4b`` at its published widths with seeded
   random weights: one parameter set on the chip behind the three
   heterogeneous replicas of ``repro.launch.serve`` and
   ``MorpheusRouter(policy="perf_aware")``, 8 requests of 8 prompt
   tokens and 8 new tokens, timed on the wall clock.

Four chips: the ``baseline`` cell at LARGE for ``least_conn`` and
``perf_aware`` with the trial axis sharded by ``shard_map``, against the
same cells on one device (``force_single=True``).

Every phase runs in this one process, which holds the chip.  Times
printed here are smoke timings, not benchmark numbers.  The last line of
standard output is one JSON object naming the device; without a TPU the
script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: simulator cells of the one-chip run: (scenario, policy, closed loop)
SIM_CELLS = (("baseline", "least_conn", False),
             ("churn", "perf_aware", False),
             ("drift-fallback", "perf_aware", True))
SEEDS = tuple(range(8))
TRIALS_PER_SEED = 32
SERVE_ARCH = "minicpm3-4b"
N_REQUESTS = 8
NEW_TOKENS = 8


def log(msg: str):
    print(msg, flush=True)


def sim_phase(shape, seeds, n_trials, cells=SIM_CELLS):
    """Run each campaign cell compiled (cold, then warm) and hold its
    seed-0 stats to the serial stepper's."""
    from benchmarks.bench_campaign import CLOSED_LOOP_TOL, PARITY_TOL
    from benchmarks.bench_simcore import _drift
    from repro.core import simcore
    from repro.core.campaign import (LAST_PHASES, SUMMARY_STATS,
                                     run_scenario)
    from repro.core.scenarios import get_scenario

    def n_apps(scen):
        return len(get_scenario(scen).compile(seed=0, **shape).apps)

    for scen, pol, closed_loop in cells:
        # a scenario with fewer apps than the baseline's gets more
        # replicas per app, so that every cell holds the same R
        per_app = shape["n_replicas_per_app"] * n_apps("baseline") \
            // n_apps(scen)
        over = dict(shape, n_trials=n_trials, n_replicas_per_app=per_app)
        kw = dict(policies=[pol], include_oracle=False, **over)
        t0 = time.perf_counter()
        run_scenario(scen, seeds=seeds, backend="compiled", **kw)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = run_scenario(scen, seeds=seeds, backend="compiled", **kw)[pol]
        warm = time.perf_counter() - t0
        split = ", ".join(f"{k} {v:.3f} s" for k, v in LAST_PHASES.items())
        ref = run_scenario(scen, seeds=seeds[:1], backend="serial",
                           **kw)[pol]
        drift = _drift({k: got.per_seed[k][:1] for k in SUMMARY_STATS},
                       ref.per_seed)
        tol = CLOSED_LOOP_TOL if closed_loop else PARITY_TOL
        cfg = get_scenario(scen).compile(seed=0, **over)
        log(f"sim {scen}/{pol}: T={len(seeds) * n_trials} "
            f"R={cfg.n_replicas_per_app * len(cfg.apps)} J={cfg.n_requests} "
            f"segsum={simcore._segsum_backend()} drift={drift!r} "
            f"(limit {tol}); smoke timing, not a benchmark: "
            f"cold {cold:.3f} s, warm {warm:.3f} s ({split})")
        if not drift <= tol:
            raise AssertionError(
                f"{scen}/{pol}: compiled vs serial drift {drift!r} > {tol}")


def shard_phase(shape, seeds, n_trials, policies=("least_conn",
                                                  "perf_aware")):
    """The ``baseline`` cell with trials sharded over every device,
    against the same cell on one device."""
    from benchmarks.bench_campaign import PARITY_TOL
    from benchmarks.bench_simcore import _drift, _stack
    from repro.core import simcore

    stacked, blocks, _ = _stack(seeds, n_trials, **shape)
    for pol in policies:
        runs = {}
        for single in (False, True):
            times = []
            for _ in range(2):                   # cold, warm
                t0 = time.perf_counter()
                out = simcore.run_compiled(stacked, pol, seed_blocks=blocks,
                                           force_single=single)
                times.append(time.perf_counter() - t0)
            runs[single] = out
            log(f"shard baseline/{pol}: backend={out['simcore_backend']} "
                f"T={stacked.cfg.n_trials}; smoke timing, not a benchmark: "
                f"cold {times[0]:.3f} s, warm {times[1]:.3f} s")
        if runs[False]["simcore_backend"] != "shard_map":
            raise AssertionError(
                f"{pol}: expected the shard_map path, got "
                f"{runs[False]['simcore_backend']}")
        drift = _drift(runs[False], runs[True])
        log(f"shard baseline/{pol}: shard_map vs force_single "
            f"drift={drift!r} (limit {PARITY_TOL})")
        if not drift <= PARITY_TOL:
            raise AssertionError(f"{pol}: shard_map drift {drift!r}")


def serve_phase(cfg, n_requests=N_REQUESTS, new_tokens=NEW_TOKENS):
    """Serve seeded requests through the router and check every output
    and the logits of the shared prefill and decode programs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import (build_fleet, init_params,
                                    make_requests)
    from repro.serving.engine import jit_decode, jit_prefill

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"serve {cfg.name}: {n_params} parameters, init "
        f"{time.perf_counter() - t0:.3f} s")
    for x in jax.tree.leaves(params):
        if x.devices() != {dev}:
            raise AssertionError(f"parameter on {x.devices()}, not {dev}")

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    router = build_fleet(cfg, params, policy="perf_aware",
                         max_new_tokens=new_tokens, rng=rng)
    log(f"serve set-up (compiles, then one bootstrap request per "
        f"replica): "
        f"{time.perf_counter() - t0:.3f} s")
    reqs = make_requests(rng, n_requests, new_tokens)
    t0 = time.perf_counter()
    for r in reqs:
        router.route(r)
    router.drain()
    wall = time.perf_counter() - t0
    for r in reqs:
        out = r.output
        if out is None or out.shape != (new_tokens,) or out.min() < 0 \
                or out.max() >= cfg.vocab_size:
            raise AssertionError(f"request {r.rid}: bad output {out}")
    log(f"serve routed to replicas {router.routed[-n_requests:]}; "
        f"RTTs s {[round(r.rtt, 4) for r in reqs]}; smoke timing, not a "
        f"benchmark: {n_requests} requests in {wall:.3f} s")

    toks = jnp.asarray(np.stack([r.tokens for r in reqs[:4]]), jnp.int32)
    logits, cache = jit_prefill(params, cfg=cfg, batch={"tokens": toks},
                                cache_len=router.replicas[0].max_seq)
    nxt = jnp.argmax(logits[:, :cfg.vocab_size], -1)[:, None]
    logits2, _ = jit_decode(params, cfg=cfg, cache=cache,
                            tokens=nxt.astype(jnp.int32))
    for name, lg in (("prefill", logits), ("decode", logits2)):
        if not bool(jnp.isfinite(lg).all()):
            raise AssertionError(f"{name} logits are not finite")
    stats = dev.memory_stats() or {}
    log(f"serve logits finite; peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the trial-sharded simulator path")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devs[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} devices", file=sys.stderr)
        return 1
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.bench_simcore import LARGE
    from repro.configs.base import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {devs[0].device_kind} x{len(devs)}, jax {jax.__version__},"
        f" compile cache {enable_compile_cache()}")
    if args.chips == 4:
        shard_phase(LARGE, SEEDS, TRIALS_PER_SEED)
    else:
        sim_phase(LARGE, SEEDS, TRIALS_PER_SEED)
        gc.collect()
        jax.clear_caches()          # the simulator's programs, before
        serve_phase(get_config(SERVE_ARCH).resolve(tp=1))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
