"""Calibration runs on the chip: the readings that the limits of
``correct`` are set from, the knee sweep of the served cells, and a
look at the profiler trace's layout.  The benchmark's own runs never
call this.

    python chipbench/calibrate.py readings --workload W --seeds 1,2,3 \
        --seconds 8 [--control]
    python chipbench/calibrate.py sweep --workload W --rates 2,3,4 \
        --seconds 20
    python chipbench/calibrate.py padding --workload W --seed 1
    python chipbench/calibrate.py trace-probe --out DIR

``readings`` runs the cell on each seed in this one process and prints
each compared number (with ``--control``, also the control's: the
simulator with its float64 state in float32, the served model's
reference in float8).  ``sweep`` serves the cell's traffic at each rate
on one fleet and prints how far the queue grew.  ``padding`` serves a
prompt alone and in a wave with a longer one and prints each one's gap
against the reference.  ``trace-probe`` traces a small program with the
segment-sum kernel and prints the trace's planes, lines and events.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def log(obj):
    print(json.dumps(obj, default=float), flush=True)


def readings(cell, devs, seeds, seconds, control):
    from chipbench.harness import kind_module, read_metrics
    from chipbench.trace import Tracer
    for s in seeds:
        t0 = time.perf_counter()
        out = kind_module(cell).run(cell, seed=s, seconds=seconds,
                                    tracer=Tracer(False), t0=t0, devs=devs,
                                    control=control)
        rec = dict(out.record, setup_s=out.setup_s, trace=None)
        log({"seed": s, "attempted": out.attempted, "failed": out.failed,
             "checks": {c.name: c.value for c in out.checks},
             "metrics": {k: v["value"] for k, v in read_metrics(
                 cell.end_to_end, rec).items()},
             "memory_peak_bytes": out.memory_peak_bytes,
             "wall_s": time.perf_counter() - t0})


def sweep(cell, devs, rates, seconds, seed):
    """The cell's traffic at each rate, one fleet for all."""
    import numpy as np

    from chipbench import gen, readers
    from chipbench.kinds import serve
    from chipbench.trace import Tracer
    conf, traffic = cell.config, cell.traffic
    mcfg = serve.model_config(conf, cell.config_name)
    params = serve.make_weights(mcfg, seed)
    most = gen.open_loop(dict(traffic, rate_per_s=max(rates)), seed,
                         seconds, mcfg.vocab_size)      # the longest outputs
    router, engines = serve.build_fleet(
        mcfg, params, conf, most, int(traffic["new_tokens"]["min"]), seed)
    for r in rates:
        sched = gen.open_loop(dict(traffic, rate_per_s=r), seed, seconds,
                              mcfg.vocab_size)
        reqs, t_wall, routed_at, route_s, busy, end, _, _ = serve.drive(
            router, engines, sched, seconds, Tracer(False), seconds)
        done = np.array([np.inf if q.t_done is None else q.t_done - t_wall
                         for q in reqs])
        rtt = done - sched.due
        half = sched.due < seconds / 2
        log({"rate_per_s": r, "requests": len(reqs),
             "unfinished": int(np.isinf(done).sum()),
             "p50_s": float(np.percentile(rtt, 50)),
             "p95_s": float(np.percentile(rtt, 95)),
             "rtt_first_half_s": float(rtt[half].mean()),
             "rtt_second_half_s": float(rtt[~half].mean()),
             "drain_after_close_s": float(end - seconds),
             "late_route_max_s": float(np.nanmax(routed_at - sched.due)),
             "tokens_per_s": readers.tokens_per_s(sched.new_tokens, done,
                                                  seconds),
             "busy_share": busy / end,
             "routed": np.bincount(router.routed[-len(reqs):],
                                   minlength=len(engines)).tolist()})


def padding(cell, devs, seed):
    """A prompt served alone, then left-padded in one wave with a prompt
    twice as long: each one's gap against the reference."""
    import numpy as np

    from chipbench import gen
    from chipbench.kinds import serve
    from repro.serving.engine import Request, ServingEngine
    conf = cell.config
    mcfg = serve.model_config(conf, cell.config_name)
    params = serve.make_weights(mcfg, seed)
    rng = gen.rng_for(seed, "padding")
    short = int(cell.traffic["prompt"]["median"])
    new = 8
    eng = ServingEngine(mcfg, params, max_batch=2, max_seq=2 * short + new)
    for label, lens in (("alone", [short]), ("mixed", [short, 2 * short])):
        reqs = [Request(rid=i, tokens=rng.integers(0, mcfg.vocab_size, n,
                                                   dtype=np.int32),
                        max_new_tokens=new) for i, n in enumerate(lens)]
        for q in reqs:
            eng.submit(q)
        eng.step_wave()
        gaps = [serve.logit_gap(params, conf, reqs, [i])[0]
                for i in range(len(reqs))]
        log({"wave": label, "prompt_lens": lens, "gaps": gaps})


def trace_probe(out_dir):
    """A small traced program with the segment-sum kernel and the host
    spans the reduction reads; prints the layout and keeps the file."""
    import glob
    import shutil

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    from chipbench import trace
    from repro.kernels.segment_sum import segment_sum
    vals = jnp.ones((16, 256), jnp.float32)
    ids = (jnp.arange(16 * 256, dtype=jnp.int32) % 40).reshape(16, 256)
    f = jax.jit(lambda v, i: segment_sum(v, i, 40).sum())
    g = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(vals, ids).block_until_ready()
    g(x).block_until_ready()
    tmp = os.path.join(out_dir, "raw")
    jax.profiler.start_trace(tmp)
    with TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(2):
            with TraceAnnotation("route"):
                f(vals, ids).block_until_ready()
            time.sleep(0.01)
            with TraceAnnotation("drain"):
                g(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out_dir, "probe.xplane.pb"))
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = [len(evs), sorted({e.name for e in evs})[:12],
                                [int(e.start_ns) for e in evs[:2]]]
        log({"plane": plane.name, "lines": lines})
    log({"reduced": trace.reduce_file(path)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("readings", "sweep", "padding",
                                     "trace-probe"))
    ap.add_argument("--workload")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, ".checkout",
                                                  "probe"))
    args = ap.parse_args(argv)
    from chipbench import harness
    if args.mode == "trace-probe":
        harness.require_chips(1)
        os.makedirs(args.out, exist_ok=True)
        trace_probe(args.out)
        return 0
    cell = harness.load_cell(args.workload)
    devs = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    if args.mode == "readings":
        readings(cell, devs, [int(s) for s in args.seeds.split(",")],
                 args.seconds, args.control)
    elif args.mode == "sweep":
        sweep(cell, devs, [float(r) for r in args.rates.split(",")],
              args.seconds, args.seed)
    else:
        padding(cell, devs, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
