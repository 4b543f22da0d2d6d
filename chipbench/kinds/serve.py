"""Cells of the served fleet: replicas of one model behind
``MorpheusRouter``, driven open loop.

What depends on the model's architecture (its ``ModelConfig``, how each
weight is drawn, its FLOPs, its smoke size, its plain reference) comes
from ``chipbench/archs/<arch>.py``, which the configuration's ``arch``
key names; this module holds none of it.

Set-up makes the weights on the device from the seed in one jitted
call, builds the fleet as ``launch/serve.build_fleet`` does with the
cell's shapes (replicas sharing one parameter set, each with its own
per-decode-step slowdown), serves one wave of every batch size at every
prompt length the schedule holds (which compiles or loads exactly the
cell's prefill and decode programs) and one bootstrap request per
replica, of the traffic's shortest output, whose RTT seeds the router's
knowledge base.

The window: every request that is due is routed (``route``), then the
fleet is drained (``drain``), and so on.  A request that falls due
during a drain is routed when it returns; its RTT still counts from its
due time.  The loop runs to the window's close even when every request
is done, and waits for the requests due in the window up to a minute
past it.

The check: every request due in the window completed with as many
tokens as it asked for; and for a sample of them, drawn from the seed
with the longest among them, the plain reference runs over the prompt
and the served tokens, and the widest gap by which a served token's
logit lies below the reference's best at that position (in units of
the reference logits' spread there) is compared with its limit.  Tokens
are greedy, so the gap is zero up to rounding.

The record carries the program's counters without naming them: the
engines' public numeric attributes and the span totals at the window's
start, the trace's start and stop, and the loop's end (``drive``), so
that a counter the program adds reaches a metric reader of its own.
"""
from __future__ import annotations

import gc
import numbers
import sys
import time
from typing import Dict, List

import numpy as np

from chipbench import archs, gen
from chipbench.harness import Cell, Check, Outcome, memory_peak_bytes

#: seconds past the window's close that due requests are waited for
GRACE_S = 60.0


#: the architecture module that made each ModelConfig, whose ``draw``
#: ``make_weights`` uses
_MADE_BY: Dict[object, object] = {}


def model_config(conf: dict, name: str = "served"):
    """The program's ``ModelConfig`` for the configuration's keys, from
    the architecture module that the configuration names."""
    arch = archs.of(conf)
    mcfg = arch.model_config(conf, name)
    if _MADE_BY.setdefault(mcfg, arch) is not arch:
        raise ValueError(f"{_MADE_BY[mcfg].__name__} and {arch.__name__} "
                         f"make the same ModelConfig {name!r}")
    return mcfg


def make_weights(mcfg, seed: int):
    """Seeded random weights in the program's layout and dtypes, made on
    the device by one jitted call; each leaf drawn by the ``draw`` of the
    architecture module that made ``mcfg`` (``model_config``)."""
    import jax
    from repro.models import model as M
    draw = _MADE_BY[mcfg].draw
    shapes = jax.eval_shape(lambda k: M.init_params(k, mcfg),
                            jax.random.PRNGKey(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]

    @jax.jit
    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        return jax.tree_util.tree_unflatten(tree, [
            draw(jax.random.fold_in(key, i), p, sd)
            for i, (p, (_, sd)) in enumerate(zip(paths, flat))])

    s = int(seed)
    return build(np.uint32(s & 0xFFFFFFFF), np.uint32((s >> 32) & 0xFFFFFFFF))


def build_fleet(mcfg, params, conf: dict, sched: gen.Schedule,
                boot_new: int, seed: int):
    """Warm exactly the cell's programs, then the replicas behind the
    router, each bootstrapped with one request."""
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.router import MorpheusRouter
    fleet = conf["fleet"]
    B = fleet["max_batch"]
    max_seq = int(sched.prompt_len.max() + sched.new_tokens.max())
    warm = ServingEngine(mcfg, params, max_batch=B, max_seq=max_seq)
    rng = gen.rng_for(seed, "warm-up")
    for plen in sorted(set(int(p) for p in sched.prompt_len)):
        for b in range(1, B + 1):
            for i in range(b):
                warm.submit(Request(rid=-1, tokens=rng.integers(
                    0, mcfg.vocab_size, plen, dtype=np.int32),
                    max_new_tokens=2))
            warm.step_wave()
    engines = [ServingEngine(mcfg, params, node=f"node-{i}", max_batch=B,
                             max_seq=max_seq, slowdown=float(s))
               for i, s in enumerate(fleet["slowdowns_s"])]
    router = MorpheusRouter(engines, policy=fleet["policy"])
    plen = int(np.bincount(sched.prompt_len).argmax())
    for rep in engines:
        rep.submit(Request(rid=-1, tokens=rng.integers(
            0, mcfg.vocab_size, plen, dtype=np.int32),
            max_new_tokens=boot_new))
        done = rep.step_wave()
        router.kb.put("serve", rep.node, rep.clock.now(),
                      done[0].rtt or 0.1)
    return router, engines


def counters(engines) -> Dict[str, float]:
    """Each engine's public numeric attributes, summed over the
    replicas: whatever counter the program's ``ServingEngine`` keeps."""
    out: Dict[str, float] = {}
    for e in engines:
        for k, v in vars(e).items():
            if not k.startswith("_") and isinstance(v, numbers.Real):
                out[k] = out.get(k, 0.0) + float(v)
    return out


def drive(router, engines, sched: gen.Schedule, seconds: float, tracer,
          trace_seconds: float):
    """The open-loop window; a traced run traces its last
    ``trace_seconds``.  Returns the requests, the window's start on the
    wall clock, each request's route time (seconds after the start), the
    route call durations, the engines' busy seconds, the loop's end,
    ``span``: the part of the window that the per-layer metrics read,
    from its start to the trace's stop (stopping a trace blocks the
    loop, and what follows would count the stall), and ``marks``: at the
    window's start (``start``), the trace's start and stop
    (``trace_start``, ``trace_stop``, in a traced run) and the loop's end
    (``end``), the time after the start (``t``), the engines'
    ``counters`` (``engines``) and the program's span totals
    (``spans``, ``repro.core.telemetry.snapshot()``)."""
    import jax
    from repro.core.telemetry import snapshot
    from repro.serving.engine import Request
    reqs = [Request(rid=i, tokens=sched.tokens[i],
                    max_new_tokens=int(sched.new_tokens[i]))
            for i in range(len(sched))]
    routed_at = np.full(len(reqs), np.nan)
    route_s: List[float] = []
    marks = {}

    def mark(name):
        marks[name] = {"t": time.time() - t0, "engines": counters(engines),
                       "spans": snapshot()}

    def busy(name):
        return (marks[name]["engines"]["busy_s"]
                - marks["start"]["engines"]["busy_s"])

    span = None
    t0 = time.time()
    mark("start")
    i = 0
    while True:
        now = time.time() - t0
        if span is None and now >= seconds - trace_seconds \
                and not tracer.active:
            tracer.start()
            if tracer.active:
                mark("trace_start")
        while i < len(reqs) and sched.due[i] <= now:
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation("route"):
                router.route(reqs[i])
            route_s.append(time.perf_counter() - a)
            routed_at[i] = time.time() - t0
            i += 1
            now = time.time() - t0
        if any(e.pending() for e in engines):
            with jax.profiler.TraceAnnotation("drain"):
                router.drain()
        elif i < len(reqs) or now < seconds:     # idle up to the close,
            nxt = sched.due[i] if i < len(reqs) else seconds  # so the
            time.sleep(max(0.0, min(nxt - now, 0.05)))  # trace is taken
        else:
            break
        now = time.time() - t0
        if tracer.active and now >= seconds:
            mark("trace_stop")
            span = {"t": now, "routes": len(route_s),
                    "busy": busy("trace_stop")}
            tracer.stop()
            print(f"trace: stopping it took {time.time() - t0 - now:.3f} s",
                  file=sys.stderr, flush=True)
        if now > seconds + GRACE_S:
            break
    end = time.time() - t0
    tracer.stop()
    mark("end")
    if span is None:
        span = {"t": min(end, seconds), "routes": len(route_s),
                "busy": busy("end")}
    return reqs, t0, routed_at, route_s, busy("end"), end, span, marks


def sample(reqs, ok: np.ndarray, seed: int, tokens: int, most: int):
    """Indices of completed requests to check: the longest, then others
    in the seed's order until ``tokens`` served tokens or ``most``."""
    done = np.flatnonzero(ok)
    if not len(done):
        return []
    lens = np.array([reqs[i].max_new_tokens for i in done])
    first = int(done[np.argmax(lens)])
    picked, n = [first], reqs[first].max_new_tokens
    for i in gen.rng_for(seed, "serve-check").permutation(done):
        if n >= tokens or len(picked) >= most:
            break
        if int(i) != first:
            picked.append(int(i))
            n += reqs[int(i)].max_new_tokens
    return picked


def gap_inputs(reqs, picked, shape=None):
    """(tokens (B, S), rows, cols, served) for the reference: each row is
    a prompt followed by its served tokens but the last, padded at the
    end; position cols[i] of row rows[i] predicts served[i].  ``shape``
    (B, S, N) pads the rows and repeats the first position up to N, so
    that every run uses one reference program."""
    seqs = [np.concatenate([reqs[i].tokens, reqs[i].output[:-1]])
            for i in picked]
    B, S, N = shape or (len(seqs), max(len(s) for s in seqs), 0)
    toks = np.zeros((B, S), np.int32)
    rows, cols, served = [], [], []
    for r, (i, s) in enumerate(zip(picked, seqs)):
        toks[r, :len(s)] = s
        p = len(reqs[i].tokens)
        for k, y in enumerate(reqs[i].output):
            rows.append(r)
            cols.append(p - 1 + k)
            served.append(int(y))
    pad = max(0, N - len(rows))
    return toks, np.array(rows + rows[:1] * pad), \
        np.array(cols + cols[:1] * pad), np.array(served + served[:1] * pad)


def logit_gap(params, conf: dict, reqs, picked, fp8: bool = False,
              shape=None):
    """Widest gap of the served tokens' logits below the reference's best
    at their positions, in units of the reference logits' standard
    deviation there, so that one limit holds at any width (``fp8``: the
    gap of the tokens the float8 control puts first)."""
    import jax.numpy as jnp
    logits_at = archs.of(conf).logits_at
    toks, rows, cols, served = gap_inputs(reqs, picked, shape)
    ref = logits_at(params, conf, toks, rows, cols)
    if fp8:
        low = logits_at(params, conf, toks, rows, cols, fp8=True)
        served = jnp.argmax(low, -1)
    got = jnp.take_along_axis(ref, jnp.asarray(served)[:, None], -1)[:, 0]
    gap = (ref.max(-1) - got) / ref.std(-1)
    return float(gap.max()), sum(len(reqs[i].output) for i in picked)


def run(cell: Cell, *, seed: int, seconds: float, tracer, t0: float,
        devs, control: bool = False) -> Outcome:
    """One run; ``control`` also reads the float8 control's gap on the
    same sample (calibration only)."""
    import jax
    conf, traffic = cell.config, cell.traffic
    mcfg = model_config(conf, cell.config_name)
    sched = gen.open_loop(traffic, seed, seconds, mcfg.vocab_size)
    params = jax.block_until_ready(make_weights(mcfg, seed))
    router, engines = build_fleet(mcfg, params, conf, sched,
                                  int(traffic["new_tokens"]["min"]), seed)
    setup_s = time.perf_counter() - t0

    reqs, t_wall, routed_at, route_s, busy, end, span, marks = drive(
        router, engines, sched, seconds, tracer,
        traffic.get("trace_seconds", seconds))
    peak = memory_peak_bytes(devs)
    del router, engines
    gc.collect()

    n = len(reqs)
    done_at = np.array([np.nan if r.t_done is None else r.t_done - t_wall
                        for r in reqs])
    started_at = np.array([np.nan if r.t_start is None
                           else r.t_start - t_wall for r in reqs])
    ok = np.array([r.output is not None and r.output.shape
                   == (r.max_new_tokens,) for r in reqs]) & ~np.isnan(done_at)
    rtt = np.where(ok, done_at - sched.due, np.inf)
    failed = int((~ok).sum())
    limits = conf["limits"]
    picked = sample(reqs, ok, seed, limits["sample_tokens"],
                    limits["sample_requests"])
    new_max = traffic["new_tokens"]["max"]
    shape = (limits["sample_requests"],
             traffic["prompt"]["max"] + new_max - 1,
             limits["sample_requests"] * new_max)
    gap, n_tok = logit_gap(params, conf, reqs, picked, shape=shape) \
        if picked else (float("inf"), 0)
    checks = [Check("served_logit_gap", gap, limits["served_logit_gap"])]
    if control and picked:
        checks.append(Check("control_served_logit_gap", logit_gap(
            params, conf, reqs, picked, fp8=True, shape=shape)[0],
            limits["served_logit_gap"]))
    late = routed_at - sched.due
    print(f"generator: routed {int(np.sum(~np.isnan(late)))} of {n} due "
          f"requests, late by median {np.nanmedian(late):.4f} s, max "
          f"{np.nanmax(late):.4f} s (a request due during a drain waits "
          f"for it); median RTT {np.median(rtt):.4f} s; loop {end:.3f} s, "
          f"engines busy {busy:.3f} s; {n_tok} served tokens checked",
          file=sys.stderr, flush=True)
    record = {
        "seconds": seconds, "rtt_s": rtt, "done_at_s": done_at,
        "prompt_len": sched.prompt_len, "new_tokens": sched.new_tokens,
        "route_s": np.asarray(route_s),
        "span": span, "config": conf, "device_kind": devs[0].device_kind,
        "t_start_s": started_at, "marks": marks,
    }
    return Outcome(setup_s=setup_s, attempted=n, failed=failed,
                   checks=checks, memory_peak_bytes=peak, record=record)
