"""Production serving launcher: replicas + Morpheus router.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-vl-7b --smoke \
      --replicas 3 --requests 24 --policy perf_aware

Requests are timed on the wall clock, so their RTTs hold the device
time as well as each replica's per-step ``slowdown``.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine
from repro.serving.router import MorpheusRouter

#: prompt length of the launcher's synthetic requests
PROMPT_LEN = 8
MAX_BATCH = 4
MAX_SEQ = 64


def init_params(cfg, seed: int = 0):
    """Seeded random weights, built on the default device by one jitted
    program."""
    return jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


def make_requests(rng, n: int, max_new_tokens: int, start: int = 0):
    return [Request(rid=start + i, tokens=rng.integers(0, 100, PROMPT_LEN),
                    max_new_tokens=max_new_tokens)
            for i in range(n)]


def build_fleet(cfg, params, *, replicas: int = 3,
                policy: str = "perf_aware", max_new_tokens: int = 4,
                rng=None) -> MorpheusRouter:
    """Heterogeneous replicas (per-decode-step slowdowns spread over
    0-80 ms) sharing one parameter set behind a MorpheusRouter.

    A throwaway engine first serves one wave of every size, so the
    shared prefill and decode are compiled before any request is timed.
    Each replica then serves one bootstrap request, whose RTT seeds the
    router's knowledge base."""
    rng = rng if rng is not None else np.random.default_rng(0)
    warm = ServingEngine(cfg, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ)
    warm_rng = np.random.default_rng(0)
    for b in range(1, MAX_BATCH + 1):
        for r in make_requests(warm_rng, b, max_new_tokens):
            warm.submit(r)
        warm.step_wave()
    slow = np.linspace(0.0, 0.08, replicas)
    engines = [ServingEngine(cfg, params, node=f"node-{i}",
                             max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                             slowdown=float(s))
               for i, s in enumerate(slow)]
    router = MorpheusRouter(engines, policy=policy)
    for rep in engines:
        rep.submit(make_requests(rng, 1, max_new_tokens, start=-1)[0])
        done = rep.step_wave()
        router.kb.put("serve", rep.node, rep.clock.now(),
                      done[0].rtt or 0.1)
    return router


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--policy", default="perf_aware",
                    choices=["perf_aware", "round_robin", "random",
                             "least_conn"])
    ap.add_argument("--max-new-tokens", type=int, default=4)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke).resolve(tp=1)
    rng = np.random.default_rng(0)
    router = build_fleet(cfg, init_params(cfg), replicas=args.replicas,
                         policy=args.policy,
                         max_new_tokens=args.max_new_tokens, rng=rng)
    reqs = make_requests(rng, args.requests, args.max_new_tokens)
    for r in reqs:
        router.route(r)
    router.drain()
    rtts = np.array([r.rtt for r in reqs])
    print(f"[serve] {cfg.name} policy={args.policy} "
          f"mean_rtt={rtts.mean():.3f}s p95={np.percentile(rtts, 95):.3f}s")


if __name__ == "__main__":
    main()
