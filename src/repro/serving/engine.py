"""Batched serving engine: wave-based prefill + decode over a slot cache.

One engine == one replica.  RTT is gateway-to-gateway (enqueue -> response),
matching the paper's definition (queue wait included).  Each engine exports
monitoring metrics (queue depth, active batch, token rate, KV occupancy,
node load) to its node's MetricsStore — the signals Morpheus predictors
learn from.  ``slowdown`` models heterogeneous/contended nodes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import span
from repro.models import model as M
from repro.monitoring.metrics import MetricsStore, SimClock

#: one jitted prefill and decode for the process: jit keys its compiled
#: programs on the static (cfg, cache_len), so every engine serving the
#: same model at the same max_seq shares one compilation of each
jit_prefill = jax.jit(M.prefill, static_argnames=("cfg", "cache_len"))
jit_decode = jax.jit(M.decode_step, static_argnames=("cfg",))


@dataclass
class Request:
    rid: int
    tokens: np.ndarray              # (prompt_len,)
    max_new_tokens: int = 16
    t_enqueue: float = 0.0
    t_start: Optional[float] = None     # when a wave took it
    t_done: Optional[float] = None
    output: Optional[np.ndarray] = None

    @property
    def rtt(self) -> Optional[float]:
        return None if self.t_done is None else self.t_done - self.t_enqueue


class ServingEngine:
    def __init__(self, cfg, params, *, node: str = "node-0", max_batch: int = 4,
                 max_seq: int = 256, slowdown: float = 0.0,
                 clock: Optional[SimClock] = None,
                 store: Optional[MetricsStore] = None, seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.node = node
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.slowdown = slowdown       # artificial per-step delay (s)
        self.clock = clock or SimClock(simulated=False)
        self.store = store or MetricsStore(clock=self.clock)
        self.queue: List[Request] = []
        self.done: List[Request] = []
        self.rng = np.random.default_rng(seed)
        self._tok_count = 0
        self._t_last = self.clock.now()
        # capacity plane (DESIGN.md §12): an inactive engine takes no
        # NEW work but still drains its queue; busy_s feeds the pool's
        # replica-seconds-busy side of the waste ledger
        self.active = True
        self.busy_s = 0.0

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        req.t_enqueue = self.clock.now()
        self.queue.append(req)
        self._export()

    @span("scrape")
    def _export(self):
        active = 0
        self.store.scrape({
            "queue_depth": float(len(self.queue)),
            "active_batch": float(active),
            "token_rate": self._rate(),
            "slowdown": self.slowdown,
        })

    def _rate(self) -> float:
        now = self.clock.now()
        dt = max(now - self._t_last, 1e-6)
        r = self._tok_count / dt
        return float(r)

    # ------------------------------------------------------------------
    def step_wave(self) -> List[Request]:
        """Serve one wave: take up to max_batch queued requests, prefill,
        decode to completion, return finished requests."""
        if not self.queue:
            return []
        t_wave0 = self.clock.now()
        wave = self.queue[: self.max_batch]
        self.queue = self.queue[self.max_batch:]
        for r in wave:
            r.t_start = t_wave0
        B = len(wave)
        plen = max(len(r.tokens) for r in wave)
        toks = np.zeros((B, plen), np.int32)
        for i, r in enumerate(wave):
            toks[i, -len(r.tokens):] = r.tokens     # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.family == "vlm":
            batch["vision_embeds"] = jnp.zeros(
                (B, self.cfg.num_frontend_tokens, self.cfg.d_model),
                jnp.bfloat16)
        if self.cfg.family == "encdec":
            batch["enc_frames"] = jnp.zeros((B, 8, self.cfg.d_model),
                                            jnp.bfloat16)
        with span("prefill"):               # dispatch until on the host
            logits, cache = jit_prefill(self.params, cfg=self.cfg,
                                        batch=batch, cache_len=self.max_seq)
            tok = np.asarray(
                jnp.argmax(logits[:, : self.cfg.vocab_size], -1), np.int32)
        n_new = max(r.max_new_tokens for r in wave)
        outs = [[] for _ in range(B)]
        for i in range(B):
            outs[i].append(tok[i])
        for _ in range(n_new - 1):
            with span("decode"):            # dispatch until on the host
                logits, cache = jit_decode(self.params, cfg=self.cfg,
                                           cache=cache,
                                           tokens=jnp.asarray(tok[:, None]))
                tok = np.asarray(
                    jnp.argmax(logits[:, : self.cfg.vocab_size], -1),
                    np.int32)
            for i in range(B):
                outs[i].append(tok[i])
            self._tok_count += B
            if self.slowdown:
                with span("slowdown"):
                    self.clock.advance(self.slowdown)
            self._export()
        jax.block_until_ready(logits)
        now = self.clock.now()
        self.busy_s += now - t_wave0       # wall/clock time spent serving
        for i, r in enumerate(wave):
            r.t_done = now
            r.output = np.array(outs[i][: r.max_new_tokens])
            self.done.append(r)
        self._export()
        return wave

    def pending(self) -> int:
        return len(self.queue)
