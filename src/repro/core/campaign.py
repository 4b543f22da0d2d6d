"""Batched multi-seed campaign runner (DESIGN.md §10).

A campaign is the scenario x policy x seed grid.  The naive way to run
it — ``run_sim(spec.compile(seed=s), policy)`` in a triple Python loop —
pays the full per-request stepping loop once per grid cell.  This module
pays it once per (scenario, policy):

* **Shared cluster construction** — each scenario's per-seed clusters
  are built once and reused across every policy (the serial loop
  rebuilds them per policy).
* **Seed batching** — the per-seed clusters are stacked along the trial
  axis into ONE cluster of ``sum(n_trials)`` trials.  Every simulator
  step is already a vectorised op over that axis, and the policy
  engine's ``score(state)`` takes the same (T, C) batch axis, so one
  lockstep pass steps the whole seed grid.  This requires all seeds to
  share the arrival stream — which ``ScenarioSpec.compile`` guarantees
  via the per-scenario ``stream_seed`` (paired comparison across seeds).

Batched and serial execution are numerically interchangeable: stacking
concatenates exactly the arrays the serial runs would use (and the
per-seed records of the prediction noise, which a pass draws only when
it reads them, bit for bit as the serial runs would), stateful
policies (the RR cursor) keep per-trial state, and ``RandomChoice`` is
handed per-seed generator blocks (``seed_blocks``) so each block draws
what its serial run would.  The closed-loop scenarios ride the same
axis: the :class:`~repro.core.online.OnlineFleet` keeps all state
per-trial and retrains with batched per-trial ridge solves, so one
lockstep pass retrains the whole stacked seed grid exactly as the
per-seed serial runs would (drift scenarios included).  ``tests/test_campaign.py`` pins parity for
every registered scenario; ``benchmarks/bench_campaign.py`` measures the
speedup (>=5x on the >=8-seed grid).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.balancer import make_policy
from repro.core.rng import rng_seed
from repro.core.scenarios import ScenarioSpec, get_scenario, scenario_names
from repro.core.simulator import SimStepper, _build_cluster, _Cluster, run_sim
from repro.core.telemetry import PhaseTimer

DEFAULT_POLICIES = ("perf_aware", "least_conn", "round_robin", "random")

#: wall-time per phase of the most recent :func:`run_scenario` call
#: ("build" + one "run:<policy>" entry per lockstep pass, with its steps
#: as "run:<policy>/<step>"), refreshed per call.  The phases double as
#: ``jax.profiler`` trace annotations (see
#: :func:`~repro.core.telemetry.span`); ``bench_campaign`` folds this
#: breakdown into the campaign artifact.
LAST_PHASES: Dict[str, float] = {}

#: summary stats aggregated per seed (means over that seed's trials);
#: also the stat set the bench parity gate compares, so batched/serial
#: coverage can't drift from what the campaign aggregates.  The
#: capacity plane's (waste, shed, SLO) triple rides the same gate.
SUMMARY_STATS = ("mean_rtt", "p50_rtt", "p95_rtt", "p99_rtt",
                 "cpu_s", "mem_s", "waste", "shed_rate",
                 "slo_violation_s", "goodput", "timeout_rate")


def _resolve(scenario) -> ScenarioSpec:
    return get_scenario(scenario) if isinstance(scenario, str) else scenario


def stack_clusters(clusters: Sequence[_Cluster]) -> _Cluster:
    """Concatenate per-seed clusters along the trial axis.  The deferred
    prediction noise stacks as its list of per-seed blocks: no array.

    Shared-stream precondition: every cluster must carry the same
    request sequence (app ids and arrival times) — the lockstep pass
    advances all stacked trials through one (app, now) per step.
    """
    c0 = clusters[0]
    for c in clusters[1:]:
        if not (np.array_equal(c.req_app, c0.req_app)
                and np.array_equal(c.req_t, c0.req_t)):
            raise ValueError(
                "stacked clusters must share one arrival stream; compile "
                "the configs from a ScenarioSpec (or set stream_seed)")
        # every non-seed knob steers the lockstep pass itself (accuracy,
        # lag, cold start, churn, hedging, ...), so a mismatch would
        # silently run all seeds under clusters[0]'s knobs
        if replace(c.cfg, seed=c0.cfg.seed) != c0.cfg:
            raise ValueError(
                "stacked clusters must share every SimConfig field "
                f"except seed; got {c.cfg} vs {c0.cfg}")
    trials = [c.cfg.n_trials for c in clusters]

    def cat(attr):
        return np.concatenate([getattr(c, attr) for c in clusters], axis=0)

    # each seed drew its own interference mix -> per-trial (T, A, A)
    def cat_imat(attr):
        return np.concatenate(
            [np.broadcast_to(getattr(c, attr), (t,) + getattr(c, attr).shape)
             for c, t in zip(clusters, trials)], axis=0)

    imat = cat_imat("imat")
    failed = None if c0.failed_node is None else cat("failed_node")
    # post-drift regime arrays stack exactly like their pre-drift
    # counterparts (per-seed redraws -> per-trial matrices); the shared
    # mean_rtt_post is config-derived, so the cfg equality above already
    # guarantees it matches across seeds
    imat_post = None if c0.imat_post is None else cat_imat("imat_post")
    accel_post = None if c0.accel_post is None else cat("accel_post")
    preempted = None if c0.preempted_node is None else cat("preempted_node")
    gray_rep = None if c0.gray_rep is None else cat("gray_rep")
    group_rep = None if c0.group_rep is None else cat("group_rep")
    z_jitter = None if c0.z_jitter is None else cat("z_jitter")
    return _Cluster(
        cfg=replace(c0.cfg, n_trials=sum(trials)),
        app_of=c0.app_of, mean_rtt=c0.mean_rtt,
        cpu_req=c0.cpu_req, mem_req=c0.mem_req,
        imat=imat, node_of=cat("node_of"), accel=cat("accel"),
        req_app=c0.req_app, req_t=c0.req_t,
        z_rtt=cat("z_rtt"),
        pred_noise=sum((c.pred_noise for c in clusters), ()),
        failed_node=failed,
        imat_post=imat_post, accel_post=accel_post,
        mean_rtt_post=c0.mean_rtt_post, preempted_node=preempted,
        gray_rep=gray_rep, group_rep=group_rep, z_jitter=z_jitter)


@dataclass
class PolicyResult:
    """One (scenario, policy) cell: per-seed stats + oracle-relative %."""
    scenario: str
    policy: str
    seeds: Tuple[int, ...]
    per_seed: Dict[str, np.ndarray]          # stat -> (S,)
    n_hedged: int = 0
    inefficiency_pct: Optional[float] = None     # mean over seeds
    inefficiency_std: Optional[float] = None     # std over seeds
    p99_inefficiency_pct: Optional[float] = None
    resource_waste_pct: Optional[float] = None
    #: capacity-plane fleet telemetry (decisions, scale events, wakeups,
    #: mean utilization, ...) as plain jsonable values; None when the
    #: scenario runs without a capacity plane
    telemetry: Optional[Dict] = None

    def stat(self, name: str) -> float:
        return float(self.per_seed[name].mean())


def _jsonable(obj):
    """Numpy -> plain python, recursively (artifact-safe telemetry)."""
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    return obj


def _block_reduce(values: np.ndarray, trials: Sequence[int],
                  fn=np.mean) -> np.ndarray:
    """Reduce a per-trial array to one value per seed block."""
    edges = np.cumsum([0] + list(trials))
    return np.array([fn(values[edges[i]:edges[i + 1]])
                     for i in range(len(trials))])


def _split_per_seed(summary: Dict[str, np.ndarray],
                    trials: Sequence[int]) -> Dict[str, np.ndarray]:
    """Collapse each seed's trial block to its mean, stat by stat."""
    out = {k: _block_reduce(summary[k], trials) for k in SUMMARY_STATS}
    out["hedged"] = _block_reduce(summary["hedged_per_trial"], trials,
                                  np.sum)
    # inefficiency is defined per trial, then averaged (matching
    # scheduling_inefficiency); keep the raw per-trial arrays it needs
    out["_trial_mean_rtt"] = summary["mean_rtt"]
    out["_trial_p99_rtt"] = summary["p99_rtt"]
    out["_trial_cpu_s"] = summary["cpu_s"]
    return out


def _attach_inefficiency(res: PolicyResult, ora: PolicyResult,
                         trials: Sequence[int]):
    pm, om = res.per_seed["_trial_mean_rtt"], ora.per_seed["_trial_mean_rtt"]
    pt, ot = res.per_seed["_trial_p99_rtt"], ora.per_seed["_trial_p99_rtt"]
    pc, oc = res.per_seed["_trial_cpu_s"], ora.per_seed["_trial_cpu_s"]
    ineff = (pm - om) / om * 100.0
    tail = (pt - ot) / np.maximum(ot, 1e-9) * 100.0
    waste = (pc - oc) / np.maximum(oc, 1e-9) * 100.0
    per_seed_ineff = _block_reduce(ineff, trials)
    res.inefficiency_pct = float(per_seed_ineff.mean())
    res.inefficiency_std = float(per_seed_ineff.std())
    res.p99_inefficiency_pct = float(tail.mean())
    res.resource_waste_pct = float(waste.mean())


def _run_stacked(stacked: _Cluster, pol_name: str, seed0: int,
                 blocks, backend: str) -> Dict[str, np.ndarray]:
    """One lockstep pass over a stacked cluster, through the requested
    backend:

    * ``"serial"`` — the reference :class:`SimStepper` loop;
    * ``"compiled"`` — the ``lax.scan`` kernel in
      :mod:`repro.core.simcore` (raises when the config is outside the
      kernel's support matrix);
    * ``"auto"`` — compiled when supported, serial otherwise.
    """
    if backend not in ("serial", "compiled", "auto"):
        raise ValueError(f"unknown backend {backend!r}; expected "
                         "'serial', 'compiled', or 'auto'")
    if backend != "serial":
        # lazy import: the serial path must stay importable without jax
        from repro.core import simcore
        reason = simcore.supports(stacked.cfg, pol_name)
        if reason is None:
            return simcore.run_compiled(stacked, pol_name,
                                        seed_blocks=blocks)
        if backend == "compiled":
            raise ValueError(
                f"backend='compiled' cannot run {pol_name!r}: {reason}")
    pol = make_policy(pol_name, seed=seed0,
                      hedge_factor=stacked.cfg.hedge_factor,
                      seed_blocks=blocks)
    return SimStepper(stacked, pol).run()


def compiled_coverage(policies: Optional[Sequence[str]] = None
                      ) -> List[Tuple[str, str, str]]:
    """Every (registered scenario, policy) pair the compiled kernel
    would kick back to the serial stepper under ``backend="auto"``, as
    ``(scenario, policy, reason)`` rows — empty means 100% compiled
    coverage.  ``bench_simcore.py --smoke`` and the test suite gate on
    this so a support-matrix regression is loud, not a silent
    10-100x slowdown in the next campaign sweep."""
    from repro.core import simcore
    pols = tuple(policies) if policies is not None \
        else DEFAULT_POLICIES + ("oracle",)
    out: List[Tuple[str, str, str]] = []
    for name in scenario_names():
        cfg = get_scenario(name).compile(seed=0)
        for pol in pols:
            reason = simcore.supports(cfg, pol)
            if reason is not None:
                out.append((name, pol, reason))
    return out


def run_scenario(scenario, policies: Sequence[str] = DEFAULT_POLICIES,
                 seeds: Sequence[int] = tuple(range(12)),
                 include_oracle: bool = True, backend: str = "serial",
                 **overrides) -> Dict[str, PolicyResult]:
    """One scenario's policy x seed grid in len(policies) lockstep passes.

    ``overrides`` patch the compiled SimConfigs (tests shrink sizes).
    Returns policy -> :class:`PolicyResult`; with ``include_oracle`` the
    oracle runs too and every result carries oracle-relative
    inefficiency / p99 / waste percentages.  ``backend`` selects the
    stepping engine per (scenario, policy) pass — see
    :func:`_run_stacked`; results agree to <= 1e-5 across backends
    (``tests/test_simcore.py``).
    """
    spec = _resolve(scenario)
    timer = PhaseTimer()
    with timer.phase("build"):
        seeds = tuple(int(s) for s in seeds)
        cfgs = [spec.compile(seed=s, **overrides) for s in seeds]
        stacked = stack_clusters([_build_cluster(c) for c in cfgs])
        trials = [c.n_trials for c in cfgs]
        blocks = [(rng_seed(c.seed, "policy"), c.n_trials) for c in cfgs]

    wanted = list(policies)
    if include_oracle and "oracle" not in wanted:
        wanted.append("oracle")
    out: Dict[str, PolicyResult] = {}
    for pol_name in wanted:
        with timer.phase(f"run:{pol_name}"):
            summary = _run_stacked(stacked, pol_name,
                                   rng_seed(cfgs[0].seed, "policy"),
                                   blocks, backend)
        out[pol_name] = PolicyResult(
            scenario=spec.name, policy=pol_name, seeds=seeds,
            per_seed=_split_per_seed(summary, trials),
            n_hedged=summary["n_hedged"],
            telemetry=(_jsonable(summary["capacity"])
                       if "capacity" in summary else None))
    if include_oracle:
        for pol_name in wanted:
            if pol_name != "oracle":
                _attach_inefficiency(out[pol_name], out["oracle"], trials)
    LAST_PHASES.clear()
    LAST_PHASES.update(timer.summary())
    return out


def run_campaign(scenarios: Optional[Sequence] = None,
                 policies: Sequence[str] = DEFAULT_POLICIES,
                 seeds: Sequence[int] = tuple(range(12)),
                 include_oracle: bool = True, backend: str = "serial",
                 **overrides) -> Dict[str, Dict[str, PolicyResult]]:
    """The full scenario x policy x seed grid through the batched path."""
    names = scenario_names() if scenarios is None else list(scenarios)
    return {(_resolve(n).name): run_scenario(
                n, policies, seeds, include_oracle, backend=backend,
                **overrides)
            for n in names}


def run_campaign_serial(scenarios: Optional[Sequence] = None,
                        policies: Sequence[str] = DEFAULT_POLICIES,
                        seeds: Sequence[int] = tuple(range(12)),
                        include_oracle: bool = True,
                        **overrides) -> Dict[str, Dict[str, PolicyResult]]:
    """Reference grid: one ``run_sim`` per (scenario, policy, seed).

    The parity baseline for tests and the speedup baseline for
    ``benchmarks/bench_campaign.py`` — same outputs, no sharing.
    """
    names = scenario_names() if scenarios is None else list(scenarios)
    out: Dict[str, Dict[str, PolicyResult]] = {}
    for name in names:
        spec = _resolve(name)
        sds = tuple(int(s) for s in seeds)
        wanted = list(policies)
        if include_oracle and "oracle" not in wanted:
            wanted.append("oracle")
        cell: Dict[str, PolicyResult] = {}
        trials: List[int] = []
        for pol_name in wanted:
            summaries = [run_sim(spec.compile(seed=s, **overrides), pol_name)
                         for s in sds]
            trials = [len(s["mean_rtt"]) for s in summaries]
            merged = {k: np.concatenate([s[k] for s in summaries])
                      for k in SUMMARY_STATS + ("hedged_per_trial",)}
            merged["n_hedged"] = sum(s["n_hedged"] for s in summaries)
            cell[pol_name] = PolicyResult(
                scenario=spec.name, policy=pol_name, seeds=sds,
                per_seed=_split_per_seed(merged, trials),
                n_hedged=merged["n_hedged"])
        if include_oracle:
            for pol_name in wanted:
                if pol_name != "oracle":
                    _attach_inefficiency(cell[pol_name], cell["oracle"],
                                         trials)
        out[spec.name] = cell
    return out


def campaign_table(results: Dict[str, Dict[str, PolicyResult]],
                   markdown: bool = False) -> str:
    """Render the scenario x policy grid as one table (p50/p95/p99 s,
    oracle-relative inefficiency % and resource waste %, plus the
    capacity plane's idle-provisioned fraction and shed rate — the
    (RTT, waste, shed) triple every cell now reports)."""
    rows = [("scenario", "policy", "p50 s", "p95 s", "p99 s",
             "ineff %", "waste %", "idle", "shed")]
    for scen, cell in results.items():
        for pol, r in cell.items():
            if pol == "oracle":
                continue
            ineff = "-" if r.inefficiency_pct is None \
                else f"{r.inefficiency_pct:.1f}±{r.inefficiency_std:.1f}"
            waste = "-" if r.resource_waste_pct is None \
                else f"{r.resource_waste_pct:.1f}"
            rows.append((scen, pol, f"{r.stat('p50_rtt'):.2f}",
                         f"{r.stat('p95_rtt'):.2f}",
                         f"{r.stat('p99_rtt'):.2f}", ineff, waste,
                         f"{r.stat('waste'):.2f}",
                         f"{r.stat('shed_rate'):.3f}"))
    if markdown:
        lines = ["| " + " | ".join(rows[0]) + " |",
                 "|" + "---|" * len(rows[0])]
        lines += ["| " + " | ".join(r) + " |" for r in rows[1:]]
        return "\n".join(lines)
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    return "\n".join("  ".join(c.ljust(w) for c, w in zip(r, widths))
                     for r in rows)
