"""Kernel purity / recompile audit over traced jaxprs.

Traces every distinct compiled-kernel variant reachable from the
registered scenario x default-policy grid (deduped by ``_Static`` — the
same object that keys the kernel cache, so "one trace per distinct
kernel" is exact) and walks the jaxprs for hazards that tier-1 only
catches dynamically, if at all:

- **host callbacks** (``pure_callback`` / ``io_callback`` /
  ``debug_callback``): a device->host round-trip inside the event
  kernel serializes the scan and breaks shard_map;
- **dynamic shapes**: any abstract value with a non-concrete dimension
  means the kernel re-traces per shape;
- **weak-typed scan carries**: a weak-typed carry leaf re-promotes on
  every dtype-touching op and can flip the carry dtype between trace
  and steady state — the classic silent-recompile hazard;
- **per-step scatter chains over budget**: each ``.at[idx].set/add`` in
  the scan body lowers to a scatter (or dynamic_update_slice); XLA:CPU
  serializes scatters, and chains of them copy the carry once per link.
  PR 7 removed exactly such a chain by hand (the per-step bucket
  reduction); this rule keeps the count from regressing.  The budget is
  calibrated against the current tree (see DESIGN.md §15); kernels that
  legitimately exceed it (the unrolled retry/breaker attempt loop) are
  baselined with a justification.

Tracing uses ``jax.make_jaxpr`` only — nothing is compiled or executed,
so the audit is cheap enough for CI but does require jax importable.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from repro.analysis.findings import ERROR, Finding
from repro.analysis.registry import AnalysisContext, rule

#: scatter-family primitive names counted against the carry budget
SCATTER_PRIMS = {"scatter", "scatter-add", "scatter-mul", "scatter-min",
                 "scatter-max", "dynamic_update_slice"}

#: calibrated ceiling for scatter-family eqns per event-kernel scan body
#: (current tree: plain kernels 2-8, capacity/closed-loop 20-28; the
#: resilience client plane unrolls 1+max_retries attempts and is
#: baselined).  Raising this number is a review decision, not a tweak.
DEFAULT_SCATTER_BUDGET = 28

SIMCORE_PATH = "src/repro/core/simcore.py"


@dataclass(frozen=True)
class KernelSpec:
    """One distinct kernel variant + the (scenario, policy) cells that
    reach it.  ``label`` is derived from ``_Static`` feature flags, not
    scenario names, so baseline keys survive scenario renames."""
    static: object               # simcore._Static
    cfg: object                  # a representative tiny SimConfig
    policy: str
    label: str
    cells: Tuple[Tuple[str, str], ...]


def static_label(st) -> str:
    feats = []
    if st.hedging:
        feats.append("hedge")
    if st.closed_loop:
        feats.append("closed")
    elif st.needs_pred:
        feats.append("pred")
    if st.snapshot:
        feats.append("snap")
    if st.cold_start:
        feats.append("cold")
    if st.churn:
        feats.append("churn")
    if st.drift:
        feats.append("drift")
    if st.capacity is not None:
        feats.append(f"cap[{st.capacity.autoscaler}]")
    if st.preempt:
        feats.append("preempt")
    if st.admission:
        feats.append("admit")
    if st.resilience is not None:
        r = [f for f, on in (("client", st.res_client),
                             ("breaker", st.res_breaker)) if on]
        feats.append("res[" + ",".join(r or ["faults"]) + "]")
    if st.native_noise:
        feats.append("native")
    return st.policy + ":" + ("+".join(feats) if feats else "plain")


def kernel_specs(scenarios: Optional[Sequence[str]] = None,
                 policies: Optional[Sequence[str]] = None,
                 n_trials: int = 2, n_requests: int = 8,
                 ) -> List[KernelSpec]:
    """Distinct kernel variants over the scenario x policy grid, at
    trace-friendly tiny sizes (shapes do not affect the audited
    structure; ``_Static`` carries no shape fields besides A/K/N, which
    we keep at scenario values so per-app layout is authentic)."""
    from repro.core.campaign import DEFAULT_POLICIES
    from repro.core.scenarios import get_scenario, scenario_names
    from repro.core.simcore import _static_for, supports

    scenarios = list(scenarios or scenario_names())
    policies = list(policies or DEFAULT_POLICIES + ("oracle",))
    by_static: Dict[object, List] = {}
    for sname in scenarios:
        spec = get_scenario(sname)
        cfg = spec.compile(n_trials=n_trials, n_requests=n_requests)
        for pol in policies:
            if supports(cfg, pol) is not None:
                continue
            st = _static_for(cfg, pol)
            by_static.setdefault(st, []).append((sname, pol, cfg))
    out: List[KernelSpec] = []
    label_counts: Dict[str, int] = {}
    for st, cells in by_static.items():
        label = static_label(st)
        n = label_counts.setdefault(label, 0)
        label_counts[label] += 1
        if n:
            label = f"{label}#{n}"     # distinct statics, same flags
        out.append(KernelSpec(
            static=st, cfg=cells[0][2], policy=cells[0][1], label=label,
            cells=tuple((s, p) for s, p, _ in cells)))
    return sorted(out, key=lambda ks: ks.label)


def trace_kernel(cfg, policy: str):
    """make_jaxpr the kernel closure for (cfg, policy) — trace only."""
    import jax

    from repro.core.simcore import _build_kernel, _lower
    from repro.core.simulator import _build_cluster

    cluster = _build_cluster(cfg)
    st, consts, xs, carry0, _aux = _lower(cluster, policy, None)
    run = _build_kernel(st)
    with jax.enable_x64():
        return jax.make_jaxpr(run)(consts, xs, carry0)


def _subjaxprs(eqn) -> Iterator:
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def iter_eqns(jaxpr) -> Iterator:
    """All equations, recursing through scan/cond/while sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from iter_eqns(sub)


def audit_jaxpr(closed, label: str,
                scatter_budget: int = DEFAULT_SCATTER_BUDGET,
                ) -> List[Finding]:
    """Purity/recompile checks on one traced kernel jaxpr."""
    findings: List[Finding] = []
    jaxpr = closed.jaxpr

    callbacks: Dict[str, int] = {}
    dynamic = 0
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if "callback" in name:
            callbacks[name] = callbacks.get(name, 0) + 1
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            shape = getattr(aval, "shape", ())
            if any(not isinstance(d, int) for d in shape):
                dynamic += 1
    for prim, n in sorted(callbacks.items()):
        findings.append(Finding(
            "kernel-purity", ERROR, SIMCORE_PATH,
            f"{label}:callback:{prim}",
            f"kernel {label} traces {n} {prim} host callback(s) — a "
            "device->host round-trip inside the scan serializes the "
            "kernel and breaks shard_map"))
    if dynamic:
        findings.append(Finding(
            "kernel-purity", ERROR, SIMCORE_PATH,
            f"{label}:dynamic-shape",
            f"kernel {label} has {dynamic} abstract value(s) with "
            "non-concrete dimensions — per-shape retracing"))

    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "scan":
            continue
        body = eqn.params["jaxpr"].jaxpr
        nc, ncarry = eqn.params["num_consts"], eqn.params["num_carry"]
        weak = [v for v in body.invars[nc:nc + ncarry]
                if getattr(v.aval, "weak_type", False)]
        if weak:
            findings.append(Finding(
                "kernel-purity", ERROR, SIMCORE_PATH,
                f"{label}:weak-carry",
                f"kernel {label} carries {len(weak)} weak-typed scan "
                "leaf/leaves — promotion can flip the carry dtype "
                "between trace and steady state (silent recompile); "
                "jnp.asarray the init with an explicit dtype"))
        scatters = sum(1 for e in iter_eqns(body)
                       if e.primitive.name in SCATTER_PRIMS)
        if scatters > scatter_budget:
            findings.append(Finding(
                "kernel-scatter-budget", ERROR, SIMCORE_PATH,
                f"{label}:scatters",
                f"kernel {label} lowers {scatters} scatter-family ops "
                f"per step (budget {scatter_budget}) — each .at[] link "
                "copies the carry and XLA:CPU serializes scatters; use "
                "an incremental carry or a gather/sort plan (PR 7)"))
    return findings


def audit_static(st, label: str) -> List[Finding]:
    """``_Static`` (the kernel cache key) must stay hashable — an
    unhashable field silently defeats the LRU and recompiles forever."""
    findings: List[Finding] = []
    try:
        hash(st)
    except TypeError as e:
        findings.append(Finding(
            "kernel-static-hashable", ERROR, SIMCORE_PATH,
            f"{label}:unhashable",
            f"_Static for kernel {label} is not hashable ({e}) — the "
            "kernel cache keys on it; every call recompiles"))
    return findings


def audit_kernels(scenarios: Optional[Sequence[str]] = None,
                  policies: Optional[Sequence[str]] = None,
                  scatter_budget: int = DEFAULT_SCATTER_BUDGET,
                  ) -> List[Finding]:
    findings: List[Finding] = []
    for ks in kernel_specs(scenarios, policies):
        findings.extend(audit_static(ks.static, ks.label))
        closed = trace_kernel(ks.cfg, ks.policy)
        findings.extend(audit_jaxpr(closed, ks.label, scatter_budget))
    return findings


def scatter_counts(scenarios: Optional[Sequence[str]] = None,
                   policies: Optional[Sequence[str]] = None,
                   ) -> Dict[str, int]:
    """Per-kernel scan-body scatter counts (budget calibration aid)."""
    out: Dict[str, int] = {}
    for ks in kernel_specs(scenarios, policies):
        closed = trace_kernel(ks.cfg, ks.policy)
        for eqn in closed.jaxpr.eqns:
            if eqn.primitive.name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                out[ks.label] = max(
                    out.get(ks.label, 0),
                    sum(1 for e in iter_eqns(body)
                        if e.primitive.name in SCATTER_PRIMS))
    return out


def _cached_audit(ctx: AnalysisContext) -> List[Finding]:
    if "jaxpr-audit" not in ctx.cache:
        ctx.cache["jaxpr-audit"] = audit_kernels()
    return ctx.cache["jaxpr-audit"]


@rule("kernel-purity", "jaxpr",
      "no host callbacks, dynamic shapes, or weak-typed scan carries in "
      "any registered kernel variant")
def _purity_rule(ctx: AnalysisContext) -> List[Finding]:
    return [f for f in _cached_audit(ctx)
            if f.rule in ("kernel-purity", "kernel-static-hashable")]


@rule("kernel-scatter-budget", "jaxpr",
      "per-step scatter-family ops in every scan body stay within the "
      "calibrated budget")
def _scatter_rule(ctx: AnalysisContext) -> List[Finding]:
    return [f for f in _cached_audit(ctx)
            if f.rule == "kernel-scatter-budget"]
