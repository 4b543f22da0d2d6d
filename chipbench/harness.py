"""The benchmark's general part: find a cell's files by name, check the
device, run the cell's kind, read its metrics and print the result.

Everything that belongs to one configuration, traffic mix or metric is
data or a file of its own:

* ``BENCHMARK.json`` names the cell's configuration, traffic and chips;
* ``configs/<config>.json`` holds the configuration as it is run, and its
  ``kind`` names the module ``kinds/<kind>.py`` that runs it; a served
  configuration's ``arch`` names ``archs/<arch>.py``, all that depends
  on the model's architecture;
* ``traffic/<traffic>.json`` holds the traffic mix's parameters;
* ``metrics/<metric>.py`` reads one metric from what the run recorded.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


@dataclass
class Check:
    """One number compared with its limit; ``ok`` when ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclass
class Outcome:
    """What a kind's ``run`` hands back to the harness."""
    setup_s: float
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: Optional[int]
    #: everything the metric readers read, by name
    record: Dict[str, object] = field(default_factory=dict)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=load_json(os.path.join(root, conf["file"])),
                traffic=load_json(os.path.join(
                    HERE, "traffic", w["traffic"] + ".json")),
                end_to_end=e2e, per_layer=layer)


def require_chips(n: int):
    """The devices of a run on ``n`` chips; raises NoChip without them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX sees {len(devs)}")
    return devs


def device_info(devs, peak_bytes: Optional[int]) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak_bytes}


def memory_peak_bytes(devs) -> Optional[int]:
    """The peak bytes in use on the fullest chip, where JAX reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_reader(metric: str):
    """``metrics/<metric>.py``, loaded by path (metric names hold dots)."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[dict], record: dict) -> Dict[str, dict]:
    """Each metric's reader over the run's record; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        v = load_reader(m["name"])(record)
        if v is None or not math.isfinite(v):
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(cell: Cell, outcome: Outcome, devs, trace: bool) -> dict:
    metrics = cell.per_layer if trace else cell.end_to_end
    record = dict(outcome.record, setup_s=outcome.setup_s)
    correct = outcome.failed == 0 and all(c.ok for c in outcome.checks)
    line = {"correct": bool(correct), "attempted": int(outcome.attempted),
            "failed": int(outcome.failed),
            "metrics": read_metrics(metrics, record),
            "device": device_info(devs, outcome.memory_peak_bytes)}
    tr = record.get("trace")
    if trace and tr is not None:
        line["device"]["busy_s"] = tr["busy_s"]
        line["device"]["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = {c.name: {"value": _number(c.value), "limit": c.limit}
                      for c in outcome.checks}
    line["checks"]["failed"] = {"value": outcome.failed, "limit": 0}
    return line


def _number(x: float):
    """A compared number for JSON: an infinite gap is written as a
    string."""
    x = float(x)
    return x if math.isfinite(x) else repr(x)


def kind_module(cell: Cell):
    return importlib.import_module(f"chipbench.kinds.{cell.config['kind']}")


def run(args, t0: float) -> dict:
    """One run of the cell ``args.workload`` on the chips it asks for."""
    cell = load_cell(args.workload)
    devs = require_chips(cell.chips)
    enable_compile_cache()
    return run_cell(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), t0=t0, devs=devs)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t0: float, devs) -> dict:
    """The cell's kind driven through set-up, window and check, and the
    result line read from what it recorded."""
    from chipbench.trace import Tracer
    tracer = Tracer(trace)
    outcome = kind_module(cell).run(cell, seed=seed, seconds=seconds,
                                    tracer=tracer, t0=t0, devs=devs)
    outcome.record["trace"] = tracer.result()
    return result_line(cell, outcome, devs, trace)


def emit(line: dict):
    """The compared numbers as the last lines on standard error, and the
    result as the last line on standard output."""
    for name, c in line["checks"].items():
        v, lim = c["value"], c["limit"]
        verdict = "ok" if float(v) <= lim else "FAIL"
        print(f"check {name}: {v!r} (limit {lim!r}) {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(line, default=_jsonable), flush=True)


def _jsonable(x):
    if hasattr(x, "item"):
        return x.item()
    raise TypeError(f"not JSON serialisable: {type(x)}")
