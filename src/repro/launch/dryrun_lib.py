"""Dry-run driver (importable; repro.launch.dryrun sets XLA_FLAGS first).

For every requested (arch x shape x mesh): lower + compile the step on the
production mesh, record memory_analysis / cost_analysis / collective bytes
into an incremental JSON artifact (resumable — completed cells are skipped).

Roofline extrapolation: XLA's cost_analysis counts a scanned layer body
once, so two extra *unrolled* compiles at depth 1 and 2 give the per-layer
marginal terms; benchmarks/roofline.py scales them to full depth.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
import traceback

import jax

from repro.configs.base import SHAPES, available_archs, get_config, supported_shapes
from repro.launch.hlo import collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell, lower_cell


def _depth_override(cfg, n_blocks: int) -> dict:
    """Config overrides that set the number of repeated blocks to n_blocks."""
    if cfg.family == "hybrid":
        return {"num_layers": n_blocks * cfg.hybrid.shared_every,
                "scan_layers": False}
    if cfg.family == "encdec":
        return {"num_layers": n_blocks, "enc_layers": n_blocks,
                "scan_layers": False}
    return {"num_layers": n_blocks, "scan_layers": False}


def _n_blocks(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.hybrid.shared_every
    return cfg.num_layers


def _mem_dict(mem) -> dict:
    return {k: getattr(mem, k) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             extrapolate: bool = True, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "chips": mesh.size}
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh)
    rec["n_blocks"] = _n_blocks(cell.cfg)
    rec["params"] = cell.cfg.param_count()
    rec["params_active"] = cell.cfg.param_count(active_only=True)

    lowered = lower_cell(cell)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    rec["memory"] = _mem_dict(mem)
    rec["cost_full"] = {k: cost.get(k) for k in ("flops", "bytes accessed")}
    rec["collectives_full"] = collective_bytes(compiled.as_text())
    rec["compile_s"] = round(time.time() - t0, 1)
    if verbose:
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops={cost.get('flops'):.3e} "
              f"bytes={cost.get('bytes accessed'):.3e}")
        print(f"  collectives: {rec['collectives_full']}")

    if extrapolate and not multi_pod:
        # per-layer marginal terms from unrolled depth-1 / depth-2 compiles
        base_cfg = get_config(arch)
        for n in (1, 2):
            t1 = time.time()
            c = build_cell(arch, shape_name, mesh,
                           overrides=_depth_override(base_cfg, n),
                           tcfg_overrides={"unroll_microbatches": True})
            comp = lower_cell(c).compile()
            cost_n = comp.cost_analysis()
            rec[f"cost_L{n}"] = {k: cost_n.get(k)
                                 for k in ("flops", "bytes accessed")}
            rec[f"collectives_L{n}"] = collective_bytes(comp.as_text())
            rec[f"compile_L{n}_s"] = round(time.time() - t1, 1)
    rec["status"] = "ok"
    return rec


def cell_list(archs, shapes):
    cells = []
    for a in archs:
        cfg = get_config(a)
        names = [s.name for s in supported_shapes(cfg)]
        skips = [n for n in SHAPES if n not in names]
        for n in names:
            if not shapes or n in shapes:
                cells.append((a, n, False))
        for n in skips:
            cells.append((a, n, None))  # recorded as skipped
    return cells


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/artifacts/dryrun.json")
    ap.add_argument("--no-extrapolate", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    archs = available_archs() if args.arch == "all" else args.arch.split(",")
    shapes = None if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)   # --force only bypasses the skip check

    def save():
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(results, f, indent=1)
        os.replace(tmp, args.out)

    for arch, shape_name, runnable in cell_list(archs, shapes):
        if runnable is None:
            key = f"{arch}|{shape_name}|skip"
            if key not in results:
                cfg = get_config(arch)
                results[key] = {
                    "arch": arch, "shape": shape_name, "status": "skipped",
                    "reason": ("full-attention arch: long_500k requires "
                               "sub-quadratic attention (see DESIGN.md)")
                    if shape_name == "long_500k" else "n/a for family",
                }
                save()
            continue
        for multi in meshes:
            key = f"{arch}|{shape_name}|{'multi' if multi else 'single'}"
            if (key in results and results[key].get("status") == "ok"
                    and not args.force):
                continue
            print(f"[dryrun] {key}", flush=True)
            try:
                rec = run_cell(arch, shape_name, multi,
                               extrapolate=not args.no_extrapolate)
            except Exception as e:  # noqa: BLE001 — record and continue
                rec = {"arch": arch, "shape": shape_name,
                       "status": "error", "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
                print(f"  ERROR {e}", flush=True)
            results[key] = rec
            save()
    n_ok = sum(1 for r in results.values() if r.get("status") == "ok")
    n_err = sum(1 for r in results.values() if r.get("status") == "error")
    n_skip = sum(1 for r in results.values() if r.get("status") == "skipped")
    print(f"[dryrun] done: {n_ok} ok, {n_err} error, {n_skip} skipped")
    return 1 if n_err else 0
