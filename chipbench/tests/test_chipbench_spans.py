"""The program's spans as the benchmark reads them: their names in a
profiler trace, the labels of idle gaps, and the readers of the metrics
that the spans feed, on made-up records."""
import glob
import os

import numpy as np
import pytest

from chipbench import harness, spans, trace


def test_program_spans_appear_in_a_cpu_trace(tmp_path):
    import jax
    from repro.configs.base import get_config
    from repro.core.campaign import run_scenario
    from repro.models import model as M
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.router import MorpheusRouter

    cfg = get_config("deepseek-67b", smoke=True).resolve(tp=1)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    reps = [ServingEngine(cfg, params, node=f"n{i}", max_batch=2,
                          max_seq=32) for i in range(2)]
    router = MorpheusRouter(reps, policy="perf_aware")
    rng = np.random.default_rng(1)
    small = dict(n_nodes=12, n_replicas_per_app=4, n_requests=40,
                 n_trials=2)
    run_scenario("churn", policies=["perf_aware"], seeds=(0,),
                 include_oracle=False, backend="compiled", **small)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        run_scenario("churn", policies=["perf_aware"], seeds=(0,),
                     include_oracle=False, backend="compiled", **small)
        router.route(Request(rid=0, tokens=rng.integers(0, 100, size=6),
                             max_new_tokens=3))
        router.drain()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    _, host = trace.read_events(path)
    names = {n for n, _, _ in host}
    assert {"window", "build", "run:perf_aware", "route", "route/predict",
            "route/pick", "drain", "drain/prefill", "drain/decode",
            "drain/scrape"} <= names
    assert {f"run:perf_aware/{s}" for s in ("lower", "put", "kernel",
                                            "fetch", "summarize")} <= names
    # each child lies inside its parent on the trace's clock
    (_, k0, k1), = [e for e in host if e[0] == "run:perf_aware/kernel"]
    (_, r0, r1), = [e for e in host if e[0] == "run:perf_aware"]
    assert r0 <= k0 < k1 <= r1


def test_a_gap_takes_the_innermost_program_span():
    # the harness's own drain annotation, the router's drain span and a
    # decode step inside it all cover the gap from 10 to 50 alike
    dev = {"/device:TPU:0": [("decode", 0, 10), ("decode", 50, 100)]}
    host = [("window", 0, 100), ("drain", 0, 100), ("drain", 1, 99),
            ("drain/decode", 5, 55), ("drain/scrape", 55, 60)]
    r = trace.reduce_events(dev, host)
    assert dict(r["idle_gaps"]) == pytest.approx({"drain/decode": 40e-9})
    # a gap that straddles two children goes to the parent, which covers
    # all of it: only a split at span edges would share it out
    host[3:] = [("drain/decode", 5, 30), ("drain/scrape", 30, 60)]
    r = trace.reduce_events(dev, host)
    assert dict(r["idle_gaps"]) == pytest.approx({"drain": 40e-9})


def _calls(*phases):
    return {"policy": "perf_aware",
            "calls": [{"phases": p} for p in phases]}


def test_sim_step_readers():
    rec = _calls(
        {"build": 7.0, "run:perf_aware": 2.0, "run:perf_aware/lower": 0.4,
         "run:perf_aware/put": 0.2, "run:perf_aware/fetch": 0.1,
         "run:perf_aware/summarize": 0.05},
        {"build": 7.0, "run:perf_aware": 2.2, "run:perf_aware/lower": 0.6,
         "run:perf_aware/put": 0.3, "run:perf_aware/fetch": 0.2,
         "run:perf_aware/summarize": 0.15})
    assert harness.load_reader("sim.lower_s")(rec) == pytest.approx(0.5)
    assert harness.load_reader("sim.transfer_s")(rec) == pytest.approx(0.4)
    assert harness.load_reader("sim.summarize_s")(rec) == pytest.approx(0.1)
    # a program without the steps' spans, and a record of another kind
    old = _calls({"build": 7.0, "run:perf_aware": 2.0})
    for name in ("sim.lower_s", "sim.transfer_s", "sim.summarize_s"):
        assert harness.load_reader(name)(old) is None
        assert harness.load_reader(name)({"rtt_s": []}) is None
    # a step missing from one call is no mean
    rec["calls"][1]["phases"].pop("run:perf_aware/put")
    assert spans.run_child_mean(rec, "put") is None


def test_predict_ms_reader():
    snap = {"route": (40, 0.008), "route/predict": (40, 0.002),
            "route/pick": (40, 0.001)}
    assert spans.per_route_ms(snap, "predict") == pytest.approx(0.05)
    assert spans.per_route_ms({"drain": (1, 1.0)}, "predict") is None
    # a retry routed again inside drain counts as a route call too
    snap.update({"drain": (5, 9.0), "drain/route": (10, 0.002),
                 "drain/route/predict": (10, 0.0005)})
    assert spans.per_route_ms(snap, "predict") == pytest.approx(0.05)


def test_predict_ms_reads_the_process_span_totals(monkeypatch):
    import repro.core.telemetry as telemetry
    read = harness.load_reader("serve.predict_ms.tail")
    assert read({"calls": []}) is None        # not a served run
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "route": (29, 0.0035), "route/predict": (29, 0.000725),
        "route/pick": (29, 0.0003), "drain": (12, 40.0),
        "drain/decode": (3000, 39.0)})
    assert read({"rtt_s": np.ones(29)}) == pytest.approx(0.025)
    # a program whose router opens no spans
    monkeypatch.setattr(telemetry, "snapshot", lambda: {})
    assert read({"rtt_s": np.ones(29)}) is None


def test_predict_ms_reads_a_route_call_made_here():
    from repro.core.telemetry import snapshot, span
    before = snapshot()
    with span("route"):
        with span("predict"):
            pass
    after = snapshot()
    assert after["route"][0] == before.get("route", (0, 0.0))[0] + 1
    read = harness.load_reader("serve.predict_ms.tail")
    assert read({"rtt_s": np.ones(1)}) == pytest.approx(
        spans.per_route_ms(after, "predict"))
