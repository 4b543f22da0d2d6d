"""BENCHMARK.json against the files the harness finds by name, and the
rules a cell's metrics keep."""
import json
import os
import re

import numpy as np
import pytest

from chipbench import archs, harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][1] == "chipbench/run.py"
    assert 1 <= bench["run_seconds"] <= 51


def test_every_name_and_unit_is_well_formed(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in
               bench["end_to_end"] + bench["per_layer"])


@pytest.mark.parametrize("section", ["configs", "workloads"])
def test_files_exist_for_every_entry(bench, section):
    for x in bench[section]:
        if section == "configs":
            conf = harness.load_json(os.path.join(ROOT, x["file"]))
            # the harness runs kinds/<kind>.py; a served configuration
            # names its architecture module, which gives the interface
            assert os.path.exists(os.path.join(
                harness.HERE, "kinds", conf["kind"] + ".py"))
            if conf["kind"] == "serve":
                arch = archs.of(conf)
                assert all(hasattr(arch, k) for k in archs.INTERFACE)
        else:
            assert os.path.exists(os.path.join(
                harness.HERE, "traffic", x["traffic"] + ".json"))


def test_every_metric_has_a_reader(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


def test_each_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        # a per-layer metric's end-to-end metric is reported where it is
        assert all(m["moves"] in e2e for m in cell.per_layer)


def test_bounds_are_within_the_rules(bench):
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_route_share_reads_only_requests_done_in_the_span():
    from chipbench.readers import route_rtt_share
    record = {"span": {"t": 10.0, "routes": 3, "busy": 5.0},
              "route_s": np.array([0.001, 0.002, 0.003, 0.004]),
              "rtt_s": np.array([1.0, 3.0, 50.0, 1.0]),
              "done_at_s": np.array([2.0, 8.0, 55.0, 12.0])}
    assert route_rtt_share(record) == pytest.approx(100 * 0.003 / 4.0)
    record["done_at_s"][:] = np.nan
    assert route_rtt_share(record) is None


def test_tokens_per_s_counts_a_request_that_straddles_the_close():
    from chipbench.readers import tokens_per_s
    new = np.array([100, 200, 300])
    # the last request completes 4 s past a 50 s close: its tokens and
    # its time count
    assert tokens_per_s(new, [10.0, 30.0, 54.0], 50.0) == pytest.approx(
        600 / 54.0)
    # all done before the close: the window's length is the time
    assert tokens_per_s(new, [10.0, 30.0, 45.0], 50.0) == pytest.approx(
        600 / 50.0)
    assert tokens_per_s(new, [10.0, np.nan, 45.0], 50.0) == pytest.approx(
        400 / 50.0)
    assert tokens_per_s(new, [np.nan] * 3, 50.0) is None
