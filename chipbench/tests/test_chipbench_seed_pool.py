"""The simulator's pool of campaign seeds: with ``seed_batches`` every
``--seed`` runs the same batches, only in another order, and the check
holds each call to the seeds it ran."""
import pytest

from chipbench import harness
from chipbench.kinds import sim
from chipbench.smoke import run_small, small

POOLED = [w["name"] for w in harness.load_json(
    harness.ROOT + "/BENCHMARK.json")["workloads"]
    if harness.load_cell(w["name"]).traffic.get("seed_batches")]
SEEDS = [2 ** 33 + s for s in range(16)]


def test_some_cell_draws_from_a_pool():
    assert "sim-baseline-least_conn" in POOLED


@pytest.mark.parametrize("name", POOLED)
def test_every_seed_runs_the_same_batches_in_its_own_order(name):
    cell = harness.load_cell(name)
    k = cell.config["seeds_per_call"]
    n = cell.traffic["seed_batches"]
    want = {tuple(range(k * b, k * b + k)) for b in range(n)}
    orders = [sim.batches(cell, s) for s in SEEDS]
    for order in orders:
        assert len(order) == n
        assert {tuple(sorted(b)) for b in order} == want
    assert orders[0] == sim.batches(cell, SEEDS[0])
    assert len({tuple(o) for o in orders}) == len(orders)


def test_without_a_pool_the_seed_picks_its_one_batch():
    cell = harness.load_cell("sim-churn-perf_aware")
    assert "seed_batches" not in cell.traffic
    assert sim.batches(cell, 5) == [sim.campaign_seeds(cell, 5)]


@pytest.mark.parametrize("name", POOLED)
def test_a_pooled_run_calls_every_batch_in_turn(name, monkeypatch):
    cell = small(name)
    order = sim.batches(cell, 2 ** 33 + 5)
    from repro.core import campaign
    real, seen = campaign.run_scenario, []

    def run_scenario(scen, seeds, **kw):
        seen.append(tuple(seeds))
        return real(scen, seeds=seeds, **kw)
    monkeypatch.setattr(campaign, "run_scenario", run_scenario)
    line = run_small(name, seed=2 ** 33 + 5, seconds=0.5)
    assert line["correct"] is True
    n = len(order)
    assert seen[:n] == order                       # set-up: each batch once
    window = seen[n:n + line["attempted"] // len(order[0])]
    assert len(window) > n
    assert window == [order[i % n] for i in range(len(window))]


@pytest.mark.parametrize("name", POOLED)
def test_a_call_answered_for_another_batch_reads_not_correct(
        name, monkeypatch):
    """Every call runs the first batch of the order whatever it is
    asked for: the check ties each call to its own seeds."""
    cell = small(name)
    first = sim.batches(cell, 2 ** 33 + 5)[0]
    from repro.core import campaign
    real = campaign.run_scenario

    def run_scenario(scen, seeds, **kw):
        return real(scen, seeds=first, **kw)
    monkeypatch.setattr(campaign, "run_scenario", run_scenario)
    line = run_small(name, seed=2 ** 33 + 5, seconds=0.5)
    assert line["correct"] is False
