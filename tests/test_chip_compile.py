"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler refuses here what the chip would refuse
(an op Mosaic cannot lower, a dtype XLA:TPU lacks, a kernel over its
fast memory).  The topology is described inside a fixture so that no
import touches the TPU library.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import simcore
from repro.core.campaign import stack_clusters
from repro.core.rng import rng_seed
from repro.core.scenarios import get_scenario
from repro.core.simulator import _build_cluster
from repro.kernels.segment_sum import segment_sum

#: bench_simcore.LARGE; the baseline scenario's 5 apps give R = 1000
LARGE = dict(n_nodes=250, n_replicas_per_app=200, n_requests=1000)
N_NODES, N_APPS, N_REPLICAS = 250, 5, 1000
MID = dict(n_nodes=60, n_replicas_per_app=50, n_requests=200)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_segment_sum_compiles_at_recount_shape(one_chip):
    """The churn/snapshot recount: (T, R) = (256, 1000) 0/1 masks into
    N * A = 1250 segments, as the simulator calls it (under x64)."""
    with jax.enable_x64():
        vals = _sds((256, N_REPLICAS), jnp.float32, one_chip)
        ids = _sds((256, N_REPLICAS), jnp.int32, one_chip)
        compiled = jax.jit(
            lambda v, i: segment_sum(v, i, N_NODES * N_APPS)
        ).lower(vals, ids).compile()
    # the kernel's name is the custom call's: a trace names it so
    assert re.search(r"%segment_sum\.\d+ = .*tpu_custom_call",
                     compiled.as_text())
    assert compiled.memory_analysis().output_size_in_bytes \
        < 2 * 256 * 1280 * 4


def test_ridge_solve_compiles_in_float64(one_chip):
    """The closed-loop retrain's batched solve at D = N + A of the
    drift-fallback scenario at LARGE, T = 256: XLA:TPU has no float64
    LU, so the f32-LU + f64-refinement form is the one that must
    compile."""
    cfg = get_scenario("drift-fallback").compile(seed=0, **LARGE)
    D = cfg.n_nodes + len(cfg.apps)
    with jax.enable_x64():
        G = _sds((256, D, D), jnp.float64, one_chip)
        b = _sds((256, D), jnp.float64, one_chip)
        jax.jit(simcore._ridge_solve).lower(G, b).compile()


def test_churn_kernel_compiles_with_pallas_recount(one_chip, monkeypatch):
    """The whole churn/perf_aware scan kernel at a mid shape, with the
    Pallas recount the TPU selects."""
    monkeypatch.setattr(simcore, "_SEGSUM_BACKEND", "pallas")
    cfg = get_scenario("churn").compile(seed=0, n_trials=8, **MID)
    cluster = stack_clusters([_build_cluster(cfg)])
    st, consts, xs, carry0, _ = simcore._lower(
        cluster, "perf_aware", [(rng_seed(0, "policy"), 8)])

    def shapes(tree):
        return {k: _sds(np.shape(v), np.asarray(v).dtype, one_chip)
                for k, v in tree.items()}

    with jax.enable_x64():
        compiled = jax.jit(simcore._build_kernel(st)).lower(
            shapes(consts), shapes(xs), shapes(carry0)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "/sim_step/" in compiled.as_text()


def test_served_model_compiles_at_published_widths(one_chip):
    """minicpm3-4b's jitted prefill and decode, as the serving engine
    calls them, on its full-width parameter shapes (about 8.1 GB)."""
    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.serving.engine import jit_decode, jit_prefill

    cfg = get_config("minicpm3-4b").resolve(tp=1)
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda k: M.init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    toks = _sds((4, 8), jnp.int32, one_chip)
    prefill = jit_prefill.lower(params, cfg=cfg, batch={"tokens": toks},
                                cache_len=64).compile()
    assert prefill.memory_analysis().argument_size_in_bytes > 8e9
    cache = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda p, t: M.prefill(p, cfg, {"tokens": t},
                                              cache_len=64)[1],
                       params, toks))
    jit_decode.lower(params, cfg=cfg, cache=cache,
                     tokens=_sds((4, 1), jnp.int32, one_chip)).compile()
