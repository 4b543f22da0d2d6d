"""``chip_smoke.py`` rehearsed on the CPU at a tiny size: each phase runs
end to end through the same entry points, and the script itself
refuses to run without a TPU."""
import importlib.util
import os
import subprocess
import sys

import pytest

from repro.configs.base import get_config
from repro.core import simcore

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
TINY = dict(n_nodes=20, n_replicas_per_app=8, n_requests=60)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_fails_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_chip_smoke_sim_phase(smoke, monkeypatch):
    # the Pallas recount in interpret mode stands in for the TPU kernel
    monkeypatch.setattr(simcore, "_SEGSUM_BACKEND", "interpret")
    smoke.sim_phase(TINY, seeds=(0, 1), n_trials=2)


def test_chip_smoke_shard_phase_single_device_refused(smoke):
    # one visible device: the trial-sharded path cannot be taken, and
    # the phase says so instead of comparing jit against itself
    with pytest.raises(AssertionError, match="shard_map"):
        smoke.shard_phase(TINY, seeds=(0,), n_trials=2,
                          policies=("least_conn",))


_SHARD4 = f"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke.shard_phase({TINY!r}, seeds=(0, 1), n_trials=2)
print("SHARD4_OK")
"""


def test_chip_smoke_shard_phase_on_four_host_devices():
    """The four-chip phase on 4 XLA host devices, in a child process:
    shard_map against force_single for both policies."""
    root = os.path.abspath(ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"),
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(root, "src"), root] + sys.path))
    out = subprocess.run(
        [sys.executable, "-c", _SHARD4, os.path.join(root, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert out.stdout.count("shard_map vs force_single drift=") == 2
    assert "SHARD4_OK" in out.stdout


def test_chip_smoke_serve_phase(smoke):
    smoke.serve_phase(get_config("minicpm3-4b", smoke=True).resolve(tp=1))
