"""Multi-device behaviour (subprocess with forced host devices):
sharded train step, elastic checkpoint reshard, compressed cross-pod psum,
and a reduced multi-pod dry-run lowering."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow   # multi-device subprocess compiles (CI full-suite job)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_sub(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    return p.stdout


def test_sharded_train_step_runs():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import get_config, TrainConfig
        from repro.launch.mesh import make_mesh
        from repro.parallel.sharding import axis_rules, make_rules
        from repro.training.train_step import make_train_state, make_train_step
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("deepseek-67b", smoke=True).resolve(tp=4, dp=2)
        tcfg = TrainConfig(microbatches=2)
        rules = make_rules(mesh, mode="train", fsdp=True, dp_axes=("data",))
        with axis_rules(rules):
            state = make_train_state(jax.random.PRNGKey(0), cfg, tcfg)
            step = make_train_step(cfg, tcfg, rules)
            batch = {"tokens": jnp.ones((8, 32), jnp.int32),
                     "labels": jnp.ones((8, 32), jnp.int32)}
            batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
            jstep = jax.jit(step)
            l0 = None
            for i in range(4):
                state, metrics = jstep(state, batch)
                if l0 is None: l0 = float(metrics["total_loss"])
            l1 = float(metrics["total_loss"])
        assert np.isfinite(l1)
        assert l1 < l0, (l0, l1)
        print("OK", l0, l1)
        """)
    assert "OK" in out


def test_elastic_checkpoint_reshard():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import Checkpointer
        from repro.launch.mesh import make_mesh
        d = tempfile.mkdtemp()
        mesh1 = make_mesh((4, 2), ("data", "model"))
        w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        w1 = jax.device_put(w, NamedSharding(mesh1, P("data", "model")))
        ck = Checkpointer(d, use_async=False)
        ck.save(5, {"w": w1}, blocking=True)
        # restore onto a DIFFERENT mesh shape + sharding (elastic resize)
        mesh2 = make_mesh((2, 4), ("data", "model"))
        template = {"w": jnp.zeros((8, 8), jnp.float32)}
        shardings = {"w": NamedSharding(mesh2, P("model", None))}
        r = ck.restore(template, shardings=shardings)
        np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(w))
        assert r["w"].sharding.spec == P("model", None)
        print("OK")
        """)
    assert "OK" in out


def test_compressed_crosspod_allreduce():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.optim.compression import make_compressed_allreduce
        mesh = make_mesh((4, 2), ("pod", "data"))
        fn = make_compressed_allreduce(mesh, axis_name="pod")
        rng = np.random.default_rng(0)
        g = {"w": jnp.asarray(rng.standard_normal((16, 32)).astype(np.float32))}
        r = {"w": jnp.zeros((16, 32), jnp.float32)}
        mean, res = jax.jit(fn)(g, r)
        # pod-replicated input -> mean == input, small quantization error
        err = float(jnp.max(jnp.abs(mean["w"] - g["w"])))
        scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
        assert err <= scale + 1e-6, (err, scale)
        # error feedback captured the residual
        assert float(jnp.max(jnp.abs(res["w"]))) <= scale + 1e-6
        print("OK", err)
        """)
    assert "OK" in out


def test_reduced_dryrun_multipod_lowering():
    out = run_sub("""
        import jax
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import build_cell, lower_cell
        from repro.launch.hlo import collective_bytes
        mesh = make_mesh((2, 2, 4), ("pod", "data", "model"))
        cell = build_cell("deepseek-67b", "train_4k", mesh,
                          overrides={"num_layers": 2, "d_model": 256,
                                     "num_heads": 8, "num_kv_heads": 4,
                                     "head_dim": 32, "d_ff": 512,
                                     "vocab_size": 1024})
        compiled = lower_cell(cell).compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        assert cost["flops"] > 0
        colls = collective_bytes(compiled.as_text())
        assert colls["_total"] > 0, colls
        print("OK", colls["_counts"])
        """, devices=16)
    assert "OK" in out
