"""The benchmark's cells cut to a size the CPU runs in seconds, with the
harness's look for a chip skipped: the rehearsal the tests make."""
import time
import types

from chipbench import archs, harness

#: a device that names the benchmark's chip, for the readers that look
#: up its peaks; nothing here runs on it
DEVICE = types.SimpleNamespace(platform="cpu", device_kind="TPU v5 lite",
                               memory_stats=lambda: None)

SIM_SMALL = dict(n_nodes=30, replicas_total=30, n_requests=120,
                 trials_per_seed=4, seeds_per_call=2)


def cut(c: harness.Cell) -> harness.Cell:
    """The cell ``c`` cut to a size the CPU runs in seconds: widths and
    depth of the smoke model (its architecture module's ``SMOKE``), a few
    replicas and requests at a rate that gives each run several,
    millisecond slowdowns."""
    if c.config["kind"] == "sim":
        c.config.update(SIM_SMALL)
    else:
        c.config.update(archs.of(c.config).SMOKE)
        c.config["fleet"]["slowdowns_s"] = [0.0, 0.002, 0.004]
        c.traffic.update(rate_per_s=4.0,
                         prompt=dict(median=16, sigma=0.0, min=16, max=16),
                         new_tokens=dict(median=4, sigma=0.6, min=2, max=8),
                         trace_seconds=1)
    return c


def small(name: str) -> harness.Cell:
    """The cell ``name`` of BENCHMARK.json, ``cut``."""
    return cut(harness.load_cell(name))


def run_small(name: str, seed: int = 2 ** 33 + 5, seconds: float = 2.0,
              trace: bool = False) -> dict:
    return harness.run_cell(small(name), seed=seed, seconds=seconds,
                            trace=trace, t0=time.perf_counter(),
                            devs=[DEVICE])
