"""One module per served architecture: ``archs/<arch>.py``, named by a
served configuration's ``arch`` key.

A module gives everything of a served cell that depends on the model's
architecture (``INTERFACE``):

* ``model_config(conf, name)``: the program's ``ModelConfig`` for the
  configuration's keys;
* ``draw(key, path, sd)``: one seeded weight of the program's parameter
  tree, at ``path`` (its keys joined by ``/``) with the shape and dtype
  of ``sd``; traced inside the one jitted call that makes every weight;
* ``request_flops(conf, prompt_len, new_tokens)``: the model FLOPs of a
  served request, its prefill and its decode steps;
* ``SMOKE``: the keys that cut the configuration to a size the CPU runs
  in seconds;
* ``logits_at(params, conf, tokens, rows, cols, fp8=False)``: the plain
  reference's float32 logits at the positions ``(rows, cols)`` of
  ``tokens``, and with ``fp8`` its lower-precision control.

A second served model therefore comes as new files only: its
configuration, its traffic, its module here, its reference under
``refs/``, the readers of its metrics, and its entries in
``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib

INTERFACE = ("model_config", "draw", "request_flops", "SMOKE", "logits_at")


def load(name: str):
    """The module ``chipbench.archs.<name>``."""
    return importlib.import_module(f"{__name__}.{name}")


def of(conf: dict):
    """The architecture module that a served configuration names."""
    return load(conf["arch"])
