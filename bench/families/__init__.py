"""Model families, one module per family, found by name.

A configuration file's ``family`` key names ``families/<family>.py``
(and its plain reference, ``reference/<family>.py``).  Each module
gives:

- ``model_config(cfg) -> ModelConfig``: the program's model for the
  configuration file ``cfg``, or a ``ValueError`` for what the family
  cannot serve;
- ``step_flops(cfg, keys)``: one decode step's algorithmic FLOPs over
  the resident rows, row ``b`` attending over ``keys[b]`` cached keys;
- ``attention_cost(cfg, keys) -> (flops, bytes)``: one call of the
  decode-attention kernel (one layer of that step).

A new family is a new module here and a new reference: no other file
changes.
"""
from __future__ import annotations

import importlib
import os


def of(cfg: dict):
    """The family module that ``cfg["family"]`` names."""
    name = cfg["family"]
    try:
        return importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise ValueError(
            f"configuration {cfg['name']!r}: family {name!r} has no module "
            f"{os.path.join('bench', 'families', name + '.py')}") from None
