"""Architecture families: everything the harness knows of a model's layers.

``family(cfg)`` imports ``bench/families/<cfg["model_type"]>.py``, so a new
architecture is a new module here and never an edit of the harness.  A
family module provides:

* ``model_config(cfg)``: the program's ``ModelConfig`` with the file's
  sizes.  It states the program structure the file describes and raises
  where the program would run something else;
* ``param_shapes(cfg)``: ``{path: (shape, dtype)}`` of the weights as the
  program stores them (paths as ``bench.gen.path_str`` spells them);
* ``loss_fn(p, tokens, labels, cfg, q)``: the plain float32 reference loss
  of one batch over the flat ``{path: array}`` weights, with every matmul
  operand passed through ``q`` (identity, or the fp8 control's rounding);
* ``n_params(cfg)`` and ``train_flops_per_token(cfg, seq)``: the
  yardstick's counts, from shapes alone.

What every family shares stays where it is: AdamW, its schedule, the
control's rounding and the faults (``bench.reference``), the digest byte
counts (``bench.flops``), inputs and weights from the seed (``bench.gen``)
and the comparison (``bench.check``).

A configuration is added as files: a family module here (unless its
``model_type`` already has one), ``bench/configs/<name>.json`` with that
``model_type``, a traffic file ``bench/traffic/<mix>.json`` if no mix fits,
and its entries in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib
import pkgutil
from types import ModuleType
from typing import List


def known() -> List[str]:
    """The families present: the modules on this package's path."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def family(cfg: dict) -> ModuleType:
    """The family module of a configuration file, by its ``model_type``."""
    name = cfg["model_type"]
    if name not in known():
        raise KeyError(f"no architecture family {name!r} in bench/families; "
                       f"known: {known()}")
    return importlib.import_module(f"{__name__}.{name}")
