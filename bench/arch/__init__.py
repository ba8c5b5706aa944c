"""What the yardstick knows about one architecture, a module each.

A configuration file names its architecture by its key ``"arch"``: the
module ``bench/arch/<arch>.py``, or, where the name holds a dot, that
module path (a test's own architecture).  Everything else of the
benchmark (weights, reference, check, readers) is generic and asks the
module.  A module provides:

- ``program_config(cfg, log=None)``: the program's ``ModelConfig`` for
  the configuration file; where the program's registry has the model,
  ``log`` reports each field, not listed under ``reduced``, on which the
  registry differs (the file's value runs);
- ``layout(cfg)``: ``{path: (shape, kind, std)}`` of every parameter
  leaf, in the tree the program's train state holds; ``kind`` is
  ``normal`` (zero mean), ``scale`` (mean one) or ``bias``;
- ``hidden(params, tokens, cfg, precision)``: the plain reference's
  final-normed hidden states, and ``unembed(params, h, cfg, precision)``
  its logits (``bench/reference.py`` says what each precision means);
- ``cast_params(params, precision)``: the weights as that precision
  computes with them (the control rounds the module's matrices to int8);
- ``step_flops(cfg, shapes)``: program -> model FLOPs of one run of
  each of ``TRAIN``, ``PREFILL`` and ``DECODE`` at the traffic's shapes;
- ``kernel_costs(cfg, shapes)``: kernel -> program -> (FLOPs, bytes) of
  one call of the kernel in that program.
"""

from __future__ import annotations

import importlib

#: the program's jitted steps, by the name the trace gives their runs
TRAIN, PREFILL, DECODE = "jit_train_step", "jit_prefill", "jit_decode"


def of(cfg: dict):
    """The architecture module a configuration file names."""
    name = cfg["arch"]
    return importlib.import_module(name if "." in name
                                   else f"{__name__}.{name}")
