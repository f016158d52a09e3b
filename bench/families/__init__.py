"""Model families: what the benchmark knows of each architecture, one module
per configuration ``model_type``, found by that name.

``bench/families/<model_type>.py`` gives:

- ``layout(m)``: the program's parameter tree of model entry ``m``, as nested
  dicts of ``bench.weights.Leaf``, with any number of stacks of layers;
- ``logits(m, params, tokens, precision)``: the plain reference's logits of
  one-token sequences, float32 at ``HIGHEST`` or the ``"fp8"`` control, run
  layer by layer so that it fits (helpers in ``bench.reference``);
- ``reduce(m)``: the entry at the program's ``.reduced()`` CPU cut, for the
  tests;
- optionally ``draws``: leaf kind -> ``draw(key, shape)`` in float32, the
  family's own kinds beside ``bench.weights.DRAWS``;
- optionally ``step_cost(m, params, token)``: (FLOPs, bytes) of one step on
  input ``token``, computed from the seeded weights, where the weights a
  step reads depend on its input (routed experts). Without it every weight
  is read once per step (``bench.costs.read_once``).

A new architecture joins the benchmark as one new file here, beside its
configuration and its entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib
from types import ModuleType

from bench import BenchError


def load(model_type: str) -> ModuleType:
    """The family module of ``model_type``; a missing one is an error that
    names the file to add."""
    name = f"{__name__}.{model_type}"
    if model_type.isidentifier():
        try:
            return importlib.import_module(name)
        except ModuleNotFoundError as e:
            if e.name != name:  # the family exists and imports something missing
                raise
    raise BenchError(
        f"no model family {model_type!r}: add bench/families/{model_type}.py "
        f"(what it gives: bench/families/__init__.py)"
    )
