"""Architectures the benchmark can serve, one module per architecture.

The module ``arch/<model_type>.py`` serves the configuration files whose
``model`` section states that ``model_type`` (a module that serves the
same block as another re-exports it, as ``granitemoe.py`` does
``llama.py``).  It provides:

- ``shape(model) -> Shape``: the sizes the metrics count work from (see
  ``Shape`` below);
- ``engine_config(config)``: the engine's ``ModelConfig`` for a
  configuration file, refusing values the engine cannot run as stated;
- ``draw(shape, seed, dtype)``: every weight, drawn on the device in one
  program from the seed; ``to_engine(weights, shape)``: the engine's
  parameter tree over the same arrays;
- the plain float32 reference: ``reference(model)`` (its sizes, hashable),
  ``forward(arch, w, tokens, first, quant=False)`` (final hidden states of
  a teacher-forced pass, padded to ``reference.BUCKET``) and
  ``logits(arch, w, x, first, quant=False)`` (``reference.OUT_BLOCK`` rows
  of logits from position ``first``); ``quant`` rounds every matrix
  product's operands through fp8 (``reference.mm``);
- ``tiny(model)``: the model section shrunk to a size the CPU tests run.

``for_model`` imports the module named by a configuration's
``model_type``; adding an architecture is adding a file here.
"""

from __future__ import annotations

import importlib
from pathlib import Path
from typing import Dict, Protocol, Tuple


class Shape(Protocol):
    """What the metrics read of a model's sizes."""

    vocab: int
    layers: int

    @property
    def attn_layers(self) -> int:
        """Layers that run the paged attention kernels."""

    def decode_flops(self, ctx: int) -> float:
        """Useful FLOPs of one decoded token over ``ctx`` tokens."""

    def prefill_flops(self, start: int, n: int) -> float:
        """Useful FLOPs of ``n`` prompt tokens after ``start`` cached."""

    def decode_attn_cost(self, ctx: int) -> Tuple[float, float]:
        """(FLOPs, bytes) of one decode row in one paged-attention layer."""

    def prefill_attn_cost(self, start: int, n: int) -> Tuple[float, float]:
        """(FLOPs, bytes) of one prefill row in one paged-attention
        layer."""


def for_model(model: Dict):
    """The architecture module named by ``model["model_type"]``."""
    mtype = model.get("model_type")
    name = f"{__name__}.{mtype}"
    if isinstance(mtype, str) and mtype.isidentifier():
        try:
            return importlib.import_module(name)
        except ModuleNotFoundError as e:
            if e.name != name:  # the module exists; an import inside failed
                raise
    raise LookupError(
        f"no architecture module serves model_type {mtype!r}: add "
        f"{Path(__file__).resolve().parent}/{mtype}.py")
