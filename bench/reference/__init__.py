"""Plain references, found by the configuration's ``reference`` key.

The key lists the reference files of a configuration, comma-separated: the
gate's (:data:`GATE`) and, where the configuration has a ``detector``, its
detector's. The detector's module keeps :data:`CONTRACT`:

- ``make_weights(key, g, d)``: the detector's weights from ``key``, made on
  the device in the tree the program's detector takes;
- ``logits(params, frames, d, *, mode)``: ``(M, H, W)`` high-precision
  frames to ``(M, n_out)`` logits, ``mode`` one of ``float32``,
  ``bfloat16`` and the control's ``float8``;
- ``frame_flops(g, d)``: the forward FLOPs of one real frame, counted from
  the algorithm;
- ``tiny(d)``: the keys of ``d`` that a CPU test shrinks, with their
  small values.

So a detector joins the benchmark with files of its own: its
configuration, naming its reference module, and that module.
"""

from __future__ import annotations

import importlib
import re

GATE = "bench/reference/gate.py"
CONTRACT = ("make_weights", "logits", "frame_flops", "tiny")

#: a relative path to a Python module: what ``import`` can find by name
_MODULE_PATH = re.compile(r"^(?:[A-Za-z_][A-Za-z0-9_]*/)*"
                          r"[A-Za-z_][A-Za-z0-9_]*\.py$")


def detector(config: dict):
    """The module of the configuration's detector reference."""
    who = config.get("name", "?")
    named = [p.strip() for p in config.get("reference", "").split(",")]
    named = [p for p in named if p and p != GATE]
    if len(named) != 1:
        raise ValueError(
            f"configuration {who!r}: its 'reference' key has to name one "
            f"detector reference beside {GATE}; it names {named}")
    path = named[0]
    if not _MODULE_PATH.match(path):
        raise ValueError(f"configuration {who!r}: detector reference "
                         f"{path!r} is not a relative path to a module")
    mod = importlib.import_module(path[:-3].replace("/", "."))
    missing = [f for f in CONTRACT if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"configuration {who!r}: detector reference {path} "
                         f"lacks {missing} of the contract {CONTRACT}")
    return mod
