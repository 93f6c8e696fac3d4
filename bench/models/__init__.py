"""The model kinds the benchmark knows, one module each:
`bench/models/<kind>.py`, found by the `kind` of a configuration's `model`.

A kind module provides
    weight_spec(m)         [(path, shape, init)], the program's weight layout
                           in the order the weights are drawn
    forward(cfg, P, x, t, text, run)
                           the plain backbone on one whole image, calling
                           run(name, fn, z) at each block the patch cache
                           wraps
    SAMPLER                "ddim" or "flow"
    step_flops(m, h, w)    operations of one backbone evaluation at (h, w)
    kernel_shapes(cfg, patches_total)
                           {kernel: {shape}} of the program's kernel calls
Shared plain layers are in `_layers.py`. Nothing here imports the program.
"""
from __future__ import annotations

import importlib
from pathlib import Path

DIR = Path(__file__).resolve().parent


def kind(name: str):
    """The module of a model kind, `bench/models/<name>.py`."""
    path = DIR / f"{name}.py"
    if not path.is_file():
        raise LookupError(f"no module for model kind {name!r}: "
                          f"bench/models/{name}.py is missing")
    return importlib.import_module(f"{__name__}.{name}")
