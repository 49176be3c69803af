"""Controls: a configuration's contract computed one step below the
precision it states, for setting the limits of ``correct`` (never a
cell's run).  ``python3 -m bench.run ... --control <name>``.

* ``float32``: the program's own float32 path (``cameo.dtype``): the
  ingest buffer and every compression in float32.
* ``compute32``: each window compressed by the float32 program while the
  ingest buffer stays float64, so the points stored are the written ones
  and only the computation (ranking, selection, the dense update and the
  deviation the compressor reports) is float32.
"""
from __future__ import annotations

import dataclasses

NAMES = ("float32", "compute32")


def apply(name: str, config: dict) -> None:
    """Switch the control ``name`` on for this process and ``config``."""
    if name == "float32":
        config["cameo"]["dtype"] = "float32"
    elif name == "compute32":
        import jax.numpy as jnp
        import numpy as np
        from repro.core import streaming
        compress_rounds = streaming.compress_rounds

        def in_float32(x, cfg, **kw):
            return compress_rounds(
                jnp.asarray(np.asarray(x), jnp.float32),
                dataclasses.replace(cfg, dtype="float32"), **kw)
        streaming.compress_rounds = in_float32
    else:
        raise ValueError(f"unknown control {name!r}; known: {NAMES}")
