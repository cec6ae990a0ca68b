"""Pallas TPU kernels for the perf-critical hot spots.

Each kernel is a subpackage: ``kernel.py`` (pl.pallas_call + explicit
BlockSpec VMEM tiling), ``ops.py`` (jit'd public wrapper), ``ref.py``
(pure-jnp oracle).  Kernels compile for the TPU they run on; only where
the default backend is the CPU do they fall back to the Pallas
interpreter (``resolve_interpret``), so no caller gets the interpreter
on a chip without asking for it.
"""
from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret mode for a Pallas call: the caller's explicit choice,
    else interpret only where the default backend is the CPU."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
