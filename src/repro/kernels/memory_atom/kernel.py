"""Synapse memory atom against HBM.

The paper's memory atom malloc/frees tunable buffers; on a TPU the analogous
resource is HBM<->VMEM bandwidth.  The kernel streams an array block-by-block
through VMEM (read + scale + write), so bytes_moved = 2 * size * passes and
the sustained rate is the HBM roofline.  ``block`` is the paper's tunable
block-size knob (§IV-E.3): small blocks under-utilize the DMA engines, and a
block must fit VMEM with its input and output double-buffered.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _stream_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 1.0000001


def stream_pass(x: jax.Array, *, block: int,
                interpret: Optional[bool] = None):
    """One read+write pass over x [n] (n % block == 0), block-tiled."""
    n = x.shape[0]
    assert n % block == 0, (n, block)
    return pl.pallas_call(
        _stream_kernel,
        grid=(n // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x)
