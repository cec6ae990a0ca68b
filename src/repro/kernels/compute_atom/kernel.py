"""Synapse compute atom on the MXU.

The paper's compute atom is "a loop of assembly code that efficiently
performs a matrix multiplication", sized to stay cache-resident, whose loop
rate throttles emulated efficiency.  TPU translation: a VMEM-resident
``tile × tile`` f32 matmul chained ``iters`` times through the MXU —
the tile never leaves VMEM, so sustained FLOP/s ~ MXU peak, and ``duty``
(handled in ops.py by scaling iters) is the paper's efficiency knob.
The trip count arrives as a prefetched SMEM scalar, so one compiled kernel
burns any number of iterations.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _burn_kernel(iters_ref, x_ref, o_ref):
    x = x_ref[...]

    def body(_, y):
        # renormalizing keeps values bounded over arbitrarily many iters
        y = jnp.dot(y, x, preferred_element_type=jnp.float32)
        return y * 0.5 + 0.25
    o_ref[...] = jax.lax.fori_loop(0, iters_ref[0], body, x)


def burn_tile(x: jax.Array, *, iters, interpret: Optional[bool] = None):
    """x: [tile, tile] f32 -> same shape; executes ``iters`` MXU matmuls
    (``iters`` may be a traced int32 scalar)."""
    tile = x.shape[0]
    assert x.shape == (tile, tile) and tile % 8 == 0, x.shape
    n = jnp.asarray(iters, jnp.int32).reshape(1)
    return pl.pallas_call(
        _burn_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec((tile, tile), lambda i, n_ref: (0, 0))],
            out_specs=pl.BlockSpec((tile, tile), lambda i, n_ref: (0, 0))),
        out_shape=jax.ShapeDtypeStruct((tile, tile), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(n, x)
