"""Jit'd wrapper used by repro.core.atoms.ComputeAtom (backend="pallas")."""
import functools

import jax
import jax.numpy as jnp

from repro.kernels.compute_atom import kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def _burn(x, iters, *, interpret):
    return kernel.burn_tile(x, iters=iters, interpret=interpret)


def burn(x=None, *, iters, tile: int = 256, interpret=None):
    """Burn ``iters`` tile matmuls; ``iters`` is traced, so every count
    shares one compiled kernel."""
    if x is None:
        x = jnp.eye(tile, dtype=jnp.float32) * 0.5
    return _burn(x, jnp.asarray(iters, jnp.int32), interpret=interpret)
