"""Jit'd wrapper used by repro.core.atoms.MemoryAtom (backend="pallas")."""
import functools

import jax
import jax.numpy as jnp

from repro.kernels.memory_atom import kernel

#: bytes of one VMEM block.  Input and output are each double-buffered, so
#: a pass holds four blocks in VMEM: 8 MiB, inside the 16 MiB that a v5e
#: kernel may use by default.  A whole 16 MiB buffer as one block does not
#: compile there.
VMEM_BLOCK_BYTES = 1 << 21


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _stream(x, iters, *, block: int, interpret):
    def body(_, y):
        return kernel.stream_pass(y, block=block, interpret=interpret)
    return jax.lax.fori_loop(0, iters, body, x)


def stream(x, *, iters, block: int = 0, interpret=None):
    """``iters`` read+write passes over ``x``, streamed through VMEM in
    ``block``-element blocks (default: ``VMEM_BLOCK_BYTES`` worth).
    ``iters`` is traced, so every pass count shares one compiled program."""
    block = min(block or VMEM_BLOCK_BYTES // x.dtype.itemsize, x.shape[0])
    return _stream(x, jnp.asarray(iters, jnp.int32), block=block,
                   interpret=interpret)
