"""Jit'd wrappers; ``flash_attention_grouped`` matches the model-layer
calling convention (q [B,S,Hk,G,hd], k/v [B,S,Hk,hd])."""
import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_kv", "group",
    "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    block_q=512, block_kv=512, group=1, interpret=None):
    """Interpret mode follows the platform unless ``interpret`` says."""
    return kernel.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, group=group, interpret=interpret)


def flash_attention_grouped(qg, k, v, *, causal=True, window=None,
                            softcap=None, block_q=512, block_kv=512,
                            interpret=None):
    """qg: [B,S,Hk,G,hd]; k/v: [B,S,Hk,hd] -> [B,S,Hk,G,hd]."""
    B, S, Hk, G, hd = qg.shape
    qf = jnp.moveaxis(qg, 1, 3).reshape(B * Hk * G, S, hd)
    kf = jnp.moveaxis(k, 1, 2).reshape(B * Hk, S, hd)
    vf = jnp.moveaxis(v, 1, 2).reshape(B * Hk, S, hd)
    out = flash_attention(qf, kf, vf, causal=causal, window=window,
                          softcap=softcap, block_q=block_q,
                          block_kv=block_kv, group=G, interpret=interpret)
    return jnp.moveaxis(out.reshape(B, Hk, G, S, hd), 3, 1)
