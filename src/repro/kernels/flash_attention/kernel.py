"""Blocked causal attention on TPU (FlashAttention-2 forward).

Grid: (batch·q_heads, num_q_blocks, num_kv_blocks) — the last axis is the
TPU-sequential accumulation axis.  Online-softmax state (m, l, acc) lives in
VMEM scratch and persists across the kv grid steps; the output block is
written once at the last kv step.  Q/K/V blocks are VMEM-tiled via BlockSpec
(block_q×hd and block_kv×hd with hd untiled — hd is 64..256 here, a multiple
of the 128 lane width or padded by mosaic).  GQA is handled in the K/V index
maps (head h reads kv head h // group) so grouped K/V are never materialized.

Against the XLA path (models/layers.attend_blocked) the win is structural:
logits/probability blocks never leave VMEM, removing the dominant
O(S²/blk·f32) HBM traffic term from the roofline (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: Optional[int],
               softcap: Optional[float], block_q: int, block_kv: int,
               nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # [bq, hd]
    k = k_ref[0]                                   # [bkv, hd]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [bq, bkv]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qp = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                 (block_q, block_kv), 0)
    kp = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_kv), 1)
    ok = (kp <= qp) if causal else jnp.ones_like(qp, bool)
    if window is not None:
        ok = jnp.logical_and(ok, qp - kp < window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = 512, block_kv: int = 512,
                    group: int = 1, interpret: Optional[bool] = None):
    """q: [BH, Sq, hd]; k, v: [BKV, Sk, hd] with BH == BKV * group."""
    BH, Sq, hd = q.shape
    BKV, Sk, _ = k.shape
    assert BH == BKV * group, (BH, BKV, group)
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Sk)
    assert Sq % block_q == 0 and Sk % block_kv == 0
    nq, nk = Sq // block_q, Sk // block_kv
    scale = hd ** -0.5

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv, nk=nk)

    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_kv, hd),
                         lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, block_kv, hd),
                         lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
