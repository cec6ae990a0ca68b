"""The flash-attention Pallas kernel vs its pure-jnp oracle (interpret
mode): shape/dtype sweeps plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import (kernel as fk, ops as fops,
                                           ref as fref)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

SWEEP = [
    # (BH, BKV, S, hd, bq, bkv, causal, window, softcap)
    (2, 2, 64, 16, 16, 16, True, None, None),
    (2, 2, 64, 16, 32, 16, True, 9, None),
    (2, 2, 64, 16, 16, 32, True, None, 30.0),
    (4, 2, 32, 8, 8, 8, True, None, None),     # GQA group=2
    (3, 1, 48, 32, 16, 16, False, None, None),  # cross-attn-like, group=3
    (2, 2, 128, 64, 64, 32, True, 40, 25.0),
]


@pytest.mark.parametrize("case", SWEEP)
def test_flash_attention_matches_ref(case):
    BH, BKV, S, hd, bq, bkv, causal, window, softcap = case
    group = BH // BKV
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (BH, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (BKV, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (BKV, S, hd), jnp.float32)
    got = fk.flash_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, block_q=bq, block_kv=bkv,
                             group=group, interpret=True)
    want = fref.flash_attention(q, k, v, causal=causal, window=window,
                                softcap=softcap, group=group)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, atol):
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (2, 64, 32), dtype)
    k = jax.random.normal(ks[1], (2, 64, 32), dtype)
    v = jax.random.normal(ks[2], (2, 64, 32), dtype)
    got = fk.flash_attention(q, k, v, causal=True, block_q=16, block_kv=16,
                             interpret=True)
    want = fref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol,
                               rtol=atol)


def test_flash_attention_grouped_layout_matches_model_layer():
    from repro.models.layers import attend_full
    B, S, Hk, G, hd = 2, 32, 2, 3, 16
    ks = jax.random.split(jax.random.key(3), 3)
    qg = jax.random.normal(ks[0], (B, S, Hk, G, hd))
    k = jax.random.normal(ks[1], (B, S, Hk, hd))
    v = jax.random.normal(ks[2], (B, S, Hk, hd))
    got = fops.flash_attention_grouped(qg, k, v, causal=True, block_q=8,
                                       block_kv=8)
    pos = jnp.arange(S)
    want = attend_full(qg, k, v, q_pos=pos, k_pos=pos, causal=True,
                       window=None, softcap=None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@given(
    s_blocks=st.integers(1, 4), bq=st.sampled_from([8, 16]),
    bkv=st.sampled_from([8, 16]), hd=st.sampled_from([8, 16]),
    causal=st.booleans(),
    window=st.one_of(st.none(), st.integers(1, 40)),
    seed=st.integers(0, 2**30),
)
@settings(max_examples=25, deadline=None)
def test_flash_attention_property(s_blocks, bq, bkv, hd, causal, window,
                                  seed):
    S = 16 * s_blocks
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (1, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (1, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (1, S, hd), jnp.float32)
    got = fk.flash_attention(q, k, v, causal=causal, window=window,
                             block_q=min(bq, S), block_kv=min(bkv, S),
                             interpret=True)
    want = fref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)
