"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps
plus hypothesis property tests (assignment deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.compute_atom import kernel as ck, ops as cops, ref as cref
from repro.kernels.flash_attention import (kernel as fk, ops as fops,
                                           ref as fref)
from repro.kernels.memory_atom import kernel as mk, ops as mops, ref as mref


# ---------------------------------------------------------------------------
# compute atom
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tile", [8, 64, 128])
@pytest.mark.parametrize("iters", [1, 3, 17])
def test_compute_atom_matches_ref(tile, iters):
    x = jax.random.normal(jax.random.key(0), (tile, tile)) * 0.1
    got = ck.burn_tile(x, iters=iters, interpret=True)
    want = cref.burn_tile(x, iters=iters)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_compute_atom_ops_flops_accounting():
    out = cops.burn(iters=4, tile=64)
    assert out.shape == (64, 64)
    assert np.isfinite(np.asarray(out)).all()
    assert cref.flops(64, 4) == 2 * 64 ** 3 * 4


@pytest.mark.parametrize("iters", [3, 7])
def test_pallas_compute_atom_burns_the_iters_it_reports(iters):
    """The Pallas-backed atom's plan reports ``iters`` tile matmuls, and
    its output is the reference burn of exactly that many (each extra
    iteration moves the result by a quarter of the last step)."""
    from repro.core.atoms import ComputeAtom, compute_operand
    atom = ComputeAtom(tile=64, backend="pallas")
    plan = atom.plan(iters * atom.flops_per_iter())
    assert plan.amount == iters * atom.flops_per_iter()
    x = compute_operand(64)[0]          # the kernel burns one tile
    got = np.asarray(plan.launch())
    np.testing.assert_allclose(
        got, np.asarray(cref.burn_tile(x, iters=iters)), rtol=1e-6)
    assert not np.allclose(got, np.asarray(cref.burn_tile(x, iters=iters - 1)),
                           rtol=1e-6)


# ---------------------------------------------------------------------------
# memory atom
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,block", [(256, 64), (1024, 1024), (4096, 512)])
def test_memory_atom_matches_ref(n, block, dtype):
    x = jnp.arange(n, dtype=dtype)
    got = mk.stream_pass(x, block=block, interpret=True)
    want = mref.stream_pass(x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-2)


def test_memory_atom_multi_pass():
    x = jnp.ones((2048,), jnp.float32)
    out = mops.stream(x, iters=5, block=256)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(x) * 1.0000001 ** 5, rtol=1e-5)
    assert mref.bytes_moved(2048 * 4, 5) == 2 * 2048 * 4 * 5


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
