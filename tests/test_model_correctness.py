"""Numerical correctness of the model substrate.

* blocked (flash-style) attention == full attention, across masks/softcap
* chunked SSD == recurrent oracle, and decode recurrence == both
* prefill+decode greedy tokens == full-context forward (per family)
* MoE: ample capacity -> output matches per-token dense expert mixture
* M-RoPE == RoPE when all three streams carry the same positions
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
from repro.configs.run import RunConfig
from repro.models import frontends, moe as moe_lib, ssm as ssm_lib
from repro.models.layers import (apply_rope, attend_blocked, attend_full)
from repro.models.model_zoo import build_model
from repro.models.params import init_params

RUN = RunConfig(param_dtype="float32", compute_dtype="float32",
                cache_dtype="float32", remat="none", loss_chunk=0,
                blocked_threshold=10**9)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("softcap", [None, 20.0])
@pytest.mark.parametrize("causal", [True, False])
def test_blocked_equals_full(window, softcap, causal):
    B, S, Hk, G, hd = 2, 64, 2, 3, 8
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hk, G, hd))
    k = jax.random.normal(ks[1], (B, S, Hk, hd))
    v = jax.random.normal(ks[2], (B, S, Hk, hd))
    pos = jnp.arange(S)
    ref = attend_full(q, k, v, q_pos=pos, k_pos=pos, causal=causal,
                      window=window, softcap=softcap)
    for bq, bkv in [(16, 16), (64, 8), (8, 32)]:
        out = attend_blocked(q, k, v, q_pos=pos, k_pos=pos, causal=causal,
                             window=window, softcap=softcap,
                             block_q=bq, block_kv=bkv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_blocked_local_flag_matches_windowed_and_global():
    B, S, Hk, G, hd = 1, 32, 1, 2, 8
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, S, Hk, G, hd))
    k = jax.random.normal(ks[1], (B, S, Hk, hd))
    v = jax.random.normal(ks[2], (B, S, Hk, hd))
    pos = jnp.arange(S)
    win = attend_full(q, k, v, q_pos=pos, k_pos=pos, causal=True, window=7,
                      softcap=None)
    glb = attend_full(q, k, v, q_pos=pos, k_pos=pos, causal=True, window=None,
                      softcap=None)
    f_t = attend_full(q, k, v, q_pos=pos, k_pos=pos, causal=True, window=7,
                      softcap=None, local_flag=jnp.bool_(True))
    f_f = attend_full(q, k, v, q_pos=pos, k_pos=pos, causal=True, window=7,
                      softcap=None, local_flag=jnp.bool_(False))
    np.testing.assert_allclose(np.asarray(f_t), np.asarray(win), atol=1e-6)
    np.testing.assert_allclose(np.asarray(f_f), np.asarray(glb), atol=1e-6)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [4, 8, 32])
@pytest.mark.parametrize("G", [1, 2])
def test_ssd_chunked_matches_reference(chunk, G):
    B, L, H, P, N = 2, 32, 4, 8, 16
    ks = jax.random.split(jax.random.key(2), 5)
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, L, G, N))
    Cm = jax.random.normal(ks[4], (B, L, G, N))
    ref, ref_state = ssm_lib.ssd_reference(x, dt, A, Bm, Cm)
    out, state = ssm_lib.ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk,
                                     return_state=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(ref_state),
                               atol=1e-4, rtol=1e-4)


def test_ssd_initial_state_continuation():
    """Running [0:L1] then [L1:L] with carried state == running [0:L]."""
    B, L, H, P, N = 1, 32, 2, 4, 8
    L1 = 16
    ks = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, L, 1, N))
    Cm = jax.random.normal(ks[4], (B, L, 1, N))
    full = ssm_lib.ssd_chunked(x, dt, A, Bm, Cm, chunk=8)
    y1, s1 = ssm_lib.ssd_chunked(x[:, :L1], dt[:, :L1], A, Bm[:, :L1],
                                 Cm[:, :L1], chunk=8, return_state=True)
    y2 = ssm_lib.ssd_chunked(x[:, L1:], dt[:, L1:], A, Bm[:, L1:], Cm[:, L1:],
                             chunk=8, initial_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(full), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Prefill + decode == full forward (greedy-token equivalence per family)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma2-2b", "mamba2-780m",
                                  "hymba-1.5b", "moonshot-v1-16b-a3b"])
def test_decode_matches_forward(arch):
    cfg = reduced_config(get_config(arch))
    model = build_model(cfg, RUN)
    params = model.init(jax.random.key(0))
    B, S = 2, 12
    tokens = jax.random.randint(jax.random.key(9), (B, S), 0, cfg.vocab_size)

    hidden_full, _, _ = model.forward(params, {"tokens": tokens})
    logits_full = model.logits(params, hidden_full)       # [B,S,V]

    # prefill on first S0 tokens, then decode the rest one at a time
    S0 = 6
    cache = model.init_cache(B, S + 2)
    _, cache, _ = model.forward(params, {"tokens": tokens[:, :S0]},
                                cache=cache)
    for t in range(S0, S):
        hid, cache, _ = model.forward(params, {"tokens": tokens[:, t:t + 1]},
                                      cache=cache, decode=True)
        lg = model.logits(params, hid)[:, 0]
        ref = logits_full[:, t]
        np.testing.assert_allclose(np.asarray(lg), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)


def test_decode_matches_forward_encdec():
    cfg = reduced_config(get_config("seamless-m4t-medium"))
    model = build_model(cfg, RUN)
    params = model.init(jax.random.key(0))
    B, Ss, St = 2, 8, 10
    src = frontends.audio_frame_embeddings(jax.random.key(1), B, Ss,
                                           cfg.d_model)
    tgt = jax.random.randint(jax.random.key(2), (B, St), 0, cfg.vocab_size)

    hidden_full, _, _ = model.forward(params,
                                      {"src_embeds": src, "tgt_tokens": tgt})
    logits_full = model.logits(params, hidden_full)

    S0 = 5
    cache = model.init_cache(B, St + 2, src_len=Ss)
    _, cache, _ = model.forward(
        params, {"src_embeds": src, "tgt_tokens": tgt[:, :S0]}, cache=cache)
    for t in range(S0, St):
        hid, cache, _ = model.forward(params, {"tokens": tgt[:, t:t + 1]},
                                      cache=cache, decode=True)
        np.testing.assert_allclose(np.asarray(model.logits(params, hid)[:, 0]),
                                   np.asarray(logits_full[:, t]),
                                   atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def _tiny_moe_cfg(top_k=2, cap=8.0):
    return ModelConfig(
        name="tiny-moe", family="moe", num_layers=1, d_model=16, num_heads=2,
        num_kv_heads=2, head_dim=8, d_ff=0, vocab_size=64,
        moe=MoEConfig(num_experts=4, top_k=top_k, d_ff_expert=32,
                      capacity_factor=cap))


def test_moe_matches_dense_mixture_with_ample_capacity():
    cfg = _tiny_moe_cfg()
    p = init_params(moe_lib.def_moe(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 8, cfg.d_model))
    out, aux = moe_lib.moe_block(p, x, cfg=cfg)
    assert float(aux["moe_drop_fraction"]) == 0.0

    # dense reference: full softmax-top-k mixture computed per token
    logits = x @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    gv, ei = jax.lax.top_k(probs, cfg.moe.top_k)
    gv = gv / gv.sum(-1, keepdims=True)
    ref = jnp.zeros_like(x)
    for e in range(cfg.moe.num_experts):
        h = jax.nn.silu(x @ p["wi_gate"][e]) * (x @ p["wi_up"][e])
        y = h @ p["wo"][e]
        w = jnp.sum(jnp.where(ei == e, gv, 0.0), -1)
        ref = ref + w[..., None] * y
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_moe_capacity_drops_tokens():
    cfg = _tiny_moe_cfg(top_k=1, cap=0.25)       # tiny capacity forces drops
    p = init_params(moe_lib.def_moe(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 16, cfg.d_model))
    out, aux = moe_lib.moe_block(p, x, cfg=cfg)
    assert np.isfinite(np.asarray(out)).all()
    assert 0.0 < float(aux["moe_drop_fraction"]) < 1.0


# ---------------------------------------------------------------------------
# M-RoPE
# ---------------------------------------------------------------------------

def test_mrope_equals_rope_for_text():
    B, S, H, hd = 2, 10, 3, 16
    x = jax.random.normal(jax.random.key(0), (B, S, H, hd))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ref = apply_rope(x, pos, theta=1e4)
    mpos = jnp.broadcast_to(pos[None], (3, B, S))
    out = apply_rope(x, mpos, theta=1e4, mrope_sections=(3, 3, 2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("softcap", [None, 15.0])
@pytest.mark.parametrize("window", [None, 9])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_gradients_match_full(causal, window, softcap):
    """Custom-VJP flash backward == autodiff through dense attention."""
    B, S, Hk, G, hd = 2, 32, 2, 2, 8
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (B, S, Hk, G, hd))
    k = jax.random.normal(ks[1], (B, S, Hk, hd))
    v = jax.random.normal(ks[2], (B, S, Hk, hd))
    w = jax.random.normal(ks[3], (B, S, Hk, G, hd))  # cotangent weights
    pos = jnp.arange(S)

    from repro.models.layers import attend_blocked, attend_full

    def loss_full(q, k, v):
        o = attend_full(q, k, v, q_pos=pos, k_pos=pos, causal=causal,
                        window=window, softcap=softcap)
        return jnp.sum(o * w)

    def loss_flash(q, k, v):
        o = attend_blocked(q, k, v, causal=causal, window=window,
                           softcap=softcap, block_q=8, block_kv=16)
        return jnp.sum(o * w)

    g_ref = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5), name


def test_flash_attention_gradients_traced_local_flag():
    B, S, Hk, G, hd = 1, 16, 1, 2, 4
    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (B, S, Hk, G, hd))
    k = jax.random.normal(ks[1], (B, S, Hk, hd))
    v = jax.random.normal(ks[2], (B, S, Hk, hd))
    pos = jnp.arange(S)
    from repro.models.layers import attend_blocked, attend_full

    for flag in (True, False):
        def lf(q):
            return jnp.sum(attend_blocked(
                q, k, v, causal=True, window=5, softcap=None,
                local_flag=jnp.bool_(flag), block_q=8, block_kv=8))

        def lr(q):
            return jnp.sum(attend_full(
                q, k, v, q_pos=pos, k_pos=pos, causal=True,
                window=5 if flag else None, softcap=None))
        np.testing.assert_allclose(np.asarray(jax.grad(lf)(q)),
                                   np.asarray(jax.grad(lr)(q)),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window,bq,bkv", [(7, 8, 8), (16, 8, 16),
                                           (9, 16, 8)])
def test_banded_attention_matches_full(window, bq, bkv):
    """Static-window banded path == dense windowed attention (fwd + grads)."""
    B, S, Hk, G, hd = 2, 64, 2, 2, 8
    ks = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(ks[0], (B, S, Hk, G, hd))
    k = jax.random.normal(ks[1], (B, S, Hk, hd))
    v = jax.random.normal(ks[2], (B, S, Hk, hd))
    w = jax.random.normal(ks[3], (B, S, Hk, G, hd))
    pos = jnp.arange(S)
    from repro.models.layers import attend_blocked, attend_full

    def lf(q, k, v):
        return jnp.sum(w * attend_blocked(q, k, v, causal=True, window=window,
                                          softcap=None, block_q=bq,
                                          block_kv=bkv))

    def lr(q, k, v):
        return jnp.sum(w * attend_full(q, k, v, q_pos=pos, k_pos=pos,
                                       causal=True, window=window,
                                       softcap=None))

    np.testing.assert_allclose(np.asarray(lf(q, k, v)),
                               np.asarray(lr(q, k, v)), rtol=2e-5)
    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def test_serving_init_matches_abstract_dtypes():
    """Materialized weights take the run's param dtype, as the abstract
    tree (what the dry-run sizes memory from) says they do."""
    from repro.configs.run import SERVE_RUN
    model = build_model(reduced_config(get_config("qwen2-1.5b")), SERVE_RUN)
    got = jax.eval_shape(model.init, jax.random.key(0))
    want = model.abstract()
    assert jax.tree.map(lambda a: a.dtype, got) == \
        jax.tree.map(lambda a: a.dtype, want)
    assert {a.dtype for a in jax.tree.leaves(got)} == {jnp.dtype(jnp.bfloat16)}
