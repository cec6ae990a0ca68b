"""Distribution correctness on 8 forced host devices (subprocess: XLA fixes
the device count at first init, so these tests re-exec python with
XLA_FLAGS).  Verifies:

  * sharded train step == single-device train step (numerics)
  * decode on a mesh == decode on one device
  * collective atom moves the planned bytes (walker cross-check)
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


@pytest.mark.subproc
def test_sharded_train_step_matches_single_device():
    _run("""
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig
    from repro.configs.run import RunConfig
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models.model_zoo import build_model
    from repro.optim.adamw import OptConfig
    from repro.parallel.sharding import TRAIN_RULES, make_rules
    from repro.train.step import (init_train_state, make_train_step,
                                  train_state_specs)
    from jax.sharding import NamedSharding

    cfg = ModelConfig(name="d", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=256, tie_embeddings=True)
    run = RunConfig(param_dtype="float32", compute_dtype="float32",
                    remat="none", loss_chunk=0)
    model = build_model(cfg, run)
    data = SyntheticLM(DataConfig(vocab_size=256, seq_len=64, global_batch=8))
    batch = data.batch_at(0)
    opt = OptConfig(lr=1e-2, warmup_steps=1, decay_steps=100,
                    weight_decay=0.0)

    # single device
    state0 = init_train_state(model, jax.random.key(0))
    step0 = jax.jit(make_train_step(model, opt))
    s0, m0 = step0(state0, batch)

    # 2x4 mesh
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_rules(mesh, TRAIN_RULES)
    specs = train_state_specs(model, mesh, rules)
    state1 = init_train_state(model, jax.random.key(0))
    state1 = jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        state1, specs)
    step1 = jax.jit(make_train_step(model, opt, mesh))
    s1, m1 = step1(state1, batch)

    np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                               rtol=1e-4)
    for a, b in zip(jax.tree.leaves(s0["params"]),
                    jax.tree.leaves(s1["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)
    print("OK sharded==single")
    """)


@pytest.mark.subproc
def test_sharded_decode_matches_single_device():
    _run("""
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.configs import get_config, reduced_config
    from repro.configs.run import RunConfig
    from repro.models.model_zoo import build_model
    from repro.serve.step import make_decode_step, make_prefill_step

    cfg = reduced_config(get_config("gemma2-2b"))
    run = RunConfig(param_dtype="float32", compute_dtype="float32",
                    cache_dtype="float32", remat="none")
    model = build_model(cfg, run)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (8, 8), 0, cfg.vocab_size)

    pre0 = jax.jit(make_prefill_step(model, max_len=16))
    dec0 = jax.jit(make_decode_step(model))
    t0, c0 = pre0(params, {"tokens": toks})
    outs0 = [int(x) for x in np.asarray(t0[:, 0])]
    for _ in range(4):
        t0, c0 = dec0(params, t0, c0)
        outs0.extend(int(x) for x in np.asarray(t0[:, 0]))

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    pre1 = jax.jit(make_prefill_step(model, max_len=16, mesh=mesh))
    dec1 = jax.jit(make_decode_step(model, mesh=mesh))
    t1, c1 = pre1(params, {"tokens": toks})
    outs1 = [int(x) for x in np.asarray(t1[:, 0])]
    for _ in range(4):
        t1, c1 = dec1(params, t1, c1)
        outs1.extend(int(x) for x in np.asarray(t1[:, 0]))
    assert outs0 == outs1, (outs0, outs1)
    print("OK decode sharded==single")
    """)


@pytest.mark.subproc
def test_collective_atom_and_walker_agree():
    _run("""
    import jax, numpy as np
    from repro.core.atoms import (COLL_BLOCK_ELEMS, COLL_GROUP,
                                  CollectiveAtom)
    from repro.core.hlo_analysis import analyze_hlo

    from repro.launch.mesh import make_mesh
    for n in (2, 4, 8):
        mesh = make_mesh((n,), ("model",), devices=jax.devices()[:n])
        atom = CollectiveAtom(mesh, axis="model")
        q = atom.quant()
        wire = 8 * 1024 * 1024.0
        thunk = atom.plan(wire)
        got = thunk()
        # the plan reports the QUANTIZED amount it emulates: whole blocks,
        # within half a block of the requested wire bytes
        assert got == q.emulated_bytes(q.iters_for(wire))
        assert abs(got - wire) <= 0.5 * q.wire_bytes_per_iter
        # cross-check with the walker on one trip of the same body: a
        # single-block trip moves one iteration's wire, a group trip (the
        # loop's whole carry) COLL_GROUP iterations'
        x = atom.loop_operand()
        assert x.shape == (n * COLL_GROUP * COLL_BLOCK_ELEMS,)
        for elems, iters in ((n * COLL_BLOCK_ELEMS, 1),
                             (x.size, COLL_GROUP)):
            lowered = jax.jit(atom.loop_body()).lower(
                jax.ShapeDtypeStruct((elems,), x.dtype))
            cost = analyze_hlo(lowered.compile().as_text())
            total = cost.collective_total
            want = iters * q.wire_bytes_per_iter
            assert abs(total - want) <= 1e-6 * want, (n, iters, total, want)
    print("OK atom bytes == walker bytes")
    """)


#: the tensor-parallel serving check below: (phase, tolerance on
#: ||served - reference|| / ||reference|| of the logits).  The weights are
#: the same bf16 numbers on both sides; the served model rounds each
#: activation to bf16 (2^-8 relative), which over 2 layers reads about 0.01
#: here, so 0.04 leaves room, and a float8 pass (~0.15) or a missing
#: exchange between chips (order 1) is far outside.  Decode's logits come
#: through the cache the sharded prefill wrote, so they get the same room.
TP_PHASES = [("prefill", 0.04), ("decode", 0.04)]


@pytest.mark.subproc
@pytest.mark.parametrize("phase,tol", TP_PHASES, ids=[p for p, _ in TP_PHASES])
def test_tensor_parallel_serving_matches_plain_reference(phase, tol):
    """A small Qwen2 with 4 KV heads served by ``Engine`` on a 1 x 4 mesh
    (prefill under ``PREFILL_RULES``, then decode through its cache under
    ``DECODE_RULES``), against the benchmark's plain float32 forward
    (``bench/reference/qwen2.py``) on the same seeded weights."""
    _run(f"""
    import sys
    sys.path.insert(0, {ROOT!r})
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from bench.archs import qwen2 as arch
    from bench.reference import qwen2 as ref
    from repro.configs.run import SERVE_RUN
    from repro.launch.mesh import make_mesh
    from repro.models.model_zoo import build_model
    from repro.parallel.sharding import DECODE_RULES, make_rules
    from repro.serve.engine import Engine

    m = dict(hidden_size=128, intermediate_size=256,
             num_attention_heads=8, num_key_value_heads=4,
             num_hidden_layers=2, vocab_size=512, rms_norm_eps=1e-6,
             rope_theta=1e6, tie_word_embeddings=False)
    model = build_model(arch.model_config(
        {{"registry": "qwen2-7b", "config": m}}), SERVE_RUN)
    mesh = make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
    specs = model.param_specs(make_rules(mesh, DECODE_RULES))
    w = ref.make_weights(m, jax.random.key(2 ** 31 + 15), shardings=jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs))
    B, S, STEPS = 4, 16, 4
    prompts = np.random.default_rng(15).integers(0, 512, (B, S),
                                                 dtype=np.int32)
    eng = Engine(model, w, batch_slots=B, max_len=S + STEPS, mesh=mesh,
                 keep_logits=True)
    tok, cache, lg = eng.prefill(w, {{"tokens": jnp.asarray(prompts)}})
    served, seq = [np.asarray(lg, np.float32)[:, 0]], [np.asarray(tok)]
    if {phase!r} == "decode":
        served = []
        for _ in range(STEPS):
            tok, cache, lg = eng.decode(w, tok, cache)
            served.append(np.asarray(lg, np.float32)[:, 0])
            seq.append(np.asarray(tok))
        tokens = np.concatenate([prompts] + seq[:-1], 1)
        pos = np.arange(S, S + STEPS, dtype=np.int32)
    else:
        tokens, pos = prompts, np.array([S - 1], np.int32)
    want = np.asarray(ref.make_forward(m)(w, jnp.asarray(tokens),
                                          jnp.asarray(pos))[0])
    got = np.stack(served, 1)
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err <= {tol}, err
    print("OK tensor-parallel", {phase!r}, err)
    """)
