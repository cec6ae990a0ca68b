#!/usr/bin/env python3
"""Drive Synapse's main path once on a TPU and check what comes out.

    python chip_smoke.py [--seed N] [--out DIR]    one chip (the default)
    python chip_smoke.py --four-chips               the 2x2-mesh path only

The one-chip run serves Qwen2-1.5B at its published widths, profiles the
served executables, stores the profiles, replays them through the atoms in
this process and through a one-worker process fleet, and runs each Pallas
kernel compiled.  Phases 1-4 and 6 run in one child process that holds the
chip; this process stays off JAX's backends so that phase 5's fleet worker
can take the chip after that child exits.  ``--four-chips`` runs the
sharded serving path on a 2x2 mesh against a one-device run of the same
weights, and replays its profile with mesh-bound collectives.

Each phase prints one line of what it found; any failed check exits
non-zero.  The last line of a passing run is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Profiles and per-phase JSON go to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.run import SERVE_RUN  # noqa: E402
from repro.core import (Emulator, ProfileStore, ResourceVector,  # noqa: E402
                        SynapseProfile, predict, profile_compiled)
from repro.core.hardware import spec_for_device_kind  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.model_zoo import build_model  # noqa: E402

ARCH = "qwen2-1.5b"
BATCH, MAX_LEN, PROMPT, NEW = 8, 1024, 512, 64
#: served logits against the float32 reference, as ||a-b|| / ||b|| over the
#: vocabulary.  The served path rounds weights and activations to bf16 (a
#: 2^-9 relative step) at every matmul, and a random-weight 28-layer stack
#: amplifies that: Qwen2 at reduced widths (d=192..768, 28 layers) served
#: in bf16 on a CPU was 0.23-0.25 off, and 2e-5 off when served in f32.
#: Logits of a wrong context (the previous position, another request, a
#: changed last token) were 1.3-1.5 off (sqrt(2) is uncorrelated), and the
#: run checks that gap itself against the previous position.
LOGITS_REL_TOL = 0.5
#: profile FLOPs against the analytic count.  The walker also counts the
#: elementwise work (norms, RoPE, softmax, SwiGLU gate), under 1% here.
FLOPS_REL_TOL = 0.05


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require_tpu(count=None):
    """Phase 1: the device as JAX reports it; anything but a TPU stops
    the run before any other phase."""
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    log("1 device", f"platform={d.platform} kind={d.device_kind} "
                    f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU, but JAX's platform "
                         f"is {d.platform!r}")
    if count is not None and len(devs) != count:
        raise SystemExit(f"this path needs {count} TPU chips, JAX sees "
                         f"{len(devs)}")
    return info, spec_for_device_kind(d.device_kind, len(devs))


# ---------------------------------------------------------------------------
# analytic counts and the plain reference
# ---------------------------------------------------------------------------

def analytic_flops(cfg, batch: int, q_len: int, kv_len: int) -> float:
    """2 x multiply-adds of one serving step from shapes alone: every
    matmul weight once per query token, the LM head once per sequence (the
    steps sample from the last position only), and QK^T plus PV over the
    full ``kv_len`` each step computes (masked entries included)."""
    d, L, f, V = cfg.d_model, cfg.num_layers, cfg.d_ff, cfg.vocab_size
    hq, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    layer_params = d * hq * hd + 2 * d * hk * hd + hq * hd * d + 3 * d * f
    dense = 2.0 * L * layer_params * batch * q_len
    head = 2.0 * d * V * batch
    attn = 4.0 * L * batch * hq * q_len * kv_len * hd
    return dense + head + attn


def reference_logits(cfg, params, tokens):
    """Last-position logits of a plain float32 Qwen2 forward over
    ``tokens`` [B, S]: no cache, no sharding, no code shared with
    ``repro.models``.  Layers are scanned and cast to float32 one at a
    time, and only the last position meets the LM head, so the reference
    fits beside the served bf16 weights."""
    d, hq, hk, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G, eps, theta = hq // hk, cfg.norm_eps, cfg.attn.rope_theta
    f32 = jnp.float32

    def norm(x, scale):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return x * (1.0 + scale.astype(f32))

    def rope(x, pos):
        inv = 1.0 / theta ** (jnp.arange(hd // 2, dtype=f32) / (hd // 2))
        ang = pos[:, None].astype(f32) * inv            # [S, hd/2]
        c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    def fwd(params, tokens):
        B, S = tokens.shape
        pos = jnp.arange(S)
        causal = pos[None, :] <= pos[:, None]
        x = jnp.take(params["embed"], tokens, axis=0).astype(f32)

        def layer(x, p):
            p = jax.tree.map(lambda a: a.astype(f32), p)
            a = p["attn"]
            h = norm(x, p["ln_attn"]["scale"])
            q = jnp.einsum("bsd,dhk->bshk", h, a["wq"]) + a["bq"]
            k = jnp.einsum("bsd,dhk->bshk", h, a["wk"]) + a["bk"]
            v = jnp.einsum("bsd,dhk->bshk", h, a["wv"]) + a["bv"]
            q = jax.vmap(lambda t: rope(t, pos))(q)
            k = jax.vmap(lambda t: rope(t, pos))(k)
            k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
            s = jnp.where(causal, s, -jnp.inf)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + jnp.einsum("bshk,hkd->bsd", o, a["wo"])
            m = p["mlp"]
            h = norm(x, p["ln_mlp"]["scale"])
            x = x + (jax.nn.silu(h @ m["wi_gate"]) * (h @ m["wi_up"])) \
                @ m["wo"]
            return x, None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        h = norm(x[:, -1], params["ln_final"]["scale"])
        return h @ params["embed"].astype(f32).T           # [B, V]

    with jax.default_matmul_precision("highest"):
        return jax.jit(fwd)(params, tokens)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def step_seconds(prefill_exe, decode_exe, params, batch, reps: int = 5):
    """Median host-clock seconds of one prefill and of one decode step,
    each on its compiled executable and run to ``block_until_ready``;
    decode steps chain their (donated) cache like the engine does."""
    pre, dec = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        tok, cache, _ = jax.block_until_ready(prefill_exe(params, batch))
        pre.append(time.perf_counter() - t0)
    for _ in range(reps):
        t0 = time.perf_counter()
        tok, cache, _ = jax.block_until_ready(decode_exe(params, tok, cache))
        dec.append(time.perf_counter() - t0)
    return float(np.median(pre)), float(np.median(dec))


def check_replay(em: Emulator, prof: SynapseProfile, rep) -> None:
    """``consumed`` is the profile's own totals, and what the atoms burned
    is that amount quantized: within half an iteration per schedule row."""
    want = prof.totals
    for name, got, ref in (("flops", rep.consumed.flops, want.flops),
                           ("hbm_bytes", rep.consumed.hbm_bytes,
                            want.hbm_bytes)):
        assert abs(got - ref) <= 1e-9 * ref, (prof.command, name, got, ref)
    sched = em.compile(prof).describe()
    rows = sched["n_rows"]
    burned_f = sched["compute_iters"] * em.compute.flops_per_iter()
    burned_m = sched["memory_iters"] * em.memory.bytes_per_iter()
    assert abs(burned_f - want.flops) <= \
        0.5 * rows * em.compute.flops_per_iter(), (burned_f, want.flops)
    assert abs(burned_m - want.hbm_bytes) <= \
        0.5 * rows * em.memory.bytes_per_iter(), (burned_m, want.hbm_bytes)


# ---------------------------------------------------------------------------
# one chip: phases 1-4 and 6 (the child that holds the chip)
# ---------------------------------------------------------------------------

def device_phases(seed: int, out: str) -> None:
    from repro.serve.engine import Engine, Request

    enable_compile_cache()
    info, spec = require_tpu()

    # -- 2: serve ---------------------------------------------------------
    cfg = get_config(ARCH)
    model = build_model(cfg, SERVE_RUN)
    params = jax.jit(model.init)(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (BATCH, PROMPT))
    engine = Engine(model, params, batch_slots=BATCH, max_len=MAX_LEN,
                    keep_logits=True)

    def serve():
        reqs = [Request(prompt=[int(t) for t in p], max_new_tokens=NEW)
                for p in prompts]
        engine.serve(reqs)
        jax.block_until_ready(engine.logits)
        return reqs

    t0 = time.perf_counter()
    serve()                                              # compiles
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    reqs = serve()
    serve_s = time.perf_counter() - t0
    assert all(len(r.out_tokens) == NEW for r in reqs)
    served = np.asarray(engine.logits[:2, 0], np.float32)
    assert served.shape == (2, cfg.vocab_size) and np.isfinite(served).all()
    # the last decode step consumed generated token NEW-1 and scored NEW
    seqs = jnp.asarray([list(p) + r.out_tokens[:NEW - 1]
                        for p, r in zip(prompts[:2], reqs[:2])], jnp.int32)
    ref = np.asarray(reference_logits(cfg, params, seqs))
    err = rel_err(served, ref)
    # the same check against a context one position short: what an
    # off-by-one cache slot would serve
    err_off = rel_err(served, reference_logits(cfg, params, seqs[:, :-1]))
    agree = int((served.argmax(-1) == ref.argmax(-1)).sum())
    log("2 serve", f"{ARCH} L={cfg.num_layers} d={cfg.d_model} "
                   f"batch={BATCH} prompt={PROMPT} new={NEW}: "
                   f"serve_s={serve_s} (first call {compile_s} s); "
                   f"logits rel_err={err} (tol {LOGITS_REL_TOL}; previous "
                   f"position {err_off}), argmax agree {agree}/2")
    assert err <= LOGITS_REL_TOL < err_off, (err, LOGITS_REL_TOL, err_off)

    # -- 3: profile the served executables ----------------------------------
    batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
    tok, cache, _ = jax.eval_shape(engine.prefill, params, batch)
    prefill_exe = engine.prefill.lower(params, batch).compile()
    decode_exe = engine.decode.lower(params, tok, cache).compile()
    store = ProfileStore(os.path.join(out, "profiles"))
    tags = {"arch": ARCH, "batch": str(BATCH), "device": info["kind"]}
    profiles = []
    for step, exe, q_len, kv_len in (("prefill", prefill_exe, PROMPT, PROMPT),
                                     ("decode", decode_exe, 1, MAX_LEN)):
        prof = profile_compiled(exe, command=f"{ARCH}:{step}",
                                tags={**tags, "step": step})
        want = analytic_flops(cfg, BATCH, q_len, kv_len)
        got = prof.totals.flops
        log("3 profile", f"{step}: {len(prof.samples)} samples, flops={got} "
                         f"analytic={want} rel={got / want - 1} "
                         f"hbm_bytes={prof.totals.hbm_bytes}")
        assert abs(got / want - 1) <= FLOPS_REL_TOL, (step, got, want)
        store.add(prof)
        profiles.append(prof)
    app_s = step_seconds(prefill_exe, decode_exe, params, batch)

    # -- 4: replay through the fused scan -----------------------------------
    em = Emulator()
    replay = []
    for prof, app in zip(profiles, app_s):
        em.emulate(prof)                                   # compiles
        rep = em.emulate(prof)
        check_replay(em, prof, rep)
        pred = predict(prof, spec)
        log("4 replay", f"{prof.command}: app_s={app} emulated_ttc_s="
                        f"{rep.ttc_s} predicted_s={pred.ttc_max} "
                        f"dominant={pred.terms.dominant} "
                        f"dispatches={rep.n_dispatches} consumed==planned")
        replay.append(rep.consumed.to_dict())

    # -- 6: each Pallas kernel, compiled, against its ref.py -----------------
    kernel_checks()

    with open(os.path.join(out, "device_phases.json"), "w") as f:
        json.dump({"device": info, "consumed": replay,
                   "keys": [[p.command, p.tags] for p in profiles]}, f)


def kernel_checks() -> None:
    from repro.kernels import resolve_interpret
    from repro.kernels.compute_atom import ops as cops, ref as cref
    from repro.kernels.flash_attention import ops as fops, ref as fref
    from repro.kernels.memory_atom import ops as mops, ref as mref

    assert resolve_interpret() is False

    def compiled_kernel(fn, *args):
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, "kernel did not compile to Mosaic"

    tile, iters = 256, 17
    # spectral norm ~1, so the burn map y -> 0.5 y x + 0.25 contracts
    x = jax.random.normal(jax.random.key(1), (tile, tile)) \
        / (2 * np.sqrt(tile))
    compiled_kernel(lambda a: cops.burn(a, iters=iters, tile=tile), x)
    got = cops.burn(x, iters=iters, tile=tile)
    with jax.default_matmul_precision("highest"):
        want = cref.burn_tile(x, iters=iters)
    e_c = rel_err(got, want)
    # the MXU may take f32 operands in bf16 passes (2^-9 relative per
    # product); the 0.5 contraction keeps that from compounding
    assert e_c <= 1e-2, e_c

    buf = jnp.arange(1 << 22, dtype=jnp.float32)        # 16 MiB
    compiled_kernel(lambda a: mops.stream(a, iters=3), buf)
    got_m = mops.stream(buf, iters=3)
    want_m = mref.stream_pass(mref.stream_pass(mref.stream_pass(buf)))
    assert np.array_equal(np.asarray(got_m), np.asarray(want_m))

    cfg = get_config(ARCH)
    hq, hk, hd, S = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 2048
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (hq, S, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (hk, S, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (hk, S, hd), jnp.bfloat16)

    def fa(q, k, v):
        return fops.flash_attention(q, k, v, causal=True, group=hq // hk)
    compiled_kernel(fa, q, k, v)
    got_f = fa(q, k, v)
    with jax.default_matmul_precision("highest"):
        want_f = fref.flash_attention(q, k, v, causal=True, group=hq // hk)
    # both sides round p and the output to bf16 (2^-9 relative) once
    e_f = rel_err(got_f.astype(jnp.float32), want_f.astype(jnp.float32))
    assert e_f <= 1e-2, e_f
    log("6 kernels", f"compiled on chip: compute_atom tile={tile} "
                     f"iters={iters} rel_err={e_c}; memory_atom 16 MiB x3 "
                     f"passes exact; flash_attention {hq}/{hk} heads hd={hd} "
                     f"S={S} rel_err={e_f}")


# ---------------------------------------------------------------------------
# one chip: phase 5 (this process coordinates, the fleet worker holds the chip)
# ---------------------------------------------------------------------------

def fleet_phase(out: str) -> dict:
    from jax._src import xla_bridge

    from repro.fleet import FleetConfig

    with open(os.path.join(out, "device_phases.json")) as f:
        done = json.load(f)
    store = ProfileStore(os.path.join(out, "profiles"))
    profiles = [store.latest(c, t) for c, t in done["keys"]]
    t0 = time.perf_counter()
    fleet = Emulator().emulate_many(profiles,
                                    config=FleetConfig.process(max_workers=1))
    wall = time.perf_counter() - t0
    assert not xla_bridge.backends_are_initialized(), \
        "the fleet coordinator initialized a JAX backend"
    for prof, rep, want in zip(profiles, fleet.reports, done["consumed"]):
        assert rep.consumed == ResourceVector.from_dict(want), prof.command
    log("5 fleet", f"process fleet, 1 worker on the chip: "
                   f"{len(fleet.reports)} profiles replayed in {wall} s; "
                   f"consumed totals bit-identical to phase 4; coordinator "
                   f"initialized no JAX backend")
    return done["device"]


def cache_check() -> None:
    """Compiled programs went to ``JAX_COMPILATION_CACHE_DIR`` when that is
    set, and to the checkout's fixed directory otherwise: nowhere else."""
    from repro.launch.compile_cache import DEFAULT_DIR
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    where = env or DEFAULT_DIR
    n = len(os.listdir(where)) if os.path.isdir(where) else 0
    assert n > 0, f"no compiled program was cached in {where}"
    if env and os.path.realpath(env) != os.path.realpath(DEFAULT_DIR):
        assert not os.path.exists(DEFAULT_DIR), \
            f"{DEFAULT_DIR} was written although the cache is {env}"
    log("cache", f"{n} entries in {where} "
                 f"({'JAX_COMPILATION_CACHE_DIR' if env else 'default'})")


# ---------------------------------------------------------------------------
# four chips: sharded serving on a 2x2 mesh
# ---------------------------------------------------------------------------

def sharded_setup(model, mesh):
    """The serving steps under the repo's megatron rules on ``mesh``, and
    the NamedShardings its parameters take there (serving weights are
    2-D sharded, model x data, as in decode)."""
    from jax.sharding import NamedSharding

    from repro.parallel.sharding import DECODE_RULES, make_rules
    from repro.serve.step import make_decode_step, make_prefill_step

    specs = model.param_specs(make_rules(mesh, DECODE_RULES))
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs)
    prefill = jax.jit(make_prefill_step(model, MAX_LEN, mesh=mesh,
                                        with_logits=True))
    decode = jax.jit(make_decode_step(model, mesh=mesh, with_logits=True),
                     donate_argnums=2)
    return prefill, decode, shardings


def four_chips(seed: int, out: str, steps: int = 8) -> dict:
    from repro.launch.mesh import make_mesh
    from repro.serve.step import make_decode_step, make_prefill_step

    enable_compile_cache()
    info, spec = require_tpu(count=4)
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices())
    cfg = get_config(ARCH)
    model = build_model(cfg, SERVE_RUN)
    params = jax.jit(model.init)(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (BATCH, PROMPT)), jnp.int32)}

    # one device, the same weights: the reference
    pre0 = jax.jit(make_prefill_step(model, MAX_LEN, with_logits=True))
    dec0 = jax.jit(make_decode_step(model, with_logits=True),
                   donate_argnums=2)
    tok, cache, lg = pre0(params, batch)
    toks, want = [tok], [lg]
    for _ in range(steps):
        tok, cache, lg = dec0(params, tok, cache)
        toks.append(tok)
        want.append(lg)
    del cache

    prefill, decode, shardings = sharded_setup(model, mesh)
    sparams = jax.device_put(params, shardings)
    del params                      # 3.1 GB on the first chip
    leaves = jax.tree.leaves(sparams)
    assert all(len(a.sharding.device_set) == 4 for a in leaves)
    split = sum(not a.sharding.is_fully_replicated for a in leaves)
    tok1, cache1, lg = prefill(sparams, batch)
    got = [lg]
    for t in toks[:-1]:             # the reference's tokens: same inputs
        tok1, cache1, lg = decode(sparams, t, cache1)
        got.append(lg)
    cache_leaves = jax.tree.leaves(cache1)
    assert all(len(a.sharding.device_set) == 4 for a in cache_leaves)
    errs = [rel_err(np.asarray(g, np.float32)[:, 0],
                    np.asarray(w, np.float32)[:, 0])
            for g, w in zip(got, want)]
    log("4x serve", f"2x2 mesh {dict(mesh.shape)}: {split}/{len(leaves)} "
                    f"parameter arrays partitioned, every array on 4 chips; "
                    f"prefill + {steps} decode steps, logits rel_err vs one "
                    f"device max={max(errs)} (tol {LOGITS_REL_TOL})")
    assert max(errs) <= LOGITS_REL_TOL, errs

    # profile the sharded executables and replay them on the same mesh
    tok_s, cache_s, _ = jax.eval_shape(prefill, sparams, batch)
    exes = (("prefill", prefill.lower(sparams, batch).compile()),
            ("decode", decode.lower(sparams, tok_s, cache_s).compile()))
    em = Emulator(mesh=mesh)
    store = ProfileStore(os.path.join(out, "profiles_2x2"))
    wire_rows = 0
    for step, exe in exes:
        prof = profile_compiled(exe, command=f"{ARCH}:{step}:2x2",
                                tags={"arch": ARCH, "mesh": "2x2",
                                      "step": step}, mesh=mesh)
        store.add(prof)
        ici = prof.totals.ici_total
        assert ici > 0, f"{step}: no collective bytes in the sharded profile"
        fused_sched = em.compile(prof)
        barrier_sched = em.compile(prof, keep_collectives=True)
        em.replay(fused_sched, command=prof.command)            # compiles
        fused = em.replay(fused_sched, command=prof.command)
        em.replay(barrier_sched, command=prof.command)
        barrier = em.replay(barrier_sched, command=prof.command)
        assert fused.consumed == barrier.consumed
        want_fused = sum(int((s.table[:, 2] > 0).sum())
                         for s in fused_sched.segments)
        want_barrier = sum(1 for b in barrier_sched.barriers
                           if b.resources.ici_total > 0)
        # a row whose wire amount rounds below half a collective block runs
        # no collective in the fused scan (the barrier path clamps it up)
        assert fused.n_collective_dispatches == want_fused
        assert barrier.n_collective_dispatches == want_barrier
        wire_rows += want_fused
        log("4x replay", f"{step}: ici_bytes={ici} by kind "
                         f"{prof.totals.ici_bytes}; fused "
                         f"{fused.n_dispatches} dispatches, "
                         f"{want_fused} collective rows, "
                         f"ttc_s={fused.ttc_s}; barrier "
                         f"{barrier.n_dispatches} dispatches, "
                         f"{want_barrier} collective legs, "
                         f"ttc_s={barrier.ttc_s}; consumed identical")
    assert wire_rows > 0, "no collective ran on the mesh in the fused replay"
    return info


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "chip_smoke"))
    ap.add_argument("--four-chips", action="store_true",
                    help="run the 2x2-mesh path and its comparison only")
    ap.add_argument("--device-phases", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.device_phases:
        device_phases(args.seed, args.out)
        return 0
    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)
    if args.four_chips:
        info = four_chips(args.seed, args.out)
    else:
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--device-phases",
             "--seed", str(args.seed), "--out", args.out], timeout=900)
        if child.returncode != 0:
            print(f"chip_smoke.py: device phases failed (exit "
                  f"{child.returncode})", file=sys.stderr)
            return child.returncode or 1
        info = fleet_phase(args.out)
    cache_check()
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
