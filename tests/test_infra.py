"""Infrastructure units: checkpoint atomicity/elastic restore, data pipeline
determinism, HLO walker parsing, layer plan, input specs."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.checkpoint.ckpt import CheckpointManager
from repro.configs import SHAPES, get_config, list_archs, cell_is_runnable
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.core.hlo_analysis import (ModuleCost, analyze_hlo, parse_module,
                                     shape_bytes, shape_numel)


# ---------------------------------------------------------------------------
# checkpoints
# ---------------------------------------------------------------------------

def _state(seed=0):
    k = jax.random.key(seed)
    return {"params": {"w": jax.random.normal(k, (8, 16)),
                       "b": jnp.zeros((16,))},
            "opt": {"mu": {"w": jnp.ones((8, 16)), "b": jnp.zeros((16,))},
                    "step": jnp.int32(7)}}


def test_checkpoint_roundtrip_and_gc(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    for step in (10, 20, 30):
        cm.save(step, _state(step))
    assert cm.all_steps() == [20, 30]            # gc keeps 2
    got, extra = cm.restore(20)
    np.testing.assert_allclose(np.asarray(got["params"]["w"]),
                               np.asarray(_state(20)["params"]["w"]))
    assert int(got["opt"]["step"]) == 7


def test_checkpoint_uncommitted_invisible(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    cm.save(5, _state())
    # simulate a crash mid-write: drop the COMMIT marker
    os.remove(os.path.join(str(tmp_path), "step_00000005", "COMMIT"))
    assert cm.latest_step() is None


def test_checkpoint_corruption_detected(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    cm.save(1, _state())
    d = os.path.join(str(tmp_path), "step_00000001")
    victim = [f for f in os.listdir(d) if f.endswith(".npy")][0]
    arr = np.load(os.path.join(d, victim))
    np.save(os.path.join(d, victim), arr + 1)
    with pytest.raises(IOError, match="corruption"):
        cm.restore(1)


def test_checkpoint_async(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    cm.save_async(3, _state())
    cm.wait()
    assert cm.latest_step() == 3


def test_checkpoint_elastic_restore_reshards(tmp_path):
    """Restore places leaves with provided shardings (elastic re-layout)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    cm = CheckpointManager(str(tmp_path))
    cm.save(1, _state())
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    sh = {"params": {"w": NamedSharding(mesh, P("data")),
                     "b": NamedSharding(mesh, P())},
          "opt": {"mu": {"w": NamedSharding(mesh, P()),
                         "b": NamedSharding(mesh, P())},
                  "step": NamedSharding(mesh, P())}}
    got, _ = cm.restore(1, shardings=sh)
    assert got["params"]["w"].sharding.is_equivalent_to(
        sh["params"]["w"], 2)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_resumable():
    cfg = DataConfig(vocab_size=97, seq_len=32, global_batch=8, seed=5)
    d1, d2 = SyntheticLM(cfg), SyntheticLM(cfg)
    b1 = d1.batch_at(42)
    b2 = d2.batch_at(42)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    # shifted-target invariant
    np.testing.assert_array_equal(np.asarray(b1["tokens"][:, 1:]),
                                  np.asarray(b1["targets"][:, :-1]))


def test_data_shards_are_disjoint_slices():
    cfg = DataConfig(vocab_size=97, seq_len=16, global_batch=8, seed=1)
    d = SyntheticLM(cfg)
    s0 = d.batch_at(3, shard_index=0, num_shards=2)
    s1 = d.batch_at(3, shard_index=1, num_shards=2)
    assert s0["tokens"].shape == (4, 16)
    assert not np.array_equal(np.asarray(s0["tokens"]),
                              np.asarray(s1["tokens"]))


@given(step=st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_data_structure_learnable(step):
    cfg = DataConfig(vocab_size=64, seq_len=64, global_batch=2, seed=0,
                     structure=1.0)
    b = SyntheticLM(cfg).batch_at(step)
    t = np.asarray(b["tokens"])
    # fully structured: next = (31*t + 17) % V
    np.testing.assert_array_equal((31 * t[:, :-1] + 17) % 64, t[:, 1:])


# ---------------------------------------------------------------------------
# HLO walker units
# ---------------------------------------------------------------------------

def test_shape_parsing():
    assert shape_bytes("f32[512,1024]{1,0}") == 512 * 1024 * 4
    assert shape_bytes("bf16[8]") == 16
    assert shape_bytes("(f32[2,2]{1,0}, s32[3])") == 16 + 12
    assert shape_bytes("pred[]") == 1
    assert shape_numel("f32[3,5]") == 15


SYNTH_HLO = """
HloModule test

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %d = f32[8,8] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%ip, %d)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8,8]) tuple(%z, %a)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body
  %ar = f32[8,8] all-reduce(%a), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%body
  ROOT %r = f32[8,8] get-tuple-element(%w), index=1
}
"""


def test_walker_trip_count_and_collectives_synthetic():
    cost = analyze_hlo(SYNTH_HLO)
    # 5 iterations of an 8x8x8 matmul
    assert cost.flops >= 5 * 2 * 8 ** 3
    assert cost.flops < 5 * 2 * 8 ** 3 + 100     # + add ops
    coll = cost.collective_bytes()
    # ring all-reduce of 256B over 4 devices: 2*256*3/4
    assert coll["all-reduce"] == pytest.approx(2 * 256 * 3 / 4)


# One layer of a tensor-parallel step as the TPU compiler writes it, cut to
# its collectives, run 3 times by a loop: an all-gather split into a chain
# of async collective fusions that share one channel (each piece holding a
# gather of the whole result), an all-reduce-scatter fusion, and an async
# collective-permute whose shape holds (operand, result, context).
CHAIN_HLO = """
HloModule chain

%ag_start (p0: f32[2,8]) -> (f32[2,8], f32[8,8], u32[]) {
  %p0 = f32[2,8] parameter(0)
  %ag.1 = f32[8,8] all-gather(%p0), channel_id=7, replica_groups=[1,4]<=[4], dimensions={0}, frontend_attributes={chain_id="0"}
  ROOT %cc.1 = (f32[2,8], f32[8,8], u32[]) custom-call(%ag.1), custom_call_target="AsyncCollectiveStart"
}

%async_collective_fusion.1 (p0: f32[2,8], p1: f32[8,8], w: f32[8,8]) -> (f32[2,8], f32[8,8], f32[2,8]) {
  %p0 = f32[2,8] parameter(0)
  %p1 = f32[8,8] parameter(1)
  %w = f32[8,8] parameter(2)
  %d = f32[2,8] dot(%p0, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag.2 = f32[8,8] all-gather(%p0), channel_id=7, replica_groups=[1,4]<=[4], dimensions={0}, frontend_attributes={chain_id="0"}
  ROOT %t = (f32[2,8], f32[8,8], f32[2,8]) tuple(%p0, %ag.2, %d)
}

%ag_done (p0: f32[2,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[2,8] parameter(0)
  %p1 = f32[8,8] parameter(1)
  %ag.3 = f32[8,8] all-gather(%p0), channel_id=7, replica_groups=[1,4]<=[4], dimensions={0}, frontend_attributes={chain_id="0"}
  ROOT %cc.2 = f32[8,8] custom-call(%p0, %p1, %ag.3), custom_call_target="AsyncCollectiveDone"
}

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %s = f32[] add(%x, %y)
}

%all-reduce-scatter.3 (input: f32[8,8]) -> f32[2,8] {
  %input = f32[8,8] parameter(0)
  %ar = f32[8,8] all-reduce(%input), channel_id=9, replica_groups={{0,1,2,3}}, to_apply=%add
  %zero = s32[] constant(0)
  ROOT %ds = f32[2,8] dynamic-slice(%ar, %zero, %zero), dynamic_slice_sizes={2,8}
}

%body (p: (s32[], f32[2,8], f32[8,8])) -> (s32[], f32[2,8], f32[8,8]) {
  %p = (s32[], f32[2,8], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[2,8] get-tuple-element(%p), index=1
  %w = f32[8,8] get-tuple-element(%p), index=2
  %f.1 = (f32[2,8], f32[8,8], u32[]) fusion(%x), kind=kCustom, calls=%ag_start
  %x1 = f32[2,8] get-tuple-element(%f.1), index=0
  %g1 = f32[8,8] get-tuple-element(%f.1), index=1
  %f.2 = (f32[2,8], f32[8,8], f32[2,8]) fusion(%x1, %g1, %w), kind=kCustom, calls=%async_collective_fusion.1
  %x2 = f32[2,8] get-tuple-element(%f.2), index=0
  %g2 = f32[8,8] get-tuple-element(%f.2), index=1
  %full = f32[8,8] fusion(%x2, %g2), kind=kCustom, calls=%ag_done
  %rs = f32[2,8] fusion(%full), kind=kCustom, calls=%all-reduce-scatter.3
  %cp = (f32[2,8], f32[2,8], u32[], u32[]) collective-permute-start(%rs), channel_id=11, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %y = f32[2,8] collective-permute-done(%cp)
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[2,8], f32[8,8]) tuple(%ip, %y, %w)
}

%cond (p: (s32[], f32[2,8], f32[8,8])) -> pred[] {
  %p = (s32[], f32[2,8], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[2,8], w: f32[8,8]) -> f32[2,8] {
  %a = f32[2,8] parameter(0)
  %w = f32[8,8] parameter(1)
  %z = s32[] constant(0)
  %init = (s32[], f32[2,8], f32[8,8]) tuple(%z, %a, %w)
  %loop = (s32[], f32[2,8], f32[8,8]) while(%init), condition=%cond, body=%body
  ROOT %r = f32[2,8] get-tuple-element(%loop), index=1
}
"""


def test_walker_counts_chained_gather_and_reduce_scatter_once():
    """Per trip and chip over 4 chips: one gather of 256 B at 3/4; one
    reduce-scatter whose 64 B result is a quarter of what it reduces, at
    64 x 3 (not an all-reduce of 256 B at 2 x 3/4); one permute of its
    64 B result (the operand and context in the start's tuple move
    nothing more).  The loop runs 3 trips, each its own execution."""
    cost = analyze_hlo(CHAIN_HLO)
    assert cost.collective_bytes() == {"all-gather": 3 * 256 * 3 / 4,
                                       "reduce-scatter": 3 * 64 * 3,
                                       "collective-permute": 3 * 64}
    permute = next(c for c in cost.collectives
                   if c.kind == "collective-permute")
    assert (permute.group_size, permute.stride) == (4, 1)
    assert cost.flops == pytest.approx(3 * 2 * 2 * 8 * 8, abs=3 * 10)


# The TPU backend emits matmuls as convolutions; these lines are the shapes
# and attributes it printed for Qwen2-1.5B's projections and LM head.
TPU_CONVS = [
    # x[8,1536] @ w[1536,4096]
    ("bf16[8,1536]", "bf16[1536,4096]",
     "bf16[8,4096]{1,0:T(8,128)(2,1)} convolution(%a, %b), "
     "dim_labels=bf_io->bf", 8 * 4096 * 1536),
    # LM head against the tied embedding [V, d]: contraction is d, not V
    ("bf16[8,1536]", "bf16[151936,1536]",
     "bf16[8,151936]{1,0} convolution(%a, %b), dim_labels=bf_oi->bf",
     8 * 151936 * 1536),
    # bsd,dhk->bshk with the head dim riding as a padded window: one real
    # tap per output position
    ("bf16[8,1536,1]", "bf16[1536,12,128]",
     "bf16[8,12,128]{2,0,1} convolution(%a, %b), "
     "window={size=12 pad=11_11 rhs_reversal=1}, dim_labels=bf0_i0o->b0f",
     8 * 1536 * 12 * 128),
]


@pytest.mark.parametrize("lhs,rhs,conv,macs", TPU_CONVS,
                         ids=["bf_io", "bf_oi", "bf0_i0o"])
def test_walker_counts_tpu_convolutions_by_dim_labels(lhs, rhs, conv, macs):
    text = (f"HloModule m\n\nENTRY %main (a: {lhs}, b: {rhs}) -> f32[] {{\n"
            f"  %a = {lhs} parameter(0)\n  %b = {rhs} parameter(1)\n"
            f"  ROOT %c = {conv}\n}}\n")
    assert analyze_hlo(text).flops == 2.0 * macs


# ---------------------------------------------------------------------------
# layer plan + cell gating
# ---------------------------------------------------------------------------

def test_layer_plan_shapes():
    from repro.models.transformer import layer_plan
    plans = {a: layer_plan(get_config(a)) for a in list_archs()}
    assert plans["qwen2-7b"] == [("scan", 0, 28, False)]
    assert plans["gemma2-2b"] == [("pair_scan", 13)]
    hy = plans["hymba-1.5b"]
    kinds = [g[0] for g in hy]
    assert kinds == ["single", "scan", "single", "scan", "single"]
    total = sum(1 if g[0] == "single" else g[2] for g in hy)
    assert total == 32


def test_cell_gating_counts():
    runnable = skipped = 0
    for a in list_archs():
        for s in SHAPES.values():
            ok, why = cell_is_runnable(get_config(a), s)
            runnable += ok
            skipped += not ok
            if not ok:
                assert why
    assert runnable == 32 and skipped == 8


# ---------------------------------------------------------------------------
# device bring-up: peaks by device kind, interpret mode, compile cache
# ---------------------------------------------------------------------------

def test_hardware_spec_is_looked_up_by_device_kind():
    from repro.core.hardware import TPU_V5E, spec_for_device_kind
    assert spec_for_device_kind("TPU v5 lite") == TPU_V5E
    assert spec_for_device_kind("TPU v5 lite", 4) == TPU_V5E.with_chips(4)
    with pytest.raises(KeyError, match="no hardware spec"):
        spec_for_device_kind("TPU v9 imaginary")


def test_pallas_interprets_only_on_the_cpu():
    from repro.kernels import resolve_interpret
    assert jax.default_backend() == "cpu"
    assert resolve_interpret() is True
    assert resolve_interpret(False) is False


def _run_python(code: str, **env) -> str:
    import subprocess
    import sys
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(env)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    full["PYTHONPATH"] = os.path.join(root, "src")
    out = subprocess.run([sys.executable, "-c", code], env=full, check=True,
                         capture_output=True, text=True, timeout=120)
    return out.stdout.strip().splitlines()[-1]


def test_emulator_construction_touches_no_device():
    """A fleet coordinator builds an Emulator before its workers start; it
    must not hold the accelerator they need."""
    code = ("from jax._src import xla_bridge\n"
            "from repro.core import Emulator\n"
            "em = Emulator()\n"
            "em.spec()\n"
            "print(xla_bridge.backends_are_initialized())")
    assert _run_python(code, JAX_PLATFORMS="cpu") == "False"


@pytest.mark.parametrize("env_dir", [False, True], ids=["default", "env"])
def test_compile_cache_directory(tmp_path, env_dir):
    """The cache is JAX_COMPILATION_CACHE_DIR when set (JAX reads it, the
    code sets nothing) and the checkout's fixed directory otherwise; a
    process held to the CPU keeps none.  No backend is initialized here,
    so naming a TPU platform only exercises the configuration."""
    from repro.launch.compile_cache import DEFAULT_DIR
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "got = enable_compile_cache()\n"
            "print(got, jax.config.jax_compilation_cache_dir,"
            " jax.config.jax_persistent_cache_min_compile_time_secs)")
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_dir else {}
    want = str(tmp_path) if env_dir else DEFAULT_DIR
    assert _run_python(code, JAX_PLATFORMS="tpu", **env) == \
        f"{want} {want} 0.0"
    assert _run_python(code, JAX_PLATFORMS="cpu", **env).startswith("None ")
    assert os.path.basename(DEFAULT_DIR) == ".jax_cache"
