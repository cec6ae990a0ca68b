"""Compile the chip path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
Pallas block larger than VMEM, a slice off the tiling, an executable too
large for HBM.  These tests compile the flash-attention kernel at
Qwen2-1.5B's widths, one fused segment program at the emulator's default
tile and block, the compute and memory legs' programs with the ring a TPU
gets, the collective atom's program on four chips, and Qwen2-1.5B's
decode step, whose profile must count the FLOPs its shapes imply (the TPU
emits matmuls as convolutions).  Qwen2-7B's
tensor-parallel prefill, compiled for the four chips of the described
v5e:2x2, must profile to the wire its sharded step moves, and the
one-chip Qwen2-1.5B steps to the totals they have always had.

The topology is described inside a module-scoped fixture and never while
a module is imported: only one process may load the TPU library, and the
test workers all import this file.
"""
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.atoms import COMPUTE_GROUP

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mosaic(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_qwen2_widths(one_chip):
    from repro.configs import get_config
    from repro.kernels.flash_attention import kernel as fk
    cfg = get_config("qwen2-1.5b")
    hq, hk, hd, S = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 2048
    compiled = jax.jit(lambda q, k, v: fk.flash_attention(
        q, k, v, causal=True, group=hq // hk, interpret=False)).lower(
        _sds(one_chip, (hq, S, hd), jnp.bfloat16),
        _sds(one_chip, (hk, S, hd), jnp.bfloat16),
        _sds(one_chip, (hk, S, hd), jnp.bfloat16)).compile()
    assert _mosaic(compiled)


def _fused_segment(one_chip):
    """The fused compute-and-memory segment program at the emulator's
    default tile and block, compiled with the ring a TPU gets."""
    from repro.core import Emulator
    from repro.core.atoms import ring_windows
    runner = Emulator()._segments
    ring = (ring_windows(runner.block_bytes, "tpu"),
            runner.block_bytes // 512, 128)
    fn = runner._fn(8, True, True, False)
    return fn.lower(_sds(one_chip, ring),
                    (_sds(one_chip, (COMPUTE_GROUP, runner.tile,
                                     runner.tile)),
                     _sds(one_chip, (), jnp.int32)),
                    _sds(one_chip, (8, 3), jnp.int32)).compile()


def _memory_plan(one_chip):
    """The per-sample memory atom's stream program, at the default block
    and with the ring a TPU gets."""
    from repro.core.atoms import MemoryAtom, ring_windows
    atom = MemoryAtom()
    ring = (ring_windows(atom.block_bytes, "tpu"),
            atom.block_bytes // 512, 128)
    return atom._stream_fn().lower(
        _sds(one_chip, ring), _sds(one_chip, (), jnp.int32),
        _sds(one_chip, (), jnp.int32)).compile()


def _compute_plan(one_chip):
    """The per-sample compute atom's burn program at the default tile."""
    from repro.core.atoms import ComputeAtom
    atom = ComputeAtom()
    return atom._loop_fn().lower(
        _sds(one_chip, (COMPUTE_GROUP, atom.tile, atom.tile)),
        _sds(one_chip, (), jnp.int32)).compile()


def test_fused_segment_compiles_at_default_tile_and_block(one_chip):
    assert "while" in _fused_segment(one_chip).as_text()


def _computations(text):
    """A compiled module's computations by name: name -> its text."""
    comps = {}
    for block in re.split(r"\n(?=%|ENTRY )", text):
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) ", block)
        if m:
            comps[m.group(1)] = block[:block.find("\n}") + 3]
    return comps


def _inlined(comps, name):
    """A computation's text with that of every fusion it calls, but not
    the bodies of its loops."""
    text = comps[name]
    for callee in re.findall(r"calls=%([\w.\-]+)", text):
        text += _inlined(comps, callee)
    return text


def _matmul(shape):
    """A matmul (the TPU emits them as convolutions) with an f32 result of
    ``shape``."""
    dims = ",".join(map(str, shape))
    return re.compile(rf"= f32\[{dims}\]\{{[^}}]*\}} "
                      rf"(convolution|dot)\(")


@pytest.mark.parametrize("program", [_fused_segment, _compute_plan])
def test_compute_leg_burns_a_tile_group_a_loop_trip(one_chip, program):
    """The compute leg is a loop whose body multiplies the whole group of
    ``COMPUTE_GROUP`` tiles in one batched matmul and holds no further
    loop, and a loop of one-tile trips for a row's remainder, each loop
    directly in the segment's scan body.  (The benchmark's trace reduction
    counts such a loop whole to the compute leg.)"""
    comps = _computations(program(one_chip).as_text())
    loops = {}                                 # body -> computation holding it
    for name, text in comps.items():
        for body in re.findall(r" while\(.*?body=%([\w.\-]+)", text):
            loops[body] = name
    t = 256
    grouped = [b for b in loops
               if _matmul((COMPUTE_GROUP, t, t)).search(_inlined(comps, b))]
    single = [b for b in loops if _matmul((t, t)).search(_inlined(comps, b))]
    assert len(grouped) == 1 and len(single) == 1, (grouped, single)
    for body in grouped + single:
        assert " while(" not in _inlined(comps, body), body
    holder = loops[grouped[0]]
    assert loops[single[0]] == holder
    if program is _fused_segment:              # the scan's body holds both
        assert holder in loops, holder
    else:
        assert holder not in loops, holder


#: an f32 array in a compiled module's text: its dims and its layout
_F32 = re.compile(r"f32\[([\d,]+)\]\{([^}]*)\}")


@pytest.mark.parametrize("program", [_fused_segment, _memory_plan])
def test_memory_leg_ring_stays_in_hbm(one_chip, program):
    """The memory leg's loop carries a ring larger than the v5e's 128 MiB
    of VMEM, laid out in HBM (no ``S(1)`` memory space); no launch copies
    it, since the carry is donated; and each pass reads, scales and
    writes back its block in one in-place fusion at a tile-aligned
    offset, so every pass moves the bytes ``bytes_per_iter`` charges
    through HBM."""
    text = program(one_chip).as_text()
    carried = [(dims, layout)
               for line in text.splitlines() if " while(" in line
               for dims, layout in _F32.findall(line.split(" while(")[0])]
    big = [(dims, layout) for dims, layout in carried
           if 4 * math.prod(map(int, dims.split(","))) > 128 << 20]
    assert big, carried
    assert all("S(1)" not in layout for _, layout in big), big
    ring = f"f32[{big[0][0]}]"
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    copies = [line for line in entry.splitlines()
              if re.search(r" copy(-start)?\(", line) and ring in line]
    assert not copies, copies
    assert "input_output_alias=" in text                 # donated
    # the compute leg's remainder loop updates its first tile in place too
    tiles = f"f32[{COMPUTE_GROUP},256,256]"
    passes = [line for line in text.splitlines()
              if "dynamic-update-slice_fusion" in line and " fusion(" in line
              and not line.split(" = ")[1].startswith(tiles)]
    assert passes and all(line.split(" = ")[1].startswith(ring)
                          for line in passes), passes


def test_qwen2_decode_profile_counts_analytic_flops(one_chip):
    from repro.configs import get_config
    from repro.configs.run import SERVE_RUN
    from repro.core import profile_compiled
    from repro.models.model_zoo import build_model
    from repro.serve.step import make_decode_step

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    cfg = get_config("qwen2-1.5b")
    model = build_model(cfg, SERVE_RUN)
    B, T = 8, 1024

    def place(tree):
        return jax.tree.map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(model.init, jax.random.key(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(B, T)))
    tok = _sds(one_chip, (B, 1), jnp.int32)
    compiled = jax.jit(make_decode_step(model), donate_argnums=2).lower(
        params, tok, cache).compile()
    assert "convolution" in compiled.as_text()
    prof = profile_compiled(compiled, command="qwen2-1.5b:decode")
    want = smoke.analytic_flops(cfg, B, 1, T)
    assert abs(prof.totals.flops / want - 1) <= smoke.FLOPS_REL_TOL, \
        (prof.totals.flops, want)


def _abstract(model, shardings):
    """The model's parameters as shapes, each leaf placed by the sharding
    of the same path in ``shardings``."""
    return jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        model.abstract(), shardings)


#: Qwen2-1.5B's served steps at the benchmark's sizes (prefill 4 x 2048
#: into a 2048-slot cache; decode at batch 32 over a 4096-slot cache),
#: profiled for one described v5e: (FLOPs, HBM bytes).  These are the
#: totals the profiler gave before it learned the TPU's collective fusions
#: (the decode pair is also what the chip's own executable profiles to);
#: a step on one chip has no collective, so they must not move.
ONE_CHIP_TOTALS = {"prefill": (24414251575580.0, 57463323648.0),
                   "decode": (121824653084.0, 7198662656.0)}


@pytest.mark.parametrize("step", sorted(ONE_CHIP_TOTALS))
def test_one_chip_qwen2_profiles_keep_their_totals(one_chip, step):
    from repro.configs import get_config
    from repro.configs.run import SERVE_RUN
    from repro.core import profile_compiled
    from repro.models.model_zoo import build_model
    from repro.serve.step import make_decode_step, make_prefill_step

    model = build_model(get_config("qwen2-1.5b"), SERVE_RUN)
    params = _abstract(model, jax.tree.map(lambda _: one_chip,
                                           model.abstract()))
    if step == "prefill":
        compiled = jax.jit(make_prefill_step(model, 2048, with_logits=True)
                           ).lower(params, {"tokens": _sds(
                               one_chip, (4, 2048), jnp.int32)}).compile()
    else:
        cache = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                             jax.eval_shape(lambda: model.init_cache(32,
                                                                     4096)))
        compiled = jax.jit(make_decode_step(model, with_logits=True),
                           donate_argnums=2).lower(
            params, _sds(one_chip, (32, 1), jnp.int32), cache).compile()
    t = profile_compiled(compiled, command=f"qwen2-1.5b:{step}").totals
    assert (t.flops, t.hbm_bytes, t.ici_bytes) == \
        (*ONE_CHIP_TOTALS[step], {})


def test_qwen2_7b_tp_prefill_profiles_the_wire_it_moves(v5e_2x2):
    """Qwen2-7B prefill of 4 x 2048 tokens on a 1 x 4 mesh (Megatron
    tensor parallelism with a sequence-parallel residual), compiled for the
    four chips of a v5e:2x2.  Per layer and chip, ring model, with
    ``act`` = B·S·d bf16 and ``n`` = 4:

    * all-gather: 2 x act·(n-1)/n, the residual gathered before attention
      and before the MLP.  The compiler splits the first into a chain of
      seven async collective fusions, which count once.
    * reduce-scatter: 1 x act·(n-1)/n, attention's output projection (an
      all-reduce-scatter fusion), plus one for the vocabulary-sharded
      embedding lookup.  The MLP's reduce-scatter the compiler runs as a
      bidirectional ring of collective-permutes instead.
    * collective-permute: that ring, 5 partial sums of act/n each, and the
      query and key weights gathered by 3 permutes each of one chip's
      shard (d·(hq/n)·hd and d·(hk/n)·hd bf16).
    * all-to-all: as the HLO has it: the queries (in two halves of the
      head dim), the keys' rotary halves and the K and V cache between
      sequence and head shards, B·(S/n)·hd·(hq + 3 hk) bf16 x (n-1)/n.

    Before the fix the chain counted seven times, the reduce-scatter at
    all-reduce cost and every permute at zero: 12.86 GB against ~6.86."""
    from jax.sharding import NamedSharding

    from repro.configs import get_config
    from repro.configs.run import SERVE_RUN
    from repro.core import profile_compiled
    from repro.launch.mesh import make_mesh
    from repro.models.model_zoo import build_model
    from repro.parallel.sharding import DECODE_RULES, make_rules
    from repro.serve.engine import Engine

    cfg = get_config("qwen2-7b")
    model = build_model(cfg, SERVE_RUN)
    mesh = make_mesh((1, 4), ("data", "model"), devices=v5e_2x2.devices)
    specs = model.param_specs(make_rules(mesh, DECODE_RULES))
    params = _abstract(model, jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs))
    B, S, n = 4, 2048, 4
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32,
                                  sharding=NamedSharding(mesh, P()))
    engine = Engine(model, params, batch_slots=B, max_len=S, mesh=mesh,
                    keep_logits=True)
    compiled = engine.prefill.lower(params, {"tokens": tokens}).compile()
    prof = profile_compiled(compiled, command="qwen2-7b:prefill", mesh=mesh)

    L, d, hd = cfg.num_layers, cfg.d_model, cfg.head_dim
    hq, hk, bf16, ring = cfg.num_heads, cfg.num_kv_heads, 2, (n - 1) / n
    act = B * S * d * bf16
    want = {
        "all-gather": 2 * L * act * ring,
        "reduce-scatter": (L + 1) * act * ring,
        "collective-permute": L * (5 * act / n
                                   + 3 * d * (hq + hk) // n * hd * bf16),
        "all-to-all": L * B * S // n * hd * (hq + 3 * hk) * bf16 * ring,
    }
    got = prof.totals.ici_bytes
    for kind, w in want.items():
        assert abs(got[kind] / w - 1) <= 0.05, (kind, got[kind], w)
    assert sum(got.values()) <= 1.05 * sum(want.values()), got


def test_collective_plan_compiles_for_four_chips(v5e_2x2):
    """The collective atom's one program, a loop of its all-reduce over the
    fixed block with a traced trip count, compiles for the four chips of a
    v5e:2x2 with the all-reduce in the loop's body: one compilation serves
    every wire amount, as on the fused path."""
    from jax.sharding import NamedSharding

    from repro.core.atoms import COLL_BLOCK_ELEMS, CollectiveAtom
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 4), ("data", "model"), devices=v5e_2x2.devices)
    atom = CollectiveAtom(mesh)
    text = atom._burn_fn().lower(
        _sds(NamedSharding(mesh, P("model")), (4 * COLL_BLOCK_ELEMS,)),
        _sds(NamedSharding(mesh, P()), (), jnp.int32)).compile().as_text()
    comps = _computations(text)
    bodies = [b for t in comps.values()
              for b in re.findall(r" while\(.*?body=%([\w.\-]+)", t)]
    assert len(bodies) == 1, bodies
    assert "all-reduce" in _inlined(comps, bodies[0])
