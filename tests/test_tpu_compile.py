"""Compile the chip path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
Pallas block larger than VMEM, a slice off the tiling, an executable too
large for HBM.  These tests compile the Pallas kernels at the sizes the
atoms and Qwen2-1.5B use, one fused segment program at the emulator's
default tile and block, the memory leg's programs with the ring a TPU
gets, and Qwen2-1.5B's decode step, whose profile must count the FLOPs its
shapes imply (the TPU emits matmuls as convolutions).

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
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mosaic(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_compute_atom_compiles_with_traced_iters(one_chip):
    from repro.kernels.compute_atom import kernel as ck
    compiled = jax.jit(
        lambda x, n: ck.burn_tile(x, iters=n, interpret=False)).lower(
        _sds(one_chip, (256, 256)), _sds(one_chip, (), jnp.int32)).compile()
    assert _mosaic(compiled)


def test_memory_atom_compiles_at_its_vmem_block(one_chip):
    from repro.kernels.memory_atom import ops as mops
    compiled = jax.jit(
        lambda x, n: mops.stream(x, iters=n, interpret=False)).lower(
        _sds(one_chip, (1 << 22,)),                       # 16 MiB
        _sds(one_chip, (), jnp.int32)).compile()
    assert _mosaic(compiled)


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
                    (_sds(one_chip, (runner.tile, runner.tile)),
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


def test_fused_segment_compiles_at_default_tile_and_block(one_chip):
    assert "while" in _fused_segment(one_chip).as_text()


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
    passes = [line for line in text.splitlines()
              if "dynamic-update-slice_fusion" in line and " fusion(" in line]
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
