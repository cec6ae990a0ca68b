"""Compile the chip path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
Pallas block larger than VMEM, a slice off the tiling, an executable too
large for HBM.  These tests compile the Pallas kernels at the sizes the
atoms and Qwen2-1.5B use, one fused segment program at the emulator's
default tile and block, and Qwen2-1.5B's decode step, whose profile must
count the FLOPs its shapes imply (the TPU emits matmuls as convolutions).

The topology is described inside a module-scoped fixture and never while
a module is imported: only one process may load the TPU library, and the
test workers all import this file.
"""
import importlib.util
import os

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


def test_fused_segment_compiles_at_default_tile_and_block(one_chip):
    from repro.core import Emulator
    em = Emulator()
    runner = em._segments
    fn = runner._fn(8, True, True, False)
    carry = (_sds(one_chip, (runner.tile, runner.tile)),
             _sds(one_chip, (runner.block_bytes // 4,)))
    compiled = fn.lower(carry, _sds(one_chip, (8, 3), jnp.int32)).compile()
    assert "while" in compiled.as_text()


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
