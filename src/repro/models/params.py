"""Parameter definition trees.

A model is described once as a nested dict of ``PDef`` leaves (shape, logical
axes, initializer).  From that single source we derive:

  * materialized parameters           (``init_params``)
  * PartitionSpecs for pjit           (``spec_tree``)
  * ShapeDtypeStructs for the dry-run (``abstract_params`` — no allocation)

Logical axis names are resolved to mesh axes by ``repro.parallel.sharding``
rules, so the same model code runs on 1 CPU device and on a 512-chip mesh.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class PDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"            # normal | zeros | ones | scaled | small
    scale: float = 1.0              # multiplier on the initializer
    dtype: Optional[Any] = None     # override the tree-wide param dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_pdef(x) -> bool:
    return isinstance(x, PDef)


def _tree_map(tree, fn, path=()):
    if is_pdef(tree):
        return fn(path, tree)
    if isinstance(tree, dict):
        return {k: _tree_map(v, fn, path + (k,)) for k, v in tree.items()}
    raise TypeError(f"bad pdef tree node at {path}: {type(tree)}")


def _leaf_seed(path: Tuple[str, ...]) -> int:
    # Deterministic per-leaf seed independent of dict iteration order.
    h = 0
    for p in path:
        for ch in str(p):
            h = (h * 1000003 + ord(ch)) % (2**31 - 1)
    return h


def _materialize(rng, pd: PDef, path, dtype):
    dt = pd.dtype or dtype
    key = jax.random.fold_in(rng, _leaf_seed(path))
    if pd.init == "zeros":
        return jnp.zeros(pd.shape, dt)
    if pd.init == "ones":
        return jnp.ones(pd.shape, dt)
    if pd.init == "normal":
        return (pd.scale * 0.02) * jax.random.normal(key, pd.shape, dt)
    if pd.init == "scaled":  # fan-in scaled (truncated-normal-ish)
        fan_in = pd.shape[0] if len(pd.shape) >= 2 else max(pd.shape[0], 1)
        # a Python float: a NumPy scalar would promote bf16 weights to f32
        std = float(pd.scale / np.sqrt(fan_in))
        return std * jax.random.normal(key, pd.shape, dt)
    if pd.init == "small":
        return (pd.scale * 1e-3) * jax.random.normal(key, pd.shape, dt)
    raise ValueError(pd.init)


def init_params(tree, rng, dtype=jnp.float32):
    return _tree_map(tree, lambda path, pd: _materialize(rng, pd, path, dtype))


def spec_tree(tree, rules):
    """PDef tree -> PartitionSpec tree via logical-axis rules.

    Divisibility-checked with row-parallel TP fallback (see
    Rules.pspec_checked): head counts that don't divide the model axis fall
    back to sharding d_model.
    """
    return _tree_map(
        tree,
        lambda path, pd: rules.pspec_checked(pd.shape, pd.axes,
                                             tp_fallback=True))


def abstract_params(tree, dtype, mesh=None, rules=None):
    """PDef tree -> ShapeDtypeStruct tree (optionally sharded) — dry-run input."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def mk(path, pd):
        dt = pd.dtype or dtype
        if mesh is None:
            return jax.ShapeDtypeStruct(pd.shape, dt)
        spec = rules.pspec_checked(pd.shape, pd.axes, tp_fallback=True)
        return jax.ShapeDtypeStruct(pd.shape, dt, sharding=NamedSharding(mesh, spec))

    return _tree_map(tree, mk)


def stack_pdefs(tree, n: int, axis_name: Optional[str] = "layers"):
    """Prepend a stacking dim (for scan-over-layers) to every leaf."""
    return _tree_map(
        tree,
        lambda path, pd: PDef((n,) + pd.shape, (axis_name,) + pd.axes,
                              pd.init, pd.scale, pd.dtype),
    )


def count_params(tree) -> int:
    total = 0

    def add(path, pd):
        nonlocal total
        n = 1
        for s in pd.shape:
            n *= s
        total += n
        return pd

    _tree_map(tree, add)
    return total


def cast_tree(params, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params)
