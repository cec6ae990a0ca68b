"""Emulation atoms: small self-contained consumers of one resource type.

Paper §IV-B, adapted per DESIGN.md §2:

  * ComputeAtom    — MXU/FPU matmul burn loop.  ``efficiency`` < 1
                     throttles it exactly like the paper's loop-rate knob
                     (emulate an app running below peak).
  * MemoryAtom     — streams a target byte count through HBM: a ring of
                     blocks larger than the chip's on-chip memory, one block
                     read, scaled and written back in place per iteration.
  * CollectiveAtom — moves a wire-byte count over a mesh axis as repeated
                     shard_map'd all-reduces of a fixed block (the paper's
                     "planned" network atom, first-class here).
  * StorageAtom    — block-wise file write/read (libc read/write, unchanged
                     from the paper; block size is the tunable the paper
                     discusses in §IV-E.3).

Each device atom has one program, an XLA loop, and the fused schedule
compiler (``repro.core.schedule``) runs the same loop body, so an atom
plan and a fused row burn alike.  Atoms expose ``plan(amount) -> Plan``
so the emulator can pre-compile, and
``seconds(amount, hw)`` — the model cost used by the TTC predictor.  A
``Plan`` separates *launch* (enqueue device work, returns an unsynced jax
value; host plans do the work and return ``None``) from *sync*, so the
emulator can dispatch every atom of a sample asynchronously and block once
at the sample barrier; calling the plan is the legacy blocking contract.
"""
from __future__ import annotations

import math
import os
import tempfile
import threading
import time
from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.calibrate import HostCalibration, calibrate
from repro.core.hardware import HardwareSpec


class Plan:
    """One planned resource consumption.

    ``launch()`` enqueues the work: device plans return the unsynced jax
    value (dispatch only — caller syncs at the sample barrier), host plans
    (storage) do the work inline and return ``None``.  Calling the plan is
    the blocking contract older callers rely on: launch, sync, and return
    the amount the plan actually emulates (quantized, so cache sharers
    agree on what was consumed).
    """

    __slots__ = ("launch", "amount")

    def __init__(self, launch: Callable[[], object], amount: float):
        self.launch = launch
        self.amount = float(amount)

    def __call__(self) -> float:
        token = self.launch()
        if token is not None:
            jax.block_until_ready(token)
        return self.amount

    @staticmethod
    def noop() -> "Plan":
        return Plan(lambda: None, 0.0)


class PlanCache:
    """Shared, keyed memo of planned atom thunks (fleet emulation).

    Keys are the atom's full plan signature — (kind, config knobs,
    quantized amount) — so identical (atom, amount) plans across a fleet of
    concurrently-replayed profiles are built, and their XLA programs traced,
    exactly once.  Builds hold a per-key guard, not the cache-wide lock:
    concurrent fleet workers building *different* plans trace concurrently,
    while a second worker asking for a key mid-build waits for the first
    builder instead of constructing a duplicate.  The returned plans are
    safe to execute concurrently: jitted callables with read-only operands,
    except the memory atom's ring, which its plans take in turn under the
    atom's lock.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._plans: Dict[Tuple, Plan] = {}
        self._building: Dict[Tuple, threading.Event] = {}
        self.plans_built = 0
        self.hits = 0

    def get_or_build(self, key: Tuple,
                     builder: Callable[[], Plan]) -> Plan:
        while True:
            with self._lock:
                plan = self._plans.get(key)
                if plan is not None:
                    self.hits += 1
                    return plan
                done = self._building.get(key)
                if done is None:
                    done = threading.Event()
                    self._building[key] = done
                    owner = True
                else:
                    owner = False
            if not owner:
                # someone else is building this key: wait, then re-check
                # (a failed build wakes us with no plan — we take over)
                done.wait()
                continue
            try:
                plan = builder()
            except BaseException:
                with self._lock:
                    self._building.pop(key, None)
                done.set()
                raise
            with self._lock:
                self._plans[key] = plan
                self.plans_built += 1
                self._building.pop(key, None)
            done.set()
            return plan

    def __len__(self) -> int:
        return len(self._plans)

    def stats(self) -> Dict[str, int]:
        return {"plans_built": self.plans_built, "hits": self.hits,
                "size": len(self._plans)}


# ---------------------------------------------------------------------------
# Picklable atom configs: the knob surface of an atom, detached from its
# live state (calibration, jitted programs, meshes, scratch buffers).  A
# spec crosses a process boundary and ``build()``s a fresh atom on the far
# side — fleet workers receive these instead of atoms.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ComputeSpec:
    tile: int = 256
    efficiency: float = 1.0

    def build(self, calib=None) -> "ComputeAtom":
        return ComputeAtom(calib, tile=self.tile, efficiency=self.efficiency)


@dataclass(frozen=True)
class MemorySpec:
    block_bytes: int = 1 << 24

    def build(self, calib=None) -> "MemoryAtom":
        return MemoryAtom(calib, block_bytes=self.block_bytes)


@dataclass(frozen=True)
class StorageSpec:
    block_bytes: int = 1 << 20
    # no directory: scratch files belong to the host the atom runs on

    def build(self, calib=None) -> "StorageAtom":
        return StorageAtom(calib, block_bytes=self.block_bytes)


#: tile-iterations one trip of the compute atom's loop burns: the loop
#: carries this many independent tiles and multiplies them in one batched
#: matmul, so its loop control is paid once a group and not once a tile.
#: On a TPU v5e replaying a Qwen2-1.5B prefill the atom reads 24.0% of the
#: bf16 peak at one tile a trip, and 67.8%, 73.2% and 72.1% at 4, 8 and 16.
COMPUTE_GROUP = 8

#: per-shard float32 elements one collective iteration moves (the
#: collective analogue of ComputeAtom.tile / MemoryAtom.block_bytes — wire
#: bytes are quantized into repeats of this block)
COLL_BLOCK_ELEMS = 1 << 15


@dataclass(frozen=True)
class CollectiveQuant:
    """Picklable wire-byte quantization of the collective atom.

    Derivable from a live ``CollectiveAtom`` (``atom.quant()``) *or* from a
    (``CollectiveSpec``, mesh-spec) pair on a host that owns no mesh at all
    (``CollectiveSpec.quant_for``) — which is what lets a meshless parent
    compile schedule tables bit-identical to the ones its mesh-owning fleet
    workers would compile.  One iteration is one shard_map'd all-reduce
    over a fixed ``block_elems``-per-shard float32 block, so the emulated
    wire amount is ``iters * wire_bytes_per_iter`` — quantized exactly like
    compute flops and memory bytes are, on every replay path.
    """
    n: int                               # collective axis size
    block_elems: int = COLL_BLOCK_ELEMS

    @property
    def factor(self) -> float:
        """Ring-model wire bytes a chip moves per shard byte of an
        all-reduce over an ``n``-way axis."""
        return 2.0 * (self.n - 1) / self.n

    @property
    def wire_bytes_per_iter(self) -> float:
        return self.factor * 4.0 * self.block_elems

    def iters_for(self, wire_bytes: float) -> int:
        per_iter = self.wire_bytes_per_iter
        if per_iter <= 0.0:        # n == 1: there is no wire to move
            return 0
        return max(int(round(wire_bytes / per_iter)), 0)

    def emulated_bytes(self, iters: int) -> float:
        return iters * self.wire_bytes_per_iter

    def to_dict(self) -> Dict:
        return {"n": self.n, "block_elems": self.block_elems}

    @staticmethod
    def from_dict(d) -> "CollectiveQuant":
        return CollectiveQuant(n=int(d["n"]),
                               block_elems=int(d["block_elems"]))


@dataclass(frozen=True)
class CollectiveSpec:
    axis: Optional[str] = None           # None: the mesh's last axis

    def build(self, mesh) -> "CollectiveAtom":
        return CollectiveAtom(mesh, axis=self.axis)

    def quant_for(self, mesh_spec) -> CollectiveQuant:
        """Quantization for the mesh a *worker* will build from
        ``mesh_spec`` (anything with ``shape``/``axes``, e.g.
        ``repro.fleet.MeshSpec``) — no live mesh required."""
        axes = tuple(mesh_spec.axes)
        axis = self.axis if self.axis is not None else axes[-1]
        if axis not in axes:
            raise ValueError(f"collective axis {axis!r} not in mesh axes "
                             f"{axes}")
        return CollectiveQuant(n=int(mesh_spec.shape[axes.index(axis)]))


class Atom:
    resource = "abstract"
    cache: Optional[PlanCache] = None      # set by fleet-mode emulators

    def plan(self, amount: float) -> Plan:
        """Returns a Plan that consumes ``amount`` (quantized) when called."""
        raise NotImplementedError

    def seconds(self, amount: float, hw: HardwareSpec) -> float:
        raise NotImplementedError

    def _cached(self, key: Tuple, builder: Callable[[], Plan]) -> Plan:
        if self.cache is None:
            return builder()
        return self.cache.get_or_build(key, builder)


def compute_burn_body(_, c):
    """One compute-atom trip: a tile matmul kept bounded by tanh, for one
    ``(tile, tile)`` tile or, batched, for each tile of a ``(group, tile,
    tile)`` carry.  The tiles of a group are independent, so the MXU
    pipelines one behind the next.  Shared with the fused schedule
    compiler so both paths burn identically per iteration."""
    return jnp.tanh(c @ c) * 0.5 + 0.5


def _burn_first_tile(i, c):
    return c.at[0].set(compute_burn_body(i, c[0]))


def compute_burn(c, iters):
    """Burn ``iters`` tile-iterations on the group carry ``c`` that
    ``compute_operand`` makes: ``iters // group`` trips of the whole group,
    then ``iters % group`` trips of its first tile alone.  Each loop holds
    ops and no further loop.  The atom's plan and the fused segment's
    compute block both run this, so a fused iteration costs exactly what an
    atom iteration costs."""
    group = c.shape[0]
    c = jax.lax.fori_loop(0, jax.lax.div(iters, group), compute_burn_body, c)
    return jax.lax.fori_loop(0, jax.lax.rem(iters, group), _burn_first_tile,
                             c)


def compute_operand(tile: int):
    """The burn loop's carry, ``COMPUTE_GROUP`` tiles; shared with the
    schedule compiler so a fused iteration costs exactly what an atom
    iteration costs."""
    return jnp.broadcast_to(jnp.eye(tile, dtype=jnp.float32) * 0.5,
                            (COMPUTE_GROUP, tile, tile))


def ring_windows(block_bytes: int, platform: str) -> int:
    """How many ``block_bytes`` windows the memory leg's ring holds on
    ``platform`` (a ``jax.default_backend()`` name).  On a TPU the ring is
    twice the on-chip memory (VMEM: 128 MiB on v5e), so XLA cannot keep
    the working set there and every pass's window streams through HBM.
    Elsewhere it is 4 MiB: small blocks still make a ring that wraps
    round, and the default 16 MiB block is a ring of one."""
    want = 2 * (128 << 20) if platform == "tpu" else 4 << 20
    return max(1, -(-want // block_bytes))


def memory_stream_body(_, state):
    """One memory-atom iteration over the ring state ``(ring, window)``
    that ``memory_operand`` makes: read window ``window`` of the ring,
    scale it and write it back in place (one read and one write pass of
    the block), then move to the next window."""
    ring, window = state
    x = jax.lax.dynamic_index_in_dim(ring, window, keepdims=False)
    ring = jax.lax.dynamic_update_index_in_dim(ring, x * 1.0000001, window, 0)
    return ring, (window + 1) % ring.shape[0]


def memory_operand(block_bytes: int):
    """The stream loop's carry: a ring of ``ring_windows`` blocks on the
    default device, and the window the next iteration works on.  Shared
    with the schedule compiler for the same reason as ``compute_operand``.

    A block is a ``(rows, 128)`` window on the ring's leading axis, so its
    offset is aligned to the TPU's tiles whatever the window: the slice,
    scale and update then fuse into one in-place pass over HBM.  (At a
    flat offset XLA cannot prove aligned, the update is a separate op
    that took four times as long as the read on a v5e.)  Programs that
    carry the ring donate it, and their caller keeps the returned ring for
    the next launch: a ring that is not donated is copied at every
    launch."""
    if block_bytes % 512:
        raise ValueError(f"memory block of {block_bytes} bytes is not a "
                         "whole number of 128-lane float32 rows")
    windows = ring_windows(block_bytes, jax.default_backend())
    return (jnp.ones((windows, block_bytes // 512, 128), jnp.float32),
            jnp.int32(0))


# ---------------------------------------------------------------------------
# Compute
# ---------------------------------------------------------------------------

class ComputeAtom(Atom):
    resource = "flops"

    def __init__(self, calib: Optional[HostCalibration] = None,
                 tile: int = 256, efficiency: float = 1.0):
        """``efficiency``: the paper's loop-rate knob — the profiled
        application's measured efficiency (achieved/peak); the atom burns
        flops/efficiency raw loop flops so wall time matches an application
        running that far below the atom's own (near-peak) rate."""
        self.calib = calib
        self.tile = tile
        self.efficiency = max(efficiency, 1e-6)
        self._fn: Optional[Callable] = None
        self._fn_lock = threading.Lock()

    def _loop_fn(self):
        # iters is a traced argument: ONE compilation serves every sample.
        # Guarded: per-key PlanCache builds run concurrently, and two
        # distinct-key builders must still share one jitted program.
        with self._fn_lock:
            if self._fn is None:
                self._fn = jax.jit(compute_burn)
            return self._fn

    def spec(self) -> ComputeSpec:
        return ComputeSpec(tile=self.tile, efficiency=self.efficiency)

    def flops_per_iter(self) -> float:
        return 2.0 * self.tile ** 3

    def iters_for(self, flops: float) -> int:
        """Quantize a raw flop amount into burn-loop iterations (the same
        rounding the fused schedule compiler uses for its tables)."""
        return max(int(round(flops / self.flops_per_iter()
                             / self.efficiency)), 0)

    def plan(self, flops: float) -> Plan:
        iters = self.iters_for(flops)
        if iters == 0:
            return Plan.noop()
        # Key on the quantized amount (iters), not the raw flops: amounts
        # that round to the same loop count are the same plan, and the plan
        # reports the amount it actually emulates so sharers agree.
        key = ("compute", self.tile, self.efficiency, iters)
        return self._cached(key, lambda: self._build_plan(iters))

    def _build_plan(self, iters: int) -> Plan:
        fn = self._loop_fn()
        x = compute_operand(self.tile)
        emulated = iters * self.flops_per_iter() * self.efficiency
        return Plan(lambda: fn(x, iters), emulated)

    def seconds(self, flops: float, hw: HardwareSpec) -> float:
        peak = hw.peak_flops * hw.flops_derate
        return flops / peak if peak else 0.0


# ---------------------------------------------------------------------------
# Memory
# ---------------------------------------------------------------------------

class MemoryAtom(Atom):
    resource = "hbm_bytes"

    def __init__(self, calib: Optional[HostCalibration] = None,
                 block_bytes: int = 1 << 24):
        self.calib = calib
        self.block_bytes = block_bytes
        self._fn: Optional[Callable] = None
        self._lock = threading.Lock()
        self._ring = None            # (ring, window), made on first launch

    def _stream_fn(self):
        # guarded like ComputeAtom._loop_fn: concurrent distinct-key plan
        # builds must share one jitted program
        with self._lock:
            if self._fn is None:
                def stream(ring, window, iters):
                    return jax.lax.fori_loop(0, iters, memory_stream_body,
                                             (ring, window))
                self._fn = jax.jit(stream, donate_argnums=0)
            return self._fn

    def spec(self) -> MemorySpec:
        return MemorySpec(block_bytes=self.block_bytes)

    def bytes_per_iter(self) -> float:
        return 2.0 * self.block_bytes              # read + write per pass

    def iters_for(self, nbytes: float) -> int:
        """Quantize a byte amount into stream-loop iterations (shared with
        the fused schedule compiler's tables)."""
        return max(int(round(nbytes / self.bytes_per_iter())), 0)

    def plan(self, nbytes: float) -> Plan:
        iters = self.iters_for(nbytes)
        if iters == 0:
            return Plan.noop()
        key = ("memory", self.block_bytes, iters)
        return self._cached(key, lambda: self._build_plan(iters))

    def _build_plan(self, iters: int) -> Plan:
        fn = self._stream_fn()
        amount = iters * self.bytes_per_iter()

        def launch():
            # the ring is donated: one launch at a time takes it, and the
            # next launch gets the ring this one returns.  The window index
            # (not donated) is the token to sync on: the next launch may
            # delete the ring before this one's caller syncs
            with self._lock:
                if self._ring is None:
                    self._ring = memory_operand(self.block_bytes)
                self._ring = fn(*self._ring, iters)
                return self._ring[1]
        return Plan(launch, amount)

    def seconds(self, nbytes: float, hw: HardwareSpec) -> float:
        bw = hw.hbm_bw * hw.hbm_derate
        return nbytes / bw if bw else 0.0


# ---------------------------------------------------------------------------
# Collective (network)
# ---------------------------------------------------------------------------

class CollectiveAtom(Atom):
    resource = "ici_bytes"

    def __init__(self, mesh=None, axis: Optional[str] = None):
        self.mesh = mesh
        self.axis = axis or (mesh.axis_names[-1] if mesh is not None else None)
        self._loop_fn: Optional[Callable] = None
        self._fn: Optional[Callable] = None
        self._fn_lock = threading.Lock()

    def spec(self) -> CollectiveSpec:
        return CollectiveSpec(axis=self.axis)

    def quant(self) -> CollectiveQuant:
        """This atom's wire-byte quantization (needs the mesh)."""
        return CollectiveQuant(n=self.mesh.shape[self.axis])

    def loop_operand(self, block_elems: int = COLL_BLOCK_ELEMS):
        """The collective loop's carry: one fixed block per shard."""
        n = self.mesh.shape[self.axis]
        return jnp.ones((n * block_elems,), jnp.float32)

    def loop_body(self) -> Callable:
        """One collective iteration: a shard_map'd all-reduce over the
        fixed block whose result matches the input shape, so
        ``lax.scan``/``fori_loop`` can carry it.  Values are kept bounded
        (psum rescaled by 1/n) because one leg may loop thousands of
        iterations.  The atom's plan and the fused segment's collective
        block both loop over this."""
        if self._loop_fn is None:
            from jax.sharding import PartitionSpec as P
            mesh, axis = self.mesh, self.axis
            n = mesh.shape[axis]

            def local(x):
                return jax.lax.psum(x, axis) * (1.0 / n)

            self._loop_fn = jax.shard_map(local, mesh=mesh,
                                          in_specs=P(axis),
                                          out_specs=P(axis),
                                          check_vma=False)
        return self._loop_fn

    def _burn_fn(self):
        # iters is a traced argument: ONE compilation serves every amount;
        # guarded like ComputeAtom._loop_fn
        with self._fn_lock:
            if self._fn is None:
                step = self.loop_body()
                self._fn = jax.jit(lambda x, iters: jax.lax.fori_loop(
                    0, iters, lambda _, v: step(v), x))
            return self._fn

    def plan(self, wire_bytes: float) -> Plan:
        if self.mesh is None:
            return Plan.noop()
        quant = self.quant()
        iters = quant.iters_for(wire_bytes)
        if iters == 0:
            return Plan.noop()
        # Keyed, like ComputeAtom/MemoryAtom, on the quantized amount, and
        # the plan reports the amount it emulates, so every cache sharer
        # agrees on what was moved.  Mesh identity is part of the key: a
        # shared cache may serve emulators on different meshes, and a
        # shard_map is bound to its.
        mesh_id = (tuple(sorted(self.mesh.shape.items())),
                   tuple(d.id for d in self.mesh.devices.flat))
        key = ("collective", self.axis, mesh_id, iters)
        return self._cached(key, lambda: self._build_plan(iters, quant))

    def _build_plan(self, iters: int, quant: CollectiveQuant) -> Plan:
        fn = self._burn_fn()
        x = self.loop_operand(quant.block_elems)
        return Plan(lambda: fn(x, iters), quant.emulated_bytes(iters))

    def seconds(self, wire_bytes: float, hw: HardwareSpec) -> float:
        bw = hw.ici_bw * hw.ici_derate
        return wire_bytes / bw if bw else 0.0


# ---------------------------------------------------------------------------
# Storage
# ---------------------------------------------------------------------------

class StorageAtom(Atom):
    resource = "storage_bytes"

    def __init__(self, calib: Optional[HostCalibration] = None,
                 block_bytes: int = 1 << 20, directory: Optional[str] = None):
        self.calib = calib
        self.block_bytes = block_bytes
        self.dir = directory or tempfile.gettempdir()
        self._buf = os.urandom(block_bytes)
        self._paths: set = set()

    def spec(self) -> StorageSpec:
        return StorageSpec(block_bytes=self.block_bytes)

    def _path(self) -> str:
        # Keyed by planning thread so concurrent fleet workers never write
        # the same scratch file; one worker reuses its file across samples.
        # Tracked so fleet runs can clean up (thread idents churn per pool).
        p = os.path.join(self.dir, f"synapse_atom_{os.getpid()}_"
                                   f"{threading.get_ident()}.bin")
        self._paths.add(p)
        return p

    def cleanup(self) -> None:
        """Remove scratch files created by past plans."""
        while self._paths:
            p = self._paths.pop()
            try:
                os.unlink(p)
            except OSError:
                pass

    def plan_write(self, nbytes: float) -> Plan:
        blocks = max(int(nbytes // self.block_bytes), 0)
        if blocks == 0:
            return Plan.noop()
        path = self._path()

        def launch():
            with open(path, "wb") as f:
                for _ in range(blocks):
                    f.write(self._buf)
                f.flush()
                os.fsync(f.fileno())
            return None
        return Plan(launch, blocks * self.block_bytes)

    def plan_read(self, nbytes: float, precreate: bool = True) -> Plan:
        blocks = max(int(nbytes // self.block_bytes), 0)
        if blocks == 0:
            return Plan.noop()
        path = self._path()
        # Populate the scratch file at *plan* time: the timed read leg must
        # not pay a hidden write on first use (and an empty file would spin
        # the wrap-around read loop forever).  Callers whose sample carries
        # a write leg that runs first pass ``precreate=False`` — that write
        # populates the file and plan-time bytes would be wasted I/O.
        def populate():
            with open(path, "wb") as f:
                for _ in range(blocks):
                    f.write(self._buf)

        if precreate and (not os.path.exists(path)
                          or os.path.getsize(path) == 0):
            populate()

        def launch():
            # the scratch file can vanish between plan and launch (another
            # replay's cleanup()); re-populate rather than fail the leg
            if not os.path.exists(path) or os.path.getsize(path) == 0:
                populate()
            done = 0
            with open(path, "rb") as f:
                while done < blocks * self.block_bytes:
                    chunk = f.read(self.block_bytes)
                    if not chunk:
                        f.seek(0)
                        continue
                    done += len(chunk)
            return None
        return Plan(launch, blocks * self.block_bytes)

    def plan(self, nbytes: float):
        return self.plan_write(nbytes)

    def seconds(self, nbytes: float, hw: HardwareSpec) -> float:
        if self.calib is None:           # measured on the first storage leg
            self.calib = calibrate()
        return nbytes / self.calib.storage_write_bps
