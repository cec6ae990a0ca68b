"""Fused collectives: mesh-bound segments replace barrier-step replay
(ISSUE 5 contracts).

Fast tests run meshless and pin the compiler/serialization layer: a
``CollectiveQuant`` quantizes wire bytes without a live mesh (so a
meshless parent compiles tables bit-identical to its mesh-owning fleet
workers'), wire-only runs fuse into three-column segment rows instead of
``BarrierStep``s, mesh-bound segments survive detach/rehydrate/pickle
(version-1 two-column payloads still load), and replaying a mesh-bound
schedule without a mesh fails loudly instead of dropping wire work.

Mesh tests (``subproc``: they re-exec python with forced host devices,
like ``test_distributed``) pin the ISSUE 5 acceptance contract: on a
2-device mesh, fused, per-sample, and ``keep_collectives=True`` barrier
replay consume bit-identical totals with agreeing collective-dispatch
counts, cache-sharing plans report the quantized amount (not the first
builder's raw wire bytes), and tiny legs' clamp-up inflation is surfaced
as ``emulated_ici_bytes``.  Fleet tests (``slow`` + ``subproc``) round-trip
a mesh-bound ``ScheduleBundle`` through a real ``ProcessFleet`` and a
loopback ``RemoteFleet``.
"""
import os
import pickle
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (CollectiveQuant, CollectiveSpec, Emulator,
                        ResourceVector, Sample, SynapseProfile,
                        collective_factor, rehydrate_schedule)
from repro.core.atoms import COLL_BLOCK_ELEMS
from repro.core.schedule import BarrierStep, FusedSegment
from repro.fleet import MeshSpec, RemoteFleet, WorkerSpec, bundle_profile

TILE = 64                  # 1 compute iter = 2*64^3  = 524288 flops
BLOCK = 1 << 18            # 1 memory  iter = 2*2^18  = 524288 bytes
FPI = 2.0 * TILE ** 3
BPI = 2.0 * BLOCK
WPI = 4.0 * COLL_BLOCK_ELEMS   # n=2 all-reduce: factor 1.0 * 4 bytes/elem

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _em(**kw):
    return Emulator(compute_tile=TILE, mem_block=BLOCK, **kw)


def _rv(flops=0.0, hbm=0.0, sw=0.0, sr=0.0, ici=0.0):
    return ResourceVector(flops=flops, hbm_bytes=hbm,
                          storage_write_bytes=sw, storage_read_bytes=sr,
                          ici_bytes={"all-reduce": ici} if ici else {})


def _profile(rvs, command="coll-test"):
    return SynapseProfile(command=command,
                          samples=[Sample(index=i, resources=r)
                                   for i, r in enumerate(rvs)])


def _wire_heavy(command="coll-test"):
    """Compute+wire mix with one storage barrier: exercises fused rows,
    a wire-bearing barrier step, and plain rows in one profile."""
    return _profile([_rv(flops=FPI, hbm=BPI, ici=4e6),
                     _rv(flops=2 * FPI),
                     _rv(ici=2e6),
                     _rv(flops=FPI, sw=2 << 20, ici=1e6),
                     _rv(hbm=BPI, ici=4e6)], command=command)


# ---------------------------------------------------------------------------
# quantization (fast, meshless)
# ---------------------------------------------------------------------------

def test_collective_quant_math():
    q = CollectiveQuant(n=2, kind="all-reduce")
    assert q.factor == collective_factor("all-reduce", 2) == 1.0
    assert q.wire_bytes_per_iter == WPI
    assert q.iters_for(4e6) == round(4e6 / WPI)
    assert q.iters_for(0.4 * WPI) == 0          # sub-half-iteration: noop
    assert q.iters_for(-1.0) == 0
    assert q.emulated_bytes(3) == 3 * WPI
    # kind changes the ring factor, and with it the per-iteration bytes
    assert CollectiveQuant(n=4, kind="all-gather").factor == 0.75
    assert CollectiveQuant(n=4, kind="collective-permute").factor == 1.0
    # n=1 has no wire: every amount quantizes to zero, never divides by 0
    assert CollectiveQuant(n=1).iters_for(1e12) == 0
    assert CollectiveQuant.from_dict(q.to_dict()) == q


def test_quant_for_mesh_spec_matches_live_mesh_quant():
    spec = CollectiveSpec()                      # axis None: last mesh axis
    mesh_spec = MeshSpec(shape=(2,), axes=("model",))
    assert spec.quant_for(mesh_spec) == CollectiveQuant(n=2)
    two_axis = MeshSpec(shape=(2, 4), axes=("data", "model"))
    assert spec.quant_for(two_axis).n == 4       # last axis
    assert CollectiveSpec(axis="data").quant_for(two_axis).n == 2
    with pytest.raises(ValueError, match="not in mesh axes"):
        CollectiveSpec(axis="pipeline").quant_for(two_axis)


# ---------------------------------------------------------------------------
# compiler: wire runs fuse (fast, meshless parent)
# ---------------------------------------------------------------------------

def test_meshless_parent_compiles_mesh_bound_segments():
    em = _em()                                   # no mesh in this process
    prof = _wire_heavy()
    mesh_spec = MeshSpec(shape=(2,), axes=("model",))
    sched = em.compile(prof, mesh_spec=mesh_spec)
    # only the STORAGE run barriers; every wire-only run is a fused row
    assert [type(s) for s in sched.steps] == \
        [FusedSegment, BarrierStep, FusedSegment]
    assert sched.mesh_bound
    assert sched.collective_quant == CollectiveQuant(n=2)
    q = sched.collective_quant
    want = [(em.compute.iters_for(FPI), em.memory.iters_for(BPI),
             q.iters_for(4e6)),
            (em.compute.iters_for(2 * FPI), 0, 0),
            (0, 0, q.iters_for(2e6))]
    assert [tuple(r) for r in sched.segments[0].table] == want
    assert sched.segments[1].table[0, 2] == q.iters_for(4e6)
    # the barrier fallback still lowers every wire run to a BarrierStep
    kept = em.compile(prof, keep_collectives=True)
    assert sum(isinstance(s, BarrierStep) for s in kept.steps) == 4
    assert not kept.mesh_bound and kept.collective_quant is None
    # and without a mesh_spec there is nothing to quantize for: folded
    folded = em.compile(prof)
    assert not folded.mesh_bound
    assert all(int(s.table[:, 2].sum()) == 0 for s in folded.segments)


def test_mesh_bound_bundle_roundtrips_through_pickle():
    em = _em()
    mesh_spec = MeshSpec(shape=(2,), axes=("model",))
    sched = em.compile(_wire_heavy(), mesh_spec=mesh_spec)
    bundle = pickle.loads(pickle.dumps(
        bundle_profile(em, _wire_heavy(), mesh_spec=mesh_spec)))
    back = bundle.rehydrate()
    assert back.mesh_bound
    assert back.collective_quant == sched.collective_quant
    for a, b in zip(sched.steps, back.steps):
        if isinstance(a, FusedSegment):
            np.testing.assert_array_equal(a.table, b.table)
            assert a.rows == b.rows              # bit-identical floats
        else:
            assert a.resources == b.resources and a.count == b.count


def test_version1_payload_loads_with_zero_wire_column():
    em = _em()
    payload = em.compile(_profile([_rv(flops=FPI), _rv(hbm=BPI)])).detach()
    assert payload["version"] == 2
    legacy = {"version": 1,
              "steps": [{"kind": "segment",
                         "table": payload["steps"][0]["table"][:, :2],
                         "rows": payload["steps"][0]["rows"]}]}
    back = rehydrate_schedule(legacy)
    seg = back.segments[0]
    assert seg.table.shape == (2, 3)
    assert seg.collective_iters == 0 and not seg.mesh_bound
    rep = em.replay(back, command="v1")
    assert rep.consumed == _profile([_rv(flops=FPI), _rv(hbm=BPI)]).totals


def test_meshless_replay_of_mesh_bound_schedule_raises():
    em = _em()
    sched = em.compile(_profile([_rv(ici=4e6)]),
                       mesh_spec=MeshSpec(shape=(2,), axes=("model",)))
    assert sched.mesh_bound
    with pytest.raises(RuntimeError, match="mesh"):
        em.replay(sched, command="meshless")


def test_folded_wire_reports_zero_emulated_ici():
    # meshless default: wire bytes are consumed (accounting) but nothing
    # executes, and the report says so instead of pretending
    em = _em()
    rep = em.emulate(_profile([_rv(flops=FPI, ici=4e6)]), fused=True)
    assert rep.consumed.ici_total == 4e6
    assert rep.emulated_ici_bytes == 0.0
    assert rep.n_collective_dispatches == 0
    assert rep.summary()["emulated_ici_bytes"] == 0.0


# ---------------------------------------------------------------------------
# mesh equivalence (subprocess: needs >=2 forced host devices)
# ---------------------------------------------------------------------------

def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


@pytest.mark.subproc
def test_fused_barrier_and_per_sample_replay_are_equivalent():
    """The ISSUE 5 acceptance contract, on a real 2-device mesh: all three
    replay modes consume bit-identical totals in the same cross-sample
    order, their collective-leg counts agree, and the fused path does it
    in O(segments) dispatches."""
    _run("""
    import jax
    from repro.core import Emulator, ResourceVector, Sample, SynapseProfile

    TILE, BLOCK = 64, 1 << 18
    FPI, BPI = 2.0 * TILE ** 3, 2.0 * BLOCK

    def rv(flops=0.0, hbm=0.0, sw=0.0, ici=0.0):
        return ResourceVector(flops=flops, hbm_bytes=hbm,
                              storage_write_bytes=sw,
                              ici_bytes={"all-reduce": ici} if ici else {})

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2,), ("model",))
    em = Emulator(compute_tile=TILE, mem_block=BLOCK, mesh=mesh)
    # alternating wire amounts so _collapse merges nothing, one storage
    # sample so the wire-bearing barrier path is exercised too
    rvs = [rv(flops=(1 + i % 2) * FPI, ici=(1 + i % 2) * 2e6)
           for i in range(16)]
    rvs.insert(8, rv(flops=FPI, sw=2 << 20, ici=1e6))
    prof = SynapseProfile(command="equiv", samples=[
        Sample(index=i, resources=r) for i, r in enumerate(rvs)])

    fused = em.emulate(prof, fused=True)
    per_sample = em.emulate(prof, fused=False)
    barrier = em.replay(em.compile(prof, keep_collectives=True),
                        command="equiv", planned=prof.totals)
    em.storage.cleanup()

    assert fused.mode == "fused" and per_sample.mode == "per_sample"
    assert fused.consumed == per_sample.consumed == barrier.consumed \\
        == prof.totals
    assert fused.n_samples == per_sample.n_samples == barrier.n_samples
    # every path executed the same 17 wire legs
    assert fused.n_collective_dispatches == 17
    assert per_sample.n_collective_dispatches == 17
    assert barrier.n_collective_dispatches == 17
    # O(segments): 2 fused dispatches + the barrier sample's 2 thunks,
    # vs one dispatch per atom per sample on the other paths
    assert fused.n_dispatches == 4, fused.n_dispatches
    assert per_sample.n_dispatches == barrier.n_dispatches == 34
    # each path emulates (quantized) roughly what the profile planned
    for rep in (fused, per_sample, barrier):
        assert abs(rep.emulated_ici_bytes - prof.totals.ici_total) \\
            < 0.05 * prof.totals.ici_total, rep.emulated_ici_bytes
    print("OK equivalence")
    """)


@pytest.mark.subproc
def test_plan_cache_sharers_report_quantized_amount_and_tiny_clamp():
    """ISSUE 5 satellites: two wire amounts quantizing to the same shard
    share one cached plan and BOTH report the quantized amount (not the
    first builder's raw bytes); sub-4n-byte legs clamp UP to one element
    per shard and the plan/report say so."""
    _run("""
    import jax
    from repro.core import (Emulator, PlanCache, ResourceVector, Sample,
                            SynapseProfile)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2,), ("model",))
    em = Emulator(compute_tile=64, mem_block=1 << 18, mesh=mesh,
                  plan_cache=PlanCache())
    atom = em.collective

    # 4e6+2 and 4e6 both quantize to 1_000_000 elems/shard -> same key;
    # the first builder's raw amount (4e6+2) must NOT leak to the sharer
    first = atom.plan(4e6 + 2.0)
    second = atom.plan(4e6)
    assert em.plan_cache.stats()["hits"] == 1
    assert first.amount == second.amount == 4e6, (first.amount,
                                                  second.amount)

    # a 10-byte leg clamps up to 1 elem/shard = 8 emulated wire bytes
    tiny = atom.plan(10.0)
    assert tiny.amount == 8.0, tiny.amount
    assert tiny() == 8.0

    # ...and the replay report surfaces the inflation: consumed keeps the
    # profile's 10 bytes, emulated reports the quantized 8
    prof = SynapseProfile(command="tiny", samples=[Sample(
        index=0, resources=ResourceVector(
            flops=2.0 * 64 ** 3, ici_bytes={"all-reduce": 10.0}))])
    rep = em.replay(em.compile(prof, keep_collectives=True), command="tiny")
    assert rep.consumed.ici_total == 10.0
    assert rep.emulated_ici_bytes == 8.0
    assert rep.summary()["emulated_ici_bytes"] == 8.0
    assert rep.n_collective_dispatches == 1

    # sub-half-block legs quantize to a NO-OP row on the fused path (like
    # compute/memory rows) — the documented granularity divergence from
    # the barrier path's clamp-up above; consumed stays bit-identical
    fused_tiny = em.emulate(prof, fused=True)
    assert fused_tiny.consumed == rep.consumed
    assert fused_tiny.n_collective_dispatches == 0
    assert fused_tiny.emulated_ici_bytes == 0.0

    # a mesh-owning parent bundling for workers of UNKNOWN mesh must ship
    # portable barrier steps, never its own mesh's quantization
    from repro.core.schedule import BarrierStep
    from repro.fleet import bundle_profile
    bprof = SynapseProfile(command="own-mesh", samples=[Sample(
        index=0, resources=ResourceVector(
            ici_bytes={"all-reduce": 4e6}))])
    shipped = bundle_profile(em, bprof).rehydrate()
    assert not shipped.mesh_bound
    assert any(isinstance(s, BarrierStep) for s in shipped.steps)

    # attach_collective must drop the runner's mesh-bound programs: they
    # close over the previous atom's mesh
    sched2 = em.compile(bprof)
    em.replay(sched2, command="warm-coll")
    assert any(k[3] for k in em._segments._fns)
    em.attach_collective(em.collective)
    assert not any(k[3] for k in em._segments._fns)
    print("OK satellites")

    # quant-mismatch guard: a schedule quantized for a 4-way mesh must not
    # replay on this 2-way one
    from repro.fleet import MeshSpec
    big = SynapseProfile(command="skewed", samples=[Sample(
        index=0, resources=ResourceVector(
            ici_bytes={"all-reduce": 4e6}))])
    sched = em.compile(big, mesh_spec=MeshSpec(shape=(4,), axes=("model",)))
    assert sched.mesh_bound
    try:
        em.replay(sched, command="skewed")
        raise SystemExit("expected RuntimeError on quant mismatch")
    except RuntimeError as e:
        assert "quantized for" in str(e)
    """)


@pytest.mark.subproc
def test_barrier_leg_runs_in_bounded_sharded_chunks():
    """A barrier leg larger than one chunk per chip runs as repeated
    chunk launches plus a remainder, on operands laid out over the mesh,
    and still reports the whole leg's quantized wire bytes."""
    _run("""
    import jax
    from repro.core import atoms
    from repro.launch.mesh import make_mesh

    atoms.COLL_CHUNK_ELEMS = 8
    mesh = make_mesh((2,), ("model",))
    atom = atoms.CollectiveAtom(mesh, axis="model", kind="all-reduce")
    n_elems = 2 * (3 * 8 + 5)                # 3 full chunks + 5 a chip
    plan = atom.plan(atom.quantized_wire_bytes(n_elems))
    assert plan.amount == atom.quantized_wire_bytes(n_elems)
    assert sorted(atom._fns) == [10, 16]     # chunk and remainder programs
    out = jax.block_until_ready(plan.launch())
    assert out.shape == (10,) and len(out.sharding.device_set) == 2
    """)


# ---------------------------------------------------------------------------
# fleet round-trips (spawn real workers / agents)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.subproc
def test_process_fleet_replays_mesh_bound_segments():
    """A meshless parent ships mesh-bound bundles; process-fleet workers
    replay them bit-identically in O(segments) dispatches — no barrier
    step for wire-only runs anywhere in the pipeline."""
    em = _em()
    prof = _profile([_rv(flops=FPI, ici=4e6), _rv(flops=2 * FPI),
                     _rv(ici=2e6), _rv(hbm=BPI)])
    mesh_spec = MeshSpec(shape=(2,), axes=("model",))
    bundle = bundle_profile(em, prof, mesh_spec=mesh_spec)
    assert bundle.rehydrate().mesh_bound
    assert not any(isinstance(s, BarrierStep)
                   for s in bundle.rehydrate().steps)
    ref = em.emulate(prof, fused=True)           # folded accounting locally
    fleet = em.emulate_many([prof, prof], max_workers=2, executor="process",
                            mesh_spec=mesh_spec)
    for rep in fleet.reports:
        assert rep.mode == "fused"
        assert rep.consumed == ref.consumed == prof.totals
        assert rep.n_samples == ref.n_samples
        assert rep.n_dispatches == 1             # whole profile, ONE scan
        assert rep.n_collective_dispatches == 2  # both wire rows executed
        assert rep.emulated_ici_bytes > 0


@pytest.mark.slow
@pytest.mark.subproc
def test_remote_fleet_replays_mesh_bound_segments():
    """The same mesh-bound bundles over loopback framed TCP: a remote
    agent's workers fuse collectives too."""
    src = os.path.join(ROOT, "src")
    env = dict(os.environ)
    old = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = src + (os.pathsep + old if old else "")

    em = _em()
    prof = _profile([_rv(flops=FPI, ici=4e6), _rv(ici=2e6), _rv(hbm=BPI)],
                    command="coll-test:remote")
    mesh_spec = MeshSpec(shape=(2,), axes=("model",))
    ref = em.emulate(prof, fused=True)

    fleet = RemoteFleet(WorkerSpec(emulator=em.spec(), mesh=mesh_spec),
                        listen="127.0.0.1:0", agents=1)
    agent = subprocess.Popen(
        [sys.executable, "-m", "repro.fleet.agent",
         "--connect", f"127.0.0.1:{fleet.bound_addr[1]}", "--workers", "1"],
        env=env)
    try:
        bundles = [bundle_profile(em, prof, mesh_spec=mesh_spec)
                   for _ in range(2)]
        reports = fleet.run(bundles, timeout=180.0)
    finally:
        fleet.close()
        try:
            agent.wait(timeout=30.0)
        except subprocess.TimeoutExpired:
            agent.kill()
            agent.wait(timeout=10.0)
    assert len(reports) == 2
    for rep in reports:
        assert rep.mode == "fused"
        assert rep.consumed == ref.consumed == prof.totals
        assert rep.n_dispatches == 1
        assert rep.n_collective_dispatches == 2
        assert rep.emulated_ici_bytes > 0
