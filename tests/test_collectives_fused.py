"""Fused collectives: mesh-bound segments replace barrier-step replay
(ISSUE 5 contracts).

Fast tests run meshless and pin the compiler/serialization layer: a
``CollectiveQuant`` quantizes wire bytes without a live mesh (so a
meshless parent compiles tables bit-identical to its mesh-owning fleet
workers'), wire-only runs fuse into three-column segment rows instead of
``BarrierStep``s, mesh-bound segments survive detach/rehydrate/pickle,
and replaying a mesh-bound schedule without a mesh fails loudly instead
of dropping wire work.

Mesh tests (``subproc``: they re-exec python with forced host devices,
like ``test_distributed``) pin the ISSUE 5 acceptance contract: on a
2-device mesh, fused, per-sample, and ``keep_collectives=True`` barrier
replay consume bit-identical totals, run the same collective legs and
report the same ``emulated_ici_bytes``: every path quantizes a leg to
whole blocks of the one all-reduce program, so a leg under half a block
is a no-op everywhere, and cache-sharing plans report the quantized
amount (not the first builder's raw wire bytes).  Fleet tests (``slow`` +
``subproc``) round-trip a mesh-bound ``ScheduleBundle`` through a real
``ProcessFleet`` and a loopback ``RemoteFleet``.
"""
import json
import os
import pickle
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (CollectiveQuant, CollectiveSpec, Emulator,
                        ResourceVector, Sample, SynapseProfile)
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
    q = CollectiveQuant(n=2)
    assert q.factor == 1.0                      # all-reduce ring: 2(n-1)/n
    assert q.wire_bytes_per_iter == WPI
    assert q.iters_for(4e6) == round(4e6 / WPI)
    assert q.iters_for(0.4 * WPI) == 0          # sub-half-iteration: noop
    assert q.iters_for(-1.0) == 0
    assert q.emulated_bytes(3) == 3 * WPI
    # the axis size sets the ring factor, and with it the bytes a block
    assert CollectiveQuant(n=4).factor == 1.5
    assert CollectiveQuant(n=4).wire_bytes_per_iter == 1.5 * 4.0 * \
        COLL_BLOCK_ELEMS
    assert CollectiveQuant(n=8).factor == 1.75
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
    order, run the same collective legs and emulate the same wire bytes,
    and the fused path does it in O(segments) dispatches."""
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
    # every path quantizes each leg to the same whole blocks
    q = em.collective.quant()
    want = sum(q.emulated_bytes(q.iters_for(r.ici_total)) for r in rvs)
    assert fused.emulated_ici_bytes == want, fused.emulated_ici_bytes
    assert per_sample.emulated_ici_bytes == want
    assert barrier.emulated_ici_bytes == want
    # ...which is, to within half a block a leg, what the profile planned
    assert abs(want - prof.totals.ici_total) <= 0.5 * 17 * \\
        q.wire_bytes_per_iter
    print("OK equivalence")
    """)


@pytest.mark.subproc
def test_plan_cache_sharers_report_quantized_amount_and_tiny_clamp():
    """ISSUE 5 satellites: two wire amounts quantizing to the same number
    of blocks share one cached plan and BOTH report the quantized amount
    (not the first builder's raw bytes); a leg under half a block is a
    no-op on the per-sample, barrier and fused paths alike, while the
    profile's bytes stay in ``consumed``."""
    _run("""
    import jax
    from repro.core import (Emulator, PlanCache, ResourceVector, Sample,
                            SynapseProfile)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2,), ("model",))
    em = Emulator(compute_tile=64, mem_block=1 << 18, mesh=mesh,
                  plan_cache=PlanCache())
    atom = em.collective
    q = atom.quant()

    # 4e6+2 and 4e6 both quantize to 31 blocks -> same key; the first
    # builder's raw amount (4e6+2) must NOT leak to the sharer
    first = atom.plan(4e6 + 2.0)
    second = atom.plan(4e6)
    assert em.plan_cache.stats()["hits"] == 1
    assert q.iters_for(4e6) == 31
    assert first.amount == second.amount == q.emulated_bytes(31), (
        first.amount, second.amount)

    # a 10-byte leg is under half a block: a no-op plan that moves nothing
    tiny = atom.plan(10.0)
    assert tiny.amount == 0.0 and tiny.launch() is None
    assert em.plan_cache.stats()["plans_built"] == 1

    # ...on every replay path: consumed keeps the profile's 10 bytes,
    # nothing is emulated and no collective leg runs
    prof = SynapseProfile(command="tiny", samples=[Sample(
        index=0, resources=ResourceVector(
            flops=2.0 * 64 ** 3, ici_bytes={"all-reduce": 10.0}))])
    barrier = em.replay(em.compile(prof, keep_collectives=True),
                        command="tiny")
    fused_tiny = em.emulate(prof, fused=True)
    per_sample = em.emulate(prof, fused=False)
    for rep in (barrier, fused_tiny, per_sample):
        assert rep.consumed.ici_total == 10.0
        assert rep.consumed == barrier.consumed
        assert rep.emulated_ici_bytes == 0.0
        assert rep.summary()["emulated_ici_bytes"] == 0.0
        assert rep.n_collective_dispatches == 0

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


#: wire legs of these many blocks: none, under and over half a block, one,
#: a fraction that rounds up, and a long leg
LEG_BLOCKS = [0, 0.4, 0.6, 1, 3.5, 1000]


@pytest.fixture(scope="module")
def collective_legs():
    """Each leg of ``LEG_BLOCKS`` replayed on a 2-device mesh by the
    per-sample path, a ``keep_collectives=True`` barrier and a fused row,
    in one subprocess.  The atom's loop body is the real all-reduce plus 1,
    so from the all-ones block the carry reads 1 + the trips it ran."""
    out = _run(f"""
    import json
    import numpy as np
    from repro.core import (Emulator, Plan, ResourceVector, Sample,
                            SynapseProfile)
    from repro.core.atoms import CollectiveAtom
    from repro.launch.mesh import make_mesh

    class Counting(CollectiveAtom):
        def loop_body(self):
            step = super().loop_body()
            return lambda x: step(x) + 1.0

    em = Emulator(compute_tile=64, mem_block=1 << 18)
    atom = Counting(make_mesh((2,), ("model",)))
    em.attach_collective(atom)
    q = atom.quant()
    carries = []
    plan = atom.plan

    def spied(wire):
        p = plan(wire)
        def launch():
            tok = p.launch()
            if tok is not None:
                carries.append(tok)
            return tok
        return Plan(launch, p.amount)
    atom.plan = spied

    def carry(tok):
        return None if tok is None else np.asarray(tok).tolist()

    out = {{}}
    for blocks in {LEG_BLOCKS!r}:
        prof = SynapseProfile(command="leg", samples=[Sample(
            index=0, resources=ResourceVector(
                ici_bytes={{"all-reduce": blocks * q.wire_bytes_per_iter}}
                if blocks else {{}}))])
        legs = {{}}
        for path in ("per_sample", "barrier", "fused"):
            carries.clear()
            if path == "fused":
                sched = em.compile(prof)
                rep = em.replay(sched, command="leg")
                tok = em._segments.launch(sched.segments[0])
                tok = None if tok is None else tok[-1]
            else:
                rep = (em.emulate(prof, fused=False) if path == "per_sample"
                       else em.replay(em.compile(prof, keep_collectives=True),
                                      command="leg"))
                tok = carries[-1] if carries else None
            legs[path] = {{"emulated": rep.emulated_ici_bytes,
                          "dispatches": rep.n_collective_dispatches,
                          "carry": carry(tok)}}
        out[str(blocks)] = {{"iters": q.iters_for(
            blocks * q.wire_bytes_per_iter), "wpi": q.wire_bytes_per_iter,
            "legs": legs}}
    print(json.dumps(out))
    """)
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.subproc
@pytest.mark.parametrize("blocks", LEG_BLOCKS)
def test_collective_leg_burns_exactly_the_planned_iterations(
        collective_legs, blocks):
    """A wire leg of ``blocks`` blocks runs round(blocks) all-reduce trips
    on the per-sample path, a barrier step and a fused row alike: equal
    ``emulated_ici_bytes`` and collective-leg counts, and carries that
    show the same number of trips; a leg under half a block runs none."""
    got = collective_legs[str(blocks)]
    iters = got["iters"]
    assert iters == max(int(round(blocks)), 0)
    legs = got["legs"]
    assert set(legs) == {"per_sample", "barrier", "fused"}
    for path, leg in legs.items():
        assert leg["emulated"] == iters * got["wpi"], (path, leg)
        assert leg["dispatches"] == int(iters > 0), (path, leg)
        if iters == 0:
            assert leg["carry"] is None, path
        else:
            carry = np.asarray(leg["carry"])
            assert carry.shape == (2 * COLL_BLOCK_ELEMS,), path
            assert (carry == 1 + iters).all(), (path, carry[:4])


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
