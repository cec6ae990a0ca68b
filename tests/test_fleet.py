"""Process-level fleet executor (ISSUE 3 + ISSUE 6 contracts).

Fast tests pin the serialization layer in-process: detach/rehydrate and
``ScheduleBundle`` pickling are bit-identical round-trips, emulator/atom
specs rebuild equivalent emulators, and ``keep_collectives`` controls
whether wire-byte runs lower to executable barrier steps.  The streaming
scheduler contracts (ISSUE 6) are pinned on an in-process loopback fleet
(``_EchoFleet``): the compile-ahead window never exceeds ``window``
pulled-but-unfinished bundles, autoscale up/down preserves bit-identical
index-order-folded totals vs a fixed-size pool, and ``FleetConfig``
round-trips pickle and folds legacy kwargs equivalently (with the
deprecation warning).

Process tests (marked ``slow`` + ``subproc`` — deselect with
``-m "not slow"`` while iterating) pin the executor: a process fleet
reports consumed totals bit-identical to in-process fused replay for every
profile, collective legs execute on per-worker meshes (nonzero collective
dispatches — the first fleet mode where they do), worker death mid-run is
survived with every bundle still reported, a poison bundle fails the run
instead of hanging it, and a streamed autoscaled fleet's totals match a
fixed-size fleet's bit-for-bit.
"""
import multiprocessing as mp
import os
import pickle
import signal
import warnings

import numpy as np
import pytest

from repro.core import (BarrierStep, Emulator, FusedSegment, ResourceVector,
                        Sample, SynapseProfile, rehydrate_schedule)
from repro.core.emulator import EmulationReport, ReportFold
from repro.fleet import (FleetBase, FleetConfig, MeshSpec, Peer,
                         ProcessFleet, ScheduleBundle, WorkerSpec,
                         bundle_profile)
from repro.scenarios import generate

TILE = 64                  # 1 compute iter = 2*64^3  = 524288 flops
BLOCK = 1 << 18            # 1 memory  iter = 2*2^18  = 524288 bytes
FPI = 2.0 * TILE ** 3
BPI = 2.0 * BLOCK


def _em(**kw):
    return Emulator(compute_tile=TILE, mem_block=BLOCK, **kw)


def _rv(flops=0.0, hbm=0.0, sw=0.0, sr=0.0, ici=0.0):
    return ResourceVector(flops=flops, hbm_bytes=hbm,
                          storage_write_bytes=sw, storage_read_bytes=sr,
                          ici_bytes={"all-reduce": ici} if ici else {})


def _profile(rvs, command="fleet-test"):
    return SynapseProfile(command=command,
                          samples=[Sample(index=i, resources=r)
                                   for i, r in enumerate(rvs)])


def _mixed(tag, ici=0.0):
    """Compute/memory runs split by a storage barrier (and an ici leg)."""
    return _profile([_rv(flops=FPI, hbm=BPI), _rv(flops=2 * FPI),
                     _rv(flops=FPI, sw=2 << 20, sr=1 << 20),
                     _rv(flops=FPI, ici=ici),
                     _rv(hbm=2 * BPI)], command=f"fleet-test:{tag}")


# ---------------------------------------------------------------------------
# serialization layer (fast, in-process)
# ---------------------------------------------------------------------------

def test_schedule_detach_rehydrate_pickle_roundtrip():
    em = _em()
    sched = em.compile(_mixed("rt", ici=4e6), keep_collectives=True)
    back = rehydrate_schedule(pickle.loads(pickle.dumps(sched.detach())))
    assert [type(s) for s in back.steps] == [type(s) for s in sched.steps]
    # barrier around the storage leg AND the collective leg
    assert sum(isinstance(s, BarrierStep) for s in back.steps) == 2
    for a, b in zip(sched.steps, back.steps):
        if isinstance(a, FusedSegment):
            np.testing.assert_array_equal(a.table, b.table)
            assert a.rows == b.rows                    # bit-identical floats
        else:
            assert a.resources == b.resources and a.count == b.count


def test_bundle_profile_pickles_and_replays_identically(tmp_path):
    em = _em()
    em.storage.dir = str(tmp_path)
    prof = _mixed("bundle")
    try:
        ref = em.emulate(prof, fused=True)
        bundle = pickle.loads(pickle.dumps(bundle_profile(em, prof)))
        assert bundle.command == prof.command
        assert bundle.n_profile_samples == len(prof.samples)
        assert bundle.planned == prof.totals
        rep = em.replay(bundle.rehydrate(), command=bundle.command)
    finally:
        em.storage.cleanup()
    assert rep.consumed == ref.consumed == prof.totals
    assert rep.n_samples == ref.n_samples


def test_rehydrate_rejects_bad_payloads():
    with pytest.raises(ValueError):
        rehydrate_schedule({"version": 99, "steps": []})
    with pytest.raises(ValueError):
        rehydrate_schedule("not a payload")
    with pytest.raises(ValueError):
        rehydrate_schedule({"version": 2, "steps": [{"kind": "wat"}]})
    # only version 2 loads, and a segment table has the three columns
    with pytest.raises(ValueError):
        rehydrate_schedule({"version": 1, "steps": []})
    with pytest.raises(ValueError, match="3 columns"):
        rehydrate_schedule({"version": 2, "steps": [
            {"kind": "segment", "table": np.zeros((1, 2), np.int32),
             "rows": []}]})


def test_emulator_spec_roundtrips_through_pickle(tmp_path):
    em = _em(efficiency=0.5, speed=2.0)
    spec = pickle.loads(pickle.dumps(em.spec()))
    em2 = spec.build()
    assert em2.compute.tile == TILE and em2.compute.efficiency == 0.5
    assert em2.memory.block_bytes == BLOCK and em2.speed == 2.0
    assert em2.calib == em.calib                 # no re-calibration drift
    assert em2.collective is None
    prof = _profile([_rv(flops=4 * FPI, hbm=2 * BPI), _rv(flops=2 * FPI)])
    assert em2.emulate(prof).consumed == em.emulate(prof).consumed


def test_keep_collectives_lowers_wire_runs_to_barriers():
    em = _em()                                   # no mesh in this process
    prof = _profile([_rv(flops=FPI), _rv(flops=FPI, ici=4e6), _rv(hbm=BPI)])
    folded = em.compile(prof)                    # default: nothing executes
    assert [type(s) for s in folded.steps] == [FusedSegment]
    kept = em.compile(prof, keep_collectives=True)
    assert [type(s) for s in kept.steps] == \
        [FusedSegment, BarrierStep, FusedSegment]
    # both account the same totals
    assert em.replay(folded, command="f").consumed == \
        em.replay(kept, command="k").consumed == prof.totals


def test_mesh_spec_validates_and_counts_devices():
    assert MeshSpec(shape=(2, 4), axes=("data", "model")).device_count == 8
    with pytest.raises(ValueError):
        MeshSpec(shape=(2, 4), axes=("model",))
    with pytest.raises(ValueError):
        MeshSpec(shape=(), axes=())


class _RecordingContext:
    """Stands in for the spawn context: records the TPU chip each worker
    would see at start, and starts nothing."""

    def __init__(self):
        self.started = []
        ctx = self

        class Proc:
            pid = 0

            def __init__(self, **kw):
                pass

            def start(self):
                ctx.started.append(os.environ.get("TPU_VISIBLE_CHIPS"))

        self.Process = Proc

    def Pipe(self):
        return mp.Pipe()


def test_process_fleet_gives_each_worker_its_own_tpu_chip(monkeypatch):
    from repro.fleet import executor
    ctx = _RecordingContext()
    monkeypatch.setattr(executor, "host_tpu_chips", lambda: 4)
    monkeypatch.setattr(executor.mp, "get_context", lambda method: ctx)
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    pf = ProcessFleet(3, WorkerSpec(emulator=_em().spec()))
    assert ctx.started == ["0", "1", "2"]
    assert [p.chip for p in pf._peers] == [0, 1, 2]
    assert "TPU_VISIBLE_CHIPS" not in os.environ      # parent env restored
    pf._peers.pop(1)                                  # chip 1 frees up
    pf._spawn()
    assert ctx.started[-1] == "1"


def test_process_fleet_refuses_more_workers_than_tpu_chips(monkeypatch):
    from repro.fleet import executor
    count_chips = executor.host_tpu_chips
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")      # CPU workers: no limit
    assert count_chips() == 0
    monkeypatch.setattr(executor, "host_tpu_chips", lambda: 1)
    with pytest.raises(ValueError, match="1 TPU chip"):
        ProcessFleet(2, WorkerSpec(emulator=_em().spec()))


def test_process_executor_rejects_per_sample_path():
    em = _em()
    with pytest.raises(ValueError):
        em.emulate_many([_mixed("x")], executor="process", fused=False)
    with pytest.raises(ValueError):
        em.emulate_many([_mixed("x")], executor="carrier-pigeon")
    # a mesh on the thread executor would be silently dropped — refuse it
    with pytest.raises(ValueError, match="process"):
        em.emulate_many([_mixed("x")], executor="thread",
                        mesh_spec=MeshSpec(shape=(2,), axes=("model",)))


# ---------------------------------------------------------------------------
# streaming scheduler + FleetConfig (fast, in-process loopback peers)
# ---------------------------------------------------------------------------

class _EchoPeer(Peer):
    """Loopback peer: ``dispatch`` writes the reply into its own pipe, so
    the scheduler's wait/collect path runs unchanged with zero
    subprocesses.  The 'replay' consumes exactly the bundle's planned
    totals, so folded aggregates are deterministic."""

    def __init__(self):
        super().__init__()
        self._r, self._w = mp.Pipe(duplex=False)
        self.ready = True

    @property
    def waitable(self):
        return self._r

    def dispatch(self, epoch, idx, bundle):
        self.tasks.add((epoch, idx))
        rep = EmulationReport(command=bundle.command, ttc_s=1e-3,
                              n_samples=bundle.n_profile_samples,
                              consumed=bundle.planned, mode="fused")
        self._w.send(("ok", epoch, idx, rep))

    def recv(self):
        return self._r.recv()

    def close(self):
        self._r.close()
        self._w.close()


class _EchoFleet(FleetBase):
    def __init__(self, n, *, autoscale=False, scale_max=3, min_workers=1):
        super().__init__()
        self._autoscale = autoscale
        self._scale_min = min_workers
        self._scale_max = scale_max
        for _ in range(n):
            self._peers.append(_EchoPeer())

    def _scale_up(self):
        if len(self._peers) >= self._scale_max:
            return False
        self._peers.append(_EchoPeer())
        self.scale_ups += 1
        return True


def _echo_bundle(i):
    # awkward float amounts on purpose: summation order changes the bits,
    # so identical fold totals really mean identical fold order
    return ScheduleBundle(command=f"echo{i}", payload={},
                          n_profile_samples=1,
                          planned=_rv(flops=0.1 * i + 0.3, hbm=0.7 * i))


def _fold_stream(fleet, bundles, **kw):
    fold = ReportFold()
    for idx, rep in fleet.stream(bundles, **kw):
        fold.add(idx, rep)
    return fold


def test_stream_window_bounds_compile_ahead():
    """The backpressure contract: a probe source counting outstanding
    pulls (pulled but not yet yielded back) never sees more than
    ``window`` in flight."""
    n, window = 24, 4
    state = {"pulled": 0, "done": 0, "peak": 0}

    def source():
        for i in range(n):
            out = state["pulled"] - state["done"]
            state["peak"] = max(state["peak"], out + 1)   # incl. this pull
            state["pulled"] += 1
            yield _echo_bundle(i)

    with _EchoFleet(1) as fleet:
        fold = ReportFold()
        for idx, rep in fleet.stream(source(), window=window):
            state["done"] += 1
            fold.add(idx, rep)
    assert fold.n_done == n
    assert state["peak"] <= window
    assert fleet.last_scaling["peak_window"] <= window
    # reports folded in index order regardless of completion order
    assert [r.command for r in fold.reports] == \
        [f"echo{i}" for i in range(n)]


def test_stream_autoscale_matches_fixed_totals_bitwise():
    """Elasticity must not change the answer: an autoscaled 1→3 pool folds
    the same aggregate bits as a fixed 3-worker pool, scales up on queue
    depth, and parks back at its floor when the stream drains."""
    bundles = [_echo_bundle(i) for i in range(30)]
    with _EchoFleet(3) as fixed:
        ref = _fold_stream(fixed, list(bundles))
    with _EchoFleet(1, autoscale=True, scale_max=3) as elastic:
        out = _fold_stream(elastic, iter(bundles), window=8)
        assert elastic.scale_ups >= 1
        assert elastic.scale_downs >= 1
        assert len(elastic._peers) == 1              # parked at the floor
    assert out.totals == ref.totals                  # bit-identical
    assert out.serial_s == ref.serial_s
    assert out.n_done == ref.n_done == 30
    sc = elastic.last_scaling
    assert sc["scale_ups"] == elastic.scale_ups
    assert 1 <= sc["peak_workers"] <= 3
    assert sc["peak_queue_depth"] >= 1


def test_fleet_config_validates_and_pickles():
    cfg = FleetConfig.process(max_workers=8, autoscale=True, min_workers=2,
                              window=16, timeout=30.0)
    assert pickle.loads(pickle.dumps(cfg)) == cfg
    assert cfg.scale_min == 2
    assert FleetConfig.remote(["h:1"]).hosts == ("h:1",)   # normalized
    with pytest.raises(ValueError):
        FleetConfig(executor="carrier-pigeon")
    with pytest.raises(ValueError):                  # hosts without remote
        FleetConfig(hosts=("h:1",))
    with pytest.raises(ValueError, match="process"):  # mesh on threads
        FleetConfig(mesh_spec=MeshSpec(shape=(2,), axes=("model",)))
    with pytest.raises(ValueError):                  # remote with no agents
        FleetConfig(executor="remote")
    with pytest.raises(ValueError):                  # agents without listen
        FleetConfig.remote(["h:1"], agents=2)
    with pytest.raises(ValueError):                  # floor without autoscale
        FleetConfig.process(min_workers=2)
    with pytest.raises(ValueError):                  # floor above ceiling
        FleetConfig.process(max_workers=2, autoscale=True, min_workers=3)
    with pytest.raises(ValueError):
        FleetConfig(window=0)
    with pytest.raises(ValueError):                  # threads can't scale
        FleetConfig(executor="thread", autoscale=True)


def test_fleet_config_folds_legacy_kwargs_equivalently():
    from repro.fleet.config import UNSET
    with pytest.warns(DeprecationWarning, match="deprecated"):
        folded = FleetConfig.fold(
            None, dict(executor="process", max_workers=3, timeout=5.0),
            caller="test")
    assert folded == FleetConfig.process(max_workers=3, timeout=5.0)
    with warnings.catch_warnings():                  # silence ≠ deprecation
        warnings.simplefilter("error")
        assert FleetConfig.fold(None, dict(executor=UNSET, hosts=UNSET),
                                caller="test") == FleetConfig()
    with pytest.raises(ValueError, match="both"):    # one surface at a time
        FleetConfig.fold(FleetConfig(), dict(max_workers=2), caller="test")
    with pytest.raises(TypeError):
        FleetConfig.fold(None, dict(bogus=1), caller="test")


def test_emulate_many_accepts_config_and_generator():
    em = _em()
    profs = [_profile([_rv(flops=FPI * (i + 1))], command=f"s{i}")
             for i in range(6)]
    with warnings.catch_warnings():                  # config= never warns
        warnings.simplefilter("error")
        ref = em.emulate_many(profs, config=FleetConfig.thread(max_workers=1))
        streamed = em.emulate_many(
            (p for p in profs),
            config=FleetConfig.thread(max_workers=1, window=2),
            collect="totals")
    assert streamed.n_replayed == ref.n_replayed == 6
    assert streamed.reports == []                    # totals mode drops them
    assert streamed.totals == ref.totals             # bit-identical fold
    assert streamed.n_samples == ref.n_samples == 6
    assert ref.summary()["total_flops"] == ref.totals.flops
    with pytest.raises(ValueError, match="collect"):
        em.emulate_many(profs, collect="everything")


# ---------------------------------------------------------------------------
# process executor (spawns real workers)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.subproc
def test_process_fleet_bit_identical_and_collectives_execute():
    """The ISSUE 3 acceptance contract: a mixed_fleet job set replayed by
    the process executor consumes bit-identical totals per profile, and a
    profile with a collective leg issues collective dispatches on the
    workers' own meshes."""
    em = _em()
    profiles = [generate("mixed_fleet", total_samples=6, seed=1),
                generate("mixed_fleet", total_samples=6, seed=2),
                generate("training_scan", n_steps=4, ckpt_every=2,
                         flops_per_step=4e7, hbm_per_step=2e6,
                         ckpt_bytes=2 << 20),
                _mixed("coll", ici=4e6)]
    refs = [em.emulate(p, fused=True) for p in profiles]
    em.storage.cleanup()
    fleet = em.emulate_many(profiles, max_workers=2, executor="process",
                            mesh_spec=MeshSpec(shape=(2,), axes=("model",)))
    assert fleet.n_profiles == len(profiles)
    assert fleet.max_workers == 2
    assert fleet.cache_stats["worker_deaths"] == 0
    for ref, rep in zip(refs, fleet.reports):
        assert rep.mode == "fused"
        assert rep.consumed == ref.consumed          # bit-identical
        assert rep.n_samples == ref.n_samples
    coll = fleet.reports[-1]
    assert coll.consumed.ici_total == 4e6
    assert coll.n_collective_dispatches > 0          # it really executed
    # fleet summary surfaces the new I/O fields
    s = coll.summary()
    assert s["ici_bytes"] == 4e6 and "storage_read_bytes" in s


@pytest.mark.slow
@pytest.mark.subproc
def test_process_fleet_survives_worker_death_and_reports_errors():
    em = _em()
    bundles = [bundle_profile(em, _mixed(i)) for i in range(6)]
    with ProcessFleet(2, WorkerSpec(emulator=em.spec())) as pf:
        pf.warmup()
        os.kill(pf.pids[0], signal.SIGKILL)          # one worker dies
        reports = pf.run(bundles)
        assert len(reports) == len(bundles)          # nothing lost
        assert pf.worker_deaths >= 1
        ref = em.emulate(_mixed(0), fused=True)
        em.storage.cleanup()
        assert all(r.consumed == ref.consumed for r in reports)
        # a malformed bundle is a loud failure, not a hang — and the
        # worker survives it.  Good bundles are in flight when the run
        # raises, so the follow-up run also proves a raised run's
        # stragglers neither leak into the next run's results nor
        # permanently occupy their workers.
        bad = ScheduleBundle(command="bad", payload={"version": 99})
        with pytest.raises(RuntimeError, match="bad"):
            pf.run([bad] + bundles)
        again = pf.run(bundles[:2])                  # pool still serves
        assert [r.command for r in again] == \
            [b.command for b in bundles[:2]]
        assert [r.consumed for r in again] == \
            [r.consumed for r in reports[:2]]


@pytest.mark.slow
@pytest.mark.subproc
def test_process_fleet_streamed_autoscale_matches_fixed():
    """The ISSUE 6 acceptance contract on real workers: a lazy profile
    source replayed by an elastic 1→2 pool folds aggregate totals
    bit-identical to a fixed 2-worker pool over the same profiles, with
    the scale record surfaced in FleetReport.scaling."""
    em = _em()
    profs = [_mixed(i) for i in range(6)]
    fixed = em.emulate_many(profs, config=FleetConfig.process(max_workers=2),
                            collect="totals")
    elastic = em.emulate_many(
        (p for p in profs),                          # no len(): a stream
        config=FleetConfig.process(max_workers=2, autoscale=True,
                                   min_workers=1, window=4),
        collect="totals")
    assert elastic.totals == fixed.totals            # bit-identical
    assert elastic.n_replayed == fixed.n_replayed == len(profs)
    assert elastic.reports == [] == fixed.reports
    assert elastic.scaling["scale_ups"] >= 1         # it really grew
    assert 1 <= elastic.scaling["peak_workers"] <= 2
    assert elastic.scaling["peak_window"] <= 4
