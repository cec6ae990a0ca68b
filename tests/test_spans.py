"""The emulator's spans (``repro.obs.span``) in a real profiler trace.

Each test traces a replay on the CPU with ``jax.profiler`` and reads the
trace back with ``ProfileData``: the spans land in the profiler's own host
plane, nested as the replay's phases are, one per phase and none per row.
"""
import glob
import os
import pathlib
import subprocess
import sys

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import Emulator, ResourceVector, Sample, SynapseProfile
from repro.obs import SPANS

TILE = 64
BLOCK = 1 << 18
FPI = 2.0 * TILE ** 3
BPI = 2.0 * BLOCK


def _profile(rvs):
    return SynapseProfile(command="spans-test",
                          samples=[Sample(index=i, resources=r)
                                   for i, r in enumerate(rvs)])


def _rv(flops=0.0, hbm=0.0, sw=0.0, sr=0.0):
    return ResourceVector(flops=flops, hbm_bytes=hbm,
                          storage_write_bytes=sw, storage_read_bytes=sr)


def traced(tmp_path, fn):
    """Run ``fn`` under a profiler trace; the ``synapse.*`` host spans it
    opened, [(start_ns, end_ns, name)] in order of start."""
    out = str(tmp_path / "trace")
    jax.profiler.start_trace(out)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    spans = [(e.start_ns, e.end_ns, e.name)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("synapse.")]
    return sorted(spans)


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_fused_emulate_spans(tmp_path):
    em = Emulator(compute_tile=TILE, mem_block=BLOCK)
    prof = _profile([_rv(flops=(1 + i % 2) * FPI, hbm=(1 + i % 2) * BPI)
                     for i in range(16)])
    em.emulate(prof)                            # builds the program
    spans = traced(tmp_path, lambda: em.emulate(prof))
    names = [n for _, _, n in spans]
    assert names == ["synapse.emulate", "synapse.schedule",
                     "synapse.segment.launch", "synapse.segment.sync",
                     "synapse.account"]
    outer = spans[0]
    assert all(_inside(sp, outer) for sp in spans[1:])
    # in that order, one after another
    for a, b in zip(spans[1:], spans[2:]):
        assert a[1] <= b[0]
    assert set(names) <= set(SPANS)


def test_storage_profile_opens_barrier_spans(tmp_path):
    em = Emulator(compute_tile=TILE, mem_block=BLOCK)
    em.storage.dir = str(tmp_path)
    # [work] [write+read] [work]: a segment, a barrier, a segment
    prof = _profile([_rv(flops=FPI, hbm=BPI),
                     _rv(flops=FPI, sw=2 << 20, sr=1 << 20),
                     _rv(flops=2 * FPI)])
    try:
        em.emulate(prof)
        spans = traced(tmp_path, lambda: em.emulate(prof))
    finally:
        em.storage.cleanup()
    names = [n for _, _, n in spans]
    assert names.count("synapse.barrier") == 1
    barrier = spans[names.index("synapse.barrier")]
    kids = [n for s, e, n in spans if _inside((s, e), barrier)
            and n != "synapse.barrier"]
    assert kids == ["synapse.barrier.launch", "synapse.barrier.sync",
                    "synapse.storage"]
    assert names.count("synapse.segment.launch") == 2
    assert set(names) <= set(SPANS)


def test_per_sample_path_opens_a_barrier_per_run(tmp_path):
    em = Emulator(compute_tile=TILE, mem_block=BLOCK)
    # two runs: three identical samples collapse into one, then one more
    prof = _profile([_rv(flops=FPI)] * 3 + [_rv(hbm=BPI)])
    em.emulate(prof, fused=False)
    spans = traced(tmp_path, lambda: em.emulate(prof, fused=False))
    names = [n for _, _, n in spans]
    assert names.count("synapse.barrier") == 2
    assert names.count("synapse.barrier.sync") == 2
    assert names[0] == "synapse.emulate" and names[-1] == "synapse.account"


def test_only_a_new_program_opens_a_compile_span(tmp_path):
    em = Emulator(compute_tile=TILE, mem_block=BLOCK)
    prof = _profile([_rv(flops=FPI, hbm=BPI), _rv(flops=2 * FPI)])
    first = traced(tmp_path / "1", lambda: em.emulate(prof))
    second = traced(tmp_path / "2", lambda: em.emulate(prof))
    compiles = [sp for sp in first if sp[2] == "synapse.segment.compile"]
    assert len(compiles) == 1
    launch = next(sp for sp in first if sp[2] == "synapse.segment.launch")
    assert _inside(compiles[0], launch)
    assert "synapse.segment.compile" not in [n for _, _, n in second]


def test_n_samples_counts_executed_samples(tmp_path):
    """Without the per-sample timings the report still counts what ran:
    a segment's rows, a storage run's samples one by one."""
    em = Emulator(compute_tile=TILE, mem_block=BLOCK)
    em.storage.dir = str(tmp_path)
    work = _rv(flops=FPI, hbm=BPI)
    store = _rv(sw=1 << 20)
    prof = _profile([work, work, store, store, _rv(flops=2 * FPI)])
    try:
        fused = em.emulate(prof)
        legacy = em.emulate(prof, fused=False)
    finally:
        em.storage.cleanup()
    # [work x2] collapses to one row; the two storage samples run apart
    assert fused.n_samples == legacy.n_samples == 4
    assert "per_sample" not in "".join(fused.to_dict())


def _mesh_bound_spans(out):
    """In a process that sees four CPU devices: a fused replay whose rows
    carry wire bytes, on a 1 x 4 mesh, traced; prints its span names,
    whether they nest and follow one another as on one chip, and the
    wire the report says it burned against the profile's."""
    import json

    from repro.core.atoms import CollectiveQuant
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 4), ("data", "model"))
    em = Emulator(compute_tile=TILE, mem_block=BLOCK, mesh=mesh)
    wpi = CollectiveQuant(n=4).wire_bytes_per_iter
    prof = _profile([ResourceVector(flops=(1 + i % 2) * FPI,
                                    hbm_bytes=(1 + i % 2) * BPI,
                                    ici_bytes={"all-gather": (1 + i % 3) * wpi})
                     for i in range(16)])
    em.emulate(prof)                            # builds the program
    rep = []
    spans = traced(pathlib.Path(out), lambda: rep.append(em.emulate(prof)))
    ordered = all(a[1] <= b[0] for a, b in zip(spans[1:], spans[2:]))
    nested = all(_inside(sp, spans[0]) for sp in spans[1:])
    print(json.dumps({"names": [n for _, _, n in spans], "ordered": ordered,
                      "nested": nested, "mode": rep[0].mode,
                      "emulated_ici": rep[0].emulated_ici_bytes,
                      "planned_ici": prof.totals.ici_total}))


@pytest.mark.subproc
def test_mesh_bound_fused_emulate_spans(tmp_path):
    """The mesh-bound fused replay, whose segments carry the collective
    leg, opens the one-chip replay's spans in the same order, and reports
    the wire it burned."""
    import json
    here = os.path.dirname(os.path.abspath(__file__))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.path.join(os.path.dirname(here), "src")}
    p = subprocess.run([sys.executable, os.path.join(here, "test_spans.py"),
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["names"] == ["synapse.emulate", "synapse.schedule",
                          "synapse.segment.launch", "synapse.segment.sync",
                          "synapse.account"], r
    assert r["ordered"] and r["nested"], r
    assert r["mode"] == "fused"
    assert r["emulated_ici"] == pytest.approx(r["planned_ici"])


@pytest.mark.subproc
def test_import_obs_leaves_jax_out():
    code = ("import sys, repro.obs; "
            "assert callable(repro.obs.span); "
            "print('jax' in sys.modules)")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = {**os.environ, "PYTHONPATH": src}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


if __name__ == "__main__":
    _mesh_bound_spans(sys.argv[1])
