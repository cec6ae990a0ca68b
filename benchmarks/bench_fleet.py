"""Fleet executor benchmark: thread vs process vs remote, cold vs warm —
plus the streamed-production-day soak (``soak()``).

Replays the same mixed fleet several ways and reports where each
executor's costs live:

  * ``thread_wall_s``   — in-process thread fleet (the PR 1/2 baseline),
                          warm plan cache and segment programs;
  * ``process_cold_s``  — first ``ProcessFleet.run`` after spawn: each
                          worker traces its fused programs once (worker
                          spawn + jax import time is reported separately
                          as ``spawn_s``);
  * ``process_warm_s``  — the same pool again: pure replay + IPC, the
                          steady-state cost a long-lived fleet pays;
  * ``remote_warm_s``   — the same bundles through the full network
                          stack: loopback TCP to ``repro.fleet.agent``
                          subprocesses (one worker each), so
                          ``framing_overhead`` = remote_warm /
                          process_warm isolates what the length-prefixed
                          pickle framing + agent proxy hop add over a raw
                          ``Pipe`` (agent join/spawn cost is
                          ``remote_join_s``).

The regression guards are deliberately loose — this container's wall-clock
ratios swing ~2x run-to-run (see bench_dispatch) — and the remote scenario
has NO wall-clock gate at all: the hard assert is correctness, which is
noise-free — every process- and remote-fleet report must consume totals
bit-identical to the in-process replay.  The warm-pool guard catches the
failure mode that matters architecturally: workers re-tracing per bundle
instead of once per process would push warm replay toward cold time and
far past the bound.

``soak()`` is the ISSUE 6 acceptance scenario: a synthetic "production
day" of profiles streamed through an elastic process fleet at a bounded
compile-ahead window, never materialized.  Its hard asserts are exact
(profile amounts are powers of two, so every fold is integer-exact in
float64): streamed totals == materialized totals == the analytic
expectation, and coordinator peak-RSS growth is *independent of profile
count* — a 10x-smaller streamed run must show no less growth (within
slack) than the full one.  Both suites merge rows into
``experiments/results/fleet.json`` keyed on a ``scenario`` field.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks.common import RESULT_DIR, emit
from repro.core import (Emulator, PlanCache, ResourceVector, Sample,
                        SynapseProfile)
from repro.fleet import (FleetConfig, ProcessFleet, RemoteFleet, WorkerSpec,
                         bundle_profile)
from repro.scenarios import generate

WORKERS = 2


def _emit_fleet(scenario: str, rows):
    """``emit`` overwrites ``fleet.json``; merge by scenario so the
    executors row and the soak row coexist in one results file."""
    path = os.path.join(RESULT_DIR, "fleet.json")
    merged = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                # rows written before scenario tagging are executors rows
                merged = [r for r in json.load(f)
                          if r.get("scenario", "executors") != scenario]
        except (ValueError, OSError):
            merged = []
    for r in rows:
        r.setdefault("scenario", scenario)
    emit("fleet", merged + rows)


def _spawn_agents(port: int, n: int):
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    old = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = src + (os.pathsep + old if old else "")
    return [subprocess.Popen(
        [sys.executable, "-m", "repro.fleet.agent",
         "--connect", f"127.0.0.1:{port}", "--workers", "1"],
        env=env) for _ in range(n)]


def fleet_profiles(k: int):
    """A mixed fleet: scan steps + checkpoints, request traffic, stragglers."""
    kinds = [
        lambda i: generate("training_scan", n_steps=8, ckpt_every=4,
                           flops_per_step=4e7, hbm_per_step=3.4e7,
                           ckpt_bytes=1 << 20),
        lambda i: generate("serving_traffic", n_requests=6, n_params=2e6,
                           prefill_tokens=64, decode_tokens=8, seed=i),
        lambda i: generate("fanout_straggler", n_workers=4, work_flops=5e7,
                           work_hbm=4e7, jitter=0.0, seed=i),
    ]
    return [kinds[i % len(kinds)](i) for i in range(k)]


def main(fast: bool = False):
    k = 4 if fast else 8
    reps = 3
    profiles = fleet_profiles(k)
    em = Emulator(plan_cache=PlanCache())

    # process and agent legs first: their workers need the accelerator,
    # and this process holds it once it replays anything itself
    bundles = [bundle_profile(em, p) for p in profiles]
    t0 = time.perf_counter()
    fleet = ProcessFleet(WORKERS, WorkerSpec(emulator=em.spec()))
    try:
        fleet.warmup()
        spawn_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        cold_reports = fleet.run(bundles)
        cold_s = time.perf_counter() - t0

        warm_s = float("inf")
        warm_reports = cold_reports
        for _ in range(reps):
            t0 = time.perf_counter()
            r = fleet.run(bundles)
            dt = time.perf_counter() - t0
            if dt < warm_s:
                warm_s, warm_reports = dt, r
    finally:
        fleet.close()

    # -- remote scenario: same bundles over loopback TCP agents ------------
    remote = RemoteFleet(WorkerSpec(emulator=em.spec()),
                         listen="127.0.0.1:0", agents=WORKERS)
    procs = _spawn_agents(remote.bound_addr[1], WORKERS)
    try:
        t0 = time.perf_counter()
        remote.warmup(timeout=300.0)
        remote_join_s = time.perf_counter() - t0

        remote.run(bundles)                    # agents trace once (cold)
        remote_warm_s = float("inf")
        remote_reports = None
        for _ in range(reps):
            t0 = time.perf_counter()
            r = remote.run(bundles)
            dt = time.perf_counter() - t0
            if dt < remote_warm_s:
                remote_warm_s, remote_reports = dt, r
    finally:
        remote.close()
        for p in procs:
            try:
                p.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                p.kill()

    cfg = FleetConfig.thread(max_workers=WORKERS)
    em.emulate_many(profiles, config=cfg)                   # warm in-process
    thread_fleet = None
    thread_s = float("inf")
    for _ in range(reps):
        f = em.emulate_many(profiles, config=cfg)
        if f.wall_s < thread_s:
            thread_s, thread_fleet = f.wall_s, f
    em.storage.cleanup()

    identical = all(
        pr.consumed == tr.consumed and pr.n_samples == tr.n_samples
        for pr, tr in zip(warm_reports, thread_fleet.reports))
    remote_identical = all(
        rr.consumed == tr.consumed and rr.n_samples == tr.n_samples
        for rr, tr in zip(remote_reports, thread_fleet.reports))
    rows = [{
        "k_profiles": k,
        "workers": WORKERS,
        "thread_wall_s": thread_s,
        "spawn_s": spawn_s,
        "process_cold_s": cold_s,
        "process_warm_s": warm_s,
        "warm_vs_thread": warm_s / thread_s if thread_s else 0.0,
        "cold_vs_warm": cold_s / warm_s if warm_s else 0.0,
        "remote_agents": WORKERS,
        "remote_join_s": remote_join_s,
        "remote_warm_s": remote_warm_s,
        "framing_overhead": remote_warm_s / warm_s if warm_s else 0.0,
        "worker_deaths": fleet.worker_deaths,
        "agent_deaths": remote.worker_deaths,
        "consumed_identical": identical,
        "remote_consumed_identical": remote_identical,
    }]
    _emit_fleet("executors", rows)
    assert identical, \
        "process-fleet totals must be bit-identical to in-process replay"
    # correctness only for the network hop — framing_overhead is reported,
    # not gated (container wall-clock swings ~2x run-to-run)
    assert remote_identical, \
        "remote-fleet totals must be bit-identical to in-process replay"
    # Loose guards only (2x run-to-run noise): warm process replay must be
    # in the same decade as the thread fleet — re-tracing per bundle would
    # be orders of magnitude off — and an absolute floor keeps tiny fast
    # runs from tripping on IPC constants.
    bound = max(5.0 * thread_s, 2.0)
    assert warm_s <= bound, \
        f"warm process fleet {warm_s:.3f}s vs bound {bound:.3f}s " \
        f"(thread fleet {thread_s:.3f}s) — are workers re-tracing per bundle?"
    return rows


# ---------------------------------------------------------------------------
# streamed production-day soak (ISSUE 6 acceptance)
# ---------------------------------------------------------------------------

# One soak sample = exactly one quantization iteration of each atom, so the
# emulated amounts are powers of two and every sum below stays integer-
# exact in float64 — the exactness the totals asserts lean on.
_SOAK_TILE = 64                  # 2 * 64^3  = 2^19 flops / iteration
_SOAK_BLOCK = 1 << 18            # 2 * 2^18  = 2^19 bytes / iteration
_SOAK_FPI = 2.0 * _SOAK_TILE ** 3
_SOAK_BPI = 2.0 * _SOAK_BLOCK


def _rss_kb() -> int:
    """Current resident set, not the ru_maxrss high-water mark — the soak
    needs growth *during* a run, and a monotone mark from warmup would
    mask it."""
    with open("/proc/self/statm") as f:
        pages = int(f.read().split()[1])
    return pages * (os.sysconf("SC_PAGE_SIZE") // 1024)


def _soak_profile(i: int, samples_per: int) -> SynapseProfile:
    # 7 distinct day shapes so the stream isn't one repeated profile;
    # amounts stay exact multiples of one iteration
    rv = ResourceVector(flops=_SOAK_FPI * (1 + i % 7), hbm_bytes=_SOAK_BPI)
    return SynapseProfile(
        command=f"soak:{i}",
        samples=[Sample(index=j, resources=rv) for j in range(samples_per)])


def _soak_source(n_profiles: int, samples_per: int, tracker=None):
    for i in range(n_profiles):
        if tracker is not None:
            tracker["peak"] = max(tracker["peak"], _rss_kb())
        yield _soak_profile(i, samples_per)


def _expected_totals(n_profiles: int, samples_per: int):
    flops = sum(samples_per * int(_SOAK_FPI) * (1 + i % 7)
                for i in range(n_profiles))
    return float(flops), float(n_profiles * samples_per * int(_SOAK_BPI))


def soak(fast: bool = False):
    """Replay a synthetic production day as a stream: profiles are pulled,
    compiled, and shipped at most ``window`` ahead of an elastic 1→3
    process fleet, with per-profile reports dropped after index-order
    folding (``collect="totals"``).  Asserts, exactly:

      * streamed totals == materialized fixed-fleet totals (bit-identical)
        == the analytic expectation — nothing lost or double-counted
        across backpressure, autoscaling, or completion reordering;
      * the fleet really scaled (≥1 scale-up, parked back at the floor);
      * coordinator peak-RSS growth is independent of profile count: the
        full run may not grow more than a 10x-smaller streamed run plus a
        fixed slack.
    """
    n_profiles = 2_000 if fast else 5_000
    samples_per = 50 if fast else 200    # 100k / 1M samples
    window = 8
    em = Emulator(compute_tile=_SOAK_TILE, mem_block=_SOAK_BLOCK)
    cfg = FleetConfig.process(max_workers=3, autoscale=True, min_workers=1,
                              window=window, timeout=3600.0)

    # -- calibration run at a tenth of the size: its RSS growth is the
    # "profile-count-independent" yardstick (and it warms jax/XLA, so the
    # big run's growth measures the pipeline, not first-touch allocations)
    small_n = max(n_profiles // 10, 50)
    base = _rss_kb()
    tracker = {"peak": base}
    em.emulate_many(_soak_source(small_n, samples_per, tracker),
                    config=cfg, collect="totals")
    small_growth = tracker["peak"] - base

    # -- the day itself, streamed ------------------------------------------
    base = _rss_kb()
    tracker = {"peak": base}
    t0 = time.perf_counter()
    streamed = em.emulate_many(
        _soak_source(n_profiles, samples_per, tracker),
        config=cfg, collect="totals")
    stream_wall = time.perf_counter() - t0
    big_growth = tracker["peak"] - base

    # -- the same profile set materialized on a fixed-size fleet -----------
    day = [_soak_profile(i, samples_per) for i in range(n_profiles)]
    t0 = time.perf_counter()
    fixed = em.emulate_many(day, config=FleetConfig.process(
        max_workers=3, window=window, timeout=3600.0), collect="totals")
    fixed_wall = time.perf_counter() - t0

    exp_flops, exp_hbm = _expected_totals(n_profiles, samples_per)
    rows = [{
        "n_profiles": n_profiles,
        "samples_per_profile": samples_per,
        "n_samples": streamed.n_samples,
        "window": window,
        "stream_wall_s": stream_wall,
        "samples_per_s": streamed.n_samples / stream_wall if stream_wall
        else 0.0,
        "materialized_wall_s": fixed_wall,
        "scale_ups": streamed.scaling.get("scale_ups", 0),
        "scale_downs": streamed.scaling.get("scale_downs", 0),
        "peak_workers": streamed.scaling.get("peak_workers", 0),
        "peak_window": streamed.scaling.get("peak_window", 0),
        "small_run_rss_growth_kb": small_growth,
        "rss_growth_kb": big_growth,
        "total_flops": streamed.totals.flops,
        "totals_bit_identical": streamed.totals == fixed.totals,
        "totals_exact": (streamed.totals.flops == exp_flops
                         and streamed.totals.hbm_bytes == exp_hbm),
    }]
    _emit_fleet("soak", rows)

    assert streamed.n_replayed == fixed.n_replayed == n_profiles
    assert streamed.n_samples == n_profiles * samples_per
    assert not streamed.reports, "collect='totals' must drop reports"
    assert streamed.totals == fixed.totals, \
        "streamed-vs-materialized totals must be bit-identical"
    assert streamed.totals.flops == exp_flops \
        and streamed.totals.hbm_bytes == exp_hbm, \
        f"soak totals drifted from the analytic expectation: " \
        f"{streamed.totals.flops} != {exp_flops}"
    assert streamed.scaling.get("scale_ups", 0) >= 1, \
        "the elastic fleet never scaled up under a backed-up queue"
    assert streamed.scaling.get("peak_window", 0) <= window
    # RSS independence: 10x the profiles may not cost more coordinator
    # memory than the small run did, beyond a fixed allocator-noise slack.
    slack_kb = 96 * 1024
    assert big_growth <= small_growth + slack_kb, \
        f"coordinator RSS grew with profile count: {big_growth}kB for " \
        f"{n_profiles} profiles vs {small_growth}kB for {small_n} " \
        f"(+{slack_kb}kB slack) — is the stream being materialized?"
    return rows


# ---------------------------------------------------------------------------
# chaos: recovery cost under a seeded fault schedule (ISSUE 7 acceptance)
# ---------------------------------------------------------------------------

def chaos(fast: bool = False):
    """Replay the same exact-amount profile set twice on a 2-worker process
    fleet — once clean, once under a seeded ``ChaosPolicy`` where every
    worker dies exactly once, on its 5th dispatch — and report what the
    faults cost: worker deaths, requeues, requeue latency, lost replay
    work, MTTR (death → replacement ready), heartbeat volume, and the
    wall-clock overhead of recovering.  The hard asserts are noise-free:
    fault-injected totals must be bit-identical to the clean run AND equal
    the analytic expectation, the scheduled deaths must actually happen,
    and every death must be measured (MTTR recorded, requeues counted).
    """
    from repro.fleet import ChaosPolicy

    n = 12 if fast else 24
    samples_per = 4
    em = Emulator(compute_tile=_SOAK_TILE, mem_block=_SOAK_BLOCK)
    profiles = [_soak_profile(i, samples_per) for i in range(n)]

    t0 = time.perf_counter()
    clean = em.emulate_many(
        profiles, config=FleetConfig.process(max_workers=WORKERS),
        collect="totals")
    clean_wall = time.perf_counter() - t0

    pol = ChaosPolicy(seed=7, kill_every=5, max_faults=1)
    # liveness 2s => 0.5s heartbeats: short enough that pings actually
    # flow within this run's few seconds, three orders of magnitude above
    # the ms-scale bundle replays so nothing is falsely reaped
    cfg = FleetConfig.process(max_workers=WORKERS, chaos=pol,
                              max_respawns=8, liveness_timeout=2.0)
    t0 = time.perf_counter()
    hurt = em.emulate_many(profiles, config=cfg, collect="totals")
    chaos_wall = time.perf_counter() - t0
    rec = hurt.recovery

    exp_flops, exp_hbm = _expected_totals(n, samples_per)
    rows = [{
        "n_profiles": n,
        "workers": WORKERS,
        "kill_every": 5,
        "clean_wall_s": clean_wall,
        "chaos_wall_s": chaos_wall,
        "recovery_overhead": chaos_wall / clean_wall if clean_wall else 0.0,
        "worker_deaths": rec.get("worker_deaths", 0),
        "requeued": rec.get("requeued", 0),
        "requeue_latency_s": rec.get("requeue_latency_s", 0.0),
        "lost_replay_s": rec.get("lost_replay_s", 0.0),
        "mttr_s": rec.get("mttr_s"),
        "heartbeats": rec.get("heartbeats", 0),
        "respawns": hurt.cache_stats.get("respawns", 0),
        "totals_bit_identical": hurt.totals == clean.totals,
        "totals_exact": (hurt.totals.flops == exp_flops
                         and hurt.totals.hbm_bytes == exp_hbm),
    }]
    _emit_fleet("chaos", rows)

    assert hurt.n_replayed == clean.n_replayed == n
    assert hurt.totals == clean.totals, \
        "fault-injected totals must be bit-identical to the clean run"
    assert hurt.totals.flops == exp_flops \
        and hurt.totals.hbm_bytes == exp_hbm, \
        "chaos totals drifted from the analytic expectation"
    assert rec.get("worker_deaths", 0) >= 1, \
        "the seeded kill schedule never fired — chaos is not reaching workers"
    assert rec.get("requeued", 0) >= rec["worker_deaths"] or \
        rec.get("requeued", 0) >= 1, \
        "deaths happened but their in-flight bundles were not requeued"
    assert rec.get("mttr_s") is not None and rec["mttr_s"] > 0.0, \
        "worker deaths were repaired but MTTR was not measured"
    assert rec.get("heartbeats", 0) >= 1, \
        "liveness_timeout was armed but no heartbeat ever arrived"
    assert rec.get("skipped") == [], "nothing should be skipped under raise"
    return rows


# ---------------------------------------------------------------------------
# dag: dependency-structured replay (ISSUE 10 acceptance scenario)
# ---------------------------------------------------------------------------

def dag(fast: bool = False):
    """Replay a fork-join diamond (``dag_diamond_workload``) on the process
    fleet's frontier scheduler and report what the structure costs and
    buys: per-run makespan vs serialized sum-of-work, the critical path
    and its parallelism ratio, and frontier bookkeeping volume (dep_wait/
    dep_release events).  Hard asserts are noise-free: the index-order
    fold must be bit-identical to the workload's analytic totals, and the
    diamond's makespan must beat the serialized sum by a real margin
    (the branches genuinely overlap — with ``fanout`` parallel branches
    and 2 workers, sum-of-work / makespan must clear 2x minus slack).
    """
    from repro.obs.recorder import Event
    from repro.scenarios.dag import dag_diamond_workload

    fanout = 4 if fast else 8
    samples_per = 2 if fast else 4
    # ~1000 compute iterations per sample: tens of ms of genuine replay
    # per branch, so scheduling/IPC overhead can't masquerade as the
    # branch window.  Straggler does 2x: visible on the critical path,
    # but not so dominant that the overlap ratio collapses toward 1.
    d = dag_diamond_workload(fanout=fanout, work_flops=1000 * _SOAK_FPI,
                             work_hbm=_SOAK_BPI, samples_per=samples_per,
                             straggler_index=0, straggler_factor=2.0)
    em = Emulator(compute_tile=_SOAK_TILE, mem_block=_SOAK_BLOCK)
    t0 = time.perf_counter()
    out = em.emulate_many(d, config=FleetConfig.process(max_workers=WORKERS))
    wall = time.perf_counter() - t0
    cp = out.dag
    events = [Event.from_dict(x) for x in out.obs["events"]]
    # branch-level overlap, from the merged timeline: the fork's whole
    # point is that branches 1..fanout replay concurrently.  (The cp
    # parallelism ratio is reported but not asserted on — the source
    # node is always the pool's first dispatch and its replay_s eats the
    # worker cold-start, which serializes the aggregate ratio toward 1.)
    disp, done = {}, {}
    for e in events:
        idx = e.get("idx")
        if e.kind == "dispatch" and idx is not None:
            disp.setdefault(idx, e.t)
        elif e.kind == "done" and idx is not None:
            done[idx] = e.t
    branch_ids = range(1, fanout + 1)
    branch_work = sum(done[i] - disp[i] for i in branch_ids)
    branch_span = max(done[i] for i in branch_ids) \
        - min(disp[i] for i in branch_ids)
    overlap = branch_work / branch_span if branch_span > 0 else 0.0
    rows = [{
        "fanout": fanout,
        "workers": WORKERS,
        "n_nodes": len(d),
        "n_edges": d.n_edges,
        "wall_s": wall,
        "makespan_s": cp.get("makespan_s", 0.0),
        "critical_path_s": cp.get("critical_path_s", 0.0),
        "sum_work_s": cp.get("sum_work_s", 0.0),
        "parallelism": cp.get("parallelism", 0.0),
        "critical_nodes": cp.get("critical_nodes", []),
        "branch_overlap": overlap,
        "dep_waits": sum(e.kind == "dep_wait" for e in events),
        "dep_releases": sum(e.kind == "dep_release" for e in events),
        "totals_exact": out.totals == d.totals,
    }]
    _emit_fleet("dag", rows)

    assert out.n_replayed == len(d)
    assert out.totals == d.totals, \
        "frontier-scheduled fold drifted from the workload's analytic totals"
    assert cp and cp["n_nodes"] == len(d) and cp["n_edges"] == d.n_edges
    assert rows[0]["dep_releases"] >= 1
    # the structural win: branch replay intervals overlap across the two
    # workers, so their summed work exceeds the window they span.  Ideal
    # is 2x with 2 workers; demand 1.3x to keep the guard loose against
    # container wall-clock swing while still catching a frontier that
    # accidentally serializes independent branches.
    assert overlap >= 1.3, \
        f"no overlap: {branch_work:.3f}s of branch work spanned " \
        f"{branch_span:.3f}s — the frontier is serializing the fork"
    return rows


if __name__ == "__main__":
    main()
    soak()
    chaos()
    dag()
