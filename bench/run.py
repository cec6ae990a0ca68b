#!/usr/bin/env python3
"""Synapse's replay fidelity on the chip: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<name>.json``) under a traffic mix
(``bench/traffic/<name>.json``), with the limits of its correctness check in
``bench/limits/<cell>.json``.  The configuration's ``arch`` names the
program's model for it and its analytic operations
(``bench/archs/<arch>.py``) and its plain reference
(``bench/reference/<arch>.py``); the traffic's ``step`` names the kind of
step that serves it (``bench/steps/<step>.py``).  Each metric is read by
``bench/metrics/<name>.py``.  Adding any of them adds files; none is edited.

Set-up builds the served model from its configuration with the benchmark's
own bf16 weights from the seed, compiles the cell's step (prefill or
decode), serves it once, profiles the compiled step with
``profile_compiled``, and warms the fused replay ``Emulator.emulate``.  The
window then alternates one call of the application's compiled step with
the traffic's ``replays_per_step`` replays of its profile, each timed on
the host clock to ``block_until_ready``, for ``--seconds``; nothing
compiles inside it.
``--trace 1`` traces a short stretch of the same alternation after the
window, for the device metrics.  Once the window has closed and the peak
memory is read, the served results are compared with a plain float32
reference, and the replay with the profile.

The last line of standard output is one JSON object; the comparisons, each
with its limit, are the last lines of standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

#: the compile cache when ``JAX_COMPILATION_CACHE_DIR`` is not set: a fixed
#: path in the checkout, so every run of a cell there finds the programs
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
#: seconds of alternation the traced stretch covers (at least one round)
TRACE_SECONDS = 0.5


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(name: str, bench: dict):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    return SimpleNamespace(
        name=name, chips=w["chips"],
        config=load_json(BENCH, "configs", w["config"] + ".json"),
        traffic=load_json(BENCH, "traffic", w["traffic"] + ".json"),
        limits=load_json(BENCH, "limits", name + ".json"))


def enable_cache():
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts traces and compilations (cache loads included) while on."""
    _listening = None

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.events = {dispatch.JAXPR_TRACE_EVENT,
                       dispatch.BACKEND_COMPILE_EVENT}
        self.on, self.n = False, 0
        if CompileCounter._listening is None:
            jax.monitoring.register_event_duration_secs_listener(
                lambda *a, **k: CompileCounter._listening._event(*a, **k))
        CompileCounter._listening = self

    def _event(self, event, duration, **_):
        if self.on and event in self.events:
            self.n += 1


def devices_for(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"this benchmark measures a TPU; JAX's platform is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX sees "
                         f"{len(devs)}")
    return devs, devs[:chips]


def seed_key(seed: int):
    import jax
    word = np.random.SeedSequence(seed).generate_state(1, np.uint32)[0]
    return jax.random.key(int(word))


def peak_memory(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             bench: dict, require_chip: bool = True, control: bool = False):
    """One run of ``cell``; returns the result object the last line prints.
    ``control`` also reads the lower-precision control on the same served
    positions, put in the program's place, and judges it by the cell's
    limits (under ``control`` in the result)."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench.lib import checks, peaks
    from repro.configs.run import SERVE_RUN
    from repro.core import Emulator, profile_compiled
    from repro.models.model_zoo import build_model

    arch_name = cell.config["arch"]
    arch = load_module(os.path.join(BENCH, "archs", arch_name + ".py"))
    ref = load_module(os.path.join(BENCH, "reference", arch_name + ".py"))
    kind_of_step = load_module(os.path.join(BENCH, "steps",
                                            cell.traffic["step"] + ".py"))
    counter = CompileCounter()
    all_devs, devs = devices_for(cell.chips, require_chip)
    kind = all_devs[0].device_kind
    peak = peaks.peak_for(kind) if require_chip else None
    phases = {}

    @contextlib.contextmanager
    def phase(name):
        with TraceAnnotation(f"bench.setup.{name}"):
            t0 = time.perf_counter()
            yield
            phases[name] = time.perf_counter() - t0

    m = cell.config["config"]
    t = cell.traffic
    mesh, shardings = None, None
    with phase("weights"):
        model = build_model(arch.model_config(cell.config), SERVE_RUN)
        if cell.chips > 1:
            from jax.sharding import NamedSharding

            from repro.launch.mesh import make_mesh
            from repro.parallel.sharding import DECODE_RULES, make_rules
            ms = cell.config["mesh"]
            mesh = make_mesh(tuple(ms.values()), tuple(ms), devices=devs)
            specs = model.param_specs(make_rules(mesh, DECODE_RULES))
            shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                                     specs)
        else:
            shardings = jax.tree.map(
                lambda _: jax.sharding.SingleDeviceSharding(devs[0]),
                model.abstract())
        weights = ref.make_weights(m, seed_key(seed), shardings=shardings)
        want = jax.tree.map(lambda a: (a.shape, a.dtype), model.abstract())
        got = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
        if want != got:
            raise SystemExit("the reference's weight layout is not the "
                             "serving model's")
        jax.block_until_ready(weights)
    with phase("compile_and_serve"):
        drive = kind_of_step.Step(t, model, weights, mesh, m["vocab_size"],
                                  np.random.default_rng(seed))
        jax.block_until_ready(drive.step())
    with phase("profile"):
        prof = profile_compiled(drive.exe, command=f"{cell.name}",
                                tags={"cell": cell.name}, mesh=mesh)
    # the profile counts one chip's share; the cell's chips split the step
    analytic = arch.step_flops(m, drive.work) / cell.chips
    with phase("replay_warm"):
        em = Emulator(mesh=mesh)
        em.emulate(prof)
        em.emulate(prof)
    # set-up's objects go to the collector's permanent generation, so a
    # full collection inside the window scans only what the window made
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T0
    log(f"[setup] {setup_s} s: " + " ".join(f"{k}={v}" for k, v in
                                             phases.items()))

    # a round is one application step and then ``replays_per_step``
    # replays of its profile, so that a short replay fills about as much of
    # the window as the step: a host stall then moves either total by a
    # like share
    k = t["replays_per_step"]

    def round_(apps, reps):
        with TraceAnnotation("bench.app_step"):
            a = time.perf_counter()
            jax.block_until_ready(drive.step())
            apps.append(time.perf_counter() - a)
        for _ in range(k):
            with TraceAnnotation("bench.replay"):
                a = time.perf_counter()
                rep = em.emulate(prof)
                reps.append(time.perf_counter() - a)
        return rep

    # -- the window --------------------------------------------------------
    counter.on = True
    apps, reps = [], []
    end = time.perf_counter() + seconds
    while not apps or time.perf_counter() < end:
        rep = round_(apps, reps)
    counter.on = False
    compiles = counter.n
    app_s, rep_s = sum(apps), sum(reps)
    log(f"[window] {len(apps)} steps and {len(reps)} replays in "
        f"{app_s + rep_s} s: app {app_s} s, replay {rep_s} s; {compiles} "
        f"compilations inside the window")
    for name, xs in (("app step", apps), ("replay", reps)):
        q = np.quantile(xs, [0, 0.5, 1])
        slow = sorted(range(len(xs)), key=lambda i: -xs[i])[:3]
        log(f"[window] {name} s: min {q[0]} median {q[1]} max {q[2]}; "
            f"slowest {[(i, xs[i]) for i in slow]}")

    reduced = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
        end = time.perf_counter() + TRACE_SECONDS
        traced = []
        while not traced or time.perf_counter() < end:
            round_(traced, [])
        jax.profiler.stop_trace()
        from bench.lib import trace as tracelib
        path = tracelib.latest_xplane(TRACE_DIR)
        reduced = tracelib.reduce(tracelib.load(path))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    mem = peak_memory(devs)
    info = {"platform": all_devs[0].platform, "kind": kind,
            "count": len(all_devs), "memory_peak_bytes": mem}
    if reduced is not None:
        info["busy_s"] = reduced.busy_s
        info["window_s"] = reduced.window_s

    # -- correctness, once the window has closed ----------------------------
    # every number the cell's limits file names is compared; a number with
    # no limit there is read but not compared (see PERF.md for why)
    read, c_read = drive.readings(ref, m, np.random.default_rng([seed, 1]),
                                  control)
    read["profile_flops_rel"] = abs(prof.totals.flops / analytic - 1)
    found = checks.against(read, cell.limits)
    found.update(checks.replay_checks(em, prof, rep))
    found["window_compilations"] = (compiles, 0)
    correct = checks.verdict(found)
    out_control = None
    if control:
        # the control in the program's place: its served numbers replace
        # the program's, every other comparison stays as the run made it
        c_found = {**found, **checks.against(c_read, cell.limits)}
        out_control = {"readings": c_read, "program": read,
                       "correct": checks.verdict(c_found)}

    # -- metrics ------------------------------------------------------------
    run = SimpleNamespace(
        setup_s=setup_s, app_s=app_s, replay_s=rep_s, steps=len(apps),
        replays=len(reps),
        profile_s=phases["profile"], profile_flops=prof.totals.flops,
        chips=cell.chips, peak=peak, trace=reduced,
        schedule=em.compile(prof).describe(),
        flops_per_iter=em.compute.flops_per_iter())
    metrics = {}
    for spec in bench["per_layer"] if trace else bench["end_to_end"]:
        reader = load_module(os.path.join(BENCH, "metrics",
                                          spec["name"] + ".py"))
        v = reader.read(run)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    result = {"correct": bool(correct), "attempted": len(apps) + len(reps), "failed": 0,
              "metrics": metrics, "device": info}
    if reduced is not None:
        result["breakdown"] = reduced.breakdown
    if out_control is not None:
        result["control"] = out_control
    result["checks"] = {k: {"value": float(v), "limit": float(lim_)}
                        for k, (v, lim_) in found.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = cell_spec(args.workload, bench)
    enable_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      bench=bench)
    for k, c in result["checks"].items():
        log(f"[check] {k} = {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
