"""Black-box compile-time profiler ("static watcher family").

``profile_compiled`` turns a compiled XLA executable into a SynapseProfile:
the trip-count-aware HLO walker supplies per-chip resource consumption, and
the entry computation's execution order supplies the *sample sequence* (one
sample per straight-line segment, trip-count samples per scan) — the static
analog of the paper's time-sampled profiling.  Granularities:

  * "step"  — a single sample for the whole step (paper: 1 sample/run —
              coarse, loses ordering, like the low-rate end of Fig. 6)
  * "scan"  — samples follow program structure (default; the layer loop
              becomes L ordered samples exactly like the paper's per-100ms
              samples follow execution phases)

``profile_step`` is the one-call convenience: jit → lower → compile →
profile, returning (profile, compiled).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax

from repro.core import hlo_analysis
from repro.core.metrics import ResourceVector, Sample, SynapseProfile
from repro.obs import clock as obs_clock


def _rv(cost: hlo_analysis.HloCost, use_dot_bytes: bool = True) -> ResourceVector:
    return ResourceVector(
        flops=cost.flops,
        hbm_bytes=cost.dot_bytes if use_dot_bytes else cost.hbm_bytes,
        ici_bytes=cost.collective_bytes())


def profile_compiled(compiled, *, command: str, tags: Optional[Dict] = None,
                     granularity: str = "scan", mesh=None,
                     use_dot_bytes: bool = True) -> SynapseProfile:
    text = compiled.as_text()
    sysinfo: Dict[str, Any] = {"backend": "xla-static"}
    if mesh is not None:
        sysinfo["mesh"] = {n: int(s) for n, s in
                           zip(mesh.axis_names, mesh.devices.shape)}
        sysinfo["n_devices"] = int(mesh.devices.size)

    samples = []
    if granularity == "step":
        cost = hlo_analysis.analyze_hlo(text)
        samples.append(Sample(index=0, resources=_rv(cost, use_dot_bytes),
                              label="step"))
    else:
        idx = 0
        for label, cost, count in hlo_analysis.sample_breakdown(text):
            rv = _rv(cost, use_dot_bytes)
            for _ in range(count):
                samples.append(Sample(index=idx, resources=rv, label=label))
                idx += 1

    prof = SynapseProfile(command=command, tags=tags or {}, samples=samples,
                          sysinfo=sysinfo)
    ma = compiled.memory_analysis()
    if ma is not None:
        prof.meta["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
        }
    ca = compiled.cost_analysis()
    if ca:
        prof.meta["xla_cost_flops"] = float(ca.get("flops", -1.0))
    return prof


def profile_step(fn, *args, command: str, tags=None, mesh=None,
                 granularity: str = "scan", donate_argnums=(),
                 ) -> Tuple[SynapseProfile, Any]:
    """Lower + compile ``fn(*args)`` (abstract or concrete) and profile it."""
    t0 = obs_clock.now()
    lowered = jax.jit(fn, donate_argnums=donate_argnums).lower(*args)
    compiled = lowered.compile()
    prof = profile_compiled(compiled, command=command, tags=tags,
                            granularity=granularity, mesh=mesh)
    prof.meta["lower_compile_s"] = obs_clock.now() - t0
    return prof, compiled
