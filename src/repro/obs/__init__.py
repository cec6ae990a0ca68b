"""Flight recorder + distributed trace/metrics layer.

The fleet's fidelity story — what dispatched where, which worker died,
when the breaker opened, how the SLO windows moved — used to live in
scattered post-hoc dicts (``FleetReport.recovery``/``scaling``,
``BundleTiming``, ``SLOEngine`` windows, chaos ``fault_events``).  This
package gives it one spine:

``clock``
    One clock domain for every stamp: a monotonic base with a wall
    anchor (``now()``/``wall()``), so queue/replay durations can never
    go negative under wall-clock steps, plus ``ClockSync`` — a per-peer
    offset estimator (handshake echo, min-RTT sample) that rebases
    worker/agent timestamps onto the coordinator timeline.

``recorder``
    ``FlightRecorder``: a bounded ring buffer of typed, picklable
    ``Event``s (dispatch, requeue, heartbeat, scale_up/down,
    fault_opened/repaired, segment_replay, collective_leg, ...) with
    sha256-scoped per-(scope, kind) ordinals, so a seeded chaos run
    emits a deterministic event *sequence* — timestamps vary, identity
    does not.  Coordinator, ``worker_loop`` and the host agent each run
    one; worker/agent buffers ship home piggybacked on result/stop
    frames as ``ObsFrame``s and merge onto the coordinator timeline.

``trace``
    Chrome trace-event JSON export (Perfetto-loadable): one track per
    worker/agent, spans from ``BundleTiming`` enqueue→dispatch→done,
    instant events for faults/scales, SLO windows as counter tracks.

``metrics``
    A small Prometheus text-format registry (counters / gauges /
    histograms backed by the service layer's ``LatencySketch``),
    scraped at ``repro.service``'s ``/metrics`` endpoint and
    snapshotted into ``FleetReport.obs``.

``spans``
    ``span(name)``: the emulator's named phases (``SPANS``) as
    ``jax.profiler.TraceAnnotation``s, on the device trace's clock, so a
    profiler trace can split the replay's idle device time by cause.

Nothing here imports jax at import time: events are plain picklable
dataclasses and the exporters are pure-Python, so the recorder rides
inside worker processes and over the framed-TCP transport for free;
``span`` imports ``jax.profiler`` on its first call.
"""
from repro.obs.clock import ClockSync, anchor, now, wall
from repro.obs.metrics import MetricsRegistry, parse_promtext
from repro.obs.recorder import Event, FlightRecorder, ObsFrame
from repro.obs.spans import SPANS, span
from repro.obs.trace import (slo_windows_ms, to_chrome_trace,
                             validate_trace, write_trace)

__all__ = [
    "ClockSync", "anchor", "now", "wall",
    "Event", "FlightRecorder", "ObsFrame",
    "slo_windows_ms", "to_chrome_trace", "validate_trace", "write_trace",
    "MetricsRegistry", "parse_promtext",
    "SPANS", "span",
]
