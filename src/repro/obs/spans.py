"""Named spans of the emulator, on the device trace's clock.

``span(name)`` returns a ``jax.profiler.TraceAnnotation``: while a
profiler trace runs, the span lands in the profiler's own host plane, on
the clock the device planes are converted to, so a trace reduction can
say what the host was doing while the device sat idle.  With no trace
running a span costs under a microsecond.

This is not a second recorder: the ``FlightRecorder`` keeps the fleet's
events, these spans time the replay's own phases.  ``jax.profiler`` is
imported on the first span, so ``import repro.obs`` stays free of JAX
(fleet coordinators rely on that).

A span is opened per replay phase (a schedule, a segment's launch or
sync, a barrier), never per row of a segment.
"""
from __future__ import annotations

#: Span names with a stable meaning.  A trace reduction gives idle device
#: time under a ``*.launch`` or ``*.compile`` span to the launch, under a
#: ``*.sync`` span to the sync, and under any other to host work.
SPANS = (
    "synapse.emulate",          # Emulator.emulate: the whole call
    "synapse.schedule",         # _collapse + compile_schedule (emulate,
                                #   Emulator.compile)
    "synapse.segment.launch",   # SegmentRunner.launch: table padding,
                                #   program lookup, the jitted call's enqueue
    "synapse.segment.compile",  # in launch: the first call of a program
                                #   that was just built (a recompile)
    "synapse.segment.sync",     # SegmentRunner.run: block_until_ready
    "synapse.barrier",          # one per-sample run (a BarrierStep, or a
                                #   run of the per-sample path)
    "synapse.barrier.launch",   # in barrier: the atom plans' enqueue
    "synapse.barrier.sync",     # in barrier: block_until_ready
    "synapse.storage",          # in barrier: the I/O thread's join
                                #   (these three once per executed sample:
                                #   each sample is its own launch and sync)
    "synapse.account",          # consumed accounting and the report
)

_annotation = None


def span(name: str):
    """A context manager that records ``name`` as a span in a running
    ``jax.profiler`` trace (and does next to nothing otherwise)."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name)
