"""Share of the replay spans (``bench.replay``, around each
``Emulator.emulate``) in which no operation ran on the device, from the
trace, averaged over the cell's chips."""


def read(run):
    tr = run.trace
    if tr is None or tr.replay_span_s <= 0:
        return None
    return 100.0 * (1.0 - tr.replay_busy_s / tr.replay_span_s)
