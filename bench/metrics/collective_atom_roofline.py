"""The collective atom's share of the chip's interconnect peak while it
runs: the wire its iterations burn (schedule iterations x the wire one
iteration moves, per replay, times the traced replays; ring model, per
chip) over the device time of the collective leg's ops inside the traced
replay spans.  An iteration is one all-reduce of the program's fixed
block over the cell's chips (``repro.core.atoms.CollectiveQuant``).  None
where the replay has no collective leg."""
from repro.core.atoms import CollectiveQuant


def read(run):
    tr = run.trace
    if tr is None or run.peak is None:
        return None
    busy = tr.legs_s.get("collective", 0.0)
    iters = run.schedule.get("collective_iters", 0)
    if busy <= 0 or iters <= 0:
        return None
    burned = iters * CollectiveQuant(n=run.chips).wire_bytes_per_iter
    return 100.0 * burned * tr.n_replays / busy / run.peak["ici_bytes_per_s"]
