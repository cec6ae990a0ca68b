"""Device time of the replay's collective leg per replay, in milliseconds:
the collective ops inside the traced replay spans, and the loops whose
body holds one (``bench/lib/trace.py``), averaged over the chips.  None
where the replay has no collective leg, as on one chip."""


def read(run):
    tr = run.trace
    if tr is None or tr.legs_s.get("collective", 0.0) <= 0:
        return None
    return tr.legs_s["collective"] / tr.n_replays * 1e3
