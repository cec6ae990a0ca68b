"""Device time of the replay's memory leg per replay, in milliseconds:
the ops inside the traced replay spans that are neither matmuls nor
collectives.  No share of HBM peak: the atom's block may stay on chip."""


def read(run):
    tr = run.trace
    if tr is None or tr.legs_s.get("memory", 0.0) <= 0:
        return None
    return tr.legs_s["memory"] / tr.n_replays * 1e3
