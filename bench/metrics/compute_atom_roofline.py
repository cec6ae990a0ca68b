"""The compute atom's share of the chip's bf16 peak while it runs: the
FLOPs its iterations burn (schedule iterations x FLOPs per iteration, per
replay, times the traced replays) over the device time of the compute
leg's ops inside the traced replay spans."""


def read(run):
    tr = run.trace
    if tr is None or run.peak is None:
        return None
    busy = tr.legs_s.get("compute", 0.0)
    if busy <= 0 or run.schedule["compute_iters"] <= 0:
        return None
    burned = run.schedule["compute_iters"] * run.flops_per_iter
    return 100.0 * burned * tr.n_replays / busy / run.peak["bf16_flops"]
