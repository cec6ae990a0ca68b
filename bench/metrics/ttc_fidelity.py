"""Replay fidelity: the replay's mean wall time over the application
step's, or its inverse where the replay is the faster, so 1 is a replay
that takes exactly the application's time.  Both are whole-window totals
over counts on the host clock."""


def read(run):
    r = (run.replay_s / run.replays) / (run.app_s / run.steps)
    return min(r, 1.0 / r)
