"""``profile_compiled`` on the cell's compiled step in set-up, host clock,
in milliseconds."""


def read(run):
    return run.profile_s * 1e3
