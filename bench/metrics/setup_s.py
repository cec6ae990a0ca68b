"""Seconds from the process's start to the window's: imports, weights,
compiling (or loading) the step and the replay, one served call, the
profile and the replay's warm-up."""


def read(run):
    return run.setup_s
