"""The application's compiled step, host clock to ``block_until_ready``:
window total over steps, in milliseconds."""


def read(run):
    return run.app_s / run.steps * 1e3
