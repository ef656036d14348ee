"""Process start to the window's opening: imports, weights, the scheduler,
compilation or the compile cache, and the warm-up of every shape the
cell's traffic uses (host clock)."""


def read(run):
    return run.setup_s
