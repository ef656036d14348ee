"""Share of the traced window in which no operation ran on the chip, in %."""

from benchmarks.chip import trace


def read(run):
    busy = trace.busy_s(run.trace) if run.trace else None
    return None if busy is None else 100.0 * (1.0 - busy / trace.window_s(run.trace))
