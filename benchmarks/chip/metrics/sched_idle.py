"""Share of the traced window in which the chip ran no operation while the
host was inside a program span (``submit`` or ``step`` and their children),
less the launch wait, in % (``program_spans.py``)."""

from benchmarks.chip import program_spans


def read(run):
    r = program_spans.reduce(run)
    return None if r is None else 100.0 * r["sched_idle_s"] / r["window_s"]
