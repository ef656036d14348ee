"""Mean host time of one ``DecodeScheduler.step`` call in the traced window,
from the program's own ``step`` span (``repro.serve.tracing``)."""

from benchmarks.chip import program_spans


def read(run):
    r = program_spans.reduce(run)
    return None if r is None else r["host_step_ms"]
