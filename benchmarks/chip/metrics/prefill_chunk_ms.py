"""Mean device time of one call of the scheduler's jitted prefill-chunk
program in the traced window."""

from benchmarks.chip import trace


def read(run):
    t = trace.program_times(run.trace, trace.CHUNK_PROGRAM) if run.trace else []
    return 1e3 * sum(t) / len(t) if t else None
