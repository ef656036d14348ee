"""Least time the chip needs for the work of the window's decode steps
(roofline.py: the larger of FLOPs over peak FLOP/s and bytes over peak
bandwidth, per step), over the decode program's device time, in %."""

from benchmarks.chip import roofline, trace
from benchmarks.chip.record import decode_steps


def read(run):
    t = trace.program_times(run.trace, trace.DECODE_PROGRAM) if run.trace else []
    steps = decode_steps(run)
    if not t or not steps:
        return None
    least = [roofline.least_seconds(
        roofline.decode_flops(run.dm, s.decode_tokens, s.context),
        roofline.decode_bytes(run.dm, s.decode_tokens, s.context), run.peak)
        for s in steps]
    return 100.0 * (sum(least) / len(least)) / (sum(t) / len(t))
