"""Model FLOPs of a decode step (roofline.py) over its device time times the
chip's peak bf16 FLOP/s, in %: the whole step's share of the chip's peak."""

from benchmarks.chip import roofline, trace
from benchmarks.chip.record import decode_steps


def read(run):
    t = trace.program_times(run.trace, trace.DECODE_PROGRAM) if run.trace else []
    steps = decode_steps(run)
    if not t or not steps:
        return None
    flops = sum(roofline.decode_flops(run.dm, s.decode_tokens, s.context)
                for s in steps) / len(steps)
    return 100.0 * flops / ((sum(t) / len(t)) * run.peak["bf16_flops_per_s"])
