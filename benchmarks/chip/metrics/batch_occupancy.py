"""Mean number of slots in each decode step of the window, from the slots'
token counters (``n_out``): the scheduler's batching."""

from benchmarks.chip.record import decode_steps


def read(run):
    steps = decode_steps(run)
    return sum(s.decode_tokens for s in steps) / len(steps) if steps else None
