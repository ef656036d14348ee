"""95th percentile of every gap between consecutive tokens of every request,
where the later token reached the host inside the window (host clock)."""

from benchmarks.chip.record import tail, token_gaps


def read(run):
    v = tail(token_gaps(run), 0.95)
    return None if v is None else 1e3 * v
