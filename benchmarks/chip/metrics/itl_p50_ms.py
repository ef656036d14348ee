"""Median of the same set of gaps as ``itl_p95_ms`` (host clock)."""

from benchmarks.chip.record import tail, token_gaps


def read(run):
    v = tail(token_gaps(run), 0.5)
    return None if v is None else 1e3 * v
