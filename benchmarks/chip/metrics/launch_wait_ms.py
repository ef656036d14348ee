"""Mean chip idle time between the end of the program's ``decode`` span and
the start on the chip of the decode program that span dispatched, over the
traced window's calls (``program_spans.py``)."""

from benchmarks.chip import program_spans


def read(run):
    r = program_spans.reduce(run)
    if r is None or not r["launch_calls"]:
        return None
    return 1e3 * r["launch_wait_s"] / r["launch_calls"]
