"""Output tokens the host received inside the window, over the window's
seconds (host clock)."""


def read(run):
    n = sum(1 for r in run.requests.values() for t in r.times if run.in_window(t))
    return n / run.seconds
