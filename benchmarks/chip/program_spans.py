"""The program's own host spans, set against the device trace.

``repro.serve.tracing`` keeps every scheduler span recorded while the
profiler ran (``submit``, ``admit``, ``step``, ``chunk``, ``grow``,
``decode``, ``finish``) on the host's ``time.perf_counter()`` clock.  The
harness's ``step`` span wraps each program ``step`` span, and the harness's
spans are on the trace's clock, so one offset puts the program's spans
there: the median, over the window's steps, of (start of the harness span -
start of the program span it wraps).  :func:`reduce` then splits the first
chip's idle time in the window three ways:

- launch wait: for each call of the decode program, the idle time between
  the end of the ``decode`` span that dispatched it (the newest one that
  began before the call started) and the call's start on the chip; a call
  whose newest such span already has a call is left out (one dispatch, one
  call: it can only come of the device's clock and the host's disagreeing);
- scheduler: the rest of the idle time while the host was inside a program
  span, named after the innermost span it fell in;
- the rest: idle time outside every program span (the harness's own work),
  named after the harness span it fell in.

A program without ``repro.serve.tracing`` records no spans, and every
reading here is ``None``.
"""

from __future__ import annotations

from bisect import bisect_left, bisect_right
from statistics import median
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import trace
from benchmarks.chip.record import tail
from benchmarks.chip.trace import _clip, union

Iv = Tuple[float, float]
# how far a program span may stray from the harness span around it, once
# the two clocks are lined up
PAIR_SLACK_S = 1e-3


def ring() -> Optional[list]:
    """The program's recorded spans, or ``None`` for a program that keeps
    none."""
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    return tracing.spans()


def measure(ivs: Sequence[Iv]) -> float:
    return sum(b - a for a, b in ivs)


def complement(busy: List[Iv], lo: float, hi: float) -> List[Iv]:
    """The stretches of ``[lo, hi]`` outside the sorted, disjoint ``busy``."""
    edges = [lo, *(x for iv in busy for x in iv), hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def intersect(a: List[Iv], b: List[Iv]) -> List[Iv]:
    """Both lists sorted and disjoint."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: List[Iv], b: List[Iv]) -> List[Iv]:
    """``a`` less ``b``, both sorted and disjoint."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if hi > lo:
            out.append((lo, hi))
    return out


class _Index:
    """Sorted, disjoint intervals, cut to any stretch by bisection."""

    def __init__(self, ivs: List[Iv]):
        self.ivs = ivs
        self.starts = [a for a, _ in ivs]
        self.ends = [b for _, b in ivs]

    def within(self, lo: float, hi: float) -> List[Iv]:
        i, j = bisect_right(self.ends, lo), bisect_left(self.starts, hi)
        return _clip(self.ivs[i:j], lo, hi)


def offset(prog_steps: list, harness_steps: List[Iv], guess: float) -> List[float]:
    """Per-step (harness start - program start), for every program ``step``
    span that a harness ``step`` span holds.  ``guess`` lines the clocks up
    first (the window's opening on both); each program span is paired with
    the harness span that starts nearest to it, once with ``guess`` and again
    with the median of that first pairing."""
    starts = [s for s, _ in harness_steps]

    def nearest(x: float) -> int:
        j = bisect_left(starts, x)
        return min((k for k in (j - 1, j) if 0 <= k < len(starts)),
                   key=lambda k: abs(starts[k] - x))

    if not starts or not prog_steps:
        return []
    off = median(starts[nearest(p.start + guess)] - p.start for p in prog_steps)
    out = []
    for p in prog_steps:
        a, b = harness_steps[nearest(p.start + off)]
        if a - PAIR_SLACK_S <= p.start + off and p.end + off <= b + PAIR_SLACK_S:
            out.append(a - p.start)
    return out


def reduce(run, spans: Optional[list] = None) -> Optional[Dict]:
    """The split of the traced window's idle time; ``spans`` stands in for
    the program's ring (tests).  ``None`` where there is nothing to read."""
    own_ring = spans is None
    if own_ring:
        if getattr(run, "program_spans", None) is not None:
            return run.program_spans
        spans = ring()
    tr = run.trace
    if not tr or not spans:
        return None
    lo, hi = tr["window"]
    win = [s for s in spans if run.t0 <= s.start and s.end <= run.t1]
    steps = sorted((s for s in win if s.name == "step"), key=lambda s: s.start)
    harness = sorted((a, b) for n, a, b in tr["spans"] if n == "step")
    devs = [d for d in tr["devices"].values() if d["ops"]]
    offs = sorted(offset(steps, harness, lo - run.t0))
    if not offs or not devs:
        return None
    off = median(offs)
    dev = devs[0]
    busy = union(_clip([(s, e) for _, s, e in dev["ops"]], lo, hi))
    idle = complement(busy, lo, hi)
    idle_ix = _Index(idle)

    # launch wait, per call of the decode program
    decodes = sorted((s.start + off, s.end + off) for s in win if s.name == "decode")
    d_starts = [a for a, _ in decodes]
    calls = sorted((cs, ce) for name, cs, ce in dev["modules"]
                   if trace._is_program(name, trace.DECODE_PROGRAM) and lo <= cs and ce <= hi)
    waits, stretches, paired = [], [], set()
    for cs, _ in calls:
        k = bisect_right(d_starts, cs) - 1
        if k < 0 or k in paired:
            continue    # no decode span of its own in view: the clocks disagree here
        paired.add(k)
        w = idle_ix.within(decodes[k][1], cs) if cs > decodes[k][1] else []
        waits.append(measure(w))
        stretches += w
    lw = union(stretches)
    rest_idle = subtract(idle, lw)
    rest_ix = _Index(rest_idle)

    # scheduler: idle while the host was in a program span, by innermost span
    mapped = {s.index: (max(s.start + off, lo), min(s.end + off, hi)) for s in win}
    kids: Dict[int, List[Iv]] = {}
    for s in win:
        if s.parent in mapped:
            kids.setdefault(s.parent, []).append(mapped[s.index])
    phases: Dict[str, Dict[str, float]] = {}
    for s in win:
        a, b = mapped[s.index]
        own = subtract([(a, b)], union(kids.get(s.index, []))) if b > a else []
        ph = phases.setdefault(s.name, {"spans": 0, "self_s": 0.0, "idle_s": 0.0})
        ph["spans"] += 1
        ph["self_s"] += measure(own)
        ph["idle_s"] += sum(measure(rest_ix.within(x, y)) for x, y in own)
    in_prog = union([iv for iv in mapped.values() if iv[1] > iv[0]])
    sched_idle_s = measure(intersect(rest_idle, in_prog))
    outside = subtract(rest_idle, in_prog)
    out_ix = _Index(outside)
    by_harness: Dict[str, float] = {}
    for n, a, b in tr["spans"]:
        by_harness[n] = by_harness.get(n, 0.0) + measure(out_ix.within(a, b))
    outside_s = measure(outside)
    by_harness["other"] = outside_s - sum(by_harness.values())

    result = {
        "window_s": hi - lo,
        "idle_s": measure(idle),
        "offset_s": off,
        "offset_spread_s": tail(offs, 0.99) - tail(offs, 0.01),
        "steps": len(steps),
        "paired_steps": len(offs),
        "host_step_ms": 1e3 * sum(s.end - s.start for s in steps) / len(steps),
        "sched_idle_s": sched_idle_s,
        "launch_calls": len(waits),
        "launch_wait_s": sum(waits),
        "outside_idle_s": outside_s,
        "phases": phases,
        "outside_by_span": by_harness,
    }
    if own_ring:
        run.program_spans = result      # the three readers share one reduction
    return result
