"""The readers of the program's own host spans (``program_spans.py`` and the
``sched_host_ms``, ``sched_idle`` and ``launch_wait_ms`` metrics), on the
CPU: synthetic program spans nested in the harness spans of a trace recorded
on a v5e, checked against a brute-force reading on a 1-microsecond grid; and
the program's spans paired with the harness's on a real (CPU) trace."""

from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import program_spans, record, trace  # noqa: E402
from benchmarks.chip import run as harness  # noqa: E402
from repro.serve import tracing  # noqa: E402
from repro.serve.tracing import Span  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
D = 4321.012345          # program clock = trace clock + D
METRICS = ("sched_host_ms", "sched_idle", "launch_wait_ms")


def recorded():
    """The v5e trace, with the harness spans a run around its one decode
    call would have recorded: a step that dispatched it, the submit and
    token read before the next step (which the trace holds)."""
    with gzip.open(DATA / "v5e_starcoder2_code_trace.json.gz", "rt") as f:
        tr = json.load(f)
    tr["spans"] += [["step", 0.0, 0.0019], ["token_read", 0.0019, 0.0355],
                    ["submit", 0.0355, 0.0372]]
    return tr


# program spans on the trace's clock: (name, start, end, parent index, ids)
SPANS = [
    ("step", 0.0, 0.0019, None, {"step": 7}),
    ("grow", 0.0000005, 0.0000015, 0, {"pages": 1}),
    ("decode", 0.000002, 0.00001, 0, {}),             # dispatches the call at 0.002
    ("finish", 0.0005, 0.0018, 0, {"request_id": []}),
    ("submit", 0.0356, 0.0370, None, {"request_id": "r9"}),
    ("admit", 0.0358, 0.0366, 4, {"request_id": "r9"}),
    ("step", 0.037760344, 0.0551, None, {"step": 8}),  # the trace's own step span
    ("chunk", 0.0378, 0.0405, 6, {"request_id": "r9"}),
    ("grow", 0.0406, 0.0409, 6, {"pages": 0}),
    ("decode", 0.041, 0.0412, 6, {}),
    ("finish", 0.0413, 0.055, 6, {"request_id": ["r3"]}),
    ("admit", 0.0414, 0.0416, 10, {"request_id": "r10"}),
]


def synthetic_run(tr):
    lo, hi = tr["window"]
    run = record.Run(t0=lo + D, t1=hi + D, setup_s=0.0, requests={},
                     window_requests=[], steps=[], dm={}, n_pages=0, trace=tr)
    spans = [Span(i, n, a + D, b + D, p, ids) for i, (n, a, b, p, ids) in enumerate(SPANS)]
    return run, spans


def grid_reading(tr):
    """Busy, in-program-span and launch-wait masks on a 1 us grid, from the
    definitions alone."""
    lo, hi = tr["window"]
    n = int(round((hi - lo) * 1e6))

    def mask(ivs):
        m = np.zeros(n, bool)
        for a, b in ivs:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                m[int(round((a - lo) * 1e6)):int(round((b - lo) * 1e6))] = True
        return m

    dev = next(iter(tr["devices"].values()))
    busy = mask([(s, e) for _, s, e in dev["ops"]])
    prog = mask([(a, b) for _, a, b, _, _ in SPANS])
    waits = []
    decodes = sorted((a, b) for name, a, b, _, _ in SPANS if name == "decode")
    for name, cs, ce in dev["modules"]:
        if name.startswith("jit__step_impl") and lo <= cs and ce <= hi:
            a, b = max((d for d in decodes if d[0] <= cs), default=(None, None))
            if a is not None:
                waits.append(mask([(b, cs)]) & ~busy)
    lw = np.any(waits, axis=0) if waits else np.zeros(n, bool)
    return busy, prog, lw, waits


def test_offset_is_recovered_and_idle_splits_as_on_a_grid():
    tr = recorded()
    run, spans = synthetic_run(tr)
    r = program_spans.reduce(run, spans)
    assert r["offset_s"] == pytest.approx(-D, abs=1e-9)
    assert r["offset_spread_s"] == pytest.approx(0.0, abs=1e-9)
    assert r["steps"] == r["paired_steps"] == 2
    busy, prog, lw, waits = grid_reading(tr)
    idle = ~busy
    # each interval edge rounds to the grid by at most half a microsecond
    n_edges = 2 * (len(trace.idle_gaps(tr, n=10 ** 6)) + len(SPANS) + 2)
    tol = 0.5e-6 * n_edges
    assert r["launch_calls"] == len(waits) == 1
    assert r["launch_wait_s"] == pytest.approx(1e-6 * waits[0].sum(), abs=4e-6)
    assert r["launch_wait_s"] > 2e-5           # the idle start of the slice
    assert r["sched_idle_s"] == pytest.approx(1e-6 * (idle & prog & ~lw).sum(), abs=tol)
    assert r["outside_idle_s"] == pytest.approx(1e-6 * (idle & ~prog & ~lw).sum(), abs=tol)
    assert r["sched_idle_s"] > 1e-3                # the submit's and chunk-step gaps
    assert r["host_step_ms"] == pytest.approx(
        1e3 * ((0.0019 - 0.0) + (0.0551 - 0.037760344)) / 2, rel=1e-9)


def test_idle_decomposes_into_program_spans_launch_wait_and_the_rest():
    tr = recorded()
    run, spans = synthetic_run(tr)
    r = program_spans.reduce(run, spans)
    idle = trace.window_s(tr) - trace.busy_s(tr)
    assert r["idle_s"] == pytest.approx(idle, abs=1e-9)
    assert r["sched_idle_s"] + r["launch_wait_s"] + r["outside_idle_s"] == \
        pytest.approx(idle, abs=1e-6)
    # the scheduler's share, by innermost span, and the rest, by harness span
    ph = r["phases"]
    assert sum(p["idle_s"] for p in ph.values()) == pytest.approx(r["sched_idle_s"],
                                                                  abs=1e-9)
    assert sum(r["outside_by_span"].values()) == pytest.approx(r["outside_idle_s"],
                                                               abs=1e-9)
    assert ph["admit"]["spans"] == 2 and ph["step"]["spans"] == 2
    # self time: a span's duration less its children's
    assert ph["submit"]["self_s"] == pytest.approx((0.0370 - 0.0356) - (0.0366 - 0.0358),
                                                   abs=1e-9)
    assert r["outside_by_span"]["token_read"] > 0
    assert all(v >= -1e-12 for v in r["outside_by_span"].values())


def test_a_decode_span_pairs_with_one_call_only():
    """A second decode call after the same ``decode`` span (which only a
    device clock out of line with the host's can show) is left out, so that
    no idle counts twice and the three parts still add up."""
    tr = recorded()
    dev = next(iter(tr["devices"].values()))
    dev["modules"].append(["jit__step_impl(1)", 0.0390, 0.0392])
    run, spans = synthetic_run(tr)
    r = program_spans.reduce(run, spans)
    assert r["launch_calls"] == 1
    idle = trace.window_s(tr) - trace.busy_s(tr)
    assert r["sched_idle_s"] + r["launch_wait_s"] + r["outside_idle_s"] == \
        pytest.approx(idle, abs=1e-6)


def test_readers_report_the_reduction_and_nothing_without_spans(monkeypatch):
    tr = recorded()
    run, spans = synthetic_run(tr)
    r = program_spans.reduce(run, spans)
    read = {m: harness.reader(m) for m in METRICS}
    # a program that records no spans (the ring is empty): nothing to read
    tracing.clear()
    assert all(read[m](run) is None for m in METRICS)
    # a program without the tracing module at all
    import repro.serve
    monkeypatch.delattr(repro.serve, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.serve.tracing", None)
    run2, _ = synthetic_run(recorded())
    assert program_spans.ring() is None
    assert all(read[m](run2) is None for m in METRICS)
    run.program_spans = r
    assert read["sched_host_ms"](run) == r["host_step_ms"]
    assert read["sched_idle"](run) == pytest.approx(
        100 * r["sched_idle_s"] / trace.window_s(tr))
    assert read["launch_wait_ms"](run) == pytest.approx(1e3 * r["launch_wait_s"])


def test_benchmark_json_reads_the_program_spans_in_the_itl_p50_cells():
    from benchmarks.chip import cell

    b = cell.benchmark()
    itl = next(m for m in b["end_to_end"] if m["name"] == "itl_p50_ms")
    for name in METRICS:
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert m["source"] == "program_span" and m["moves"] == "itl_p50_ms"
        assert m["workloads"] == itl["workloads"]
        for w in m["workloads"]:
            assert m in cell.metrics_for(b, w, traced=True)


def test_program_steps_pair_with_the_harness_steps_on_a_real_trace():
    """A traced tiny run on the CPU: every program ``step`` span of the
    window sits in a harness ``step`` span, at one offset up to
    microseconds."""
    import jax

    from test_chip_benchmark import CELL, tiny_chat, tiny_starcoder2

    tracing.clear()
    _, run = harness.run_cell(CELL, seed=5, seconds=2, traced=True,
                              devices=jax.devices(), cfg=tiny_starcoder2(),
                              spec=tiny_chat())
    win = [s for s in tracing.spans() if run.t0 <= s.start and s.end <= run.t1]
    tracing.clear()
    steps = sorted((s for s in win if s.name == "step"), key=lambda s: s.start)
    hsteps = sorted((a, b) for n, a, b in run.trace["spans"] if n == "step")
    offs = sorted(program_spans.offset(steps, hsteps, run.trace["window"][0] - run.t0))
    assert len(steps) > 20 and len(offs) == len(steps)
    assert record.tail(offs, 0.99) - record.tail(offs, 0.01) < 1e-3
