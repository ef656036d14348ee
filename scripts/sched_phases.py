#!/usr/bin/env python
"""Where a serving cell's device idle goes, phase by phase of the scheduler.

One traced run of a cell of ``BENCHMARK.json`` on the chip (as
``benchmarks/chip/run.py --trace 1`` makes it), then the program's own host
spans (``repro.serve.tracing``) set against the device trace by
``benchmarks/chip/program_spans.py``:

    python3 scripts/sched_phases.py --workload starcoder2-3b.batch-gen \
        --seed 11 --seconds 50 --out chiprun_out/phases/batch-gen.11.json

It writes one JSON object: the run's result line (``result``); the
reduction (``spans``: host self time and chip idle per span name, launch
wait, idle outside the program's spans by harness span, and the clock check,
the p1-p99 spread of the per-step offset between harness and program
``step`` spans); ``grow`` and ``finish`` host time split by pages mapped
and requests finished; where each decode call starts and ends on the chip
against the host's ``decode`` and ``token_read`` spans; the traced run's host-clock metrics (``itl_p50_ms`` ...,
to set against an untraced run of the same seed); the check that
``sched_idle`` + launch wait + the rest add up to ``device_idle``; and what
one ``span`` costs on this host with the profiler off and on.  The last
line of standard output is a one-line summary.
"""

from __future__ import annotations

import argparse
import bisect
import itertools
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import cell, program_spans  # noqa: E402
from benchmarks.chip import run as harness  # noqa: E402


def span_cost(n: int = 1_000_000, n_on: int = 50_000) -> dict:
    """Microseconds a ``with span(...)`` costs here: with the profiler off
    (and the bare call), and with it on (annotation and ring record)."""
    import jax

    from repro.serve import tracing
    from repro.serve.tracing import span

    def loop(k, body):
        t = time.perf_counter()
        body(itertools.repeat(None, k))
        return 1e6 * (time.perf_counter() - t) / k

    def empty(it):
        for _ in it:
            pass

    def call(it):
        for _ in it:
            span("decode", step=1)

    def with_(it):
        for _ in it:
            with span("decode", step=1):
                pass

    base = loop(n, empty)
    out = {"loop_us": base, "off_call_us": loop(n, call) - base,
           "off_with_us": loop(n, with_) - base}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["on_with_us"] = loop(n_on, with_) - base
        finally:
            jax.profiler.stop_trace()
    tracing.clear()
    return out


def split_by_ids(run) -> dict:
    """Mean host time of the window's ``grow`` spans by pages mapped, and of
    its ``finish`` spans by requests finished (their token reads wait for
    the step's decode)."""
    from repro.serve import tracing

    keys = {"grow": lambda ids: ids["pages"], "finish": lambda ids: len(ids["request_id"])}
    acc: dict = {}
    for s in tracing.spans():
        if s.name in keys and run.t0 <= s.start and s.end <= run.t1:
            n, t = acc.get((s.name, keys[s.name](s.ids)), (0, 0.0))
            acc[(s.name, keys[s.name](s.ids))] = (n + 1, t + s.end - s.start)
    out: dict = {}
    for (name, k), (n, t) in sorted(acc.items()):
        out.setdefault(name, {})[str(k)] = {"spans": n, "mean_ms": 1e3 * t / n}
    return out


def launch_gaps(run, offset_s: float) -> dict:
    """Quantiles, in microseconds, of where each decode call runs on the
    chip against the host's spans: its start less the start and less the
    end of the ``decode`` span that dispatched it (the newest before it),
    and the end of the harness's ``token_read`` span it ends in less its end
    (the host's wait for the step, then the token read)."""
    from repro.serve import tracing

    from benchmarks.chip import record, trace

    lo, hi = run.trace["window"]
    decodes = sorted((s.start + offset_s, s.end + offset_s) for s in tracing.spans()
                     if s.name == "decode" and run.t0 <= s.start and s.end <= run.t1)
    reads = sorted((a, b) for n, a, b in run.trace["spans"] if n == "token_read")
    dev = next(d for d in run.trace["devices"].values() if d["ops"])
    out = {"start-decode_start": [], "start-decode_end": [], "read_end-end": []}
    for name, cs, ce in dev["modules"]:
        if not (trace._is_program(name, trace.DECODE_PROGRAM) and lo <= cs and ce <= hi):
            continue
        k = bisect.bisect_right([a for a, _ in decodes], cs) - 1
        if k >= 0:
            out["start-decode_start"].append(1e6 * (cs - decodes[k][0]))
            out["start-decode_end"].append(1e6 * (cs - decodes[k][1]))
        j = bisect.bisect_right([a for a, _ in reads], ce) - 1
        if j >= 0 and ce <= reads[j][1]:
            out["read_end-end"].append(1e6 * (reads[j][1] - ce))
    return {k: {f"p{q}": record.tail(sorted(v), q / 100) for q in (1, 50, 99)}
            for k, v in out.items() if v}


def timeline(run, offset_s: float, min_gap_s: float = 1e-4) -> dict:
    """The window's raw timeline on the trace's clock, for reading by hand:
    program spans (shifted by ``offset_s``), harness spans, decode-program
    calls on the first chip, and the chip's idle gaps longer than
    ``min_gap_s``."""
    from repro.serve import tracing

    from benchmarks.chip import program_spans, trace

    lo, hi = run.trace["window"]
    dev = next(d for d in run.trace["devices"].values() if d["ops"])
    busy = trace.union(trace._clip([(s, e) for _, s, e in dev["ops"]], lo, hi))
    return {
        "window": [lo, hi],
        "program": [[s.name, s.start + offset_s, s.end + offset_s] for s in tracing.spans()
                    if run.t0 <= s.start and s.end <= run.t1],
        "harness": run.trace["spans"],
        "decode_calls": [[s, e] for n, s, e in dev["modules"]
                         if trace._is_program(n, trace.DECODE_PROGRAM) and lo <= s and e <= hi],
        "idle": [[a, b] for a, b in program_spans.complement(busy, lo, hi) if b - a > min_gap_s],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    bench = cell.benchmark()
    w = cell.workload(bench, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < w["chips"]:
        harness.log(f"needs {w['chips']} TPU chip(s); JAX sees {len(devices)} "
                    f"{devices[0].platform} device(s). Nothing was run.")
        return 2
    harness.enable_compile_cache()
    out, run = harness.run_cell(w, seed=args.seed, seconds=args.seconds, traced=True,
                                devices=devices[: w["chips"]])
    r = program_spans.reduce(run)
    host = {m["name"]: harness.reader(m["name"])(run)
            for m in cell.metrics_for(bench, w["name"], traced=False)}
    rec = {"workload": w["name"], "seed": args.seed, "result": out, "spans": r,
           "traced_host_metrics": host}
    idle = out["metrics"].get("device_idle", {}).get("value")
    if r is not None and idle is not None:
        parts = 100.0 * (r["sched_idle_s"] + r["launch_wait_s"]
                         + r["outside_idle_s"]) / r["window_s"]
        rec["decomposition_pp"] = {"device_idle": idle, "parts": parts,
                                   "difference": parts - idle}
    rec["by_ids"] = split_by_ids(run)
    if r is not None:
        rec["launch_gap_us"] = launch_gaps(run, r["offset_s"])
        rec["timeline"] = timeline(run, r["offset_s"])
    rec["span_cost"] = span_cost()
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(rec, indent=1))
    brief = {k: v["value"] for k, v in out["metrics"].items()}
    brief.update(correct=out["correct"], **{f"traced_{k}": v for k, v in host.items()})
    if r is not None:
        brief.update(offset_spread_us=1e6 * r["offset_spread_s"],
                     paired=f"{r['paired_steps']}/{r['steps']}",
                     launch_calls=r["launch_calls"], launch_gap_us=rec["launch_gap_us"],
                     by_ids=rec["by_ids"])
    brief.update(rec.get("decomposition_pp", {}), **rec["span_cost"])
    print(json.dumps(brief), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
