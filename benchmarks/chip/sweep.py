"""Knee sweep of an open-loop cell: the same mix at several fixed rates, in
one process, to find the highest rate the chip sustains with no growing
backlog.  Run once when a cell is defined; the cell's traffic file then
records 4/5 of that rate.

    python3 benchmarks/chip/sweep.py --workload minicpm-2b.chat \
        --rates 2 3 4 5 6 --seconds 20 --seed 11

Per rate it prints one JSON line: the offered and delivered output tokens per
second, the first-token wait of the requests due in the window's first and
second half (a backlog that grows makes the second half wait longer), the
requests still without a first token when the window closed, and the run's
correctness readings.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import cell, record  # noqa: E402
from benchmarks.chip import run as harness  # noqa: E402


def summary(rate: float, out: dict, run: record.Run) -> dict:
    mid = run.t0 + run.seconds / 2
    halves = ([], [])
    for rid in run.window_requests:
        r = run.requests[rid]
        wait = (r.times[0] if r.times else run.drain_end) - r.due
        halves[r.due >= mid].append(wait)
    late = sum(1 for rid in run.window_requests
               if not any(t <= run.t1 for t in run.requests[rid].times[:1]))
    offered = sum(run.requests[r].max_new for r in run.window_requests) / run.seconds
    return {"rate_per_s": rate, "offered_tok_s": offered,
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "ttft_p50_first_half_ms": 1e3 * record.tail(halves[0], 0.5),
            "ttft_p50_second_half_ms": 1e3 * record.tail(halves[1], 0.5),
            "no_first_token_at_close": late, "attempted": out["attempted"],
            "failed": out["failed"], "correct": out["correct"],
            "checks": out["checks"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax

    w = cell.workload(cell.benchmark(), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    for i, rate in enumerate(args.rates):
        out, run = harness.run_cell(w, seed=args.seed + i, seconds=args.seconds,
                                    traced=False, devices=devices[: w["chips"]],
                                    rate_per_s=rate)
        print(json.dumps(summary(rate, out, run)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
