"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/control.py --workload minicpm-2b.chat \
        --seeds 101 102 ... --seconds 15 [--fault token]

For each seed, one run of the cell at its own load (a short window, long
enough to finish the mix's longest requests), then the correctness check
with the control: the widest and the mean logit gap of the served tokens
(the program's readings) and of the tokens the fp8 reference puts first at
the same positions (the control's readings), and whether ``correct`` came
out false with the control in the program's place.  ``--fault token``
plants a fault in the timed path first (:data:`FAULTS`); the served
tokens' readings are then the fault's, with the quantiles of the gaps of
single served tokens.  The benchmark's own runs never run the control or a
fault.  One JSON line per seed, then for each number the largest served
reading and the smallest control reading.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import cell, check  # noqa: E402
from benchmarks.chip import run as harness  # noqa: E402


def alter_token(sched) -> None:
    """Every token is altered where it is sampled: the served token is the
    next id after the one the logits choose."""
    pure = sched._sample_pure
    vocab = sched.model.cfg.vocab

    def wrong(logits, key):
        return (pure(logits, key) + 1) % vocab

    sched._sample_pure = wrong


FAULTS = {"token": alter_token}
NUMBERS = ("widest_logit_gap", "mean_logit_gap")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    import jax

    w = cell.workload(cell.benchmark(), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    readings = []
    for seed in args.seeds:
        out, run = harness.run_cell(w, seed=seed, seconds=args.seconds, traced=False,
                                    devices=devices[: w["chips"]], control=True,
                                    sabotage=FAULTS.get(args.fault))
        g = run.gaps
        readings.append(g)
        served_correct, _ = check.judge(
            g, cell.config(w["config"])["correctness"]["limits"],
            bad=out["checks"]["bad_requests"]["value"],
            checked=out["checks"]["checked_requests"]["value"])
        line = {"seed": seed, "fault": args.fault, "served_correct": served_correct,
                "control_correct": out["correct"],
                **{k: v for k, v in g.items() if not k.endswith("gaps")},
                "checks": out["checks"],
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
        if args.fault and "gaps" in g:
            q = (0.01, 0.05, 0.25, 0.5)
            line["served_gap_quantiles"] = dict(zip(
                [str(x) for x in q], np.quantile(g["gaps"], q).tolist(), strict=True))
        print(json.dumps(line), flush=True)
    summary = {"workload": w["name"], "fault": args.fault, "seeds": args.seeds}
    for n in NUMBERS:
        summary[n] = {"served_largest": max(r[n] for r in readings),
                      "served_smallest": min(r[n] for r in readings),
                      "control_smallest": min(r["control_" + n] for r in readings)}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
