"""Serving example: the paper's queue/batcher fronts a real decode loop.

Inference requests take the exact write-request path from the paper —
per-session FIFO queues, batched event-function invocation, ordered
completions, pay-per-invoke billing — with a reduced recurrentgemma serving
tokens behind it.  Shows batching amortization and per-session FIFO order.

    PYTHONPATH=src python examples/serve_batched.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.launch.serve import run_serving


def main() -> None:
    frontend = run_serving("recurrentgemma-2b-smoke", n_requests=12, max_new=6,
                           sessions=3, batch_size=4)
    # per-session FIFO: completions must arrive in submission order
    for sess, ids in frontend.completions.items():
        nums = [int(r[1:]) for r in ids]
        assert nums == sorted(nums), f"FIFO violated in {sess}"
    print("\nper-session FIFO order verified across batched invocations")


if __name__ == "__main__":
    main()
