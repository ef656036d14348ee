"""What one run recorded, and the arithmetic the metric readers share.

The harness fills a :class:`Run`; each ``metrics/<name>.py`` reads it with a
``read(run)`` that returns a number, or ``None`` where it finds nothing to
read (the harness then leaves the metric out).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence


@dataclasses.dataclass
class Req:
    rid: str
    due: float                  # host clock, seconds
    prompt_len: int
    max_new: int
    times: List[float] = dataclasses.field(default_factory=list)  # per token
    tokens: Optional[list] = None   # served tokens, once finished
    prompt: object = None           # (P,) int32, kept for the check


@dataclasses.dataclass
class Step:
    t: float                    # host clock once the step's tokens are held
    decode_tokens: int          # active slots in the step's decode
    context: int                # positions those slots attend, new ones included


@dataclasses.dataclass
class Run:
    t0: float                   # window opens (host clock)
    t1: float                   # window closes
    setup_s: float
    requests: Dict[str, Req]
    window_requests: List[str]  # requests whose latency the window owns
    steps: List[Step]           # steps that ended inside the window
    dm: Dict                    # configuration sizes (cell.dims)
    n_pages: int
    peak: Optional[Dict[str, float]] = None
    trace: Optional[Dict] = None    # trace.load(...) of the window
    drain_end: float = 0.0
    finished: List[Req] = dataclasses.field(default_factory=list)
    lateness: List[float] = dataclasses.field(default_factory=list)  # submit - due
    gaps: Dict[str, float] = dataclasses.field(default_factory=dict)  # check.gaps

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1


def tail(values: Sequence[float], q: float) -> Optional[float]:
    """The nearest-rank ``q`` quantile over every sample: the smallest value
    with at least a share ``q`` of the samples at or below it."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def token_gaps(run: Run) -> List[float]:
    """Every gap between consecutive tokens of every request, where the
    later token reached the host inside the window."""
    out = []
    for r in run.requests.values():
        out += [b - a for a, b in zip(r.times, r.times[1:], strict=False)
                if run.in_window(b)]
    return out


def decode_steps(run: Run) -> List[Step]:
    return [s for s in run.steps if s.decode_tokens > 0]
