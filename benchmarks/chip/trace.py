"""Profiler trace in, per-layer numbers out.

:func:`load` reads the ``.xplane.pb`` the JAX profiler writes into a plain
dict (device ops and programs per chip, the harness's host spans, the traced
window), and the rest reduces that dict.  Tests check the reduction on a
small trace recorded on the chip, kept as that dict.

On a TPU the profiler gives one plane per chip, ``/device:TPU:<id>``, with a
line ``XLA Ops`` (one event per operation) and a line ``XLA Modules`` (one
event per call of a compiled program, named after the jitted function).
The harness's spans (``jax.profiler.TraceAnnotation``) sit on the host
plane.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

TPU_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPANS = ("window", "submit", "step", "token_read", "generator")
# the jitted functions of the scheduler's decode step and prefill chunk
DECODE_PROGRAM = "_step_impl"
CHUNK_PROGRAM = "chunk_step"

Interval = Tuple[str, float, float]          # name, start s, end s


def options():
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    return o


def load(logdir: str, device_ids: Optional[List[int]] = None) -> Dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "spans":
    [...], "window": [start, end]}``, times in seconds on the profiler's
    clock.  ``device_ids``: the chips the cell ran on; the planes of other
    chips of the host are left out."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: Dict[str, Dict[str, List[Interval]]] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith(TPU_PLANE):
            if device_ids is not None and _plane_id(plane.name) not in device_ids:
                continue
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] += [(e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events if e.name in SPANS]
    win = [s for s in spans if s[0] == "window"]
    if not win:
        raise ValueError("the trace holds no 'window' span")
    return {"devices": devices, "spans": [s for s in spans if s[0] != "window"],
            "window": [win[0][1], win[0][2]]}


def _plane_id(name: str) -> int:
    return int(name[len(TPU_PLANE):].split()[0])


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def busy_s(tr: Dict) -> Optional[float]:
    """Seconds of the window in which some operation ran, averaged over the
    chips traced."""
    lo, hi = tr["window"]
    per = [sum(b - a for a, b in union(_clip([(s, e) for _, s, e in d["ops"]], lo, hi)))
           for d in tr["devices"].values() if d["ops"]]
    return sum(per) / len(per) if per else None


def window_s(tr: Dict) -> float:
    return tr["window"][1] - tr["window"][0]


def program_times(tr: Dict, program: str) -> List[float]:
    """Device seconds of each call of the jitted ``program`` in the window,
    on the first chip that ran it."""
    lo, hi = tr["window"]
    for d in tr["devices"].values():
        t = [e - s for n, s, e in d["modules"]
             if _is_program(n, program) and lo <= s and e <= hi]
        if t:
            return t
    return []


def _is_program(module: str, program: str) -> bool:
    """Module events are named ``jit_<function>`` (with an id suffix on some
    versions)."""
    name = module.split("(")[0].strip()
    return name == f"jit_{program}" or name.startswith(f"jit_{program}.")


def top_ops(tr: Dict, n: int = 10) -> List[List]:
    """The device operations that took most time in the window (first chip)."""
    lo, hi = tr["window"]
    tot: Dict[str, float] = {}
    for d in tr["devices"].values():
        for name, s, e in d["ops"]:
            if lo <= s < hi:
                tot[name] = tot.get(name, 0.0) + (min(e, hi) - s)
        break
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Dict, n: int = 10) -> List[List]:
    """The longest stretches of the window with no operation on the first
    chip, each named by the host span it fell in (the innermost span at its
    middle; ``other`` where the harness was in none)."""
    lo, hi = tr["window"]
    devs = [d for d in tr["devices"].values() if d["ops"]]
    if not devs:
        return []
    busy = union(_clip([(s, e) for _, s, e in devs[0]["ops"]], lo, hi))
    edges = [lo, *(x for iv in busy for x in iv), hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) / 2
        inside = [sp for sp in tr["spans"] if sp[1] <= mid <= sp[2]]
        name = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside else "other"
        out.append([name, b - a])
    return out
