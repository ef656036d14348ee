"""The on-chip serving benchmark: one cell, one run, one result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
run makes the configuration's weights on the cell's chips from ``--seed``
(on several chips in the program's placement over the configuration's
``serving.mesh``), builds ``DecodeScheduler`` on that mesh with the serving
defaults of ``repro.launch.serve``,
warms every shape the mix uses, then plays the mix against the scheduler on
the host's clock for ``--seconds``: requests are submitted when due, the
harness calls ``step()``, waits for the step's state and timestamps every
token the step produced, as a streaming server would when it sends them.
Requests due in the window are followed to their first token after it
closes (and the profiler has stopped), for at most ``DRAIN_S``.  Then the
peak memory is read, the program's state freed, and a sample of finished
requests checked against the float32 reference (``check.py``).

``--trace 1`` records the window with the JAX profiler and reports the
cell's per-layer metrics instead of its end-to-end ones.  The last line of
standard output is the result as JSON; the numbers compared for ``correct``
are also the last lines of standard error.  Without a TPU, or with fewer
chips than the cell asks for, the run exits with code 2 and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import cell, check, record, roofline, traffic  # noqa: E402
from benchmarks.chip import trace as tracing  # noqa: E402

DRAIN_S = 60.0           # how long a request due in the window is followed after it


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """The program's persistent compilation cache, in the directory
    ``repro.launch.compile_cache`` chooses (``$JAX_COMPILATION_CACHE_DIR``,
    else the checkout's fixed ``.jax_cache/``), keeping every program: JAX
    by default leaves out those that compile in under a second, and each run
    would compile them again, so set-up would not find every program in the
    cache after a cell's first run."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def reader(name: str) -> Callable:
    path = Path(__file__).resolve().parent / "metrics" / f"{name}.py"
    if not path.exists():
        raise cell.CellError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts XLA compilations (cache hits excluded) while ``armed``."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class Harness:
    """Drives a scheduler on the host's clock and records every token."""

    def __init__(self, sched):
        self.sched = sched
        self.reqs: Dict[str, record.Req] = {}
        self.steps: List[record.Step] = []
        self.lateness: List[float] = []
        self.finished: List[record.Req] = []

    def submit(self, r: traffic.Request, due: float) -> None:
        import jax

        now = time.perf_counter()
        with jax.profiler.TraceAnnotation("submit"):
            self.sched.submit(r.rid, r.rid, r.prompt, r.max_new)
        self.lateness.append(now - due)
        self.reqs[r.rid] = record.Req(rid=r.rid, due=due, prompt_len=len(r.prompt),
                                      max_new=r.max_new, prompt=r.prompt)

    def _held(self) -> Dict[str, int]:
        """Tokens each request's slot has produced so far."""
        from repro.serve.lifecycle import SlotState

        out = {}
        for s in self.sched.slots:
            if s.req is not None and s.state in (SlotState.ACTIVE, SlotState.DRAINED):
                out[s.req.request_id] = s.n_out
        return out

    def step(self) -> record.Step:
        import jax
        import numpy as np

        with jax.profiler.TraceAnnotation("step"):
            done = self.sched.step()
        with jax.profiler.TraceAnnotation("token_read"):
            jax.block_until_ready(self.sched.cache)
            np.asarray(self.sched.last_tokens)
        t = time.perf_counter()
        held = self._held()
        for c in done:
            held[c.request_id] = len(c.tokens)
        new_tokens = first = context = 0
        for rid, n in held.items():
            r = self.reqs.get(rid)
            if r is None or n <= len(r.times):
                continue
            k = n - len(r.times)
            if not r.times:
                first += 1
            r.times += [t] * k
            new_tokens += k
            context += r.prompt_len + n - 1
        for c in done:
            r = self.reqs.get(c.request_id)
            if r is not None:
                r.tokens = [int(x) for x in c.tokens]
                self.finished.append(r)
        return record.Step(t=t, decode_tokens=new_tokens - first, context=context)


def warm_up(sched, spec: Dict, *, seconds: float, chunk: int, vocab: int,
            seed: int) -> int:
    """Compile or load now every program and shape the window runs: serve a
    two-token request for every prefill tail length the mix uses and one at
    its longest prompt, and take the completion slice the scheduler reads
    (``out_buf[i, :max_new]``) at every output length of the mix, which
    serving them would take as many decode steps as the longest output."""
    import jax
    import numpy as np

    sh = traffic.shapes(spec, seconds=seconds, chunk=chunk)
    rng = np.random.default_rng(seed ^ 0x5EED)
    for i, p in enumerate([*sh["tails"], sh["prompt_max"]]):
        sched.submit(f"w{i}", f"w{i}", rng.integers(0, vocab, size=p, dtype=np.int32), 2)
    for n in sh["outputs"]:
        np.asarray(sched.out_buf[0, :n])
    steps = 0
    while sched.busy():
        sched.step()
        # one step in flight at a time, as in the window, where the harness
        # waits for each step's state before it reads the step's tokens
        jax.block_until_ready(sched.cache)
        steps += 1
    return steps


def serve(sched, spec: Dict, reqs: List[traffic.Request], *, seconds: float,
          traced: bool, counter: CompileCounter, devices) -> record.Run:
    """The measured window (and the drain after it)."""
    import jax

    h = Harness(sched)
    backlog = spec["arrival"] == "backlog"
    if backlog:
        t = time.perf_counter()
        for r in reqs:
            h.submit(r, t)
        # the admission burst is set-up: open once no slot is admitting
        while True:
            h.step()
            if sched.admitting_slots() == 0 and sched.active_slots() > 0:
                break
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        jax.profiler.start_trace(logdir, profiler_options=tracing.options())
    counter.armed = True
    t0 = time.perf_counter()
    t1 = t0 + seconds
    i = 0
    window_reqs = [r.rid for r in reqs] if not backlog else []
    with jax.profiler.TraceAnnotation("window"):
        while True:
            now = time.perf_counter()
            if now >= t1:
                break
            while not backlog and i < len(reqs) and t0 + reqs[i].due <= now:
                h.submit(reqs[i], t0 + reqs[i].due)
                i += 1
            if sched.busy():
                s = h.step()
                if s.t <= t1:
                    h.steps.append(s)
            else:
                nxt = t0 + reqs[i].due if (not backlog and i < len(reqs)) else t1
                with jax.profiler.TraceAnnotation("generator"):
                    time.sleep(max(0.0, min(nxt, t1) - time.perf_counter()))
    counter.armed = False
    if traced:
        jax.profiler.stop_trace()
    # follow every request due in the window to its first token; the trace
    # is read after this, so that its reading does not eat the drain's time
    while not backlog and i < len(reqs):
        h.submit(reqs[i], t0 + reqs[i].due)
        i += 1
    end = time.perf_counter() + DRAIN_S
    while (not backlog and sched.busy() and time.perf_counter() < end
           and any(not h.reqs[r].times for r in window_reqs)):
        h.step()
    tr = None
    if traced:
        tr = tracing.load(logdir, [d.id for d in devices])
        shutil.rmtree(logdir, ignore_errors=True)
    run = record.Run(t0=t0, t1=t1, setup_s=t0 - T_START, requests=h.reqs,
                     window_requests=window_reqs, steps=h.steps, dm={},
                     n_pages=sched.n_pages, trace=tr, drain_end=time.perf_counter(),
                     finished=h.finished, lateness=h.lateness)
    return run


def run_cell(workload: Dict, *, seed: int, seconds: float, traced: bool,
             devices, cfg: Optional[Dict] = None, spec: Optional[Dict] = None,
             rate_per_s: Optional[float] = None, control: bool = False,
             sabotage: Optional[Callable] = None):
    """One run of a cell on ``devices[:chips]``; returns the result line's
    object and the :class:`record.Run` it was read from.  ``cfg`` and
    ``spec`` override the cell's files (tests), ``rate_per_s`` the mix's
    rate (the knee sweep), ``control`` puts the control in the program's
    place for ``correct`` (the program's readings stay in ``run.gaps``), and
    ``sabotage(sched)`` breaks the timed path (tests, ``control.py``)."""
    import jax

    from repro.models import build_model
    from repro.models.layers import to_compute_dtype

    from benchmarks.chip import weights

    cfg = cfg or cell.config(workload["config"])
    spec = spec or cell.traffic(workload["traffic"])
    dm = cell.dims(cfg)
    arch = cell.program_config(cfg)
    mesh = cell.mesh(cfg, devices, workload["chips"])
    devices = devices[: workload["chips"]]
    model = build_model(arch)
    program_head = not arch.tie_embeddings
    want = jax.eval_shape(lambda k: to_compute_dtype(model.init(k)), jax.random.key(0))
    shardings = None
    if mesh is not None:
        from repro.dist.sharding import param_shardings

        shardings = param_shardings(want, mesh)
    make = weights.make(dm, program_head=program_head, shardings=shardings)
    have = jax.eval_shape(make, *weights.split_seed(0))
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(have) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(have),
                strict=True)):
        raise cell.CellError("the benchmark's weights do not match the program's "
                             f"layout: {want} vs {have}")
    counter = CompileCounter()
    params = jax.block_until_ready(make(*weights.split_seed(seed)))
    log(f"weights made at {time.perf_counter() - T_START:.2f} s")
    sched = cell.build_scheduler(model, params, cfg, mesh)
    if mesh is not None:
        # the scheduler places the params itself; made in its placement,
        # they must stay where they are, or each chip would briefly hold
        # two copies of its share
        moved = [jax.tree_util.keystr(p) for (p, a), b in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves(sched.params), strict=True)
            if a.sharding != b.sharding]
        if moved:
            raise cell.CellError(f"the scheduler moved {len(moved)} weight leaves "
                                 f"from where they were made: {moved[:4]}")
    if sabotage is not None:
        sabotage(sched)
    reqs = traffic.generate(spec, seed=seed, seconds=seconds, vocab=dm["vocab"],
                            rate_per_s=rate_per_s)
    n_warm = warm_up(sched, spec, seconds=seconds, chunk=cfg["serving"]["prefill_chunk"],
                     vocab=dm["vocab"], seed=seed)
    log(f"warm-up: {n_warm} steps, done at {time.perf_counter() - T_START:.2f} s")
    run = serve(sched, spec, reqs, seconds=seconds, traced=traced, counter=counter,
                devices=devices)
    run.dm = dm
    kind = devices[0].device_kind
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    lat = sorted(run.lateness)
    log(f"generator lateness: median {1e3 * record.tail(lat, 0.5):.3f} ms, "
        f"max {1e3 * lat[-1]:.3f} ms over {len(lat)} submits" if lat else
        "generator: no submits")
    log(f"compilations inside the window: {counter.count}")
    log(f"scheduler: {sched.stats()}")
    finished = list(run.finished)
    del sched, params
    gc.collect()

    t_check = time.perf_counter()
    picked = check.sample(finished, spec["check_requests"], seed)
    k = spec["check_requests"]
    g = check.gaps(dm, seed, picked, k=k, t_ref=cfg["serving"]["max_seq"],
                   n_ref=traffic.shapes(spec, seconds=seconds, chunk=1)["output_max"],
                   program_head=program_head, control=control, devices=devices)
    bad = sum(1 for r in finished
              if len(r.tokens) != r.max_new
              or not all(0 <= t < dm["vocab"] for t in r.tokens))
    log(f"reference check: {g['tokens']} served tokens of {len(picked)} requests "
        f"in {time.perf_counter() - t_check:.2f} s")
    # with ``control`` the fp8 reference's first choices stand in the
    # program's place: the same numbers, limits and judgement
    correct, checks = check.judge(g, cfg["correctness"]["limits"], bad=bad,
                                  checked=len(picked),
                                  prefix="control_" if control else "")
    run.gaps = g

    run.peak = (roofline.peaks(kind, len(devices)) if devices[0].platform == "tpu"
                else None)
    names = cell.metrics_for(cell.benchmark(), workload["name"], traced=traced)
    metrics = {}
    for m in names:
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    attempted = len(run.window_requests) or sum(
        1 for r in run.requests.values() if any(run.in_window(t) for t in r.times))
    failed = sum(1 for rid in run.window_requests if not run.requests[rid].times)
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(mem)}
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if traced and run.trace:
        device["busy_s"] = tracing.busy_s(run.trace)
        device["window_s"] = tracing.window_s(run.trace)
        out["breakdown"] = {"device_ops": tracing.top_ops(run.trace),
                            "idle_gaps": tracing.idle_gaps(run.trace)}
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return out, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    w = cell.workload(cell.benchmark(), args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < w["chips"]:
        log(f"needs {w['chips']} TPU chip(s); JAX sees {len(devices)} "
            f"{devices[0].platform} device(s). Nothing was run.")
        return 2
    log(f"compile cache at {enable_compile_cache()}; JAX found the chip at "
        f"{time.perf_counter() - T_START:.2f} s")
    out, _ = run_cell(w, seed=args.seed, seconds=args.seconds, traced=bool(args.trace),
                   devices=devices[: w["chips"]])
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
