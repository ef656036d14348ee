"""Host spans of the serving scheduler (``repro.serve.tracing``): off and
free without a profile, nested and keyed by request and step under one, and
the jitted program names the benchmark's per-layer metrics read."""

from __future__ import annotations

import glob
import itertools
import os
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model
from repro.serve import tracing
from repro.serve.scheduler import DecodeScheduler
from repro.serve.tracing import span


@pytest.fixture(scope="module")
def tiny():
    cfg = configs.get("minicpm-2b").reduced()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def paged(tiny, n_slots=2):
    cfg, model, params = tiny
    return DecodeScheduler(model, params, n_slots=n_slots, max_seq=32,
                           kv_mode="paged", page_size=4, prefill_chunk=5)


def test_span_off_is_one_shared_no_op_and_records_nothing():
    tracing.clear()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert span("a") is span("b", request_id="r0", step=3)
    with span("step", step=1) as ids:
        assert ids is None
    assert tracing.spans() == []


def test_span_off_allocates_nothing():
    """A call with the profiler off adds nothing to the traced peak over an
    empty loop of the same length (one allocation a call, freed at once,
    would raise the peak by its size)."""
    def calls(it):
        for _ in it:
            span("decode", step=7)

    def empty(it):
        for _ in it:
            pass

    peaks = {}
    for f in (empty, calls, empty, calls):
        f(itertools.repeat(None, 1000))
        it = itertools.repeat(None, 10_000)
        tracemalloc.start()
        try:
            tracemalloc.reset_peak()
            before, _ = tracemalloc.get_traced_memory()
            f(it)
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        peaks[f.__name__] = peak - before
    assert peaks["calls"] <= peaks["empty"]


def test_scheduler_spans_nest_by_step_and_request(tiny, tmp_path):
    cfg = tiny[0]
    sched = paged(tiny)
    rng = np.random.default_rng(0)
    prompts = {f"r{i}": rng.integers(0, cfg.vocab, size=p).astype(np.int32)
               for i, p in enumerate((12, 7, 9))}
    tracing.clear()
    n_steps = 0
    with jax.profiler.trace(str(tmp_path)):
        for rid, p in prompts.items():
            sched.submit(f"s{rid}", rid, p, 4)
        while sched.busy():
            sched.step()
            n_steps += 1
    got = tracing.spans()
    tracing.clear()
    by_index = {s.index: s for s in got}
    steps = [s for s in got if s.name == "step"]
    assert len(steps) == n_steps
    assert all(s.parent is None for s in steps)
    # a step's number is the scheduler's count of decode steps before it
    decoded = {s.parent for s in got if s.name == "decode"}
    assert [s.ids["step"] for s in steps] == list(
        itertools.accumulate((s.index in decoded for s in steps[:-1]), initial=0))
    assert len(decoded) == sched.steps
    names = {s.name for s in got}
    assert names == {"submit", "admit", "step", "chunk", "grow", "decode", "finish"}
    for s in got:
        assert s.start <= s.end
        if s.name in ("chunk", "grow", "decode", "finish"):
            p = by_index[s.parent]
            assert p.name == "step"
            assert p.start <= s.start and s.end <= p.end
        if s.parent is not None:
            p = by_index[s.parent]
            assert p.start <= s.start and s.end <= p.end
    # the third request waits for a slot: admitted from a step, not a submit
    admits = {s.ids["request_id"]: by_index[s.parent].name
              for s in got if s.name == "admit"}
    assert admits["r0"] == admits["r1"] == "submit" and admits["r2"] != "submit"
    assert sum(s.ids["pages"] for s in got if s.name == "grow") > 0
    for rid in prompts:
        kinds = {s.name for s in got if rid == s.ids.get("request_id")
                 or s.name == "finish" and rid in s.ids["request_id"]}
        assert kinds == {"submit", "admit", "chunk", "finish"}, (rid, kinds)
    # the same spans sit in the profiler's trace, beside the device ops
    pb = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(pb[0])
    host = {e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert {f"sched.{n}" for n in names} <= host


def test_the_ring_keeps_the_newest_spans(tmp_path):
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        for i in range(tracing.RING + 3):
            with span("step", step=i):
                pass
        with span("step", step=-1):
            with span("decode"):
                pass
    got = tracing.spans()
    tracing.clear()
    assert len(got) == tracing.RING
    assert got[0].ids["step"] == 5
    assert got[-1].name == "decode" and got[-1].parent == got[-2].index
    assert got[-1].index == tracing.RING + 4


def test_jitted_programs_keep_the_names_the_benchmark_reads(tiny):
    """``benchmarks/chip`` reads the decode and chunk programs' device time
    by their module names in the profiler trace."""
    sched = paged(tiny)
    mask = jnp.ones((sched.n_slots,), bool)
    dec = sched._decode.lower(sched.params, sched.cache, sched.last_tokens,
                              sched.out_buf, sched.out_pos, mask, jax.random.key(0))
    chunk = sched._chunk.lower(sched.params, sched.cache,
                               jnp.zeros((1, 5), jnp.int32), 0)
    assert dec.as_text().startswith("module @jit__step_impl ")
    assert chunk.as_text().startswith("module @jit_chunk_step ")
