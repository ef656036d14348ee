"""Continuous-batching decode scheduler: per-slot cache correctness against
the whole-batch reference, cross-session FIFO through the shared dispatch
queue, crash/redelivery idempotence, sampling semantics, and the 16x16 mesh
placement of the live decode cache."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import SimCloud
from repro.core.simcloud import FaultPlan
from repro.launch.serve import build_frontend, run_serving
from repro.models import build_model
from repro.serve import sampling
from repro.serve.engine import generate
from repro.serve.scheduler import DecodeScheduler


def tiny(arch="minicpm-2b"):
    cfg = configs.get(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# Scheduler-level correctness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["minicpm-2b", "mamba2-1.3b", "recurrentgemma-2b"])
def test_staggered_admission_matches_solo_decode(arch):
    """Requests admitted into a shared decode batch at *different* steps must
    generate exactly what they'd generate alone — the per-slot ring (and the
    recurrent states) cannot leak across slots or across admission times."""
    cfg, model, params = tiny(arch)
    P, N = 12, 5
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=P).astype(np.int32) for _ in range(3)]
    # seq_len sizes the reference's ring for prompt+decode: the legacy
    # prompt-sized default silently evicts once decode wraps it
    ref = {i: np.asarray(generate(model, params, jnp.asarray(p)[None], N,
                                  seq_len=P + N))[0]
           for i, p in enumerate(prompts)}

    sched = DecodeScheduler(model, params, n_slots=3, max_seq=P + N)
    got = {}
    sched.submit("a", "r0", prompts[0], N)
    for _ in range(2):                      # r0 decodes alone for two steps
        for fin in sched.step():
            got[int(fin.request_id[1:])] = fin.tokens
    sched.submit("b", "r1", prompts[1], N)  # joins mid-flight
    sched.step()
    sched.submit("c", "r2", prompts[2], N)
    while sched.busy():
        for fin in sched.step():
            got[int(fin.request_id[1:])] = fin.tokens
    assert sorted(got) == [0, 1, 2]
    for i in range(3):
        np.testing.assert_array_equal(got[i], ref[i],
                                      err_msg=f"slot {i} diverged from solo decode")


def test_overbudget_request_clamped_to_ring_capacity():
    """A decode budget that would wrap the full-attention KV ring past the
    prompt is clamped at admission; what IS generated matches solo decode."""
    cfg, model, params = tiny()          # dense: full-attention ring
    P, fit = 16, 8
    prompt = np.arange(P, dtype=np.int32) % cfg.vocab
    ref = np.asarray(generate(model, params, jnp.asarray(prompt)[None], fit,
                              seq_len=P + fit))[0]

    sched = DecodeScheduler(model, params, n_slots=2, max_seq=P + fit)
    sched.submit("s0", "r0", prompt, max_new=20)   # asks past the ring
    done = []
    while sched.busy():
        done.extend(sched.step())
    assert len(done) == 1
    assert done[0].tokens.shape == (fit,), "budget must clamp to max_seq - prompt"
    np.testing.assert_array_equal(done[0].tokens, ref)

    # a prompt that leaves no decode room in a full-attention ring is
    # rejected loudly — clamping would silently drop its leading tokens
    with pytest.raises(ValueError, match="no decode room"):
        sched.submit("s1", "r1", np.zeros(P + fit, np.int32), max_new=4)

    # SSM states have no ring: only the output buffer bounds the budget
    _, m2, p2 = tiny("mamba2-1.3b")
    s2 = DecodeScheduler(m2, p2, n_slots=2, max_seq=12)
    s2.submit("s0", "r0", np.zeros(8, np.int32), max_new=999)
    assert s2.slots[0].req.max_new == 12


def test_sampling_flags_rejected_on_greedy_fallback():
    """The whole-batch fallback decodes greedily — sampling knobs must fail
    loudly instead of being silently dropped."""
    with pytest.raises(ValueError, match="continuous scheduler"):
        run_serving("whisper-base-smoke", n_requests=2, max_new=3, sessions=1,
                    temperature=0.8, quiet=True)


def test_session_fifo_gate_and_slot_reuse():
    """A session's second request is admitted only after its first completes,
    and freed slots are re-admitted from the pending list."""
    cfg, model, params = tiny()
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=24)
    p = np.zeros(8, np.int32)
    sched.submit("s0", "a0", p, 3)
    sched.submit("s0", "a1", p, 3)   # same session: must wait for a0
    sched.submit("s1", "b0", p, 3)
    assert sched.slots[0].req.request_id == "a0"
    assert sched.slots[1].req.request_id == "b0"
    assert [r.request_id for r in sched.pending] == ["a1"]
    order = []
    while sched.busy():
        order.extend(f.request_id for f in sched.step())
    assert order.index("a0") < order.index("a1")
    assert sched.completed == 3 and not sched.pending


# ---------------------------------------------------------------------------
# Full serving stack (queues + frontend + scheduler)
# ---------------------------------------------------------------------------


def _drive(frontend, cloud, n_requests, sessions, prompt_len, max_new, vocab):
    from repro.launch.serve import spawn_workload

    spawn_workload(cloud, frontend, vocab=vocab, n_requests=n_requests,
                   sessions=sessions, prompt_len=prompt_len, max_new=max_new)
    cloud.run()


def test_cross_session_batching_preserves_fifo():
    cfg, model, params = tiny()
    cloud = SimCloud(seed=0)
    fe = build_frontend(cloud, cfg, model, params, mode="continuous",
                        batch_size=4, max_new=4, prompt_len=8)
    _drive(fe, cloud, 12, 4, 8, 4, cfg.vocab)
    assert sum(len(v) for v in fe.completions.values()) == 12
    for sess, ids in fe.completions.items():
        nums = [int(r[1:]) for r in ids]
        assert nums == sorted(nums), f"FIFO violated in {sess}"
    # the whole workload fits one continuous invocation: cross-session batch
    assert fe.runtime.stats["serve"].invocations < 12
    assert fe.scheduler.occupancy() > 1.0


def test_crash_redelivers_batch_without_duplicating_completions():
    """At-least-once delivery through the scheduler: a crash mid-invocation
    (after some completions) redelivers the same batch; completions are
    deduped by request id, so every request completes exactly once."""
    cfg, model, params = tiny()
    cloud = SimCloud(seed=0, faults=FaultPlan(
        crashes={("serve", "post-complete"): 0}))
    fe = build_frontend(cloud, cfg, model, params, mode="continuous",
                        batch_size=4, max_new=3, prompt_len=8)
    _drive(fe, cloud, 8, 4, 8, 3, cfg.vocab)
    assert fe.runtime.stats["serve"].crashes == 1
    assert fe.dispatch.redeliveries >= 1
    done = [r for ids in fe.completions.values() for r in ids]
    assert sorted(done) == [f"r{i}" for i in range(8)], done
    assert len(done) == len(set(done)), "duplicated completions after redelivery"
    for sess, ids in fe.completions.items():
        nums = [int(r[1:]) for r in ids]
        assert nums == sorted(nums), f"FIFO violated in {sess} after redelivery"


def test_whole_batch_fallback_for_encdec():
    """Families without a per-slot decode path (enc-dec) fall back to the
    shared whole-batch flavour and still cross-session batch."""
    fe = run_serving("whisper-base-smoke", n_requests=6, max_new=3, sessions=2,
                     batch_size=3, quiet=True)
    assert fe.scheduler is None and fe.mode == "shared"
    assert sum(len(v) for v in fe.completions.values()) == 6


# ---------------------------------------------------------------------------
# Mesh path: dist.cache_shardings on the live decode cache
# ---------------------------------------------------------------------------


def test_cache_shardings_resolve_on_16x16():
    from jax.sharding import AbstractMesh

    cfg, model, params = tiny("qwen3-14b")
    mesh = AbstractMesh((16, 16), ("data", "model"))
    # ring mode: the paged pool's specs are pinned in test_paged_kvcache
    sched = DecodeScheduler(model, params, n_slots=16, max_seq=32, mesh=mesh,
                            kv_mode="ring")
    specs = sched.cache_specs
    # kv rings (L, B, T, H, D): batch on data; the reduced config's 4 kv
    # heads don't divide model=16, so the guard falls back to the time dim
    # (PartitionSpec normalizes a one-axis tuple entry to the bare name)
    assert specs["k"][1] == "data"
    assert specs["k"][2] == "model"
    assert specs["positions"][1] == "data"


def test_scheduler_decodes_under_concrete_mesh():
    from jax.sharding import Mesh

    cfg, model, params = tiny()
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=16, mesh=mesh)
    sched.submit("s0", "r0", np.zeros(8, np.int32), 3)
    out = []
    while sched.busy():
        out.extend(sched.step())
    assert len(out) == 1 and out[0].tokens.shape == (3,)


# ---------------------------------------------------------------------------
# Sampling semantics (top-k fix)
# ---------------------------------------------------------------------------


def test_topk_restricts_support_to_exactly_k():
    """Ties with the k-th logit must NOT widen the candidate set."""
    logits = jnp.asarray([[3.0, 2.0, 2.0, 2.0, -1.0]])  # three-way tie at k=2
    seen = set()
    for s in range(64):
        tok = sampling.temperature_sample(jax.random.key(s), logits,
                                          temperature=1.0, top_k=2)
        seen.add(int(tok[0]))
    assert seen <= {0, 1}, f"top-k leaked tied logits: {seen}"
    assert 0 in seen and 1 in seen  # both top-2 candidates reachable


def test_topk_ge_vocab_and_topk_one():
    logits = jnp.asarray([[0.1, 5.0, -2.0, 1.0]])
    # top_k >= vocab must not index past the sort
    tok = sampling.temperature_sample(jax.random.key(0), logits,
                                      temperature=1.0, top_k=17)
    assert 0 <= int(tok[0]) < 4
    # the -1 "disabled" sentinel means no top-k, not a crash
    tok = sampling.temperature_sample(jax.random.key(0), logits,
                                      temperature=1.0, top_k=-1)
    assert 0 <= int(tok[0]) < 4
    # top_k=1 degenerates to greedy regardless of key
    for s in range(8):
        tok = sampling.temperature_sample(jax.random.key(s), logits,
                                          temperature=1.0, top_k=1)
        assert int(tok[0]) == 1
    # low temperature concentrates on the argmax even without top-k
    tok = sampling.temperature_sample(jax.random.key(0), logits,
                                      temperature=1e-4, top_k=0)
    assert int(tok[0]) == 1


def test_run_serving_bf16_weights_serve_the_fp32_tokens():
    """``run_serving`` serves bf16 weights made by one jitted init+cast
    program; the tokens are those the float32 masters serve."""
    from repro.launch.serve import spawn_workload

    kw = dict(n_requests=6, sessions=2, prompt_len=16, max_new=4)
    fe = run_serving("minicpm-2b-smoke", batch_size=3, quiet=True, **kw)
    cfg = configs.get("minicpm-2b-smoke")
    model = build_model(cfg)
    cloud = SimCloud(seed=0)
    ref = build_frontend(cloud, cfg, model, model.init(jax.random.key(0)),
                         mode="continuous", batch_size=3,
                         max_new=kw["max_new"], prompt_len=kw["prompt_len"])
    spawn_workload(cloud, ref, vocab=cfg.vocab, **kw)
    cloud.run()
    assert fe.completions == ref.completions
    for sess, outs in ref.results.items():
        assert [np.asarray(t).tolist() for t in fe.results[sess]] == \
            [np.asarray(t).tolist() for t in outs]
