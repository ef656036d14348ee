"""Paged-block KV pool + chunked prefill: exact-match parity against the
ring scheduler and solo decode, page-allocator safety properties, crash-
redelivery of interrupted admissions, and the freed-slot isolation the
pool's free-on-completion depends on."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model, kvcache
from repro.serve.engine import generate
from repro.serve.lifecycle import SlotState
from repro.serve.scheduler import DecodeScheduler

PARITY_ARCHS = ["minicpm-2b", "moonshot-v1-16b-a3b", "recurrentgemma-2b"]


def tiny(arch="minicpm-2b"):
    cfg = configs.get(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def run_all(sched, submits, got=None):
    """Drive a scheduler: ``submits`` maps step-index -> list of
    (session, rid, prompt, max_new); returns {rid_num: tokens}."""
    got = got if got is not None else {}
    step = 0
    while sched.busy() or any(k >= step for k in submits):
        for args in submits.get(step, ()):
            sched.submit(*args)
        for fin in sched.step():
            got[int(fin.request_id[1:])] = fin.tokens
        step += 1
        assert step < 500, "scheduler failed to drain"
    return got


# ---------------------------------------------------------------------------
# Exact-match parity: paged == ring == solo decode (greedy)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_paged_parity_staggered_multichunk(arch):
    """Prompts spanning 1..3 pages, admitted at different steps, prefilled in
    chunks smaller than a page: every request's tokens must equal both the
    PR 2 ring scheduler's and an eviction-free solo B=1 decode, token for
    token.  The paged gather reassembles pages in logical order, so the
    attention view is lane-for-lane the ring view — this is the exactness
    the whole rewrite is held to."""
    cfg, model, params = tiny(arch)
    page = 8
    lengths = [6, 12, 20]                 # 1, 2 and 3 pages of 8
    N = 4
    max_seq = max(lengths) + N
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, size=L).astype(np.int32)
               for L in lengths]
    ref = {i: np.asarray(generate(model, params, jnp.asarray(p)[None], N,
                                  seq_len=max_seq))[0]
           for i, p in enumerate(prompts)}

    submits = {0: [("a", "r0", prompts[0], N)],
               2: [("b", "r1", prompts[1], N)],
               3: [("c", "r2", prompts[2], N)]}
    ring = run_all(DecodeScheduler(model, params, n_slots=3, max_seq=max_seq,
                                   kv_mode="ring"), submits)
    paged = run_all(DecodeScheduler(model, params, n_slots=3, max_seq=max_seq,
                                    kv_mode="paged", page_size=page,
                                    prefill_chunk=5), submits)
    assert sorted(ring) == sorted(paged) == [0, 1, 2]
    for i in range(3):
        np.testing.assert_array_equal(
            paged[i], ref[i], err_msg=f"{arch} r{i}: paged != solo decode")
        np.testing.assert_array_equal(
            paged[i], ring[i], err_msg=f"{arch} r{i}: paged != ring scheduler")


def test_paged_parity_ssm_chunked():
    """SSM keeps its ring-free O(1) state (no pool pages at all) but the
    chunked admission must still thread the recurrence across chunk
    boundaries exactly."""
    cfg, model, params = tiny("mamba2-1.3b")
    P, N = 12, 5
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab, size=P).astype(np.int32)
    ref = np.asarray(generate(model, params, jnp.asarray(prompt)[None], N,
                              seq_len=P + N))[0]
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=P + N,
                            kv_mode="paged", page_size=4, prefill_chunk=5)
    got = run_all(sched, {0: [("s", "r0", prompt, N)]})
    np.testing.assert_array_equal(got[0], ref)
    assert sched.allocator.n_pages == 0          # truly ring-free
    assert sched.stats()["prefill_chunks"] == 3  # 5 + 5 + 2


def test_paged_update_view_matches_ring_lanes():
    """kvcache-level parity: writes routed through an (arbitrarily ordered)
    page table and gathered back must be lane-for-lane identical to the ring
    buffer, with the same validity mask."""
    B, T, H, D, ps = 2, 16, 2, 4, 4
    rng = np.random.default_rng(0)
    ring = {"k": jnp.zeros((B, T, H, D)), "v": jnp.zeros((B, T, H, D)),
            "positions": -jnp.ones((B, T), jnp.int32)}
    # physical pages deliberately scrambled: logical order must not care
    table = jnp.asarray([[5, 2, 7, 0], [1, 6, 3, 4]], jnp.int32)
    paged = {"kp": jnp.zeros((9, ps, H * D)), "vp": jnp.zeros((9, ps, H * D)),
             "page_table": table}
    assert kvcache.cache_capacity(paged) == T

    # two chunked writes per row, staggered row lengths: row 0 fills 0..7,
    # row 1 fills 0..4 (the second chunk's scatter crosses a page boundary)
    for pos, S in [(jnp.asarray([0, 0], jnp.int32), 3),
                   (jnp.asarray([3, 3], jnp.int32), 5)]:
        k_new = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        v_new = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        if S == 5:                  # row 1 stops at length 5: trim its chunk
            k_new = k_new.at[1, 2:].set(0.0)
            v_new = v_new.at[1, 2:].set(0.0)
        ring = kvcache.cache_update_layer(ring, k_new, v_new, pos)
        paged = kvcache.cache_update_layer(paged, k_new, v_new, pos)
    # row 1's ring holds writes past its live length; mask them like upto does
    ring["positions"] = ring["positions"].at[1, 5:].set(-1)

    upto = jnp.asarray([8, 5], jnp.int32)
    rk, rv, rpos, rvalid = kvcache.cache_kv_view(ring)
    pk, pv, ppos, pvalid = kvcache.cache_kv_view(paged, upto=upto, n_kv=H)
    w = np.asarray(rvalid)
    np.testing.assert_array_equal(np.asarray(pvalid)[:, : T], w)
    np.testing.assert_array_equal(np.asarray(pk)[w], np.asarray(rk)[w])
    np.testing.assert_array_equal(np.asarray(pv)[w], np.asarray(rv)[w])

    # a write whose page is unmapped (or off the table) is dropped, not
    # wrapped into someone else's page
    hole = {"kp": paged["kp"], "vp": paged["vp"],
            "page_table": table.at[0, 1].set(-1)}
    before = np.asarray(hole["kp"])
    after = kvcache.cache_update_layer(
        hole, k_new[:, :1], v_new[:, :1], jnp.asarray([ps, T], jnp.int32))
    np.testing.assert_array_equal(np.asarray(after["kp"]), before)


# ---------------------------------------------------------------------------
# Freed-slot isolation (the PR 2 _step_impl inactive-slot fix)
# ---------------------------------------------------------------------------


def test_freed_slot_cannot_corrupt_later_admission():
    """A freed slot's stale state keeps flowing through the batched decode
    step.  Its token writes and output-ring advance must be masked out, and
    its unmapped page table must drop its pool writes — otherwise reused
    pages would be corrupted.  The sequence: complete A (pages freed), keep B
    decoding (the stale A row rides along), then admit C into A's slot reusing
    A's pages — C must still match solo decode exactly."""
    cfg, model, params = tiny()
    P, N_short, N_long = 8, 2, 12
    rng = np.random.default_rng(11)
    pa = rng.integers(0, cfg.vocab, size=P).astype(np.int32)
    pb = rng.integers(0, cfg.vocab, size=P).astype(np.int32)
    pc = rng.integers(0, cfg.vocab, size=P).astype(np.int32)
    max_seq = P + N_long
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=max_seq,
                            kv_mode="paged", page_size=4,
                            kv_pages=2 * ((P + N_long) // 4 + 1))
    sched.submit("a", "r0", pa, N_short)
    sched.submit("b", "r1", pb, N_long)
    got = {}
    steps_after_free = 0
    submitted_c = False
    n = 0
    while sched.busy():
        n += 1
        assert n < 300
        for fin in sched.step():
            got[int(fin.request_id[1:])] = fin.tokens
        if 0 in got and not submitted_c:
            steps_after_free += 1
            if steps_after_free == 3:    # stale row rode along for 3 steps
                sched.submit("c", "r2", pc, N_short)
                submitted_c = True
    for i, (p, N) in enumerate([(pa, N_short), (pb, N_long), (pc, N_short)]):
        ref = np.asarray(generate(model, params, jnp.asarray(p)[None], N,
                                  seq_len=max_seq))[0]
        np.testing.assert_array_equal(got[i], ref, err_msg=f"r{i} corrupted")
    # pool fully drained and the invariant held
    a = sched.allocator
    assert a.in_use == 0 and a.free_count == a.n_pages


def test_inactive_slot_outputs_frozen():
    """The regression the paged pool makes load-bearing: a decode step must
    not advance out_pos or write tokens for slots that are not active."""
    cfg, model, params = tiny()
    sched = DecodeScheduler(model, params, n_slots=3, max_seq=16,
                            kv_mode="paged", page_size=4)
    sched.submit("s", "r0", np.zeros(4, np.int32), 8)
    for _ in range(3):
        sched.step()
    out_pos = np.asarray(sched.out_pos)
    lengths = np.asarray(sched.cache["length"])
    assert out_pos[0] == 4                      # 1 prefill token + 3 steps
    np.testing.assert_array_equal(out_pos[1:], 0)
    np.testing.assert_array_equal(lengths[1:], 0)
    np.testing.assert_array_equal(np.asarray(sched.out_buf)[1:], 0)


# ---------------------------------------------------------------------------
# Crash redelivery of a half-finished chunked admission
# ---------------------------------------------------------------------------


def test_reset_mid_admission_replays_exactly():
    """reset() while a slot is still `admitting` (some chunks landed) +
    queue redelivery must reproduce the exact same tokens, and the half-
    prefilled slot must never have reached sampling."""
    cfg, model, params = tiny("recurrentgemma-2b")
    P, N = 20, 4
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, cfg.vocab, size=P).astype(np.int32)
    max_seq = P + N
    ref = np.asarray(generate(model, params, jnp.asarray(prompt)[None], N,
                              seq_len=max_seq))[0]

    sched = DecodeScheduler(model, params, n_slots=2, max_seq=max_seq,
                            kv_mode="paged", page_size=8, prefill_chunk=6)
    sched.submit("s", "r0", prompt, N)
    sched.step()                       # chunk 1 of 4 lands
    sched.step()                       # chunk 2 of 4 lands
    st = sched.slots[0]
    assert st.state is SlotState.ADMITTING and st.chunk_i == 2
    assert sched.admitted == 0, "half-prefilled slot reached sampling"
    assert sched.allocator.in_use > 0

    sched.reset()                      # crash: abort in-flight admission
    a = sched.allocator
    assert a.in_use == 0 and a.free_count == a.n_pages
    assert (sched._page_rows == -1).all()

    sched.submit("s", "r0", prompt, N)  # queue redelivery
    got = run_all(sched, {})
    np.testing.assert_array_equal(got[0], ref,
                                  err_msg="redelivered admission diverged")


def test_frontend_crash_redelivery_with_chunked_prefill():
    """End-to-end at-least-once through the queue layer with the paged
    scheduler: a crash after the first completion redelivers; every request
    completes exactly once and in FIFO order per session."""
    from repro.core import SimCloud
    from repro.core.simcloud import FaultPlan
    from repro.launch.serve import build_frontend, spawn_workload

    cfg, model, params = tiny()
    cloud = SimCloud(seed=0, faults=FaultPlan(
        crashes={("serve", "post-complete"): 0}))
    fe = build_frontend(cloud, cfg, model, params, mode="continuous",
                        batch_size=4, max_new=3, prompt_len=8,
                        kv_mode="paged", page_size=4, prefill_chunk=3)
    spawn_workload(cloud, fe, vocab=cfg.vocab, n_requests=8, sessions=4,
                   prompt_len=8, max_new=3)
    cloud.run()
    assert fe.runtime.stats["serve"].crashes == 1
    done = [r for ids in fe.completions.values() for r in ids]
    assert sorted(done) == [f"r{i}" for i in range(8)]
    assert len(done) == len(set(done))
    a = fe.scheduler.allocator
    assert a.in_use == 0 and a.free_count + a.in_use == a.n_pages
    stats = fe.serving_stats()
    assert stats["kv_pages_high_water"] > 0
    assert stats["prefill_chunks"] >= 8 * 3   # 8 tokens / chunk 3 -> 3 chunks


# ---------------------------------------------------------------------------
# Pool sizing / admission gate
# ---------------------------------------------------------------------------


def test_admission_waits_for_pool_pages():
    """With a pool sized for one request, the second request holds in
    pending until the first completes and frees its pages — lazy mapping
    must never be able to deadlock mid-decode."""
    cfg, model, params = tiny()
    P, N = 8, 4
    need = -(-(P + N - 1) // 4)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=P + N,
                            kv_mode="paged", page_size=4, kv_pages=need)
    p = np.zeros(P, np.int32)
    sched.submit("a", "r0", p, N)
    sched.submit("b", "r1", p, N)
    assert sched.slots[0].occupied and sched.slots[1].empty
    assert [r.request_id for r in sched.pending] == ["r1"]
    got = run_all(sched, {})
    assert sorted(got) == [0, 1]
    assert sched.allocator.high_water <= need


def test_page_starved_request_not_overtaken_by_its_session():
    """Per-session FIFO survives the pool gate: when a session's long r0 is
    held for pages, its short r1 must be held with it — not slip into the
    free slot ahead of it."""
    cfg, model, params = tiny()
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=24,
                            kv_mode="paged", page_size=4, kv_pages=8)
    sched.submit("x", "r0", np.zeros(16, np.int32), 8)   # takes 6 pages
    sched.submit("y", "r1", np.zeros(16, np.int32), 8)   # starved: needs 6
    sched.submit("y", "r2", np.zeros(4, np.int32), 2)    # fits, but gated by r1
    assert sched.slots[1].empty
    assert [r.request_id for r in sched.pending] == ["r1", "r2"]
    order = []
    while sched.busy():
        order.extend(f.request_id for f in sched.step())
    assert order.index("r1") < order.index("r2"), "pool gate broke session FIFO"


def test_prompt_overrunning_page_table_rejected():
    cfg, model, params = tiny()
    sched = DecodeScheduler(model, params, n_slots=1, max_seq=8,
                            kv_mode="paged", page_size=4)
    with pytest.raises(ValueError, match="no decode room"):
        sched.submit("s", "r0", np.zeros(8, np.int32), 4)
    with pytest.raises(ValueError):
        DecodeScheduler(model, params, n_slots=1, max_seq=64,
                        kv_mode="paged", page_size=4, kv_pages=2)


# ---------------------------------------------------------------------------
# Page-pool sharding rules
# ---------------------------------------------------------------------------


def test_paged_cache_shardings_resolve_on_16x16():
    from jax.sharding import AbstractMesh

    cfg, model, params = tiny("qwen3-14b")
    mesh = AbstractMesh((16, 16), ("data", "model"))
    sched = DecodeScheduler(model, params, n_slots=16, max_seq=32,
                            kv_mode="paged", page_size=16, mesh=mesh)
    specs = sched.cache_specs
    # pool (L, Np, ps, H*D): shared across slots -> replicated over data;
    # the reduced config's 4 kv heads don't divide model=16, so the guard
    # falls back to the within-page lane dim (never the page dim — the
    # kernel's table-indirect page slices would all-gather the pool)
    assert all(e is None or e == "model" for e in specs["kp"])
    assert specs["kp"][2] == "model" and specs["kp"][1] is None
    # page table (L, n_slots, max_pages): slot batch on data (PartitionSpec
    # normalizes a one-axis tuple entry to the bare axis name)
    assert specs["page_table"][1] == "data"


def test_paged_scheduler_decodes_under_concrete_mesh():
    from jax.sharding import Mesh

    cfg, model, params = tiny()
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=16, mesh=mesh,
                            kv_mode="paged", page_size=4, prefill_chunk=4)
    sched.submit("s0", "r0", np.zeros(8, np.int32), 3)
    got = run_all(sched, {})
    assert got[0].shape == (3,)


# ---------------------------------------------------------------------------
# PageAllocator properties (hypothesis)
# ---------------------------------------------------------------------------


def test_allocator_basics():
    a = kvcache.PageAllocator(4)
    p = a.alloc(3)
    assert len(set(p)) == 3 and a.free_count == 1 and a.high_water == 3
    a.free(p[:2])
    assert a.free_count + a.in_use == 4
    with pytest.raises(ValueError):
        a.free([p[0]])               # double free
    with pytest.raises(RuntimeError):
        a.alloc(4)                   # exhausted
    a.reset()
    assert a.free_count == 4 and a.in_use == 0


def _allocator_property(n_pages, ops):
    """Random submit/complete/reset interleavings: pages handed out are
    always distinct live pages, free + mapped == n_pages at every step, and
    reset() returns the pool to fully free."""
    a = kvcache.PageAllocator(n_pages)
    live = {}                        # request key -> pages
    for op, key, n in ops:
        if op == "submit":
            if key in live or n > a.free_count:
                continue
            pages = a.alloc(n)
            flat = [p for ps in live.values() for p in ps]
            assert not (set(pages) & set(flat)), "double-mapped page"
            assert all(0 <= p < n_pages for p in pages)
            live[key] = pages
        elif op == "complete":
            if key in live:
                a.free(live.pop(key))
        else:
            a.reset()
            live.clear()
            assert a.free_count == n_pages and a.in_use == 0
        assert a.free_count + a.in_use == n_pages, "page leak"
        assert a.in_use == sum(len(p) for p in live.values())
        assert a.high_water <= n_pages
    a.reset()
    assert a.free_count == n_pages and a.in_use == 0


try:  # optional dep, guarded like test_kernel_properties (skip, not error)
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.integers(1, 12),
           st.lists(st.tuples(st.sampled_from(["submit", "complete", "reset"]),
                              st.integers(0, 11), st.integers(1, 6)),
                    max_size=40))
    def test_allocator_never_double_maps_or_leaks(n_pages, ops):
        _allocator_property(n_pages, ops)

except ImportError:

    @pytest.mark.skip(reason="optional dep: property sweeps need hypothesis")
    def test_allocator_never_double_maps_or_leaks():
        pass
