"""Refcounted copy-on-write prefix sharing + cross-request session parking.

The headline invariants: (1) a request admitted over shared pages — a parked
session's journal or the content-addressed prefix index — produces
token-for-token identical output to a from-scratch solo run, while paying
prefill only for its tail; (2) shared pages are immutable: a completion must
never free a page another holder still maps (refcounts), and a writer must
never mutate a shared page in place (copy-on-write splits, verified at lane
level against the pool bytes, not just argmax); (3) ``reset()`` forgets the
prefix index and the parked table, so a crash-replayed run cannot observe
another life's shared state.
"""

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

MAX_SEQ = 32


def tiny(arch="minicpm-2b"):
    cfg = configs.get(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def drain(sched, got=None, limit=300):
    got = got if got is not None else {}
    it = 0
    while sched.busy():
        for fin in sched.step():
            got[fin.request_id] = fin
        sched.audit()
        it += 1
        assert it < limit, "scheduler failed to drain"
    return got


def solo(model, params, prompt, max_new):
    return np.asarray(generate(model, params, jnp.asarray(prompt)[None],
                               max_new, seq_len=MAX_SEQ))[0]


# ---------------------------------------------------------------------------
# PageAllocator refcounts
# ---------------------------------------------------------------------------


def test_allocator_refcounts():
    a = kvcache.PageAllocator(4)
    p = a.alloc(2)
    assert a.refcount(p[0]) == 1 and a.in_use == 2 and a.total_refs == 2
    a.share([p[0]])
    assert a.refcount(p[0]) == 2 and a.total_refs == 3
    a.release([p[0]])                       # one ref down: still mapped
    assert a.refcount(p[0]) == 1 and a.in_use == 2
    a.release([p[0]])                       # last ref: back to the free list
    assert a.refcount(p[0]) == 0 and a.in_use == 1
    assert a.free_count + a.in_use == a.n_pages
    with pytest.raises(ValueError):
        a.release([p[0]])                   # double release
    with pytest.raises(ValueError):
        a.share([p[0]])                     # sharing a freed page
    a.check()
    a.release([p[1]])
    assert a.free_count == 4 and a.total_refs == 0


def test_allocator_free_alias_keeps_refcount_semantics():
    """`free` (the pre-refcount name) is one release, not a force-free."""
    a = kvcache.PageAllocator(2)
    (p,) = a.alloc(1)
    a.share([p])
    a.free([p])
    assert a.refcount(p) == 1 and a.in_use == 1
    a.free([p])
    assert a.free_count == 2


# ---------------------------------------------------------------------------
# Prefix index: content addressing + LRU eviction
# ---------------------------------------------------------------------------


def test_page_hashes_chain_on_prefix():
    ps = 4
    t1 = np.arange(12, dtype=np.int32)
    t2 = t1.copy()
    t2[1] = 99                               # first page differs
    h1, h2 = kvcache.page_hashes(t1, ps), kvcache.page_hashes(t2, ps)
    assert len(h1) == 3                      # full pages only
    assert h1[0] != h2[0]
    # chaining: identical page-2 *content* still hashes apart because the
    # prefix differs — sharing keys on the whole token history
    assert h1[1] != h2[1] and h1[2] != h2[2]
    assert kvcache.page_hashes(t1[:11], ps) == h1[:2]   # partial page dropped


def test_prefix_index_publish_lookup_evict():
    a = kvcache.PageAllocator(6)
    idx = kvcache.PrefixIndex()
    pages = a.alloc(3)
    hashes = kvcache.page_hashes(np.arange(12, dtype=np.int32), 4)
    assert idx.publish(hashes, pages, a) == 3
    assert all(a.refcount(p) == 2 for p in pages)
    assert idx.publish(hashes, pages, a) == 0          # dedupe: no new refs
    assert idx.lookup(hashes) == pages
    other = kvcache.page_hashes(np.arange(100, 112, dtype=np.int32), 4)
    assert idx.lookup(other) == []
    assert idx.lookup([hashes[0], other[0], hashes[2]]) == [pages[0]]
    # the holder releases: pages survive on the index's reference alone
    a.release(pages)
    assert a.in_use == 3
    # eviction reclaims index references until enough pages are free
    dropped = idx.evict(a, need_free=5)
    assert dropped == 2 and a.free_count == 5 and len(idx) == 1
    idx.clear(a)
    assert a.free_count == 6 and a.total_refs == 0


# ---------------------------------------------------------------------------
# Copy-on-write at the kvcache level: lane-exact, original untouched
# ---------------------------------------------------------------------------


def test_copy_pages_lane_exact():
    rng = np.random.default_rng(3)
    pool = {"kp": jnp.asarray(rng.standard_normal((2, 5, 4, 6)), jnp.float32),
            "vp": jnp.asarray(rng.standard_normal((2, 5, 4, 6)), jnp.float32),
            "page_table": jnp.zeros((2, 1, 2), jnp.int32)}
    out = kvcache.copy_pages(pool, [1, 3], [0, 4])
    for k in ("kp", "vp"):
        np.testing.assert_array_equal(np.asarray(out[k][:, 0]),
                                      np.asarray(pool[k][:, 1]))
        np.testing.assert_array_equal(np.asarray(out[k][:, 4]),
                                      np.asarray(pool[k][:, 3]))
        np.testing.assert_array_equal(np.asarray(out[k][:, [1, 2, 3]]),
                                      np.asarray(pool[k][:, [1, 2, 3]]))
    np.testing.assert_array_equal(np.asarray(out["page_table"]),
                                  np.asarray(pool["page_table"]))


def test_gather_scatter_slot_state_round_trip():
    cfg, model, params = tiny("recurrentgemma-2b")
    sched = DecodeScheduler(model, params, n_slots=3, max_seq=16, page_size=4)
    rng = np.random.default_rng(5)
    sched.submit("s", "r0", rng.integers(0, cfg.vocab, 8).astype(np.int32), 3)
    sched.step(); sched.step()
    snap = jax.device_get(kvcache.gather_slot_state(sched.cache, 0))
    # state excludes the shared pool and the page table
    flat = dict(kvcache._iter_pool_leaves(snap))
    assert all(k[-1] not in ("kp", "vp", "page_table") for k in flat)
    # scatter into a different slot and gather back: bit-identical
    back = kvcache.scatter_slot_state(sched.cache, 2, snap)
    snap2 = jax.device_get(kvcache.gather_slot_state(back, 2))
    jax.tree_util.tree_map(np.testing.assert_array_equal, snap, snap2)


# ---------------------------------------------------------------------------
# The sharp edge: shared page freed under a live reader / CoW mid-decode
# ---------------------------------------------------------------------------


def test_release_keeps_shared_page_and_cow_splits_mid_decode():
    """Two slots share an indexed prefix page; the one that completes first
    must not free it (the other still maps it), and a decode write through
    a shared page must CoW-split — verified lane-level: the shared page's
    pool bytes are bit-identical before and after, not just argmax."""
    cfg, model, params = tiny()
    ps, N = 4, 6
    rng = np.random.default_rng(11)
    sys_p = rng.integers(0, cfg.vocab, size=2 * ps).astype(np.int32)
    pa = np.concatenate([sys_p, rng.integers(0, cfg.vocab, 3).astype(np.int32)])
    pb = np.concatenate([sys_p, rng.integers(0, cfg.vocab, 4).astype(np.int32)])
    pc = np.concatenate([sys_p, rng.integers(0, cfg.vocab, 5).astype(np.int32)])
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=MAX_SEQ,
                            page_size=ps, prefill_chunk=5, prefix_sharing=True)
    got = {}
    sched.submit("a", "r0", pa, N)
    drain(sched, got)                       # publishes a's full pages
    sys_pages = sched.prefix_index.lookup(kvcache.page_hashes(sys_p, ps))
    assert len(sys_pages) == 2
    indexed = sorted(sched.prefix_index.pages)
    before = {k: np.asarray(jnp.take(sched.cache[k], jnp.asarray(indexed),
                                     axis=1))
              for k in ("kp", "vp")}

    # b (short) and c (long) admit concurrently over the shared sys pages
    sched.submit("b", "r1", pb, 3)
    sched.submit("c", "r2", pc, 8)
    assert sched.slots[0].shared == sys_pages
    assert sched.slots[1].shared == sys_pages
    assert sched.allocator.refcount(sys_pages[0]) == 3   # index + b + c

    def step_into(got):
        for fin in sched.step():
            got[fin.request_id] = fin

    it = 0
    while "r1" not in got:        # b (3 tokens) finishes well before c (8)
        step_into(got)
        sched.audit()
        it += 1
        assert it < 20
    # b completed and released its references: the page survives for c
    assert sched.allocator.refcount(sys_pages[0]) == 2   # index + c
    assert all(sched.allocator.refcount(p) >= 1 for p in indexed)
    c_slot = sched.slots[1]
    assert c_slot.state is SlotState.ACTIVE

    # force a *decode* write through a shared page: give c's current append
    # page an external reference (as a parked journal would hold) and step.
    # (step until the append page is resident — a fresh page maps lazily
    # during the decode step itself)
    while int(sched._page_rows[1, c_slot.len // ps]) < 0:
        step_into(got)
        assert c_slot.state is SlotState.ACTIVE
    append_page = int(sched._page_rows[1, c_slot.len // ps])
    sched.allocator.share([append_page])
    page_before = {k: np.asarray(sched.cache[k][:, append_page])
                   for k in ("kp", "vp")}
    cow0 = sched.cow_splits
    step_into(got)
    assert sched.cow_splits == cow0 + 1, "decode write did not CoW-split"
    for k in ("kp", "vp"):                   # original bytes untouched
        np.testing.assert_array_equal(
            np.asarray(sched.cache[k][:, append_page]), page_before[k])
    assert append_page not in sched.slots[1].pages
    sched.allocator.release([append_page])   # drop the synthetic holder
    sched.audit()
    drain(sched, got)

    # lane-level: the published pages never moved a bit through all of it
    after = {k: np.asarray(jnp.take(sched.cache[k], jnp.asarray(indexed),
                                    axis=1))
             for k in ("kp", "vp")}
    for k in ("kp", "vp"):
        np.testing.assert_array_equal(before[k], after[k])
    # token-for-token parity for every request that ran over shared pages
    for rid, p, n in [("r0", pa, N), ("r1", pb, 3), ("r2", pc, 8)]:
        np.testing.assert_array_equal(got[rid].tokens, solo(model, params, p, n),
                                      err_msg=f"{rid} diverged from solo")
    assert got["r2"].reused_tokens == 2 * ps


@pytest.mark.parametrize("arch", ["minicpm-2b", "recurrentgemma-2b"])
def test_multiturn_park_parity(arch):
    """Turn 2/3 extend the session history: the parked journal serves the
    resident prefix, only the tail prefills, and the output is exactly the
    from-scratch solo run.  Both families reuse the whole *consumed* span —
    prompt and generated tokens alike — because decode-written KV is bitwise
    what a re-prefill would write (the S=1 decode path IS the chunk path at
    S=1); only the last sampled token, whose KV was never written, re-feeds."""
    cfg, model, params = tiny(arch)
    N = 3
    rng = np.random.default_rng(7)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=MAX_SEQ,
                            page_size=4, prefill_chunk=5,
                            park_sessions=True, prefix_sharing=True)
    hist = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    prefill_per_turn = []
    expect_reused = 0
    for turn in range(3):
        before = sched.prefill_tokens
        got = {}
        sched.submit("s", f"r{turn}", hist, N)
        drain(sched, got)
        np.testing.assert_array_equal(
            got[f"r{turn}"].tokens, solo(model, params, hist, N),
            err_msg=f"{arch} turn {turn} diverged")
        prefill_per_turn.append(sched.prefill_tokens - before)
        assert got[f"r{turn}"].reused_tokens == expect_reused
        assert prefill_per_turn[-1] == len(hist) - expect_reused
        # what the journal serves next turn: everything consumed — prompt
        # plus all but the last generated token (its KV was never written)
        expect_reused = len(hist) + N - 1
        hist = np.concatenate([hist, got[f"r{turn}"].tokens.astype(np.int32),
                               rng.integers(0, cfg.vocab, 2).astype(np.int32)])
    # turn >= 2 prefills only the tail while the prompt kept growing
    assert prefill_per_turn[1] < prefill_per_turn[0]
    assert prefill_per_turn[2] <= prefill_per_turn[1]
    assert sched.park_hits == 2 and sched.parks == 3


def test_park_offload_restores_from_blob():
    """Pool pressure offloads a parked journal through the PageBlobStore;
    the session's next request restores the blob (one GET) instead of
    re-prefilling, still token-exact."""
    cfg, model, params = tiny()
    N = 4
    rng = np.random.default_rng(9)
    p1 = rng.integers(0, cfg.vocab, size=10).astype(np.int32)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=MAX_SEQ,
                            page_size=4, park_sessions=True)
    got = {}
    sched.submit("s", "r0", p1, N)
    drain(sched, got)
    rec = sched._parked["s"]
    sched._offload_parked(rec)
    sched.audit()
    assert rec.blob_key and not rec.pages and rec.slot is None
    assert sched.blob_store.bytes_stored > 0
    p2 = np.concatenate([p1, got["r0"].tokens.astype(np.int32),
                         rng.integers(0, cfg.vocab, 3).astype(np.int32)])
    sched.submit("s", "r1", p2, N)
    drain(sched, got)
    np.testing.assert_array_equal(got["r1"].tokens, solo(model, params, p2, N))
    assert sched.blob_store.gets == 1
    assert got["r1"].reused_tokens == len(p1) + N - 1   # full consumed span


def test_park_blob_restore_slices_to_reused_span():
    """A blob journal can hold more pages than the next request reuses (a
    short extension keeps at least one prompt token as the prefill tail):
    the restore must allocate and inject only the reused span, not the
    whole blob — the whole-blob version over-allocates past the admission's
    reservation."""
    cfg, model, params = tiny()
    rng = np.random.default_rng(31)
    p1 = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=MAX_SEQ,
                            page_size=4, park_sessions=True)
    got = {}
    sched.submit("s", "r0", p1, 12)          # long generated tail: 5-page blob
    drain(sched, got)
    rec = sched._parked["s"]
    sched._offload_parked(rec)
    sched.audit()
    assert len(rec.blob_pidx) == 5           # ceil((8+12-1)/4)
    # next turn is an 11-token prompt: reuse caps at P-1 = 10 tokens (one
    # token must remain as the prefill tail), i.e. 3 of the 5 blob pages
    p2 = np.concatenate([p1, got["r0"].tokens[:3].astype(np.int32)])
    sched.submit("s", "r1", p2, 4)
    assert sched.slots[0].state is SlotState.ADMITTING or \
        sched.slots[1].state is SlotState.ADMITTING
    assert sched.blob_store.gets == 1
    drain(sched, got)
    np.testing.assert_array_equal(got["r1"].tokens, solo(model, params, p2, 4))
    assert got["r1"].reused_tokens == len(p2) - 1


def test_short_matching_resubmission_keeps_journal():
    """A prompt that matches the journal but leaves no prefill tail (hybrid:
    a resubmission of exactly the consumed span) must not be treated as
    divergence — the journal survives and serves the next real extension."""
    cfg, model, params = tiny("recurrentgemma-2b")
    rng = np.random.default_rng(37)
    p1 = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=MAX_SEQ,
                            page_size=4, park_sessions=True)
    got = {}
    sched.submit("s", "r0", p1, 3)
    drain(sched, got)
    hist = np.concatenate([p1, got["r0"].tokens.astype(np.int32)])
    # journal: history = 11, consumed = 10.  P = 10 == consumed leaves no
    # tail token to prefill: consistent but too short — reuse nothing, but
    # do NOT drop the journal
    sched.submit("s", "r1", hist[:10], 3)
    drain(sched, got)
    np.testing.assert_array_equal(got["r1"].tokens,
                                  solo(model, params, hist[:10], 3))
    assert sched.park_misses == 0            # NOT a divergence
    assert got["r1"].reused_tokens == 0
    # a real extension afterwards still park-hits (the superseding journal:
    # history = 13, consumed = 12)
    hist2 = np.concatenate([hist[:10], got["r1"].tokens.astype(np.int32),
                            rng.integers(0, cfg.vocab, 2).astype(np.int32)])
    sched.submit("s", "r2", hist2, 3)
    drain(sched, got)
    np.testing.assert_array_equal(got["r2"].tokens,
                                  solo(model, params, hist2, 3))
    assert sched.park_hits == 1
    assert got["r2"].reused_tokens == 12     # the full consumed span


def test_exact_resubmission_reuses_consumed_span():
    """The case the consumed-span lift unlocks for the hybrid: resubmitting
    the full recorded history (P = consumed + 1) now reuses every consumed
    token and prefills only the last sampled one — previously an exact
    resubmission was 'too short' because the recurrent rows demanded the
    whole prompt be re-fed."""
    cfg, model, params = tiny("recurrentgemma-2b")
    rng = np.random.default_rng(41)
    p1 = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=MAX_SEQ,
                            page_size=4, park_sessions=True)
    got = {}
    sched.submit("s", "r0", p1, 3)
    drain(sched, got)
    hist = np.concatenate([p1, got["r0"].tokens.astype(np.int32)])
    before = sched.prefill_tokens
    sched.submit("s", "r1", hist, 3)         # P = 11 = consumed + 1
    drain(sched, got)
    np.testing.assert_array_equal(got["r1"].tokens,
                                  solo(model, params, hist, 3))
    assert sched.park_hits == 1
    assert got["r1"].reused_tokens == len(hist) - 1
    assert sched.prefill_tokens - before == 1    # only the sampled token


def test_slot_pressure_evicts_parked_then_restores():
    """All slots parked; a third session's admission reclaims the oldest
    residency (rows snapshot to the record); when the evicted session
    returns, its journal restores into a *different* slot — still exact."""
    cfg, model, params = tiny("recurrentgemma-2b")
    N = 3
    rng = np.random.default_rng(13)
    pa = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    pb = rng.integers(0, cfg.vocab, size=9).astype(np.int32)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=MAX_SEQ,
                            page_size=4, park_sessions=True)
    got = {}
    sched.submit("a", "r0", pa, N)
    drain(sched, got)
    sched.submit("b", "r1", pb, N)
    drain(sched, got)
    assert sched.parked_slots() == 2
    pc = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    sched.submit("c", "r2", pc, N)           # no empty slot: evicts a's
    drain(sched, got)
    assert sched.park_evictions == 1
    assert sched._parked["a"].slot is None
    assert sched._parked["a"].state is not None
    pa2 = np.concatenate([pa, got["r0"].tokens.astype(np.int32),
                          rng.integers(0, cfg.vocab, 2).astype(np.int32)])
    sched.submit("a", "r3", pa2, N)
    drain(sched, got)
    for rid, p in [("r0", pa), ("r1", pb), ("r2", pc), ("r3", pa2)]:
        np.testing.assert_array_equal(got[rid].tokens, solo(model, params, p, N),
                                      err_msg=f"{rid} diverged")
    assert got["r3"].reused_tokens == len(pa) + N - 1


def test_park_ttl_expires_idle_sessions():
    cfg, model, params = tiny()
    rng = np.random.default_rng(17)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=MAX_SEQ,
                            page_size=4, park_sessions=True, park_ttl_steps=4)
    got = {}
    sched.submit("s", "r0", rng.integers(0, cfg.vocab, 8).astype(np.int32), 3)
    drain(sched, got)
    assert "s" in sched._parked
    # another session keeps the step clock moving past the TTL
    sched.submit("t", "r1", rng.integers(0, cfg.vocab, 8).astype(np.int32), 8)
    drain(sched, got)
    assert sched.park_expirations == 1 and "s" not in sched._parked
    sched.audit()
    # every page the expired journal held is reclaimed
    assert sched.allocator.total_refs == sum(
        len(r.pages) for r in sched._parked.values()) + len(sched.prefix_index)


def test_reset_clears_prefix_index_and_parked_table():
    """Crash replay must not observe stale cross-request sharing: reset()
    forgets the index and the parked table, and the redelivered session
    replays from its prompt — full prefill, same tokens."""
    cfg, model, params = tiny()
    N = 4
    rng = np.random.default_rng(19)
    p1 = rng.integers(0, cfg.vocab, size=10).astype(np.int32)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=MAX_SEQ,
                            page_size=4, park_sessions=True,
                            prefix_sharing=True)
    got = {}
    sched.submit("s", "r0", p1, N)
    drain(sched, got)
    assert sched._parked and len(sched.prefix_index) > 0
    sched.reset()
    assert not sched._parked and len(sched.prefix_index) == 0
    a = sched.allocator
    assert a.in_use == 0 and a.free_count == a.n_pages and a.total_refs == 0
    # replay: turn-2 prompt finds nothing resident — full prefill, exact
    p2 = np.concatenate([p1, got["r0"].tokens.astype(np.int32),
                         rng.integers(0, cfg.vocab, 3).astype(np.int32)])
    before = sched.prefill_tokens
    sched.submit("s", "r1", p2, N)
    drain(sched, got)
    assert sched.park_hits == 0 and sched.index_hits == 0
    assert sched.prefill_tokens - before == len(p2)
    np.testing.assert_array_equal(got["r1"].tokens, solo(model, params, p2, N))


def test_sharing_requires_paged_pool():
    cfg, model, params = tiny()
    for kw in ({"prefix_sharing": True}, {"park_sessions": True}):
        with pytest.raises(ValueError, match="paged"):
            DecodeScheduler(model, params, n_slots=2, max_seq=16,
                            kv_mode="ring", **kw)


def test_index_sharing_gated_to_attention_families():
    """Hybrid recurrent rows cannot be rebuilt from KV pages alone: the
    index is never consulted (or published) for them, while parking — which
    keeps the rows — stays on."""
    cfg, model, params = tiny("recurrentgemma-2b")
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=MAX_SEQ,
                            page_size=4, prefix_sharing=True,
                            park_sessions=True)
    assert sched.prefix_sharing and not sched._index_sharing
    rng = np.random.default_rng(23)
    got = {}
    sched.submit("s", "r0", rng.integers(0, cfg.vocab, 8).astype(np.int32), 3)
    drain(sched, got)
    assert len(sched.prefix_index) == 0 and sched.parks == 1


def test_preempt_restore_of_unparked_slot_stays_exact():
    """A slot decoding over shared parked pages gets preempted: the blob
    captures the shared prefix too, the restore owns everything, and the
    journal keeps its own references — still token-exact for both lives."""
    cfg, model, params = tiny()
    N = 6
    rng = np.random.default_rng(29)
    p1 = rng.integers(0, cfg.vocab, size=9).astype(np.int32)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=MAX_SEQ,
                            page_size=4, park_sessions=True, offload=True)
    got = {}
    sched.submit("s", "r0", p1, N)
    drain(sched, got)
    p2 = np.concatenate([p1, got["r0"].tokens.astype(np.int32),
                         rng.integers(0, cfg.vocab, 2).astype(np.int32)])
    sched.submit("s", "r1", p2, N)
    sched.submit("t", "r2", rng.integers(0, cfg.vocab, 8).astype(np.int32), N)
    steps = 0
    while sched.busy():
        if steps == 3:
            victim = next(s for s in sched.slots
                          if s.state is SlotState.ACTIVE and s.pages)
            sched.preempt(victim.index)
        for fin in sched.step():
            got[fin.request_id] = fin
        sched.audit()
        steps += 1
        assert steps < 300
    np.testing.assert_array_equal(got["r1"].tokens, solo(model, params, p2, N))
    assert sched.preemptions == 1 and sched.restores == 1


def test_frontend_bills_park_retention():
    """Parked-retention economics surface through the serving frontend: a
    pressure-offloaded journal's blob accrues Table-4 S3 retention over
    simulated time, the restore GET is billed as an object read, and the
    prompt tokens it saved are itemized next to the bill."""
    from repro.core import SimCloud
    from repro.coord.serving_front import InferenceRequest, ServingFrontend

    cfg, model, params = tiny()
    cloud = SimCloud(seed=0)
    # pool sized so session t's fresh admission must offload s's journal
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=20, page_size=4,
                            kv_pages=5, park_sessions=True,
                            prefix_sharing=True)
    fe = ServingFrontend(cloud, scheduler=sched, batch_size=2)
    rng = np.random.default_rng(0)
    p1 = rng.integers(0, cfg.vocab, size=10).astype(np.int32)
    cloud.run_task(fe.submit(InferenceRequest("s", "q0", p1, 4)), name="c0")
    cloud.run()
    assert sched.parked_slots() == 1
    cloud.run_task(fe.submit(
        InferenceRequest("t", "q1",
                         rng.integers(0, cfg.vocab, 10).astype(np.int32), 4)),
        name="c1")
    cloud.run()
    assert sched.park_offloads == 1          # pool pressure pushed s's blob
    p2 = np.concatenate([p1, np.asarray(fe.results["s"][0], np.int32),
                         rng.integers(0, cfg.vocab, 2).astype(np.int32)])
    cloud.run_task(fe.submit(InferenceRequest("s", "q2", p2, 4)), name="c2")
    cloud.run()
    np.testing.assert_array_equal(
        fe.results["s"][1],
        np.asarray(generate(model, params, jnp.asarray(p2)[None], 4,
                            seq_len=20))[0])
    stats = fe.serving_stats()
    assert stats["park_hits"] == 1
    assert stats["shared_prefix_tokens"] == len(p1) + 4 - 1   # consumed span
    assert stats["park_storage_usd"] > 0.0   # blob bytes x sim-time retention
    assert cloud.op_counts.get("obj_read", 0) >= 1   # the restore GET billed
    assert cloud.op_counts.get("obj_write", 0) >= 1  # the offload PUT billed


def test_shared_pool_specs_survive_sharing():
    """Sharing never changes pool placement: pages have no slot axis, so
    the shared pool keeps its within-page lane dim on ``model`` (replicated
    over dp — the paged kernel's per-(page, head) block slices stay local)
    with the prefix index on."""
    from jax.sharding import AbstractMesh

    cfg, model, params = tiny("qwen3-14b")
    mesh = AbstractMesh((2, 2), ("data", "model"))
    sched = DecodeScheduler(model, params, n_slots=4, max_seq=32,
                            page_size=8, mesh=mesh, prefix_sharing=True,
                            park_sessions=True, offload=True)
    specs = sched.cache_specs
    assert specs is not None
    kp = specs["layers"]["kp"] if "layers" in specs else specs["kp"]
    assert kp[-2] == "model"
    assert all(e is None for e in kp[:-2] + kp[-1:])
    assert sched.stage_specs is not None
