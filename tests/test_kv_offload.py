"""Storage-backed KV page offload + the explicit slot lifecycle.

The headline invariant: a preempted-then-restored slot produces
token-for-token identical output to a never-preempted run — across
dense/moe/hybrid — because ``gather_pages``/``scatter_pages`` are exact
inverses through the page table and a PREEMPTED slot's rows are frozen
under the decode mask.  Plus: the pressure/idleness preemption policy,
restore funding (no deadlock / thrash), lifecycle transition legality,
crash-reset blob hygiene, offload billing through the serving frontend,
staging-buffer sharding specs, and the startup pool-sizing validation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.storage import PageBlobStore
from repro.models import build_model, kvcache
from repro.serve.engine import generate
from repro.serve.lifecycle import IllegalTransition, Slot, SlotState
from repro.serve.scheduler import DecodeScheduler

PARITY_ARCHS = ["minicpm-2b", "moonshot-v1-16b-a3b", "recurrentgemma-2b"]


def tiny(arch="minicpm-2b"):
    cfg = configs.get(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def drain(sched, got=None, hooks=None, limit=500):
    """Run a scheduler dry; ``hooks`` maps an iteration index to a callback
    (e.g. a forced preemption or a late submit)."""
    got = got if got is not None else {}
    hooks = hooks or {}
    it = 0
    while sched.busy():
        if it in hooks:
            hooks[it](sched)
        for fin in sched.step():
            got[int(fin.request_id[1:])] = fin
        it += 1
        assert it < limit, "scheduler failed to drain"
    return got


# ---------------------------------------------------------------------------
# Lifecycle state machine
# ---------------------------------------------------------------------------


def test_lifecycle_transitions_validated():
    s = Slot(index=0)
    s.to(SlotState.ADMITTING).to(SlotState.ACTIVE).to(SlotState.PREEMPTED)
    with pytest.raises(IllegalTransition):
        s.to(SlotState.ACTIVE)          # preempted must go through RESTORING
    s.to(SlotState.RESTORING)
    with pytest.raises(IllegalTransition):
        s.to(SlotState.PREEMPTED)       # a funded restore runs to completion
    s.to(SlotState.ACTIVE).to(SlotState.DRAINED).to(SlotState.EMPTY)
    with pytest.raises(IllegalTransition):
        Slot(index=1).to(SlotState.ACTIVE)   # EMPTY cannot skip ADMITTING
    # crash recovery is the one escape hatch
    s2 = Slot(index=2)
    s2.to(SlotState.ADMITTING)
    s2.force_empty()
    assert s2.state is SlotState.EMPTY and s2.req is None


def test_scheduler_slots_expose_states():
    cfg, model, params = tiny()
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=16,
                            page_size=4, prefill_chunk=3)
    assert all(s.empty for s in sched.slots)
    sched.submit("s", "r0", np.zeros(7, np.int32), 3)
    assert sched.slots[0].state is SlotState.ADMITTING
    sched.step()                         # chunk 1/3
    assert sched.admitting_slots() == 1 and sched.active_slots() == 0
    drain(sched)
    assert all(s.empty for s in sched.slots)


# ---------------------------------------------------------------------------
# gather/scatter exact-inverse property (scrambled page tables)
# ---------------------------------------------------------------------------


def _round_trip(n_pages, ps, H, D, table_rows, seed):
    """extract(inject(pages)) == pages: pages extracted through one
    (scrambled) page table, injected into a cold pool through another,
    and re-extracted must be bit-identical — layer-stacked pool included."""
    rng = np.random.default_rng(seed)
    L = 2
    pool = {
        "kp": jnp.asarray(rng.standard_normal((L, n_pages, ps, H * D)),
                          jnp.float32),
        "vp": jnp.asarray(rng.standard_normal((L, n_pages, ps, H * D)),
                          jnp.float32),
        "page_table": jnp.asarray(table_rows, jnp.int32)[None].repeat(L, 0),
        "length": jnp.zeros((len(table_rows),), jnp.int32),
    }
    for row in table_rows:
        ids = [p for p in row if p >= 0]      # logical order through the table
        if not ids:
            continue
        blob = kvcache.gather_pages(pool, ids)
        assert set(blob) == {"kp", "vp"}
        assert blob["kp"].shape == (L, len(ids), ps, H * D)
        # inject into a cold pool at *different* physical pages (restore
        # never gets the same pages back) and extract again
        new_ids = [(p + 1) % n_pages for p in ids]
        cold = {
            "kp": jnp.zeros_like(pool["kp"]),
            "vp": jnp.zeros_like(pool["vp"]),
            "page_table": pool["page_table"],
            "length": pool["length"],
        }
        back = kvcache.gather_pages(
            kvcache.scatter_pages(cold, new_ids, blob), new_ids)
        for k in ("kp", "vp"):
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(blob[k]))
        # nbytes metering matches the staged payload
        assert kvcache.blob_nbytes(blob) == sum(
            np.asarray(blob[k]).nbytes for k in ("kp", "vp"))


def test_gather_scatter_round_trip_scrambled():
    _round_trip(9, 4, 2, 3, [[5, 2, 7, -1], [1, 6, -1, -1], [-1, -1, -1, -1]],
                seed=0)


def test_scatter_leaves_other_pages_untouched():
    rng = np.random.default_rng(1)
    pool = {"kp": jnp.asarray(rng.standard_normal((4, 2, 4)), jnp.float32),
            "vp": jnp.asarray(rng.standard_normal((4, 2, 4)), jnp.float32),
            "page_table": jnp.zeros((1, 2), jnp.int32)}
    blob = kvcache.gather_pages(pool, [3])
    out = kvcache.scatter_pages(pool, [0], blob)
    np.testing.assert_array_equal(np.asarray(out["kp"][1:]),
                                  np.asarray(pool["kp"][1:]))
    np.testing.assert_array_equal(np.asarray(out["kp"][0]),
                                  np.asarray(pool["kp"][3]))
    # slicing a blob is slicing its page axis
    piece = kvcache.slice_page_blob(blob, 0, 1)
    assert piece["kp"].shape == (1, 2, 4)


try:  # optional dep, guarded like test_kernel_properties (skip, not error)
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=30, deadline=None)
    @given(st.integers(0, 10_000), st.integers(1, 3), st.integers(1, 4))
    def test_gather_scatter_round_trip_property(seed, rows, max_pages):
        rng = np.random.default_rng(seed)
        n_pages = rows * max_pages + 3
        table = np.full((rows, max_pages), -1, np.int64)
        perm = rng.permutation(n_pages)
        k = 0
        for r in range(rows):               # scrambled, partially-filled rows
            fill = int(rng.integers(0, max_pages + 1))
            table[r, :fill] = perm[k:k + fill]
            k += fill
        _round_trip(n_pages, int(rng.integers(1, 5)), 2, 3, table.tolist(),
                    seed=seed + 1)

except ImportError:

    @pytest.mark.skip(reason="optional dep: property sweeps need hypothesis")
    def test_gather_scatter_round_trip_property():
        pass


# ---------------------------------------------------------------------------
# Preempt-mid-decode -> restore -> finish: token-for-token parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_preempt_restore_parity(arch):
    """Force a preemption mid-decode while a second slot keeps the batch
    (and the shared pool) evolving, let the restore interleave chunk by
    chunk, and require the preempted request's tokens to equal the
    eviction-free solo reference exactly."""
    cfg, model, params = tiny(arch)
    P, N = 12, 8
    rng = np.random.default_rng(7)
    pa = rng.integers(0, cfg.vocab, size=P).astype(np.int32)
    pb = rng.integers(0, cfg.vocab, size=P).astype(np.int32)
    refs = [np.asarray(generate(model, params, jnp.asarray(p)[None], N,
                                seq_len=P + N))[0] for p in (pa, pb)]

    sched = DecodeScheduler(model, params, n_slots=2, max_seq=P + N,
                            page_size=4, prefill_chunk=5, offload=True)
    sched.submit("a", "r0", pa, N)
    sched.submit("b", "r1", pb, N)

    def force(s):
        s.preempt(0)
        assert s.slots[0].state is SlotState.PREEMPTED
        assert not s.slots[0].pages and s.blob_store.puts == 1

    got = drain(sched, hooks={6: force})
    for i in range(2):
        np.testing.assert_array_equal(
            got[i].tokens, refs[i],
            err_msg=f"{arch} r{i}: preempt/restore diverged from solo")
    assert got[0].preempts == 1 and got[1].preempts == 0
    assert sched.restores == 1 and sched.restored_pages == sched.offload_pages
    a = sched.allocator
    assert a.in_use == 0 and a.free_count == a.n_pages
    assert sched.blob_store.bytes_stored == 0     # restored blob deleted


def test_pressure_preemption_admits_starved_request():
    """A pool-gated arrival triggers the policy: the longest-resident ACTIVE
    slot is evicted to storage, the newcomer admits immediately instead of
    stalling, and the victim restores when pressure clears — both exact."""
    cfg, model, params = tiny()
    P, N = 8, 12
    need = -(-(P + N - 1) // 4)                   # 5 pages each
    rng = np.random.default_rng(9)
    pa = rng.integers(0, cfg.vocab, size=P).astype(np.int32)
    pb = rng.integers(0, cfg.vocab, size=P).astype(np.int32)
    refs = [np.asarray(generate(model, params, jnp.asarray(p)[None], N,
                                seq_len=P + N))[0] for p in (pa, pb)]

    sched = DecodeScheduler(model, params, n_slots=2, max_seq=P + N,
                            page_size=4, kv_pages=need + 1, offload=True)
    sched.submit("a", "r0", pa, N)

    def arrive(s):
        assert s.slots[0].state is SlotState.ACTIVE
        s.submit("b", "r1", pb, N)                # pool-gated: 1 page free
        assert s.preemptions == 1, "pressure did not preempt"
        assert s.slots[0].state is SlotState.PREEMPTED
        assert s.slots[1].state is SlotState.ADMITTING, \
            "starved request should admit right after the eviction"

    got = drain(sched, hooks={3: arrive})
    for i in range(2):
        np.testing.assert_array_equal(got[i].tokens, refs[i],
                                      err_msg=f"r{i} corrupted by preemption")
    assert got[0].admitted_step < got[1].admitted_step
    assert got[0].finished_step > got[1].finished_step  # victim finished last
    assert sched.restores == 1


def test_idle_floor_blocks_preemption():
    """`idle_preempt_steps` is the anti-thrash floor: a slot younger than it
    is not preemptible, so the arrival holds in pending instead."""
    cfg, model, params = tiny()
    P, N = 8, 12
    need = -(-(P + N - 1) // 4)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=P + N,
                            page_size=4, kv_pages=need + 1, offload=True,
                            idle_preempt_steps=1000)
    sched.submit("a", "r0", np.zeros(P, np.int32), N)
    for _ in range(3):
        sched.step()
    sched.submit("b", "r1", np.zeros(P, np.int32), N)
    assert sched.preemptions == 0
    assert [r.request_id for r in sched.pending] == ["r1"]
    got = drain(sched)
    assert sorted(got) == [0, 1]                  # completion-time frees admit it
    assert sched.preemptions == 0


def test_restore_waits_for_pressure_to_clear():
    """A preempted slot must not steal its pages back while the request it
    was evicted for is still decoding (preempt<->restore thrash)."""
    cfg, model, params = tiny()
    P, N = 8, 12
    need = -(-(P + N - 1) // 4)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=P + N,
                            page_size=4, kv_pages=need + 1, offload=True)
    sched.submit("a", "r0", np.zeros(P, np.int32), N)
    sched.step(); sched.step()
    sched.submit("b", "r1", np.zeros(P, np.int32), N)   # evicts r0
    assert sched.slots[0].state is SlotState.PREEMPTED
    for _ in range(4):
        sched.step()
        assert sched.slots[0].state is SlotState.PREEMPTED, \
            "restore funded while the pool is still under pressure"
    drain(sched)
    assert sched.restores == 1 and sched.completed == 2


def test_reset_with_preempted_slot_replays_cleanly():
    """Crash recovery with a blob in flight: reset() clears the store and
    the preempted slot; redelivery replays from the prompt and still
    matches the solo reference."""
    cfg, model, params = tiny("recurrentgemma-2b")
    P, N = 12, 6
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, cfg.vocab, size=P).astype(np.int32)
    ref = np.asarray(generate(model, params, jnp.asarray(prompt)[None], N,
                              seq_len=P + N))[0]
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=P + N,
                            page_size=4, offload=True)
    sched.submit("s", "r0", prompt, N)
    sched.step(); sched.step()
    sched.preempt(0)
    assert sched.blob_store.bytes_stored > 0
    sched.reset()
    assert sched.blob_store.bytes_stored == 0 and not sched.blob_store.blobs
    a = sched.allocator
    assert a.in_use == 0 and a.free_count == a.n_pages
    assert all(s.empty for s in sched.slots)
    sched.submit("s", "r0", prompt, N)            # queue redelivery
    got = drain(sched)
    np.testing.assert_array_equal(got[0].tokens, ref)


def test_offload_requires_paged_pool():
    cfg, model, params = tiny()
    with pytest.raises(ValueError, match="paged"):
        DecodeScheduler(model, params, n_slots=2, max_seq=16,
                        kv_mode="ring", offload=True)
    with pytest.raises(ValueError, match="preempt_policy"):
        DecodeScheduler(model, params, n_slots=2, max_seq=16,
                        preempt_policy="lru")


# ---------------------------------------------------------------------------
# Frontend: billing + gauges through the serving stack
# ---------------------------------------------------------------------------


def test_frontend_bills_offload_storage_ops():
    from repro.core import SimCloud
    from repro.launch.serve import build_frontend, spawn_workload

    cfg, model, params = tiny()
    P, N = 8, 8
    need = -(-(P + N - 1) // 4)
    cloud = SimCloud(seed=0)
    fe = build_frontend(cloud, cfg, model, params, mode="continuous",
                        batch_size=2, max_new=N, prompt_len=P,
                        page_size=4, kv_pages=need + 1, offload=True)
    spawn_workload(cloud, fe, vocab=cfg.vocab, n_requests=4, sessions=4,
                   prompt_len=P, max_new=N)
    cloud.run()
    assert sum(len(v) for v in fe.completions.values()) == 4
    stats = fe.serving_stats()
    assert stats["preemptions"] >= 1 and stats["restores"] >= 1
    assert stats["offload_bytes"] > 0 and stats["restore_bytes"] > 0
    # every put/get journaled by the store was billed by the frontend
    assert stats["offload_storage_ops"] == (stats["offload_puts"]
                                            + stats["offload_gets"]
                                            + fe.scheduler.blob_store.deletes)
    from repro.core.cost import page_blob_cost
    assert stats["offload_storage_usd"] == pytest.approx(
        page_blob_cost(stats["offload_puts"], stats["offload_gets"]))
    assert cloud.op_counts.get("obj_write", 0) >= stats["offload_puts"]
    assert cloud.op_counts.get("obj_read", 0) >= stats["offload_gets"]


def test_blob_store_metering():
    bs = PageBlobStore()
    bs.put("a", {"x": 1}, 2048)
    bs.put("b", {"x": 2}, 1024)
    assert bs.bytes_stored == 3072 and bs.high_water_bytes == 3072
    assert bs.get("a") == {"x": 1} and bs.bytes_in == 2048
    bs.delete("a")
    assert bs.bytes_stored == 1024 and bs.high_water_bytes == 3072
    ops = bs.drain_ops()
    assert [o[0] for o in ops] == ["put", "put", "get", "delete"]
    assert bs.drain_ops() == []
    with pytest.raises(KeyError):
        bs.get("a")
    bs.clear()
    assert bs.bytes_stored == 0 and not bs.blobs


# ---------------------------------------------------------------------------
# Staging-buffer sharding + startup sizing validation
# ---------------------------------------------------------------------------


def test_offload_stage_shardings_resolve():
    from jax.sharding import AbstractMesh

    from repro.dist.sharding import offload_stage_shardings

    cfg, model, params = tiny("qwen3-14b")
    mesh = AbstractMesh((16, 16), ("data", "model"))
    sched = DecodeScheduler(model, params, n_slots=16, max_seq=32,
                            page_size=16, mesh=mesh, offload=True)
    specs = sched.stage_specs
    # the chunk mirrors the pool's lane-first rule: page_size=16 divides
    # model=16, so the within-page lane dim rides the model axis and nothing
    # else does (page dim replicated even though it would divide)
    assert specs is not None and "kp" in specs
    assert specs["kp"][-2] == "model"
    assert all(e is None for i, e in enumerate(specs["kp"]) if i != len(specs["kp"]) - 2)
    # when the lane doesn't divide, heads x head_dim are the fallback —
    # exactly the pool's own fallback order, so scatter/gather stay
    # shard-local
    mesh2 = AbstractMesh((2, 2), ("data", "model"))
    stage2 = {"kp": jax.ShapeDtypeStruct((3, 5, 32), jnp.bfloat16)}
    specs2 = jax.tree_util.tree_map(
        lambda s: s.spec, offload_stage_shardings(stage2, mesh2))
    assert specs2["kp"][-1] == "model"
    assert all(e is None for i, e in enumerate(specs2["kp"]) if i != 2)
    # neither divides -> fully replicated (never the page dim)
    stage3 = {"kp": jax.ShapeDtypeStruct((4, 5, 3), jnp.bfloat16)}
    specs3 = jax.tree_util.tree_map(
        lambda s: s.spec, offload_stage_shardings(stage3, mesh2))
    assert all(e is None for e in specs3["kp"])


def test_pool_sizing_validated_at_startup():
    from repro.launch.serve import validate_pool_sizing

    # one 16+8-token admission = 6 pages of 4, plus 3 more decoding slots
    assert validate_pool_sizing(batch_size=4, prompt_len=16, max_new=8,
                                page_size=4, kv_pages=9) == 9
    with pytest.raises(ValueError, match="max-size admission"):
        validate_pool_sizing(batch_size=4, prompt_len=16, max_new=8,
                             page_size=4, kv_pages=8)
    # offload relaxes the floor to one admission (preemption absorbs the
    # rest) but the largest single request must still fit the pool
    assert validate_pool_sizing(batch_size=4, prompt_len=16, max_new=8,
                                page_size=4, kv_pages=6, offload=True) == 6
    with pytest.raises(ValueError, match="even one max-size admission"):
        validate_pool_sizing(batch_size=4, prompt_len=16, max_new=8,
                             page_size=4, kv_pages=5, offload=True)
    with pytest.raises(ValueError, match="--page-size"):
        validate_pool_sizing(batch_size=2, prompt_len=8, max_new=4,
                             page_size=0)
    with pytest.raises(ValueError, match="--prefill-chunk"):
        validate_pool_sizing(batch_size=2, prompt_len=8, max_new=4,
                             page_size=4, prefill_chunk=0)

    from repro.launch.serve import run_serving
    with pytest.raises(ValueError, match="max-size admission"):
        run_serving("minicpm-2b-smoke", n_requests=1, max_new=8, prompt_len=16,
                    batch_size=4, page_size=4, kv_pages=8, quiet=True)
