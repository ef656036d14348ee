"""Randomized fleet differential harness (FaaSKeeper elasticity, pinned).

Seeded random event sequences — submits (fresh / multi-turn extension /
cross-session shared prefix), scale-up bursts, forced scale-downs, worker
crashes mid-decode / mid-park / mid-restore via ``FaultPlan``, wedged
workers reaped by heartbeat eviction — drive a :class:`FleetController` of
disposable ``DecodeScheduler`` workers over one shared blob store, and every
completed request is asserted **token-for-token equal** to the eviction-free
solo reference.  The fleet-wide ledger (per-worker allocator/refcount audit,
session exclusivity, blob ownership: every ``kv/`` spill exactly one owner,
every ``park/`` journal owned by its record and/or a not-yet-superseded
``park-meta``) is audited after every controller tick, and at quiescence the
store must hold nothing but committed journals and index blobs.

Tier-1 runs a fixed seed set (dense widest; moe and hybrid pin the
family-specific paths).  CI additionally runs a non-blocking chaos sweep
(``FLEET_CHAOS_SWEEP`` = base seed); any failing sequence's event log is
dumped to ``artifacts/diff_failures/`` so the exact trace rides the CI
artifact, exactly like ``test_sched_differential``.

The scale-to-zero round trip and its crash-during-drain fallback are pinned
as dedicated scenarios at the bottom.
"""

from __future__ import annotations

import json
import os
import zlib
from pathlib import Path
from typing import Optional

import jax
import numpy as np
import pytest

from repro import configs
from repro.coord import MembershipService
from repro.core import FaultPlan
from repro.core.storage import PageBlobStore
from repro.models import build_model
from repro.serve.fleet import PARK_META_PREFIX, FleetController
from repro.serve.scheduler import DecodeScheduler
from tests.conftest import make_service
from tests.test_sched_differential import SoloRef

MAX_SEQ = 32
PAGE_SIZE = 4
N_SLOTS = 2                       # per-worker decode slots (small: forces
MAX_WORKERS = 3                   # routing + autoscale under modest load)
PREFILL_CHUNK = 3
MAX_NEW = (2, 4)
FRESH_LEN = (5, 12)
EXTEND_LEN = (1, 4)
N_EVENTS = 22
CRASH_POINTS = ("mid-decode", "mid-restore", "mid-park")

# tier-1 seed matrix: dense widest, moe/hybrid pin family-specific KV paths
TIER1_SEEDS = ([("minicpm-2b", s) for s in range(4)]
               + [("moonshot-v1-16b-a3b", s) for s in range(2)]
               + [("recurrentgemma-2b", s) for s in range(2)])

FAILURE_DIR = Path("artifacts/diff_failures")

_ARCH_CACHE = {}


def _arch(name):
    """Build (or fetch) the shared-store worker pool + fleet + solo
    reference for ``name``.  The fleet is constructed once per arch (jit
    once) and ``reset()`` between sequences — the same recycle path a
    worker death takes."""
    if name not in _ARCH_CACHE:
        cfg = configs.get(name).reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        store = PageBlobStore()
        workers = [DecodeScheduler(model, params, n_slots=N_SLOTS,
                                   max_seq=MAX_SEQ, page_size=PAGE_SIZE,
                                   prefill_chunk=PREFILL_CHUNK, offload=True,
                                   prefix_sharing=True, park_sessions=True,
                                   blob_store=store, index_journal=True)
                   for _ in range(MAX_WORKERS)]
        fleet = FleetController(workers, min_workers=0, scale_to_zero=True,
                                drain_idle_steps=3)
        ref = SoloRef(model, params)
        _ARCH_CACHE[name] = (cfg, model, params, fleet, ref)
    return _ARCH_CACHE[name]


def _quiesce_ledger(fleet: FleetController) -> None:
    """At quiescence (no workers, no work) the shared store may hold only
    committed state: park journals pointed at by a ``park-meta`` record,
    the meta records themselves, and content-addressed index blobs —
    no preempt spills, no orphaned journals."""
    meta_blobs = {m["blob_key"] for m in fleet._iter_metas().values()}
    for key in fleet.blob_store.blobs:
        assert not key.startswith("kv/"), f"leaked preempt spill {key!r}"
        if key.startswith("park/"):
            assert key in meta_blobs, f"orphaned park journal {key!r}"


def _run_fleet_sequence(arch: str, seed: int,
                        log: Optional[list] = None) -> list:
    """One seeded fleet event sequence; appends every event to ``log`` (a
    caller-owned list survives an assertion failure) and raises on any
    parity or ledger violation."""
    cfg, _model, _params, fleet, ref = _arch(arch)
    tag = f"fleet-{arch}"
    rng = np.random.default_rng(zlib.crc32(tag.encode()) * 100003 + seed)

    # the fault plan is part of the seeded sequence: each (worker, point)
    # can fail-stop once, at a random occurrence of that hazard window
    crashes = {}
    for k in range(MAX_WORKERS):
        for point in CRASH_POINTS:
            if rng.random() < 0.25:
                crashes[(f"fleet:w{k}", point)] = int(rng.integers(0, 6))
    fleet.reset(faults=FaultPlan(crashes=crashes))
    cloud, svc = make_service(seed=seed)
    fleet.membership = MembershipService(svc)

    def sweep():
        # one scheduled-heartbeat run: evicts failed sessions' ephemerals,
        # which is how the controller learns a wedged worker is dead
        svc.start_heartbeat(period=1.0, max_runs=1)
        cloud.run()

    sessions = [f"s{i}" for i in range(int(rng.integers(3, 6)))]
    history = {s: None for s in sessions}
    inflight = {}
    shared_sys = rng.integers(0, cfg.vocab, size=2 * PAGE_SIZE).astype(np.int32)
    log = log if log is not None else []
    log.append({"arch": arch, "seed": seed, "sessions": len(sessions),
                "crashes": [[f, p, n] for (f, p), n in crashes.items()]})
    rid = 0

    def submit(sess):
        nonlocal rid
        h = history[sess]
        roll = rng.random()
        if h is not None and roll < 0.6 and len(h) + 8 <= MAX_SEQ:
            prompt = np.concatenate(
                [h, rng.integers(0, cfg.vocab,
                                 int(rng.integers(*EXTEND_LEN))).astype(np.int32)])
            kind = "extend"
        elif roll < 0.8:
            prompt = np.concatenate(
                [shared_sys, rng.integers(0, cfg.vocab,
                                          int(rng.integers(*FRESH_LEN))).astype(np.int32)])
            kind = "shared"
        else:
            prompt = rng.integers(0, cfg.vocab,
                                  int(rng.integers(*FRESH_LEN))).astype(np.int32)
            kind = "fresh"
        max_new = int(rng.integers(MAX_NEW[0], MAX_NEW[1] + 1))
        max_new = min(max_new, MAX_SEQ - len(prompt))
        if max_new < 1:
            history[sess] = None
            return
        name = f"r{rid}"
        rid += 1
        fleet.submit(sess, name, prompt, max_new)
        inflight[sess] = (name, prompt, max_new)
        log.append({"ev": "submit", "session": sess, "rid": name,
                    "kind": kind, "prompt": prompt.tolist(),
                    "max_new": max_new})

    def on_finished(fins):
        for fin in fins:
            name, prompt, max_new = inflight.pop(fin.session)
            assert fin.request_id == name, \
                "per-session FIFO violated across the fleet"
            expect = ref.run(prompt, max_new, session=fin.session)
            got = np.asarray(fin.tokens)
            log.append({"ev": "complete", "rid": name,
                        "tokens": got.tolist()})
            np.testing.assert_array_equal(
                got, expect,
                err_msg=f"{arch} seed {seed} {name}: fleet diverged from "
                        f"the eviction-free solo reference")
            history[fin.session] = np.concatenate(
                [prompt, got.astype(np.int32)])

    for _ev in range(N_EVENTS):
        for sess in sessions:
            if sess not in inflight and rng.random() < 0.35:
                submit(sess)
        if rng.random() < 0.08:
            w = fleet.scale_up()
            log.append({"ev": "scale-up",
                        "worker": w.worker_id if w else None})
        if rng.random() < 0.08:
            wid = fleet.scale_down()
            log.append({"ev": "scale-down", "worker": wid})
        if rng.random() < 0.06:
            live = [w.worker_id for w in fleet.workers.values()
                    if w.state != "wedged"]
            if live:
                wid = live[int(rng.integers(len(live)))]
                fleet.fail_worker(wid)
                log.append({"ev": "wedge", "worker": wid})
        if rng.random() < 0.25:
            sweep()
        on_finished(fleet.step())
        fleet.audit()
    guard = 0
    while fleet.busy():
        guard += 1
        assert guard < 500, "fleet failed to drain"
        sweep()                       # wedged workers come back only via
        on_finished(fleet.step())     # heartbeat eviction
        fleet.audit()
        log.append({"ev": "drain-step"})
    guard = 0
    while fleet.live_workers():       # idle cooldown down to zero workers
        guard += 1
        assert guard < 100, "fleet failed to scale to zero"
        sweep()
        fleet.step()
        fleet.audit()
    assert not inflight, f"requests lost: {inflight}"
    _quiesce_ledger(fleet)
    fleet.audit()
    return log


def _run_and_dump(arch: str, seed: int) -> None:
    log: list = []
    try:
        _run_fleet_sequence(arch, seed, log)
    except Exception as e:
        # the sequence is a pure function of (arch, seed): the artifact
        # carries the replay recipe + the event trace up to the failure
        FAILURE_DIR.mkdir(parents=True, exist_ok=True)
        path = FAILURE_DIR / f"seq_fleet_{arch}_{seed}.json"
        path.write_text(json.dumps(
            {"arch": arch, "seed": seed, "error": str(e)[:2000],
             "repro": f"_run_fleet_sequence({arch!r}, {seed})",
             "events": log},
            indent=2))
        raise


@pytest.mark.parametrize("arch,seed", TIER1_SEEDS,
                         ids=[f"{a}-{s}" for a, s in TIER1_SEEDS])
def test_fleet_differential(arch, seed):
    _run_and_dump(arch, seed)


SWEEP_BASE = os.environ.get("FLEET_CHAOS_SWEEP")


@pytest.mark.skipif(SWEEP_BASE is None,
                    reason="fleet chaos sweep runs in the non-blocking CI "
                           "job (set FLEET_CHAOS_SWEEP=<base seed>)")
@pytest.mark.parametrize("k", range(4))
def test_fleet_chaos_sweep(k):
    base = int(SWEEP_BASE) % 1_000_000
    for arch in ("minicpm-2b", "moonshot-v1-16b-a3b", "recurrentgemma-2b"):
        _run_and_dump(arch, 5000 + base + k)


# ---------------------------------------------------------------------------
# Scale-to-zero round trip (and its crash-during-drain fallback)
# ---------------------------------------------------------------------------


def _drive(fleet: FleetController, max_steps: int = 500) -> dict:
    fins = {}
    for _ in range(max_steps):
        for fin in fleet.step():
            fins[fin.request_id] = fin
        fleet.audit()
        if not fleet.busy():
            return fins
    raise AssertionError("fleet failed to drain")


def _to_zero(fleet: FleetController, max_steps: int = 60) -> None:
    for _ in range(max_steps):
        if not fleet.live_workers():
            return
        fleet.step()
        fleet.audit()
    raise AssertionError("fleet failed to scale to zero")


def test_scale_to_zero_round_trip():
    """Multi-turn session across a scale-to-zero gap: turn 1 completes, the
    fleet drains to zero (journal + prefix index externalized to blob), and
    turn 2 cold-starts a fresh worker that restores the parked journal,
    re-adopts the index, prefills only the new tokens — and produces output
    identical to the never-scaled solo reference."""
    cfg, _model, _params, fleet, ref = _arch("minicpm-2b")
    fleet.reset()
    fleet.membership = None
    rng = np.random.default_rng(7)
    p1 = rng.integers(0, cfg.vocab, 10).astype(np.int32)
    fleet.submit("sessA", "t1", p1, 3)
    t1 = np.asarray(_drive(fleet)["t1"].tokens)
    np.testing.assert_array_equal(t1, ref.run(p1, 3))

    _to_zero(fleet)
    assert fleet.live_workers() == 0
    assert PARK_META_PREFIX + "sessA" in fleet.blob_store.blobs, \
        "drain did not commit the parked journal to the directory"
    assert any(k.startswith("index/") for k in fleet.blob_store.blobs), \
        "prefix index was not journaled to blob"
    _quiesce_ledger(fleet)

    p2 = np.concatenate([p1, t1.astype(np.int32),
                         rng.integers(0, cfg.vocab, 2).astype(np.int32)])
    fleet.submit("sessA", "t2", p2, 3)
    fin2 = _drive(fleet)["t2"]
    assert fleet.cold_starts_from_zero == 2      # each turn woke the fleet
    assert fleet.meta_adoptions == 1, "cold start did not adopt the journal"
    assert fleet.fleet_stats()["index_adopted"] > 0, \
        "cold start did not rebuild the prefix index from blob"
    assert fin2.reused_tokens >= len(p1), \
        "cold start re-prefilled tokens the journal already covered"
    np.testing.assert_array_equal(np.asarray(fin2.tokens), ref.run(p2, 3))
    # adoption consumed the directory entry once the session completed
    assert PARK_META_PREFIX + "sessA" not in fleet.blob_store.blobs


def test_scale_to_zero_crash_during_drain():
    """The commit-point claim: a crash *between* the journal's KV blob PUT
    and the park-meta PUT leaves no directory entry, the orphaned KV blob is
    GC'd, and the session's next turn falls back to a full re-prefill —
    token-identical output, zero reused tokens (correct, just slower)."""
    cfg, model, params, _fleet_, ref = _arch("minicpm-2b")
    store = PageBlobStore()
    # no prefix sharing: the fallback must not be rescued by the index
    w = DecodeScheduler(model, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                        page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK,
                        park_sessions=True, blob_store=store)
    fleet = FleetController(
        [w], min_workers=0, scale_to_zero=True, drain_idle_steps=2,
        faults=FaultPlan(crashes={("fleet:w0", "mid-park"): 0}))
    rng = np.random.default_rng(11)
    p1 = rng.integers(0, cfg.vocab, 9).astype(np.int32)
    fleet.submit("sessA", "t1", p1, 3)
    t1 = np.asarray(_drive(fleet)["t1"].tokens)
    np.testing.assert_array_equal(t1, ref.run(p1, 3))

    _to_zero(fleet)                   # drain crashes mid-park
    assert fleet.crashes == 1
    assert PARK_META_PREFIX + "sessA" not in store.blobs, \
        "interrupted drain must not leave a committed directory entry"
    assert not any(k.startswith("park/") for k in store.blobs), \
        "orphaned journal KV blob survived the kill-path GC"

    p2 = np.concatenate([p1, t1.astype(np.int32),
                         rng.integers(0, cfg.vocab, 2).astype(np.int32)])
    fleet.submit("sessA", "t2", p2, 3)
    fin2 = _drive(fleet)["t2"]
    assert fin2.reused_tokens == 0, \
        "nothing durable survived — the fallback is a full re-prefill"
    assert fleet.meta_adoptions == 0 and fleet.meta_dropped == 0
    np.testing.assert_array_equal(np.asarray(fin2.tokens), ref.run(p2, 3))
