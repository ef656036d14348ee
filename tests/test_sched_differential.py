"""Randomized scheduler differential harness.

Seeded random event sequences — admit (fresh / multi-turn extension /
cross-session shared prefix), force-preempt, park, unpark, restore, TTL
expiry — drive the full ``DecodeScheduler`` stack (paged pool + chunked
prefill + offload + refcounted prefix sharing + session parking) across
3–5 sessions, and every completed request is asserted **token-for-token
equal** to the eviction-free solo reference, with the allocator / refcount /
reservation invariants audited after every step (``DecodeScheduler.audit``).

Tier-1 runs a fixed seed set (dense gets the widest sweep; moe and hybrid
pin the family-specific paths).  CI additionally runs a non-blocking
randomized sweep (``SCHED_DIFF_SWEEP`` = base seed); any failing sequence's
event log is dumped to ``artifacts/diff_failures/`` so the exact trace rides
the CI artifact.

A hypothesis property (import-guarded like the kernel properties) pins the
alloc/share/CoW/release round trip on the allocator alone.
"""

from __future__ import annotations

import json
import os
import zlib
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model, kvcache
from repro.serve.engine import make_decode_step, make_prefill
from repro.serve.lifecycle import SlotState
from repro.serve.scheduler import DecodeScheduler

MAX_SEQ = 32
PAGE_SIZE = 4
N_SLOTS = 3
PREFILL_CHUNK = 3
MAX_NEW = (2, 4)                  # per-request decode budget range
FRESH_LEN = (5, 12)               # fresh prompt length range
EXTEND_LEN = (1, 4)               # extra user tokens per multi-turn turn
N_EVENTS = 28

# tier-1 seed matrix: >= 25 sequences total, dense widest
TIER1_SEEDS = ([("minicpm-2b", s) for s in range(15)]
               + [("moonshot-v1-16b-a3b", s) for s in range(5)]
               + [("recurrentgemma-2b", s) for s in range(5)])

# tier-1 speculative matrix: >= 25 sequences, all archetypes, same event
# soup (forced preempts, parking, prefix sharing) with draft-and-verify on.
# ``spec`` = (draft arch, draft init seed, k): draft seed 0 is the target's
# own params (self-draft, high acceptance — exercises the accept fast path);
# a different seed or arch is a disagreeing draft (low acceptance — hammers
# the rejection / length-rewind / hybrid-rollback path every round).
TIER1_SPEC_SEEDS = (
    [("minicpm-2b", ("minicpm-2b", 0, 3), s) for s in range(8)]
    + [("minicpm-2b", ("minicpm-2b", 7, 2), s) for s in range(4)]
    + [("moonshot-v1-16b-a3b", ("moonshot-v1-16b-a3b", 0, 3), s) for s in range(4)]
    + [("moonshot-v1-16b-a3b", ("minicpm-2b", 7, 2), s) for s in range(2)]
    + [("recurrentgemma-2b", ("minicpm-2b", 0, 3), s) for s in range(7)])

FAILURE_DIR = Path("artifacts/diff_failures")

_ARCH_CACHE = {}


class SoloRef:
    """Eviction-free solo greedy reference with jit reuse across prompts:
    one decode step (fixed MAX_SEQ cache shape) and one prefill per distinct
    prompt length, so 25 sequences don't recompile per request.

    ``mesh`` builds a *mesh-matched* reference: params placed through the
    storage registry and the steps policy-bound, so the reference's
    model-axis partitioning (and therefore its bf16 reduction order) is the
    same as the sharded scheduler's.  The sharded moe/hybrid differential
    rows need this — see the sharded section's comment.
    """

    def __init__(self, model, params, mesh=None):
        self.model, self.params = model, params
        self._policy = None
        if mesh is not None:
            from repro.dist import sharding as shd

            msize = shd.MeshRules.for_mesh(mesh).model_size(mesh)
            n_kv = getattr(model.cfg, "n_kv_heads", 0) or model.cfg.n_heads
            self._policy = shd.ShardingPolicy.default(
                mesh, batch_shardable=False,
                attn_mode="head" if n_kv % msize == 0 else "seq",
                decode_stationary=True)
            self.params = jax.device_put(
                params, shd.param_shardings(params, mesh))
        self._decode = jax.jit(make_decode_step(model, policy=self._policy))
        self._prefills = {}
        self._memo = {}

    def run(self, prompt, max_new: int, session: str = "ref") -> np.ndarray:
        # stateless across requests — the session tag only matters for the
        # session-mirroring SchedRef
        key = (np.asarray(prompt, np.int32).tobytes(), max_new)
        if key in self._memo:
            return self._memo[key]
        P = len(prompt)
        pre = self._prefills.get(P)
        if pre is None:
            pre = self._prefills[P] = jax.jit(
                make_prefill(self.model, seq_len=MAX_SEQ,
                             policy=self._policy))
        tok, cache = pre(self.params, jnp.asarray(prompt, jnp.int32)[None])
        out = [int(tok[0])]
        for _ in range(max_new - 1):
            tok, _, cache = self._decode(self.params, cache, tok[:, None])
            out.append(int(tok[0]))
        self._memo[key] = np.asarray(out, np.int32)
        return self._memo[key]


class SchedRef:
    """Eviction-free reference run through a *second scheduler* on the same
    mesh: same jitted step set, same batch/pool shapes and shardings — one
    request at a time, ample pool, no offload/forced preempts/sharing/spec.
    What it isolates is exactly the differential claim: the event soup's
    machinery (preemption, restore, forced parking, CoW, chunked admission
    interleaving, batched draft catch-up, verify rounds) must be
    token-invisible relative to an unstressed run of the *same* sharded
    step set.

    Sessions are mirrored (``park_sessions=True``, no TTL): a multi-turn
    extend in the stressed run reuses its history's decode-written KV, and
    on the mesh decode-written KV is *not* bitwise equal to chunk-prefilled
    KV (the projection gemm's bf16 reduction order depends on dispatch
    shape), so the reference must take the same parked-extend path to
    byte-compare like against like."""

    def __init__(self, model, params, *, mesh, n_slots, attn_backend):
        self._sched = DecodeScheduler(
            model, params, n_slots=n_slots, max_seq=MAX_SEQ,
            page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK,
            park_sessions=True, mesh=mesh, attn_backend=attn_backend)
        self._rid = 0

    def reset(self):
        self._sched.reset()

    def run(self, prompt, max_new: int, session: str = "ref") -> np.ndarray:
        s = self._sched
        self._rid += 1
        s.submit(session, f"ref{self._rid}", np.asarray(prompt, np.int32),
                 max_new)
        for _ in range(10_000):
            fins = s.step()
            if fins:
                return np.asarray(fins[0].tokens)
        raise AssertionError("reference scheduler failed to complete")


def _arch(name, spec=None, sched_kw=None, cache_key=None, ref_mesh=None,
          ref_kind="solo"):
    """Build (or fetch) the scheduler + solo reference for ``name``.

    ``spec=(draft_arch, draft_seed, k)`` turns on draft-and-verify
    speculative decoding; ``draft_seed == 0`` with ``draft_arch == name``
    reuses the target's own params (self-draft).  The solo reference is
    always non-speculative — that IS the parity claim.  ``sched_kw``
    overrides scheduler constructor kwargs (the sharded subset passes
    ``mesh=``/``n_slots=``); ``cache_key`` keys the memo for such variants;
    ``ref_mesh`` builds the solo reference mesh-matched instead of
    single-device; ``ref_kind="sched"`` swaps the solo reference for a
    :class:`SchedRef` (an unstressed second scheduler on the same mesh).
    """
    key = (name, spec, cache_key)
    if key not in _ARCH_CACHE:
        cfg = configs.get(name).reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        kw = {}
        if spec is not None:
            draft_arch, draft_seed, k = spec
            if draft_arch == name and draft_seed == 0:
                draft_model, draft_params = model, params
            else:
                draft_model = build_model(configs.get(draft_arch).reduced())
                draft_params = draft_model.init(jax.random.key(draft_seed))
            kw = dict(draft_model=draft_model, draft_params=draft_params,
                      spec_k=k)
        kw.update(sched_kw or {})
        kw.setdefault("n_slots", N_SLOTS)
        sched = DecodeScheduler(model, params,
                                max_seq=MAX_SEQ, page_size=PAGE_SIZE,
                                prefill_chunk=PREFILL_CHUNK, offload=True,
                                prefix_sharing=True, park_sessions=True, **kw)
        if ref_kind == "sched":
            skw = sched_kw or {}
            ref = SchedRef(model, params, mesh=skw["mesh"],
                           n_slots=skw.get("n_slots", N_SLOTS),
                           attn_backend=skw.get("attn_backend", "gather"))
        else:
            ref = SoloRef(model, params, mesh=ref_mesh)
        _ARCH_CACHE[key] = (cfg, sched, ref)
    return _ARCH_CACHE[key]


def _run_sequence(arch: str, seed: int, log: Optional[list] = None,
                  spec=None, sched_kw=None, cache_key=None,
                  ref_mesh=None, ref_kind="solo") -> list:
    """One seeded event sequence; appends every event to ``log`` (so a
    caller-owned list survives an assertion failure) and raises on any
    parity or invariant violation."""
    cfg, sched, ref = _arch(arch, spec, sched_kw, cache_key, ref_mesh,
                            ref_kind)
    sched.reset()
    if hasattr(ref, "reset"):
        ref.reset()               # SchedRef carries per-session KV state
    # zlib.crc32, not hash(): str hashing is salted per process, and a
    # failing (arch, seed) must replay bit-identically from the artifact
    tag = arch if spec is None else f"{arch}+{spec[0]}:{spec[1]}:{spec[2]}"
    rng = np.random.default_rng(zlib.crc32(tag.encode()) * 100003 + seed)
    sched.park_ttl_steps = int(rng.choice([0, 0, 18]))
    sessions = [f"s{i}" for i in range(int(rng.integers(3, 6)))]
    history = {s: None for s in sessions}     # completed conversation so far
    inflight = {}                             # session -> (rid, prompt, max_new)
    shared_sys = rng.integers(0, cfg.vocab, size=2 * PAGE_SIZE).astype(np.int32)
    log = log if log is not None else []
    log.append({"arch": arch, "seed": seed, "ttl": sched.park_ttl_steps,
                "sessions": len(sessions), "spec": spec})
    rid = 0

    def submit(sess):
        nonlocal rid
        h = history[sess]
        roll = rng.random()
        if h is not None and roll < 0.6 and len(h) + 8 <= MAX_SEQ:
            # multi-turn: extend this session's parked conversation
            prompt = np.concatenate(
                [h, rng.integers(0, cfg.vocab,
                                 int(rng.integers(*EXTEND_LEN))).astype(np.int32)])
            kind = "extend"
        elif roll < 0.8:
            # shared system prompt across sessions (prefix-index food)
            prompt = np.concatenate(
                [shared_sys, rng.integers(0, cfg.vocab,
                                          int(rng.integers(*FRESH_LEN))).astype(np.int32)])
            kind = "shared"
        else:
            prompt = rng.integers(0, cfg.vocab,
                                  int(rng.integers(*FRESH_LEN))).astype(np.int32)
            kind = "fresh"
        max_new = int(rng.integers(MAX_NEW[0], MAX_NEW[1] + 1))
        max_new = min(max_new, MAX_SEQ - len(prompt))   # full-ring room
        if max_new < 1:
            history[sess] = None              # conversation too long: restart
            return
        name = f"r{rid}"
        rid += 1
        sched.submit(sess, name, prompt, max_new)
        inflight[sess] = (name, prompt, max_new)
        log.append({"ev": "submit", "session": sess, "rid": name,
                    "kind": kind, "prompt": prompt.tolist(),
                    "max_new": max_new})

    def on_finished(fins):
        for fin in fins:
            name, prompt, max_new = inflight.pop(fin.session)
            assert fin.request_id == name, "per-session FIFO violated"
            expect = ref.run(prompt, max_new, session=fin.session)
            got = np.asarray(fin.tokens)
            log.append({"ev": "complete", "rid": name,
                        "tokens": got.tolist()})
            np.testing.assert_array_equal(
                got, expect,
                err_msg=f"{arch} seed {seed} {name}: scheduler diverged "
                        f"from the eviction-free solo reference")
            history[fin.session] = np.concatenate(
                [prompt, got.astype(np.int32)])

    for _ev in range(N_EVENTS):
        for sess in sessions:
            if sess not in inflight and rng.random() < 0.35:
                submit(sess)
        if rng.random() < 0.12:
            victims = [s for s in sched.slots
                       if s.state is SlotState.ACTIVE and s.pages]
            if victims:
                v = victims[int(rng.integers(len(victims)))]
                log.append({"ev": "preempt", "slot": v.index})
                sched.preempt(v.index)
        fins = sched.step()
        sched.audit()
        on_finished(fins)
    while sched.busy():
        on_finished(sched.step())
        sched.audit()
        log.append({"ev": "drain-step"})
        assert len(log) < 4000, "failed to drain"
    # quiescent state: only parked journals and the index may hold pages
    a = sched.allocator
    held = (sum(len(r.pages) for r in sched._parked.values())
            + len(sched.prefix_index))
    assert a.total_refs == held, f"leaked references: {a.total_refs} != {held}"
    return log


def _run_and_dump(arch: str, seed: int, spec=None, sched_kw=None,
                  cache_key=None, ref_mesh=None, ref_kind="solo") -> None:
    log: list = []
    try:
        _run_sequence(arch, seed, log, spec=spec, sched_kw=sched_kw,
                      cache_key=cache_key, ref_mesh=ref_mesh,
                      ref_kind=ref_kind)
    except Exception as e:
        # the sequence is a pure function of (arch, seed, spec): the artifact
        # carries both the replay recipe and the event trace up to the
        # failure, and CI uploads the directory on failure
        FAILURE_DIR.mkdir(parents=True, exist_ok=True)
        tag = "" if spec is None else f"_spec_{spec[0]}_{spec[1]}_{spec[2]}"
        if cache_key is not None:
            tag += "_" + "_".join(str(p) for p in cache_key)
        path = FAILURE_DIR / f"seq_{arch}{tag}_{seed}.json"
        path.write_text(json.dumps(
            {"arch": arch, "seed": seed, "spec": spec,
             "error": str(e)[:2000],
             "repro": f"_run_sequence({arch!r}, {seed}, spec={spec!r})",
             "events": log},
            indent=2))
        raise


@pytest.mark.parametrize("arch,seed", TIER1_SEEDS,
                         ids=[f"{a}-{s}" for a, s in TIER1_SEEDS])
def test_sched_differential(arch, seed):
    _run_and_dump(arch, seed)


@pytest.mark.parametrize(
    "arch,spec,seed", TIER1_SPEC_SEEDS,
    ids=[f"{a}-draft_{sp[0]}_{sp[1]}_k{sp[2]}-{s}"
         for a, sp, s in TIER1_SPEC_SEEDS])
def test_sched_differential_spec(arch, spec, seed):
    """Same event soup as :func:`test_sched_differential` — multi-turn
    parking, cross-session shared prefixes, forced preempts, TTL expiry —
    with draft-and-verify speculative decoding on, asserted token-for-token
    equal to the *non-speculative* solo reference and audited every step.
    Self-draft rows pin the accept fast path; disagreeing-draft rows reject
    nearly every proposal and so hammer the length-rewind (and, for the
    hybrid, the recurrent-row rollback + replay) machinery."""
    _run_and_dump(arch, seed, spec=spec)


SWEEP_BASE = os.environ.get("SCHED_DIFF_SWEEP")


@pytest.mark.skipif(SWEEP_BASE is None,
                    reason="randomized sweep runs in the non-blocking CI job "
                           "(set SCHED_DIFF_SWEEP=<base seed>)")
@pytest.mark.parametrize("k", range(8))
def test_sched_differential_sweep(k):
    base = int(SWEEP_BASE) % 1_000_000
    for arch in ("minicpm-2b", "moonshot-v1-16b-a3b", "recurrentgemma-2b"):
        _run_and_dump(arch, 1000 + base + k)


# ---------------------------------------------------------------------------
# Multi-device sharded parity (8-device host mesh)
# ---------------------------------------------------------------------------
#
# The CI multi-device job runs these under
# ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; without 8 devices
# they skip (tier-1 covers the path through test_system's subprocess smoke
# instead).  Mesh (2, 4): slots shard on ``data`` (n_slots=4), heads / pool
# lanes on ``model`` — PAGE_SIZE=4 divides model=4, so the paged_kernel rows
# take the shard_map *lane* decomposition of the fused gather.  The event
# soup is the same as above: forced preempts, parking, prefix sharing, spec
# rounds.
#
# Reference choice per family (``ref``):
#
# * ``solo`` — the unmodified single-device reference: the strict 1-device
#   == 8-device token-for-token claim.  Dense holds it (measured ~7e-4
#   bf16 logit drift from cross-shard reduction order, far inside its
#   argmax margins) — including the spec rows and the shard_map lane rows.
# * ``sched`` — a :class:`SchedRef`: the same sharded scheduler, same mesh
#   and backend, run eviction-free one request at a time.  MoE and hybrid
#   need a mesh-matched reference: bf16 cross-shard reduction order shifts
#   the router's top-k on near-tied gates (moe) and feeds back through the
#   recurrence (hybrid), so their 1-vs-8 logits diverge wholesale
#   (~0.1-0.3 at ~0.8 logit scale; exact in fp32, which pins it as
#   reassociation, not a bug).  A solo reference *on the mesh* is still not
#   numerically matched — the batched dp-sharded step and the paged pool's
#   lane layout reassociate differently than a B=1 ring — so the reference
#   goes through the scheduler's own step set, and the differential claim
#   becomes: every scheduler *mechanism* (paging, chunked prefill,
#   preempt/restore, parking, CoW, batched catch-up, verify) is
#   token-invisible on the mesh, bitwise.

N_SLOTS_SHARDED = 4          # divides dp=2 (mesh (2, 4))

SHARDED_SEEDS = [
    ("minicpm-2b", "gather", "solo", 0),
    ("minicpm-2b", "paged_kernel", "solo", 0),
    ("minicpm-2b", "paged_kernel", "solo", 3),
    ("moonshot-v1-16b-a3b", "gather", "sched", 0),
    ("moonshot-v1-16b-a3b", "paged_kernel", "sched", 1),
    ("recurrentgemma-2b", "gather", "sched", 1),
]
# Spec on the mesh: dense rows hold the strict solo claim; moe rows pin the
# rewind machinery (disagreeing draft) and the accept fast path (self-draft)
# against the mesh-matched scheduler reference.  There is NO hybrid spec row
# here, deliberately: the verify chunk scores S = k + 1 tokens per dispatch
# while the non-speculative reference consumes them one S=1 step at a time,
# and on the mesh those two dispatch shapes reassociate bf16 differently —
# the hybrid's recurrence feeds that sub-ulp drift back on itself (and its
# rollback+replay path re-runs accepted spans at yet another chunk shape),
# flipping 1-2 argmaxes per sequence on every seed scanned.  Hybrid spec is
# pinned bitwise single-device (TIER1_SPEC_SEEDS), and hybrid-on-mesh by its
# non-spec row above.
SHARDED_SPEC_SEEDS = [
    ("minicpm-2b", ("minicpm-2b", 0, 3), "solo", 0),
    ("minicpm-2b", ("minicpm-2b", 7, 2), "solo", 1),
    ("moonshot-v1-16b-a3b", ("minicpm-2b", 0, 3), "sched", 0),
    ("moonshot-v1-16b-a3b", ("moonshot-v1-16b-a3b", 0, 3), "sched", 2),
]

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="sharded parity needs an 8-device mesh "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _host_mesh():
    from repro.launch.mesh import make_mesh

    return make_mesh((2, 4), ("data", "model"))


@needs_mesh
@pytest.mark.parametrize(
    "arch,backend,ref,seed", SHARDED_SEEDS,
    ids=[f"{a}-{b}-{r}-{s}" for a, b, r, s in SHARDED_SEEDS])
def test_sched_differential_sharded(arch, backend, ref, seed):
    mesh = _host_mesh()
    sched_kw = dict(mesh=mesh, n_slots=N_SLOTS_SHARDED, attn_backend=backend)
    _run_and_dump(arch, seed, sched_kw=sched_kw,
                  cache_key=("sharded", backend, ref), ref_kind=ref)


@needs_mesh
@pytest.mark.parametrize(
    "arch,spec,ref,seed", SHARDED_SPEC_SEEDS,
    ids=[f"{a}-draft_{sp[0]}_{sp[1]}_k{sp[2]}-{r}-{s}"
         for a, sp, r, s in SHARDED_SPEC_SEEDS])
def test_sched_differential_sharded_spec(arch, spec, ref, seed):
    """Speculative decoding on the mesh: the batched draft catch-up, the
    draft steps and the verify chunk all run policy-bound (spec forces the
    gather backend, so the shard_map pool path is exercised by the non-spec
    rows above).  The reference is always non-speculative."""
    mesh = _host_mesh()
    sched_kw = dict(mesh=mesh, n_slots=N_SLOTS_SHARDED)
    _run_and_dump(arch, seed, spec=spec, sched_kw=sched_kw,
                  cache_key=("sharded", "spec", ref), ref_kind=ref)


# ---------------------------------------------------------------------------
# Hypothesis property: alloc/share/CoW/release round trips on the allocator
# ---------------------------------------------------------------------------

try:  # optional dep, guarded like test_kernel_properties (skip, not error)
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 10_000_000))
    def test_alloc_share_cow_release_property(seed):
        """Random op soup against a shadow refcount model: the allocator's
        ``free + in_use == n_pages`` invariant, per-page refcounts, and the
        total-refs meter all stay exact through alloc / share / release /
        CoW swaps, and releasing every holder returns the pool to fully
        free."""
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 12))
        a = kvcache.PageAllocator(n)
        shadow = {}                 # page -> refcount
        holders = []                # one entry per outstanding reference
        for _ in range(60):
            op = rng.choice(["alloc", "share", "release", "cow"])
            if op == "alloc" and a.free_count:
                k = int(rng.integers(1, a.free_count + 1))
                pages = a.alloc(k)
                assert len(set(pages)) == k
                assert not any(p in shadow for p in pages), "page reissued"
                for p in pages:
                    shadow[p] = 1
                    holders.append(p)
            elif op == "share" and shadow:
                p = int(rng.choice(list(shadow)))
                a.share([p])
                shadow[p] += 1
                holders.append(p)
            elif op == "release" and holders:
                p = holders.pop(int(rng.integers(len(holders))))
                a.release([p])
                shadow[p] -= 1
                if not shadow[p]:
                    del shadow[p]
            elif op == "cow" and holders and a.free_count:
                # a writer splits: fresh private page in, old reference out
                old = holders.pop(int(rng.integers(len(holders))))
                new = a.alloc(1)[0]
                shadow[new] = 1
                holders.append(new)
                a.release([old])
                shadow[old] -= 1
                if not shadow[old]:
                    del shadow[old]
            a.check()
            assert a.in_use == len(shadow)
            assert a.total_refs == sum(shadow.values()) == len(holders)
            for p, rc in shadow.items():
                assert a.refcount(p) == rc
        for p in holders:
            a.release([p])
        assert a.free_count == n and a.in_use == 0 and a.total_refs == 0

except ImportError:

    @pytest.mark.skip(reason="optional dep: property sweeps need hypothesis")
    def test_alloc_share_cow_release_property():
        pass
