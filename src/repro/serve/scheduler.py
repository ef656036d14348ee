"""Slot-based continuous-batching decode scheduler over a paged KV pool.

A fixed-width decode batch (``n_slots``) steps one token per active slot per
call; free slots are re-admitted from a shared cross-session queue of pending
requests.  Every slot runs the explicit lifecycle in
:mod:`repro.serve.lifecycle`::

    EMPTY -> ADMITTING -> ACTIVE -> (PREEMPTED -> RESTORING -> ACTIVE)* -> DRAINED

Two KV layouts:

* ``kv_mode='paged'`` (default): one shared ``(n_pages, page_size, Hkv*D)``
  pool per layer plus a per-slot page table
  (:func:`repro.models.kvcache.paged_cache`).  Pages are handed out by a
  host-side free list (:class:`repro.models.kvcache.PageAllocator`) —
  mapped on first write, freed on completion — so KV memory scales with
  *live tokens*, not ``n_slots * max_seq``.  Admission is **chunked**: the
  prompt is split into ``prefill_chunk``-sized pieces and one chunk runs per
  :meth:`step` call (a B=1 forward against the shared pool, interleaved with
  the batch's decode step), so a long-prompt admission never stalls the
  other slots for more than one chunk.  Admission is reservation-gated: a
  request is only admitted when the pool's uncommitted pages cover its worst
  case, so lazy mapping can never deadlock mid-decode.

* ``kv_mode='ring'``: the PR 2 baseline — per-slot rings sized ``max_seq``
  and monolithic prefill-on-admit.

**Storage-backed preemption** (``offload=True``, paged mode): the FaaSKeeper
move — durable state belongs in cloud storage, compute is ephemeral and
reclaimable — applied to the KV pool.  When a pending request is pool-gated
(an admission stall), the preemption policy picks victim slots among the
ACTIVE ones (oldest resident first — the idleness signal — then most pages
pinned; ``idle_preempt_steps`` sets the minimum residency so fresh slots are
never thrashed), extracts each victim's pages through its page table into a
position-ordered blob (:func:`kvcache.gather_pages`), PUTs it to the
:class:`repro.core.storage.PageBlobStore`, and frees the pages *and* the
victim's whole reservation back to the pool.  The victim parks in PREEMPTED:
its slot row (recurrent state, lengths, output ring) stays frozen under the
decode mask, but it pins zero pool capacity.  When pool pressure clears (no
pending request is pool-gated and the uncommitted margin covers the
victim's worst case again), the slot funds a restore: the blob is fetched
and injected **chunk by chunk, interleaved with decode exactly like prefill
chunks** (:func:`kvcache.scatter_pages` onto freshly allocated pages, the
page table re-mapped), and the slot resumes ACTIVE — token-for-token
identical to a never-preempted run, because the gather/scatter pair is an
exact inverse through the page table and the masked rows never advanced.
Restores are FIFO in preemption order and, once funded, run to completion
(RESTORING slots are never re-preempted), so offload cannot deadlock or
livelock the pool.  Storage traffic is journaled on the blob store and
billed by the serving frontend under the calibrated object-store models.

Either way the batched decode step masks non-ACTIVE slots out of the token
write, the output ring advance, and every per-slot cache row
(``kvcache.mask_slot_rows``): a freed, mid-admission, or preempted slot's
stale state cannot advance, and its dangling pool writes are dropped by the
unmapped page table.

**Refcounted copy-on-write prefix sharing** (``prefix_sharing=True``, paged
mode): KV pages are a shared resource.  The :class:`kvcache.PageAllocator`
refcounts every page (alloc/share/release); completed requests publish their
*full* pages — generated span included, since decode-written KV is bitwise
prefill KV — into a content-addressed :class:`kvcache.PrefixIndex` keyed by
token-chain hashes, and a new request whose prompt carries an indexed prefix
maps those pages **read-only** (one extra reference each) and prefills only
its tail.  Any write through a page with refcount > 1 — a chunked-prefill
tail landing in a shared boundary page, or a decode append — first
copy-on-write splits the page (:func:`kvcache.copy_pages`) onto a fresh
page and remaps only the writer's table, so every other reference keeps
reading the original bytes (FaaSFS's journaled CoW consistency model).
Index sharing is only consulted for pure-attention families (dense/moe):
recurrent rows (hybrid conv/RG-LRU state) cannot be reconstructed from KV
pages alone.

**Cross-request session parking** (``park_sessions=True``): the FaaSKeeper
session move — a session's state outlives the invocation that built it.  A
completed slot enters ``PARKED`` instead of freeing its pages: a
per-session record takes ownership of the page references (plus the token
history and, once the slot itself is reclaimed, a host snapshot of the
per-slot rows), so the session's *next* request — whose prompt extends the
recorded history, the multi-turn chat shape — maps the parked pages shared
and prefills only the new tokens.  Parked capacity is fully reclaimable:
a new admission may take the slot (rows snapshot to the record), and under
pool pressure parked pages offload through the same
:class:`~repro.core.storage.PageBlobStore` path preemption uses — the next
request then restores the blob instead of re-prefilling, trading a storage
GET + retention for prompt-length compute.  ``park_ttl_steps`` bounds the
retention window.  ``reset()`` clears the prefix index and the parked
table: a crash-replayed run must never observe another life's shared state.

**Draft-and-verify speculative decoding** (``draft_model=..., spec_k=k``,
paged + gather + greedy): a small draft model proposes ``k`` tokens per
active slot per tick; the target scores all of them (plus the pending
canonical token) in ONE chunked decode step against the shared paged pool
and accepts the longest prefix matching its own argmax, emitting 1..k+1
tokens per round.  The invariant is exactness, not luck: every emitted
token is the target's greedy argmax over a fully canonical prefix, so the
output stream is token-for-token identical to the non-speculative run.
This leans on the same contract that un-blocked generated-tail reuse — an
S=1 decode step IS the chunk path at S=1 and writes bitwise-identical KV —
so a verify chunk's accepted span needs no fixup, rejected KV positions
simply sit past the rewound length until the next round overwrites them,
and hybrid recurrent rows snapshot/replay around partial accepts
(:meth:`_spec_round`).

Per-session FIFO is preserved structurally: a session's next request is only
admitted after its predecessor completes (the ``_active_sessions`` gate), and
the pending list is scanned in arrival order.

``mesh`` applies :func:`repro.dist.sharding.cache_shardings` to the live
decode cache; with offload enabled the staging-buffer specs resolve through
:func:`repro.dist.sharding.offload_stage_shardings` into ``stage_specs``.

Supported families: ``dense``, ``moe``, ``ssm``, ``hybrid`` (decoder-only
LMs; the enc-dec families keep the whole-batch serving path).  SSM keeps its
ring-free O(1) state — no pool, so nothing to offload, but admission still
chunks.
"""

from __future__ import annotations

import copy
import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.storage import PageBlobStore
from ..models import kvcache
from . import sampling
from .engine import _policy_scope, make_chunk_step, make_offload_steps
from .lifecycle import Slot, SlotState
from .tracing import span

CONTINUOUS_FAMILIES = ("dense", "moe", "ssm", "hybrid")

PREEMPT_POLICIES = ("none", "pressure")


def supports_continuous(cfg) -> bool:
    return getattr(cfg, "family", None) in CONTINUOUS_FAMILIES


@dataclasses.dataclass
class _Request:
    session: str
    request_id: str
    prompt: Any                 # (P,) int tokens
    max_new: int
    submit_step: int = 0
    hashes: Any = None          # prompt page-chain hashes, computed once (a
    # held request is re-matched every _fill_slots pass)


@dataclasses.dataclass
class CompletedRequest:
    session: str
    request_id: str
    tokens: np.ndarray          # (max_new,) generated tokens
    admitted_step: int
    finished_step: int
    submitted_step: int = 0     # admission stall = admitted - submitted
    preempts: int = 0           # times this request was preempted mid-decode
    reused_tokens: int = 0      # prompt tokens served from shared/parked pages


@dataclasses.dataclass
class ParkedSession:
    """The durable half of a parked session: the KV-page journal a completed
    request leaves behind so its session's next request restores instead of
    re-prefilling.  Owns one allocator reference per resident page; the
    journal is immutable (writers CoW-split), dropped only when superseded
    by a longer history, diverged from, expired, or reset."""

    session: str
    history: np.ndarray         # prompt + generated tokens
    consumed: int               # tokens whose KV/recurrent state is captured
    # (decode-written KV is bitwise what a re-prefill would write — the S=1
    # decode path IS the chunk path at S=1 — so the whole consumed span is
    # reusable, generated tokens included; no prefill-path/decode-path split)
    page_row: np.ndarray        # logical -> physical page map at park time
    pages: List[int]            # resident page references the record owns
    slot: Optional[int] = None  # still holding its slot (rows live on device)
    state: Any = None           # host row snapshot once the slot is reclaimed
    blob_key: Optional[str] = None        # pages offloaded under pool pressure
    blob_pidx: List[int] = dataclasses.field(default_factory=list)
    parked_step: int = 0


@dataclasses.dataclass
class _MatchPlan:
    """How much of an arriving prompt is already resident, and where."""

    kind: str = "none"          # none | park | park-blob | index
    C: int = 0                  # matched tokens (their KV will be reused)
    pages: List[int] = dataclasses.field(default_factory=list)  # logical order
    record: Optional[ParkedSession] = None


def _kv_leaf(cache):
    """The cache's first KV storage leaf (pool ``kp`` or ring ``k``); None
    for ring-free state."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if getattr(path[-1], "key", None) in ("kp", "k"):
            return leaf
    return None


class DecodeScheduler:
    """Continuous batching over a shared paged pool (or per-slot rings)."""

    def __init__(self, model, params, *, n_slots: int = 4, max_seq: int = 64,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 mesh=None, kv_mode: str = "paged", page_size: int = 16,
                 kv_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 offload: bool = False,
                 preempt_policy: Optional[str] = None,
                 idle_preempt_steps: int = 0,
                 blob_store: Optional[PageBlobStore] = None,
                 prefix_sharing: bool = False,
                 park_sessions: bool = False,
                 park_ttl_steps: int = 0,
                 index_journal: bool = False,
                 attn_backend: str = "gather",
                 draft_model=None, draft_params=None, spec_k: int = 0):
        if not supports_continuous(model.cfg):
            raise ValueError(
                f"family {model.cfg.family!r} has no per-slot decode path; "
                f"continuous batching supports {CONTINUOUS_FAMILIES}")
        if kv_mode not in ("paged", "ring"):
            raise ValueError(f"kv_mode must be 'paged' or 'ring', got {kv_mode!r}")
        if attn_backend not in ("gather", "paged_kernel"):
            raise ValueError("attn_backend must be 'gather' or 'paged_kernel', "
                             f"got {attn_backend!r}")
        if attn_backend == "paged_kernel":
            if kv_mode != "paged":
                raise ValueError(
                    "attn_backend='paged_kernel' streams the shared page pool "
                    "through the Pallas kernel; it needs kv_mode='paged'")
            if model.cfg.family == "ssm":
                raise ValueError("attn_backend='paged_kernel' needs attention "
                                 "layers; SSM decode has no KV pool")
            # rebind a copy so a gather-mode scheduler sharing this model
            # object keeps the reference dispatch (cfg drives the decode
            # paths' backend branch at trace time)
            model = copy.copy(model)
            model.cfg = dataclasses.replace(model.cfg,
                                            attn_backend="paged_kernel")
        self.attn_backend = attn_backend
        if preempt_policy is None:
            preempt_policy = "pressure" if offload else "none"
        if preempt_policy not in PREEMPT_POLICIES:
            raise ValueError(f"preempt_policy must be one of {PREEMPT_POLICIES}, "
                             f"got {preempt_policy!r}")
        if offload and kv_mode != "paged":
            raise ValueError("KV offload needs the paged pool (kv_mode='paged'); "
                             "per-slot rings have no page granularity to evict")
        if (prefix_sharing or park_sessions) and kv_mode != "paged":
            raise ValueError(
                "prefix sharing / session parking need the paged pool "
                "(kv_mode='paged'); per-slot rings have no shareable pages")
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.temperature = temperature
        self.top_k = top_k
        self.kv_mode = kv_mode
        self._seed = seed
        self._key = jax.random.key(seed)
        self._has_kv = model.cfg.family != "ssm"   # SSM state is ring-free
        self.offload = bool(offload) and kv_mode == "paged" and self._has_kv
        self.preempt_policy = preempt_policy if self.offload else "none"
        self.idle_preempt_steps = idle_preempt_steps
        # -- prefix sharing / session parking -------------------------------
        self.prefix_sharing = (bool(prefix_sharing) and kv_mode == "paged"
                               and self._has_kv)
        self.park_sessions = (bool(park_sessions) and kv_mode == "paged"
                              and self._has_kv)
        self.park_ttl_steps = park_ttl_steps
        # index sharing reconstructs state from KV pages alone, which only
        # pure-attention families allow (hybrid conv/RG-LRU rows are not in
        # the pool); parking keeps the rows, so it covers every family
        self._attention_only = model.cfg.family in ("dense", "moe")
        self._index_sharing = self.prefix_sharing and self._attention_only
        self.prefix_index = kvcache.PrefixIndex()
        # fleet mode: journal every published index entry to the (shared)
        # blob store so shared prefixes survive this worker's death, and
        # namespace this worker's transient blob keys (preempt spills,
        # parked-journal offloads) so a dead worker's keys can be garbage
        # collected without racing a successor's
        self.index_journal = bool(index_journal) and self._index_sharing
        self.blob_ns = ""
        self.index_journal_puts = 0
        self.index_adopted = 0
        self._parked: Dict[str, ParkedSession] = {}
        # every program that takes the cache and returns it updated donates
        # it (the caller rebinds ``self.cache`` to the result), so the pool
        # is written in place instead of copied
        self._copy_pages = jax.jit(kvcache.copy_pages, donate_argnums=(0,))
        self._gather_state = jax.jit(kvcache.gather_slot_state)
        self._scatter_state = jax.jit(kvcache.scatter_slot_state,
                                      donate_argnums=(0,))
        self.shared_prefix_tokens = 0   # prompt tokens never re-prefilled
        self.park_hits = 0
        self.park_misses = 0
        self.index_hits = 0
        self.cow_splits = 0
        self.parks = 0
        self.park_evictions = 0         # parked slots reclaimed for admissions
        self.park_offloads = 0          # parked page sets pushed to the blob store
        self.park_expirations = 0

        if kv_mode == "paged":
            self.page_size = page_size
            self.max_pages = -(-max_seq // page_size)
            self.n_pages = (kv_pages if kv_pages is not None
                            else n_slots * self.max_pages)
            if self._has_kv and self.n_pages < self.max_pages:
                raise ValueError(
                    f"kv_pages={self.n_pages} cannot hold even one slot's "
                    f"max_pages={self.max_pages}")
            self.prefill_chunk = prefill_chunk   # None -> whole prompt, one chunk
            self.allocator = kvcache.PageAllocator(
                self.n_pages if self._has_kv else 0)
            # host mirror of the device page table + pages committed to
            # admitted-but-not-yet-mapped growth (the admission gate)
            self._page_rows = np.full((n_slots, self.max_pages), -1, np.int32)
            self._reserved = 0
            self.cache = kvcache.paged_cache(
                model, n_slots, page_size=page_size, n_pages=self.n_pages,
                max_pages=self.max_pages)
        else:
            self.cache = kvcache.batched_cache(model, n_slots, max_seq)

        # -- offload plumbing ------------------------------------------------
        self.blob_store = blob_store if blob_store is not None else PageBlobStore()
        # restore chunking mirrors prefill chunking: a restore step moves
        # about one prefill chunk's worth of tokens (>= 1 page)
        self._restore_chunk_pages = (
            max(1, self.prefill_chunk // self.page_size)
            if kv_mode == "paged" and self.prefill_chunk else None)
        self._preempted_order: List[int] = []   # slot indices, FIFO restores
        self.preemptions = 0
        self.restores = 0
        self.restore_chunks = 0
        self.offload_pages = 0
        self.restored_pages = 0

        # -- mesh placement + sharded step set -------------------------------
        # With a *concrete* mesh the whole hot path goes multi-device: state
        # (params, cache, slot arrays) is device_put through the storage
        # registry, and every jitted step below binds a ShardingPolicy so
        # activations constrain to the mesh and the fused paged gather runs
        # under shard_map against the lane-sharded pool.  An AbstractMesh
        # still resolves the spec pytrees (lowering / analysis callers) but
        # binds the single-device steps.
        self.cache_specs = None
        self.stage_specs = None
        self._mesh = mesh if isinstance(mesh, jax.sharding.Mesh) else None
        self._policy = None
        if mesh is not None:
            from ..dist import sharding as shd

            shardings = shd.cache_shardings(self.cache, mesh)
            self.cache_specs = jax.tree_util.tree_map(
                lambda s: s.spec, shardings)
            if self.offload:
                stage = jax.eval_shape(
                    lambda c: kvcache.gather_pages(c, jnp.zeros((1,), jnp.int32)),
                    self.cache)
                self.stage_specs = jax.tree_util.tree_map(
                    lambda s: s.spec, shd.offload_stage_shardings(stage, mesh))
            if self._mesh is not None:   # concrete: place state, build policy
                self.cache = jax.device_put(self.cache, shardings)
                self.params = jax.device_put(
                    self.params, shd.param_shardings(self.params, self._mesh))
                self._policy = self._build_policy(model, self._mesh)

        # steps bind the policy + spec pytrees only when a concrete mesh is
        # live — with cache_specs but no policy (AbstractMesh) the constrain
        # helpers would be dead weight in the trace
        skw = (dict(policy=self._policy, cache_specs=self.cache_specs)
               if self._policy is not None else {})
        if kv_mode == "paged":
            self._chunk = jax.jit(make_chunk_step(model, **skw),
                                  donate_argnums=(1,))
        else:
            ring_policy = self._policy

            def _ring_prefill(p, toks):
                with _policy_scope(ring_policy):
                    return model.prefill(p, toks, seq_len=max_seq)

            self._prefill = jax.jit(_ring_prefill)
        self._extract, self._inject = make_offload_steps(
            policy=self._policy, cache_specs=self.cache_specs,
            stage_specs=self.stage_specs)

        self._decode = jax.jit(self._step_impl, donate_argnums=(1,))
        # decode and chunk dispatches whose old pool leaf was deleted at
        # dispatch (donated) or left alive (the step wrote a copy)
        self.pool_donated_steps = 0
        self.pool_copied_steps = 0

        # -- draft-and-verify speculative decoding --------------------------
        # The draft proposes spec_k tokens per slot per round; the target
        # scores all of them (plus the pending canonical token) in ONE
        # chunked decode step against the shared paged pool and accepts the
        # longest matching prefix.  Output is token-for-token identical to
        # non-speculative decode because every emitted token is the target's
        # greedy argmax over a fully canonical prefix — which requires the
        # bitwise S=1-decode==chunked-prefill KV contract the models now
        # hold.  Rejected positions' KV is rewound by length (pool pages
        # stay mapped; the next round's chunk overwrites before any read),
        # and hybrid recurrent rows snapshot/replay around partial accepts.
        self.spec_k = int(spec_k)
        self._spec = draft_model is not None and self.spec_k >= 1
        if self._spec:
            if kv_mode != "paged" or not self._has_kv:
                raise ValueError(
                    "speculative decoding verifies chunks against the shared "
                    "paged pool; it needs kv_mode='paged' and a KV-bearing "
                    "target (dense/moe/hybrid)")
            if temperature > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only: accept/reject "
                    "compares the target's argmax, which temperature "
                    "sampling does not produce")
            if attn_backend != "gather":
                raise ValueError(
                    "speculative decoding needs attn_backend='gather': the "
                    "fused paged kernel only serves S=1 steps, so a verify "
                    "chunk would switch dispatch mid-request")
            if draft_params is None:
                raise ValueError("spec decoding needs draft_params")
            if getattr(draft_model.cfg, "family", None) not in ("dense", "moe"):
                raise ValueError(
                    "draft family must be dense or moe: the draft rewinds "
                    "to the accepted prefix every round, which recurrent "
                    "state cannot do cheaply")
            if draft_model.cfg.vocab != model.cfg.vocab:
                raise ValueError(
                    f"draft vocab {draft_model.cfg.vocab} != target vocab "
                    f"{model.cfg.vocab}")
            self.draft_model = draft_model
            self.draft_params = draft_params
            # per-slot ring sized for the deepest proposal the draft reaches
            # (the page table's span can overhang max_seq by a partial page)
            # PLUS the batched catch-up's back-padding: a round's widest
            # pending span W pads every row, so a row at canonical length L
            # writes (garbage, never-read) lanes up to L + W - 1.  The ring
            # scatter wraps at capacity, so the ring must be deeper than the
            # padded worst case (L <= span + spec_k, W <= max_seq + 1) or a
            # pad write would land on a live lane.
            span = self.max_pages * self.page_size
            self.draft_cache = kvcache.batched_cache(
                draft_model, n_slots, 2 * span + self.spec_k + 2)
            from .engine import (make_draft_catchup_step, make_draft_step,
                                 make_spec_verify_step)

            self._draft_policy = None
            self._draft_cache_specs = None
            if self._mesh is not None:
                from ..dist import sharding as shd

                self._draft_policy = self._build_policy(draft_model,
                                                        self._mesh)
                d_sh = shd.cache_shardings(self.draft_cache, self._mesh)
                self._draft_cache_specs = jax.tree_util.tree_map(
                    lambda s: s.spec, d_sh)
                self.draft_cache = jax.device_put(self.draft_cache, d_sh)
                self.draft_params = jax.device_put(
                    self.draft_params,
                    shd.param_shardings(self.draft_params, self._mesh))
            dkw = (dict(policy=self._draft_policy,
                        cache_specs=self._draft_cache_specs)
                   if self._draft_policy is not None else {})
            self._draft_catchup = jax.jit(
                make_draft_catchup_step(draft_model, **dkw))
            self._draft_step = jax.jit(make_draft_step(draft_model, **dkw))
            # a hybrid round keeps the pre-verify cache to roll recurrent
            # rows back on a partial accept, so its verify cannot donate
            self._verify = jax.jit(
                make_spec_verify_step(model, max_seq=max_seq, **skw),
                donate_argnums=() if model.cfg.family == "hybrid" else (1,))
        self.spec_rounds = 0
        self.spec_proposed = 0          # draft tokens offered to the verifier
        self.spec_accepted = 0          # draft tokens accepted
        self.spec_emitted = 0           # tokens emitted by verify rounds

        self.slots: List[Slot] = [Slot(index=i) for i in range(n_slots)]
        self.last_tokens = jnp.zeros((n_slots,), jnp.int32)
        # device-side per-slot output ring: tokens accumulate on device and
        # are pulled to host once per *completion*, not once per step — a
        # decode step is a single async dispatch with no host sync
        self.out_buf = jnp.zeros((n_slots, max_seq), jnp.int32)
        self.out_pos = jnp.zeros((n_slots,), jnp.int32)
        if self._mesh is not None:
            # slot-batched state follows the cache's slot axis onto dp
            from ..dist.sharding import batch_shardings

            state = {"last": self.last_tokens, "buf": self.out_buf,
                     "pos": self.out_pos}
            state = jax.device_put(state, batch_shardings(state, self._mesh))
            self.last_tokens = state["last"]
            self.out_buf = state["buf"]
            self.out_pos = state["pos"]
        self.pending: List[_Request] = []
        self._active_sessions: set = set()
        self._chunk_rr = 0            # round-robin over admitting slots
        self._restore_rr = 0          # round-robin over restoring slots
        # -- occupancy / throughput accounting --------------------------------
        self.steps = 0
        self.slot_steps = 0           # sum over steps of active slots
        self.page_step_sum = 0        # sum over steps of pages in use
        self.prefill_tokens = 0
        self.prefill_chunks = 0
        self.decode_tokens = 0
        self.admitted = 0
        self.completed = 0

    # -- mesh mode -----------------------------------------------------------------

    def _build_policy(self, model, mesh):
        """ShardingPolicy for one model on the live mesh: slots on dp when
        they divide, heads on model when the kv-head count divides (else the
        seq fallback), and — for the fused paged backend — the shard_map
        pool decomposition switched on so :func:`paged_attn_decode`
        dispatches the per-shard kernel instead of letting GSPMD all-gather
        the lane-sharded pool."""
        from ..dist import sharding as shd

        rules = shd.MeshRules.for_mesh(mesh)
        msize = rules.model_size(mesh)
        cfg = model.cfg
        n_kv = getattr(cfg, "n_kv_heads", 0) or getattr(cfg, "n_heads", 1)
        return shd.ShardingPolicy.default(
            mesh,
            batch_shardable=bool(rules.dp)
            and self.n_slots % rules.dp_size(mesh) == 0,
            attn_mode="head" if n_kv % msize == 0 else "seq",
            decode_stationary=True,
            shard_map_pool=self.attn_backend == "paged_kernel")

    def _stage_put(self, blob):
        """Place a staging blob (restore chunk / parked-session blob) on the
        mesh per ``offload_stage_shardings`` *before* injecting, so the
        sharded scatter's operand already sits in the pool's lane layout —
        the host->device transfer is the reshard, not an extra collective
        inside the step."""
        if self._mesh is None or self.stage_specs is None:
            return blob
        from jax.sharding import NamedSharding

        return jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(
                leaf, NamedSharding(self._mesh, spec)),
            blob, self.stage_specs)

    # -- admission ----------------------------------------------------------------

    def submit(self, session: str, request_id: str, prompt, max_new: int) -> None:
        """Enqueue a request; admitted into a free slot as soon as its
        session has no in-flight predecessor (per-session FIFO gate) and —
        in paged mode — the pool's uncommitted pages cover its worst case
        (or the preemption policy can evict enough to make them).

        ``max_new`` is clamped to what the slot can hold without silent
        corruption: the output ring caps it at ``max_seq``, and on a
        full-attention KV layout (no sliding window — detected via
        ``cache_len``) generation past ``max_seq - len(prompt)`` would wrap
        the ring / run off the page table, so the budget stops there; a
        prompt that leaves no decode room at all is rejected outright
        (clamping would silently drop its leading tokens).  Windowed rings
        wrap by design; the paged table is linear, so windowed families are
        bounded by its ``max_pages * page_size`` span instead.  SSM states
        never bound the budget beyond the output ring.
        """
        with span("submit", request_id=request_id):
            prompt = np.asarray(prompt)
            P = int(prompt.shape[-1])
            limit = self.max_seq
            cache_len = getattr(self.model, "cache_len", None)
            has_full_ring = (self._has_kv
                             and cache_len is not None
                             and cache_len(self.max_seq + 1) > self.max_seq)
            if has_full_ring:
                room = self.max_seq - P
                if room <= 0:
                    raise ValueError(
                        f"request {request_id!r}: prompt of {P} "
                        f"tokens leaves no decode room in the max_seq={self.max_seq} "
                        "full-attention ring; size max_seq >= prompt + max_new")
                limit = min(limit, room)
            elif self.kv_mode == "paged" and self._has_kv:
                # windowed attention wraps a ring but cannot wrap the linear
                # page table: bound the budget by the table's span
                room = self.max_pages * self.page_size - P
                if room <= 0:
                    raise ValueError(
                        f"request {request_id!r}: prompt of {P} tokens overruns "
                        f"the {self.max_pages}x{self.page_size} page table")
                limit = min(limit, room)
            max_new = max(1, min(max_new, limit))
            self.pending.append(_Request(session, request_id, prompt, max_new,
                                         submit_step=self.steps))
            self._fill_slots()

    def busy(self) -> bool:
        """In-flight work pending.  PARKED retention is not work: a parked
        slot is a cache entry, not a request — spinning on it would hold the
        serving invocation open forever."""
        return any(s.working for s in self.slots) or bool(self.pending)

    def free_slots(self) -> int:
        """Slots a new admission can take (EMPTY, plus PARKED ones — parked
        residency is reclaimable, its record survives on the host)."""
        return sum(1 for s in self.slots if s.empty or s.parked)

    def parked_slots(self) -> int:
        return sum(1 for s in self.slots if s.parked)

    def active_slots(self) -> int:
        """Slots decoding+sampling this step (admitting/preempted excluded)."""
        return sum(1 for s in self.slots if s.decoding)

    def admitting_slots(self) -> int:
        return sum(1 for s in self.slots if s.state is SlotState.ADMITTING)

    def preempted_slots(self) -> int:
        return sum(1 for s in self.slots
                   if s.state in (SlotState.PREEMPTED, SlotState.RESTORING))

    def wants_more(self) -> bool:
        """Whether claiming more queued work could improve occupancy.

        Any free slot justifies claiming deeper: a FIFO queue can hold a long
        run of one session's (gated) requests in front of another session's
        admissible one, so the lookahead must not be capped — held-back
        requests wait in ``pending`` in arrival order and are requeued on a
        crash, so over-claiming never loses or reorders work."""
        return self.free_slots() > 0

    def _pages_needed(self, req: _Request) -> int:
        """Worst-case page count: prompt + all decode writes (the completing
        step samples its last token from a write at P + max_new - 2)."""
        if not (self.kv_mode == "paged" and self._has_kv):
            return 0
        tokens = int(np.asarray(req.prompt).shape[-1]) + req.max_new - 1
        return -(-tokens // self.page_size)

    def _uncommitted(self) -> int:
        """Pool pages not yet promised to anyone (the admission currency)."""
        return self.allocator.free_count - self._reserved

    def _fill_slots(self) -> None:
        held: List[_Request] = []
        held_sessions: set = set()    # a held request gates its whole session:
        # a page-starved r0 must not be overtaken by its session's smaller r1
        pool_starved = False
        if self.park_sessions and self.park_ttl_steps > 0:
            self._expire_parked()
        for req in self.pending:
            if req.session in self._active_sessions or req.session in held_sessions:
                held.append(req)      # FIFO gate: predecessor decoding or held
                held_sessions.add(req.session)
                continue
            plan = self._match_prefix(req)
            slot = self._slot_for(plan)
            if slot is None and not any(s.parked for s in self.slots):
                held.append(req)
                held_sessions.add(req.session)
                continue
            need = self._plan_pages(req, plan)
            if need and self._uncommitted() < need:
                # pool gate: reclaim shareable capacity first (index refs,
                # then parked retention), then try the preemption policy
                self._reclaim_pool(need, keep=plan.record, pinned=plan.pages)
                if (self._uncommitted() < need
                        and not self._preempt_for(need)):
                    pool_starved = True
                    held.append(req)
                    held_sessions.add(req.session)
                    continue
            if slot is None:
                # only now — with the pool gate passed — reclaim a parked
                # residency (a held request must not cost a snapshot);
                # _reclaim_pool may already have freed one by offloading
                slot = next((s for s in self.slots if s.empty), None)
                if slot is None:
                    victim = min((s for s in self.slots if s.parked),
                                 key=lambda s: s.parked_step)
                    self._evict_parked_slot(self._parked[victim.session])
                    slot = self.slots[victim.index]
            self._admit(slot, req, plan)
        self.pending = held
        # restores only start when pool pressure has cleared: no pending
        # request is pool-gated, and the uncommitted margin funds the
        # victim's whole worst case (prevents preempt<->restore thrash)
        if not pool_starved:
            self._start_restores()

    # -- prefix matching / parked-capacity planning -------------------------

    def _match_prefix(self, req: _Request) -> _MatchPlan:
        """The longest resident prefix of this prompt: the session's parked
        journal if the prompt extends it (pages or blob), else the longest
        indexed full-page chain.  At least the last prompt token always
        re-runs — its logits seed sampling."""
        plan = _MatchPlan()
        if not (self.kv_mode == "paged" and self._has_kv):
            return plan
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        P = len(prompt)
        rec = self._parked.get(req.session) if self.park_sessions else None
        if rec is not None:
            lim = min(P, len(rec.history))
            eq = prompt[:lim] == rec.history[:lim]
            common = lim if eq.all() else int(np.argmin(eq))
            if self._attention_only:
                # reuse everything consumed — generated tokens included
                # (decode KV is bitwise prefill KV) — capped at P-1: the
                # last prompt token always re-runs to seed sampling
                C = min(rec.consumed, common, P - 1)
            else:
                # recurrent rows advanced through every consumed token and
                # cannot rewind: all or nothing, with >= 1 tail token left
                # to re-run for the seeding logits
                C = rec.consumed if (common >= rec.consumed
                                     and P >= rec.consumed + 1) else 0
            if C > 0:
                plan.kind = "park-blob" if rec.blob_key else "park"
                plan.C = C
                plan.record = rec
                if not rec.blob_key:
                    plan.pages = [int(rec.page_row[i])
                                  for i in range(-(-C // self.page_size))]
                return plan
            if common < lim:
                # the prompt *contradicts* the journal: it can never serve
                # this session again (per-session FIFO — this req is next)
                self._drop_record(self._parked.pop(req.session))
                self.park_misses += 1
            # else: consistent but too short to reuse (hybrid: an exact
            # resubmission of the recorded history) — keep the journal;
            # completion supersedes it
        if self._index_sharing:
            if req.hashes is None:
                req.hashes = kvcache.page_hashes(prompt, self.page_size)
            k_max = max(0, P - 1) // self.page_size   # tail >= 1 token
            pids = self.prefix_index.lookup(req.hashes[:k_max])
            if pids:
                plan.kind = "index"
                plan.C = len(pids) * self.page_size
                plan.pages = [int(p) for p in pids]
        return plan

    def _plan_pages(self, req: _Request, plan: _MatchPlan) -> int:
        """Reservation size under the plan: full worst case minus the full
        pages mapped read-only (shared pages cost nothing until a CoW split;
        the boundary partial page's split is inside the writable span, and a
        blob unpark re-allocates its pages out of the same reservation)."""
        total = self._pages_needed(req)
        if plan.kind in ("park", "index"):
            return total - plan.C // self.page_size
        return total

    def _slot_for(self, plan: _MatchPlan) -> Optional[Slot]:
        """A free admission target: the plan's own parked slot (in-place
        unpark) or any EMPTY slot.  PARKED residencies are reclaimable too,
        but only *after* the pool gate passes — ``_fill_slots`` defers that
        eviction so a held request never costs a journal its row snapshot."""
        if (plan.kind == "park" and plan.record.slot is not None):
            return self.slots[plan.record.slot]
        return next((s for s in self.slots if s.empty), None)

    def _admit(self, slot: Slot, req: _Request,
               plan: Optional[_MatchPlan] = None) -> None:
        with span("admit", request_id=req.request_id):
            if self.kv_mode == "paged":
                self._admit_paged(slot, req, plan or _MatchPlan())
                return
            prompt = jnp.asarray(req.prompt, jnp.int32)[None]      # (1, P)
            logits, one = self._prefill(self.params, prompt)
            tok = self._sample(logits[:, -1])                      # (1,)
            self.cache = kvcache.cache_insert_slot(self.cache, one, slot.index)
            self.last_tokens = self.last_tokens.at[slot.index].set(tok[0])
            self.out_buf = self.out_buf.at[slot.index, 0].set(tok[0])
            self.out_pos = self.out_pos.at[slot.index].set(1)
            slot.to(SlotState.ADMITTING).to(SlotState.ACTIVE)  # monolithic prefill
            slot.req = req
            slot.n_out = 1
            slot.admitted_step = self.steps
            slot.submitted_step = req.submit_step
            slot.active_since = self.steps
            self._active_sessions.add(req.session)
            self.prefill_tokens += int(prompt.shape[1])
            self.admitted += 1

    def _admit_paged(self, slot: Slot, req: _Request, plan: _MatchPlan) -> None:
        """Begin a chunked admission.  With no resident prefix the slot's
        rows are cleared and the whole prompt is staged; with one, the
        matched pages are mapped read-only (shared) or restored from the
        parked blob, the parked rows are reinstalled if the slot changed,
        and only the prompt's tail is staged — the prefill the shared pages
        already paid for is skipped."""
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        C = plan.C
        need = self._plan_pages(req, plan)
        # plain chunking — a 1-token final chunk is fine (the S=1 forward IS
        # the chunk path at S=1 and writes bitwise-identical KV)
        tail = prompt[C:]
        size = self.prefill_chunk or len(tail)
        chunks = [tail[i:i + size] for i in range(0, len(tail), size)]
        in_place = (plan.kind == "park" and plan.record.slot == slot.index)
        if not in_place:
            self.cache = kvcache.cache_clear_slot(self.cache, slot.index)
            self._page_rows[slot.index, :] = -1
        self._reserved += need
        slot.to(SlotState.ADMITTING)
        slot.session = None
        slot.req = req
        slot.chunks = chunks
        slot.chunk_i = 0
        slot.len = C                  # host mirror of the slot's live length
        slot.pages = []
        slot.shared = []
        slot.need = need
        slot.reused = C
        slot.n_out = 0
        slot.preempts = 0
        slot.admitted_step = self.steps
        slot.submitted_step = req.submit_step
        self._active_sessions.add(req.session)
        if plan.kind in ("park", "index"):
            # map the matched prefix read-only: one extra reference per page
            self.allocator.share(plan.pages)
            slot.shared = list(plan.pages)
            for i, pid in enumerate(plan.pages):
                self._page_rows[slot.index, i] = pid
            self.cache = kvcache.set_page_row(
                self.cache, slot.index, self._page_rows[slot.index])
            if plan.kind == "park":
                rec = plan.record
                if in_place:
                    # the new request will overwrite the live rows; keep the
                    # journal self-contained so it can offload mid-flight
                    rec.state = jax.device_get(
                        self._gather_state(self.cache, slot.index))
                    rec.slot = None
                elif rec.state is not None:
                    self.cache = self._scatter_state(
                        self.cache, slot.index, rec.state)
                # the snapshot's length is rec.consumed; rewind to the
                # matched span C (attention families may reuse less than
                # consumed when the prompt diverges inside the generated
                # span or the seeding-tail cap bites)
                self.cache["length"] = self.cache["length"].at[slot.index].set(C)
                self.park_hits += 1
            else:
                # index pages carry KV only — set the slot's consumed length
                # (index matches are gated to pure-attention families, so
                # there are no recurrent rows to reconstruct)
                self.cache["length"] = self.cache["length"].at[slot.index].set(C)
                self.index_hits += 1
        elif plan.kind == "park-blob":
            # restore only the reused span of the journal's blob out of
            # this admission's own reservation (an attention family may
            # reuse far fewer pages than the blob holds — a long generated
            # tail re-prefills instead of restoring); the record keeps its
            # whole blob until superseded
            rec = plan.record
            npg = -(-C // self.page_size)
            pids = self.allocator.alloc(npg)
            self._reserved -= npg
            slot.pages = list(pids)
            for j in range(npg):
                self._page_rows[slot.index, rec.blob_pidx[j]] = pids[j]
            blob = self.blob_store.get(rec.blob_key)
            if npg < len(rec.blob_pidx):
                blob = kvcache.slice_page_blob(blob, 0, npg)
            self.cache = self._inject(self.cache,
                                      jnp.asarray(pids, jnp.int32),
                                      self._stage_put(blob))
            self.cache = kvcache.set_page_row(
                self.cache, slot.index, self._page_rows[slot.index])
            self.cache = self._scatter_state(self.cache, slot.index, rec.state)
            self.cache["length"] = self.cache["length"].at[slot.index].set(C)
            self.park_hits += 1
        self.shared_prefix_tokens += C
        if C % self.page_size and slot.shared:
            # eagerly CoW-split the partial boundary page: the batched decode
            # step's masked rows still write (dropped only by *unmapped*
            # tables), so a shared page this slot will write into must go
            # private before the next decode/verify step, not lazily at
            # chunk time
            self._prepare_write_span(slot, C, 1)

    def _map_page(self, slot: Slot, page_idx: int) -> None:
        """Host-side mapping only — the caller pushes the updated row to the
        device once per chunk/step (one dispatch per row, not per page)."""
        pid = self.allocator.alloc(1)[0]
        self._page_rows[slot.index, page_idx] = pid
        slot.pages.append(pid)
        self._reserved -= 1

    def _release_slot(self, slot: Slot) -> None:
        """Release a DRAINED slot's page references (owned pages free when
        their last reference dies; shared pages just drop one count) and any
        unused reservation; unmap its device page-table row so residual
        decode traffic is dropped."""
        slot.to(SlotState.EMPTY)
        if not (self.kv_mode == "paged" and self._has_kv):
            self.slots[slot.index] = Slot(index=slot.index)
            return
        self._reserved -= slot.need - len(slot.pages)
        if slot.pages or slot.shared:
            self.allocator.release(slot.pages + slot.shared)
        self._page_rows[slot.index, :] = -1
        self.cache = kvcache.set_page_row(
            self.cache, slot.index, self._page_rows[slot.index])
        self.slots[slot.index] = Slot(index=slot.index)

    # -- session parking (cross-request KV retention) ------------------------

    def _publish_index(self, row: np.ndarray, history: np.ndarray,
                       hashes=None) -> None:
        """Publish a finished sequence's full pages — generated span
        included — into the prefix index (content-addressed by token chain;
        the index takes one reference per adopted page).  Resident KV covers
        ``len(history) - 1`` tokens (the final sampled token was never
        consumed), and decode-written KV is bitwise prefill KV, so every
        full page under that span is exactly what a re-prefill of the same
        tokens would produce.  ``hashes`` reuses the request's cached prompt
        chain when it already covers the span (the chain property makes the
        prompt hashes a prefix of the history hashes)."""
        full = (len(history) - 1) // self.page_size
        if not full:
            return
        if hashes is None or len(hashes) < full:
            hashes = kvcache.page_hashes(history[: full * self.page_size],
                                         self.page_size)
        pids = [int(row[i]) for i in range(full)]
        self.prefix_index.publish(hashes[:full], pids, self.allocator)
        if self.index_journal:
            # persist the published entries: each full page's contents go to
            # the shared store under its chain hash, so a successor worker
            # can re-adopt this prefix after this worker dies
            self.index_journal_puts += self.prefix_index.journal(
                zip(hashes[:full], pids, strict=True), self.blob_store,
                lambda ids: jax.device_get(
                    self._extract(self.cache, jnp.asarray(ids, jnp.int32))))

    def _park_slot(self, slot: Slot, req: _Request, tokens: np.ndarray) -> None:
        """Park a DRAINED slot: ownership of every mapped page transfers to
        the session's journal record, full pages are published to the prefix
        index, and the slot enters PARKED with its device row unmapped (so
        its masked decode traffic can never touch the journal)."""
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        history = np.concatenate([prompt,
                                  np.asarray(tokens, np.int32).reshape(-1)])
        consumed = slot.len
        row = self._page_rows[slot.index].copy()
        self._reserved -= slot.need - len(slot.pages)
        if self._index_sharing:
            self._publish_index(row, history, hashes=req.hashes)
        old = self._parked.pop(req.session, None)
        if old is not None:
            self._drop_record(old)          # superseded journal
        self._parked[req.session] = ParkedSession(
            session=req.session, history=history, consumed=consumed,
            page_row=row, pages=slot.pages + slot.shared, slot=slot.index,
            parked_step=self.steps)
        self._page_rows[slot.index, :] = -1
        self.cache = kvcache.set_page_row(
            self.cache, slot.index, self._page_rows[slot.index])
        slot.to(SlotState.PARKED)
        slot.session = req.session
        slot.parked_step = self.steps
        slot.req = None
        slot.pages, slot.shared = [], []
        slot.need = 0
        self.parks += 1

    def _evict_parked_slot(self, rec: ParkedSession) -> None:
        """Reclaim a parked slot for a new admission: snapshot its rows to
        the host (lengths + recurrent state; the pages stay resident, owned
        by the record) and free the slot."""
        slot = self.slots[rec.slot]
        rec.state = jax.device_get(self._gather_state(self.cache, rec.slot))
        slot.to(SlotState.EMPTY)
        self.slots[rec.slot] = Slot(index=rec.slot)
        rec.slot = None
        self.park_evictions += 1

    def _offload_parked(self, rec: ParkedSession) -> None:
        """Pool pressure: push a parked journal's pages to the blob store
        (position-ordered, like a preemption) and release the references —
        the session's next request restores the blob instead of
        re-prefilling, paying a storage GET for prompt-length compute."""
        if rec.slot is not None:
            self._evict_parked_slot(rec)
        npg = -(-rec.consumed // self.page_size)
        phys = [int(rec.page_row[i]) for i in range(npg)]
        blob = jax.device_get(
            self._extract(self.cache, jnp.asarray(phys, jnp.int32)))
        key = f"park/{self.blob_ns}{rec.session}/s{self.steps}"
        self.blob_store.put(key, blob, kvcache.blob_nbytes(blob))
        rec.blob_key = key
        rec.blob_pidx = list(range(npg))
        self.allocator.release(rec.pages)
        rec.pages = []
        self.park_offloads += 1

    def _drop_record(self, rec: ParkedSession) -> None:
        """Forget a journal (superseded, diverged, expired, or reclaimed):
        release its page references and delete its blob; a still-resident
        slot goes back to EMPTY."""
        if rec.slot is not None:
            slot = self.slots[rec.slot]
            slot.to(SlotState.EMPTY)
            self.slots[rec.slot] = Slot(index=rec.slot)
        if rec.pages:
            self.allocator.release(rec.pages)
        if rec.blob_key:
            self.blob_store.delete(rec.blob_key)

    def _expire_parked(self) -> None:
        for session, rec in list(self._parked.items()):
            if self.steps - rec.parked_step > self.park_ttl_steps:
                self._drop_record(self._parked.pop(session))
                self.park_expirations += 1

    # -- fleet hooks (worker drain / cold start) -----------------------------

    def externalize_session(self, session: str) -> ParkedSession:
        """Fleet drain: detach one parked journal from this worker entirely.
        The record's pages are pushed to the (shared) blob store if still
        resident, its slot is reclaimed, and the record — now pure host data
        plus a blob key — is popped and returned for the controller to hand
        to a successor worker.  After this the worker holds no reference to
        the session."""
        rec = self._parked.pop(session)
        if rec.pages:
            self._offload_parked(rec)
        return rec

    def adopt_parked(self, rec: ParkedSession) -> None:
        """Fleet routing: install an externalized (blob-resident) journal so
        the next admission for its session restores from the shared store
        instead of re-prefilling.  The record must hold no pool references —
        those died with the worker that wrote it."""
        if rec.pages or rec.slot is not None:
            raise ValueError(
                f"adopting session {rec.session!r} with live pool state "
                "(pages/slot are worker-local and do not transfer)")
        rec.parked_step = self.steps
        self._parked[rec.session] = rec

    def adopt_index_journal(self) -> int:
        """Worker cold start: re-adopt journaled prefix-index entries from
        the shared blob store into this fresh pool (allocate, scatter, adopt
        — the alloc-time reference transfers to the index).  Bounded so
        adoption always leaves at least one slot's worst case uncommitted;
        index pages are reclaimable cache either way, so a skipped entry
        only costs a re-prefill."""
        if not self.index_journal:
            return 0

        def install(pid, blob):
            self.cache = self._inject(self.cache,
                                      jnp.asarray([pid], jnp.int32),
                                      self._stage_put(blob))

        n = self.prefix_index.rebuild(
            self.blob_store, self.allocator,
            budget=lambda: self._uncommitted() - self.max_pages,
            install=install)
        self.index_adopted += n
        return n

    def _reclaim_pool(self, need: int, keep: Optional[ParkedSession] = None,
                      pinned: Sequence[int] = ()) -> None:
        """Pool-gated admission: reclaim shareable capacity cheapest-first —
        drop LRU prefix-index references (free if nobody else maps the
        page), then offload parked journals to the blob store, oldest
        first.  ``keep`` is the record the admission itself consumes and
        ``pinned`` the index pages its plan is about to map."""
        self.prefix_index.evict(self.allocator, self._reserved + need,
                                pinned=pinned)
        if self._uncommitted() >= need:
            return
        for rec in sorted((r for r in self._parked.values()
                           if r.pages and r is not keep),
                          key=lambda r: r.parked_step):
            if self._uncommitted() >= need:
                break
            self._offload_parked(rec)

    # -- preemption / restore (storage-backed slot reclamation) -----------------

    def _preempt_for(self, need: int) -> bool:
        """Free at least ``need - uncommitted`` pages by preempting ACTIVE
        victims; all-or-nothing (a partial eviction would pay the offload
        transfer without unblocking the admission)."""
        if self.preempt_policy != "pressure":
            return False
        deficit = need - self._uncommitted()
        victims = [s for s in self.slots
                   if s.state is SlotState.ACTIVE and s.pages
                   and s.age(self.steps) >= self.idle_preempt_steps]
        # idleness-driven ranking: the longest-resident slot first (the
        # mostly-idle long-runner), then the one pinning the most pages
        victims.sort(key=lambda s: (s.age(self.steps), len(s.pages)),
                     reverse=True)
        chosen, freed = [], 0
        for v in victims:
            if freed >= deficit:
                break
            chosen.append(v)
            freed += v.need   # eviction releases pages AND reservation
        if freed < deficit:
            return False
        for v in chosen:
            self._preempt(v)
        return True

    def preempt(self, index: int) -> None:
        """Preempt one ACTIVE slot now (the policy calls this; exposed so
        tests and drivers can force a preemption point)."""
        self._preempt(self.slots[index])

    def _preempt(self, slot: Slot) -> None:
        slot.to(SlotState.PREEMPTED)
        row = self._page_rows[slot.index]
        pidx = [i for i in range(self.max_pages) if row[i] >= 0]
        phys = [int(row[i]) for i in pidx]
        # extract in logical order and stage to host: the blob is position-
        # ordered no matter how scrambled the physical table was
        blob = jax.device_get(
            self._extract(self.cache, jnp.asarray(phys, jnp.int32)))
        nbytes = kvcache.blob_nbytes(blob)
        key = f"kv/{self.blob_ns}{slot.req.request_id}/p{slot.preempts}"
        self.blob_store.put(key, blob, nbytes)
        slot.blob_key = key
        slot.blob_pidx = pidx
        slot.restore_i = 0
        slot.preempts += 1
        # release the slot's whole pool commitment: page references dropped
        # (owned pages free; shared prefix pages keep their other holders),
        # unmapped growth back to the uncommitted margin.  The restore era
        # owns every page it injects — the blob covers shared prefix pages
        # too — so the reservation grows back to the full worst case.
        self._reserved -= slot.need - len(slot.pages)
        self.allocator.release(slot.pages + slot.shared)
        slot.pages = []
        slot.shared = []
        slot.need = self._pages_needed(slot.req)
        self._page_rows[slot.index, :] = -1
        self.cache = kvcache.set_page_row(
            self.cache, slot.index, self._page_rows[slot.index])
        self._preempted_order.append(slot.index)
        self.preemptions += 1
        self.offload_pages += len(phys)

    def _start_restores(self) -> None:
        """Fund restores FIFO in preemption order: a later blob must not
        overtake an earlier one (its session would see out-of-order work)."""
        for idx in list(self._preempted_order):
            slot = self.slots[idx]
            if self._uncommitted() < slot.need:
                # retention must never starve a restore: index references
                # and parked journals are reclaimable cache, a preempted
                # request is real work.  Without this the drain livelocks
                # once retention holds the whole pool (nothing else calls
                # the reclaim path when the pending queue is empty).
                self._reclaim_pool(slot.need)
            if self._uncommitted() < slot.need:
                break
            slot.to(SlotState.RESTORING)
            self._reserved += slot.need
            slot.blob = self.blob_store.get(slot.blob_key)
            self._preempted_order.remove(idx)
            self.restores += 1

    def _run_restore_chunk(self, slot: Slot) -> None:
        """Inject one chunk of a restoring slot's blob: allocate fresh
        physical pages, scatter the blob slice into them, re-map the page
        table.  The final chunk reactivates the slot — it rejoins the decode
        batch the same step, like an admission whose last chunk landed."""
        n = len(slot.blob_pidx)
        hi = min(slot.restore_i + (self._restore_chunk_pages or n), n)
        phys = []
        for j in range(slot.restore_i, hi):
            pid = self.allocator.alloc(1)[0]
            self._reserved -= 1
            slot.pages.append(pid)
            self._page_rows[slot.index, slot.blob_pidx[j]] = pid
            phys.append(pid)
        piece = kvcache.slice_page_blob(slot.blob, slot.restore_i, hi)
        self.cache = self._inject(self.cache, jnp.asarray(phys, jnp.int32),
                                  self._stage_put(piece))
        self.cache = kvcache.set_page_row(
            self.cache, slot.index, self._page_rows[slot.index])
        self.restored_pages += hi - slot.restore_i
        slot.restore_i = hi
        self.restore_chunks += 1
        if hi == n:
            self.blob_store.delete(slot.blob_key)
            slot.blob = None
            slot.blob_key = None
            slot.blob_pidx = []
            slot.to(SlotState.ACTIVE)
            slot.active_since = self.steps

    def drain_offload_ops(self) -> list:
        """Storage ops since the last drain — the frontend bills these under
        the calibrated obj_read/obj_write latency + Table-4 cost models."""
        return self.blob_store.drain_ops()

    def _prepare_write_span(self, slot: Slot, pos0: int, count: int) -> int:
        """Make the pages under ``[pos0, pos0 + count)`` writable for this
        slot: map unmapped pages (alloc-on-write, within the reservation)
        and copy-on-write split any mapped page that another reference
        still reads — the writer gets a private copy on a fresh page and
        remaps only its own table row, so the prefix index / parked journal
        / sibling slot keeps reading the original bytes.  Returns the number
        of pages it mapped."""
        mapped = 0
        hi = min((pos0 + count - 1) // self.page_size, self.max_pages - 1)
        for pidx in range(pos0 // self.page_size, hi + 1):
            pid = int(self._page_rows[slot.index, pidx])
            if pid < 0:
                if len(slot.pages) < slot.need:
                    self._map_page(slot, pidx)
                    mapped += 1
                # else: reservation exhausted — the dangling final write
                # past it scatters out of bounds and is dropped
            elif self.allocator.refcount(pid) > 1:
                new = self.allocator.alloc(1)[0]
                if pid in slot.shared:
                    # the split of a shared prefix page was part of this
                    # admission's reservation (need counts every writable page)
                    self._reserved -= 1
                    slot.shared.remove(pid)
                else:
                    # an owned page some external holder (index/journal) still
                    # references: swap it out, reservation-neutral
                    slot.pages.remove(pid)
                slot.pages.append(new)
                self.cache = self._copy_pages(
                    self.cache, jnp.asarray([pid], jnp.int32),
                    jnp.asarray([new], jnp.int32))
                self.allocator.release([pid])
                self._page_rows[slot.index, pidx] = new
                self.cow_splits += 1
                mapped += 1
        if mapped:
            self.cache = kvcache.set_page_row(
                self.cache, slot.index, self._page_rows[slot.index])
        return mapped

    def _run_chunk(self, slot: Slot) -> None:
        """One prefill chunk for one admitting slot (alloc-on-write: map the
        pages the chunk's span touches — CoW-splitting any shared boundary
        page — then a B=1 forward against the shared pool).  The final
        chunk's logits seed the slot's first token."""
        with span("chunk", request_id=slot.req.request_id):
            chunk = slot.chunks[slot.chunk_i]
            C = len(chunk)
            pos0 = slot.len
            if self._has_kv:
                self._prepare_write_span(slot, pos0, C)
            prev = _kv_leaf(self.cache)
            logits, self.cache = self._chunk(
                self.params, self.cache, jnp.asarray(chunk)[None], slot.index)
            self._count_donation(prev)
            slot.len += C
            slot.chunk_i += 1
            self.prefill_tokens += C
            self.prefill_chunks += 1
            if slot.chunk_i == len(slot.chunks):
                tok = self._sample(logits[:, -1])
                self.last_tokens = self.last_tokens.at[slot.index].set(tok[0])
                self.out_buf = self.out_buf.at[slot.index, 0].set(tok[0])
                self.out_pos = self.out_pos.at[slot.index].set(1)
                slot.to(SlotState.ACTIVE)
                slot.active_since = self.steps
                slot.n_out = 1
                slot.chunks = None
                self.admitted += 1
                if self._spec:
                    # one host sync per admission: the draft starts from scratch
                    # on the full canonical stream (prompt + first sampled token)
                    slot.spec_last = int(tok[0])
                    slot.spec_pending = [int(t) for t in
                                         np.asarray(slot.req.prompt,
                                                    np.int32).reshape(-1)]
                    slot.spec_pending.append(slot.spec_last)
                    slot.draft_len = 0
                    self.draft_cache["length"] = (
                        self.draft_cache["length"].at[slot.index].set(0))

    def _count_donation(self, prev) -> None:
        """Count a decode or chunk dispatch as donated when its input's KV
        leaf is gone (JAX deletes a donated input at dispatch: a host
        attribute read, no sync), else as copied."""
        if prev is None:
            return
        if prev.is_deleted():
            self.pool_donated_steps += 1
        else:
            self.pool_copied_steps += 1

    # -- decode loop ---------------------------------------------------------------

    def _sample(self, logits: jnp.ndarray) -> jnp.ndarray:
        """Host-side sampling: advances the scheduler's PRNG state, then
        defers to the pure helper.  Never called from traced code — the
        jitted step takes its subkey as an argument instead."""
        key = None
        if self.temperature > 0.0:
            self._key, key = jax.random.split(self._key)
        return self._sample_pure(logits, key)

    def _sample_pure(self, logits: jnp.ndarray, key) -> jnp.ndarray:
        """Trace-safe sampling: no host state touched, key passed in."""
        if self.temperature <= 0.0:
            return sampling.greedy(logits)
        return sampling.temperature_sample(key, logits, self.temperature,
                                           self.top_k)

    def _step_impl(self, params, cache, last_tokens, out_buf, out_pos, active, key):
        """Jitted: decode one token per *active* slot, sample, append to the
        output ring.  Pure device program — nothing returns to the host.

        ``active`` (n_slots,) bool masks freed, mid-admission, and preempted
        slots out of the token write, the output-ring advance, and every
        per-slot cache row: without the mask a stale slot keeps advancing its
        length and evolving its recurrent state, which corrupts the pool
        pages (and the admission-in-progress) that position now belongs to.
        """
        from ..dist import sharding as shd

        with _policy_scope(self._policy):
            logits, new_cache = self.model.decode_step(params, cache,
                                                       last_tokens[:, None])
            new_cache = kvcache.mask_slot_rows(new_cache, cache, active)
            new_cache = shd.constrain_tree(new_cache, self.cache_specs,
                                           getattr(self._policy, "mesh", None))
            toks = self._sample_pure(logits[:, -1], key)
            toks = jnp.where(active, toks, last_tokens)
            b = jnp.arange(self.n_slots, dtype=jnp.int32)
            # inactive rows scatter out of bounds -> dropped
            col = jnp.where(active, out_pos % self.max_seq, self.max_seq)
            out_buf = out_buf.at[b, col].set(toks)
        return new_cache, toks, out_buf, out_pos + active.astype(jnp.int32)

    def _spec_round(self, active: List[int]) -> None:
        """One draft-and-verify round over the ACTIVE slots: the draft
        proposes ``spec_k`` tokens per slot, the target scores all of them
        in one chunked step against the shared paged pool, and each slot
        emits the accepted prefix plus the target's bonus/correction token
        (1..spec_k+1 tokens per round, token-for-token what S=1 decode
        would emit).

        Host/device discipline: one device->host sync per round (the
        accepted counts + emitted tokens).  Draft catch-up chunks replay the
        canonical tokens the draft has not consumed — the whole prompt on a
        fresh admission, 1-2 tokens per round thereafter — and rejected
        proposals rewind the draft row's length, so the draft cache tracks
        exactly the canonical stream.

        Rollback on rejection: KV pages need no copy — the verify chunk's
        over-run positions sit past the rewound length (invalid to every
        read) and the next round's chunk overwrites them before they can
        become visible; pages mapped or CoW-split for the span stay with
        the slot (refcount/free-list state is untouched by a reject).
        Hybrid recurrent rows DID advance through rejected tokens, so a
        partial accept restores the pre-verify row snapshot and replays the
        accepted span through the chunk path — bitwise the same KV and
        recurrent state, by the chunk-prefix property."""
        k = self.spec_k
        spec = [self.slots[i] for i in active]
        mask = np.zeros((self.n_slots,), bool)
        mask[active] = True
        mask_dev = jnp.asarray(mask)
        # 1) draft catch-up on the canonical stream: ONE batched masked
        #    dispatch over every slot's pending span (back-padded to the
        #    round's widest; each row advances by its own count).  Replaces
        #    the per-slot B=1 chunks — a round's catch-up no longer costs
        #    one dispatch per active slot.
        W = max((len(st.spec_pending) for st in spec), default=1)
        tok_rows = np.zeros((self.n_slots, W), np.int32)
        cnt_rows = np.ones((self.n_slots,), np.int32)
        for st in spec:
            n = len(st.spec_pending)
            tok_rows[st.index, :n] = st.spec_pending
            cnt_rows[st.index] = n
        self.draft_cache, draft_last = self._draft_catchup(
            self.draft_params, self.draft_cache, jnp.asarray(tok_rows),
            jnp.asarray(cnt_rows), mask_dev)
        for st in spec:
            st.draft_len += len(st.spec_pending)
            st.spec_pending = []
        # 2) k-1 batched draft steps finish the proposal window
        cols = [draft_last]
        for _ in range(k - 1):
            self.draft_cache, draft_last = self._draft_step(
                self.draft_params, self.draft_cache, draft_last, mask_dev)
            cols.append(draft_last)
        drafts = jnp.stack(cols, axis=1)                     # (n_slots, k)
        # 3) make the verify span writable (alloc-on-write + CoW split,
        #    same as decode growth but k+1 positions at once) and clamp
        #    each slot's acceptance so it cannot overrun its max_new budget
        k_eff = np.zeros((self.n_slots,), np.int32)
        for st in spec:
            self._prepare_write_span(st, st.len, k + 1)
            k_eff[st.index] = min(k, st.req.max_new - st.n_out - 1)
        verify_tokens = jnp.concatenate(
            [self.last_tokens[:, None], drafts], axis=1)     # (n_slots, k+1)
        hybrid = self.model.cfg.family == "hybrid"
        pre_cache = self.cache if hybrid else None
        (self.cache, y, a_vec, self.out_buf, self.out_pos,
         self.last_tokens) = self._verify(
            self.params, self.cache, verify_tokens, mask_dev,
            jnp.asarray(k_eff), self.out_buf, self.out_pos, self.last_tokens)
        y_h, a_h = jax.device_get((y, a_vec))     # the round's one host sync
        for st in spec:
            i = st.index
            a = int(a_h[i])
            if hybrid and a < k:
                # recurrent rows consumed all k+1 verify tokens; restore the
                # pre-verify snapshot and replay the canonical span (the
                # pending token + the a accepted drafts) through the chunk
                # path.  KV under the replay is rewritten bitwise-identically
                # (chunk-prefix property), so pages need no rollback.
                state = self._gather_state(pre_cache, i)
                self.cache = self._scatter_state(self.cache, i, state)
                replay = [st.spec_last] + [int(t) for t in y_h[i, :a]]
                _, self.cache = self._chunk(
                    self.params, self.cache,
                    jnp.asarray(replay, jnp.int32)[None], i)
            emitted = a + 1
            st.n_out += emitted
            st.len += emitted
            st.spec_last = int(y_h[i, a])
            adv = min(a, k - 1)       # drafts d1..d_adv proved canonical
            st.draft_len += adv
            # canonical tokens the draft has not consumed: y[adv..a]
            st.spec_pending = [int(t) for t in y_h[i, adv:a + 1]]
            self.spec_accepted += a
            self.spec_emitted += emitted
            self.decode_tokens += emitted
        # rejected proposals rewind the draft rows to the canonical length
        idx = jnp.asarray([st.index for st in spec], jnp.int32)
        vals = jnp.asarray([st.draft_len for st in spec], jnp.int32)
        self.draft_cache["length"] = self.draft_cache["length"].at[idx].set(vals)
        self.spec_proposed += k * len(spec)
        self.spec_rounds += 1

    def step(self) -> List[CompletedRequest]:
        """One scheduler tick: at most one prefill chunk (round-robin over
        admitting slots) and one restore chunk (round-robin over restoring
        slots), then one batched decode step — or, with speculation on, one
        draft-and-verify round — over the active slots; returns the requests
        that completed this step (their slots are refilled from the pending
        list before returning)."""
        with span("step", step=self.steps):
            self._fill_slots()
            admitting = [s for s in self.slots if s.state is SlotState.ADMITTING]
            if admitting:
                pick = admitting[self._chunk_rr % len(admitting)]
                self._chunk_rr += 1
                self._run_chunk(pick)
            restoring = [s for s in self.slots if s.state is SlotState.RESTORING]
            if restoring:
                pick = restoring[self._restore_rr % len(restoring)]
                self._restore_rr += 1
                self._run_restore_chunk(pick)
            active = [s.index for s in self.slots if s.decoding]
            if not active:
                return []
            if self._spec:
                self._spec_round(active)
            else:
                if self.kv_mode == "paged" and self._has_kv:
                    # alloc-on-write for decode growth: make the page this step's
                    # token write lands in writable — map it if unmapped (within
                    # the reservation; the final step's dangling write past it is
                    # dropped by the unmapped table), CoW-split it if shared
                    with span("grow") as ids:
                        mapped = 0
                        for i in active:
                            st = self.slots[i]
                            mapped += self._prepare_write_span(st, st.len, 1)
                        if ids is not None:
                            ids["pages"] = mapped
                with span("decode"):
                    mask = np.zeros((self.n_slots,), bool)
                    mask[active] = True
                    self._key, sub = jax.random.split(self._key)
                    prev = _kv_leaf(self.cache)
                    self.cache, self.last_tokens, self.out_buf, self.out_pos = \
                        self._decode(
                            self.params, self.cache, self.last_tokens, self.out_buf,
                            self.out_pos, jnp.asarray(mask), sub)
                    self._count_donation(prev)
                self.decode_tokens += len(active)
                for i in active:
                    st = self.slots[i]
                    st.n_out += 1
                    if self.kv_mode == "paged":
                        st.len += 1
            self.steps += 1
            self.slot_steps += len(active)
            if self.kv_mode == "paged" and self._has_kv:
                self.page_step_sum += self.allocator.in_use
            return self._finish(active)

    def _finish(self, active: List[int]) -> List[CompletedRequest]:
        """Hand back the requests whose slots reached ``max_new`` this step:
        read each one's tokens from the output ring (a blocking device-to-host
        read), park or release its slot, then refill the freed slots."""
        with span("finish") as ids:
            done = [self.slots[i] for i in active
                    if self.slots[i].n_out >= self.slots[i].req.max_new]
            if ids is not None:
                ids["request_id"] = [st.req.request_id for st in done]
            finished: List[CompletedRequest] = []
            for st in done:
                req = st.req
                st.to(SlotState.DRAINED)
                tokens = np.asarray(self.out_buf[st.index, : req.max_new])
                finished.append(CompletedRequest(
                    session=req.session, request_id=req.request_id,
                    tokens=tokens,
                    admitted_step=st.admitted_step, finished_step=self.steps,
                    submitted_step=st.submitted_step, preempts=st.preempts,
                    reused_tokens=st.reused))
                if self.park_sessions:
                    self._park_slot(st, req, tokens)
                else:
                    if self._index_sharing:
                        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
                        self._publish_index(
                            self._page_rows[st.index],
                            np.concatenate([prompt, tokens.astype(np.int32)]),
                            hashes=req.hashes)
                    self._release_slot(st)
                self._active_sessions.discard(req.session)
                self.completed += 1
            if finished:
                self._fill_slots()
        return finished

    def reset(self, *, clear_blob_store: bool = True) -> None:
        """Abort all in-flight work (crash recovery: the queue layer
        redelivers; completed requests are deduped by the frontend).  The
        pool returns to fully free, every page-table row to unmapped, the
        blob store is emptied, and the prefix index and parked-session table
        are cleared — a redelivered admission replays from its prompt, never
        from an orphaned blob or another life's shared pages.

        ``clear_blob_store=False`` is the *fleet* recycle path: when this
        scheduler is one disposable worker over a store shared with its
        siblings, wiping the store would destroy other workers' spills and
        every externalized session journal / index entry — exactly the
        durable state scale-to-zero exists to keep.  The fleet controller
        garbage-collects a dead worker's namespaced keys itself."""
        self.slots = [s.force_empty() for s in self.slots]
        self.pending = []
        self._active_sessions.clear()
        self._preempted_order = []
        # replay determinism: the post-reset schedule must be a pure
        # function of the submitted work, not of the previous life's
        # round-robin phase or sampling-key position
        self._chunk_rr = 0
        self._restore_rr = 0
        self._key = jax.random.key(self._seed)
        # allocator.reset() below wipes every reference wholesale, so the
        # index and parked table just forget their entries
        self.prefix_index.clear()
        self._parked.clear()
        self.last_tokens = jnp.zeros((self.n_slots,), jnp.int32)
        self.out_buf = jnp.zeros((self.n_slots, self.max_seq), jnp.int32)
        self.out_pos = jnp.zeros((self.n_slots,), jnp.int32)
        if clear_blob_store:
            self.blob_store.clear()
        if self.kv_mode == "paged":
            self.allocator.reset()
            self._reserved = 0
            self._page_rows[:] = -1
            for slot in range(self.n_slots):
                self.cache = kvcache.cache_clear_slot(self.cache, slot)
        if self._spec:
            # draft rows replay from scratch at the next admission; zero
            # lengths so stale ring lanes are invalid until overwritten
            self.draft_cache["length"] = jnp.zeros_like(
                self.draft_cache["length"])

    # -- invariant audit (the differential harness calls this every step) ----------

    def audit(self) -> None:
        """Raise AssertionError if any allocator / refcount / reservation
        invariant is violated.  Checks: ``free + in_use == n_pages``; every
        mapped page has refcount >= 1; the refcount total equals the
        references actually held (slot owned + slot shared + parked journals
        + prefix index); no page is owned by two slots; every page-table row
        maps exactly the pages its slot holds; the reservation ledger equals
        the outstanding worst-case growth; parked records and PARKED slots
        point at each other consistently."""
        if not (self.kv_mode == "paged" and self._has_kv):
            return
        a = self.allocator
        a.check()
        refs = 0
        owned_seen: set = set()
        for s in self.slots:
            refs += len(s.pages) + len(s.shared)
            for p in s.pages:
                assert p not in owned_seen, f"page {p} owned by two slots"
                owned_seen.add(p)
        for rec in self._parked.values():
            refs += len(rec.pages)
        refs += len(self.prefix_index)
        assert refs == a.total_refs, (
            f"refcount drift: holders sum to {refs}, allocator says "
            f"{a.total_refs}")
        for s in self.slots:
            row = self._page_rows[s.index]
            mapped = {int(p) for p in row if p >= 0}
            held = set(s.pages) | set(s.shared)
            assert mapped == held, (
                f"slot {s.index} ({s.state.value}): row maps {mapped}, "
                f"holds {held}")
            for p in mapped:
                assert a.refcount(p) >= 1, f"slot {s.index} maps freed page {p}"
        reserved = sum(
            s.need - len(s.pages) for s in self.slots
            if s.state in (SlotState.ADMITTING, SlotState.ACTIVE,
                           SlotState.RESTORING))
        assert reserved == self._reserved, (
            f"reservation ledger drift: slots imply {reserved}, "
            f"ledger says {self._reserved}")
        assert self._uncommitted() >= 0, (
            f"over-committed pool: {self._reserved} reserved, "
            f"{a.free_count} free")
        for session, rec in self._parked.items():
            if rec.slot is not None:
                s = self.slots[rec.slot]
                assert s.state is SlotState.PARKED and s.session == session, (
                    f"parked record {session} points at slot {rec.slot} "
                    f"in state {s.state.value} (session {s.session})")
            assert bool(rec.blob_key) != bool(rec.pages) or not rec.pages, (
                f"parked record {session} is both resident and offloaded")
        parked_sessions = {s.session for s in self.slots if s.parked}
        for sess in parked_sessions:
            assert sess in self._parked and self._parked[sess].slot is not None, (
                f"PARKED slot for session {sess} has no resident record")

    # -- reporting ------------------------------------------------------------------

    def occupancy(self) -> float:
        """Mean active slots per decode step (the batching lever)."""
        return self.slot_steps / self.steps if self.steps else 0.0

    def pool_occupancy(self) -> float:
        """Mean fraction of the pool in use per decode step."""
        if not (self.kv_mode == "paged" and self._has_kv and self.steps
                and self.n_pages):
            return 0.0
        return self.page_step_sum / (self.steps * self.n_pages)

    def kv_memory_stats(self) -> Dict[str, float]:
        """KV bytes: allocated pool/ring footprint and the live high-water
        mark (what the pool actually touched — the paged-vs-ring lever)."""
        per_token = kvcache.kv_bytes_per_token(self.cache)
        if self.kv_mode == "paged":
            return {
                "kv_bytes_per_token": per_token,
                "kv_pool_bytes": per_token * self.n_pages * self.page_size,
                "kv_high_water_bytes":
                    per_token * self.allocator.high_water * self.page_size,
                "kv_pages": self.n_pages,
                "kv_pages_high_water": self.allocator.high_water,
                "kv_pages_in_use": self.allocator.in_use,
                "kv_pool_occupancy": round(self.pool_occupancy(), 3),
            }
        ring_tokens = 0
        if self._has_kv:
            cache_len = getattr(self.model, "cache_len", None)
            ring_tokens = (cache_len(self.max_seq) if cache_len else self.max_seq)
        return {
            "kv_bytes_per_token": per_token,
            "kv_pool_bytes": per_token * self.n_slots * ring_tokens,
            "kv_high_water_bytes": per_token * self.n_slots * ring_tokens,
        }

    def offload_stats(self) -> Dict[str, float]:
        """Offload traffic gauges: preempt/restore counts, page counts, and
        the byte flows to/from the blob store (bytes_out = offloaded,
        bytes_in = restored)."""
        bs = self.blob_store
        return {
            "preemptions": self.preemptions,
            "restores": self.restores,
            "restore_chunks": self.restore_chunks,
            "offload_pages": self.offload_pages,
            "restored_pages": self.restored_pages,
            "offload_puts": bs.puts,
            "offload_gets": bs.gets,
            "offload_bytes": bs.bytes_out,
            "restore_bytes": bs.bytes_in,
            "offload_stored_bytes": bs.bytes_stored,
            "offload_stored_high_water_bytes": bs.high_water_bytes,
        }

    def sharing_stats(self) -> Dict[str, float]:
        """Prefix-sharing / parking gauges: prompt tokens served from
        resident pages instead of re-prefilled, hit/miss counts, CoW
        splits, and the parked-retention flows."""
        return {
            "shared_prefix_tokens": self.shared_prefix_tokens,
            "park_hits": self.park_hits,
            "park_misses": self.park_misses,
            "index_hits": self.index_hits,
            "cow_splits": self.cow_splits,
            "parks": self.parks,
            "park_evictions": self.park_evictions,
            "park_offloads": self.park_offloads,
            "park_expirations": self.park_expirations,
            "parked_sessions": len(self._parked),
            "index_pages": len(self.prefix_index),
            "index_journal_puts": self.index_journal_puts,
            "index_adopted": self.index_adopted,
        }

    def spec_stats(self) -> Dict[str, float]:
        """Speculation gauges: acceptance rate (accepted / proposed drafts)
        and verify steps per emitted token (1.0 = no speedup; 1/(k+1) =
        every draft accepted) — the cost lever is that one verify round
        prices like one decode step but emits up to k+1 tokens."""
        return {
            "spec_k": self.spec_k,
            "spec_rounds": self.spec_rounds,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "spec_emitted": self.spec_emitted,
            "spec_acceptance_rate": round(
                self.spec_accepted / self.spec_proposed, 4)
                if self.spec_proposed else 0.0,
            "spec_steps_per_token": round(
                self.spec_rounds / self.spec_emitted, 4)
                if self.spec_emitted else 0.0,
        }

    def stats(self) -> Dict[str, float]:
        out = {
            "steps": self.steps,
            "occupancy": round(self.occupancy(), 3),
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "admitted": self.admitted,
            "completed": self.completed,
            "kv_mode": self.kv_mode,
            "attn_backend": self.attn_backend,
            "pool_donated_steps": self.pool_donated_steps,
            "pool_copied_steps": self.pool_copied_steps,
        }
        if self.kv_mode == "paged":
            out["prefill_chunks"] = self.prefill_chunks
        if self.offload:
            out.update(self.offload_stats())
        if self.prefix_sharing or self.park_sessions:
            out.update(self.sharing_stats())
        if self._spec:
            out.update(self.spec_stats())
        return out
