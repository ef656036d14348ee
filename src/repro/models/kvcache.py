"""Decode-time caches: per-slot rings and the shared paged-block KV pool.

Two storage layouts behind one layer-level interface
(:func:`cache_update_layer` / :func:`cache_kv_view` dispatch on the dict
keys):

**Ring** (the classic layout).  A cache layer is a dict:
  k, v      : (B, T, Hkv, D)  ring buffer (T = window for SWA archs)
  positions : (B, T) int32    absolute position stored in each slot (-1 empty)
Memory is reserved at worst case: every row owns ``T`` slots whether the
sequence is 3 tokens or 3000.

**Paged pool** (continuous-batching serving).  One shared pool per layer plus
a per-slot page table:
  kp, vp     : (n_pages, page_size, Hkv*D)   shared block pool
  page_table : (B, max_pages) int32          slot's logical->physical map
                                             (-1 = unmapped)
A token's Hkv x D values are one minor dim.  The TPU lays an array out to
avoid padding its minor dims: with ``(..., Hkv, D)`` minor and ``D`` under
128 (minicpm-2b: 36 x 64) it would store the page dim minor-most, where a
page is no contiguous block and no gather or scatter by page runs in place —
every step would convert the whole pool to page-major order and back.
Token at absolute position ``p`` of slot ``b`` lives at
``kp[page_table[b, p // page_size], p % page_size]``.  Pages are handed out
by the host-side :class:`PageAllocator` (alloc-on-write, free-on-completion),
so pool memory scales with *live tokens* instead of ``n_slots * max_seq``.
Validity is derived, not stored: lane ``t`` is attendable iff its page is
mapped and ``t < upto`` (the caller's live length) — no positions array.
Writes to unmapped pages are dropped (the physical index is pushed out of
bounds and JAX scatters drop OOB updates), so a freed slot's stale decode
traffic can never corrupt a page that now belongs to another slot.

Both layouts are stacked over layers (leading L dim).  The layer scan
carries the stacked leaves whole and addresses its own layer by index
(``layer=`` on :func:`cache_update_layer` / :func:`cache_kv_view`): each
layer scatters its tokens at ``(layer, ...)`` and gathers its view back, so
no layer's slice of the cache is copied out and a donated cache is updated in
place.  ``layer=None`` takes one unstacked layer.  The page table is
replicated per layer (int32, negligible).  ``pos`` may be a scalar or a
``(B,)`` vector exactly as before.

The paged view gathers pages in *logical* order, so when no ring wrap has
occurred the gathered (B, max_pages*page_size, Hkv, D) tensor is lane-for-
lane identical to the ring view and attention results match bit-for-bit —
the property the paged parity suite pins.
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import COMPUTE_DTYPE

# Leaf keys of the shared page pool: no slot axis, never sliced or masked
# per-slot.
POOL_KEYS = frozenset({"kp", "vp"})


def init_attn_cache(n_layers: int, B: int, T: int, n_kv: int, head_dim: int) -> Dict:
    return {
        "k": jnp.zeros((n_layers, B, T, n_kv, head_dim), COMPUTE_DTYPE),
        "v": jnp.zeros((n_layers, B, T, n_kv, head_dim), COMPUTE_DTYPE),
        "positions": -jnp.ones((n_layers, B, T), jnp.int32),
        "length": jnp.zeros((), jnp.int32),  # absolute position of next token
    }


def decode_positions(pos, B: int, S: int) -> jnp.ndarray:
    """(B, S) absolute query positions for a decode step.

    ``pos`` is the scalar shared length or a ``(B,)`` per-slot length vector.
    """
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        pos = pos[:, None]
    return jnp.broadcast_to(pos + jnp.arange(S, dtype=jnp.int32), (B, S))


def is_paged(layer_cache: Dict) -> bool:
    return "kp" in layer_cache


def cache_capacity(layer_cache: Dict) -> int:
    """Static token capacity of one row of a layer cache (ring T, or the
    page table's logical span for the pool)."""
    if is_paged(layer_cache):
        return layer_cache["page_table"].shape[-1] * layer_cache["kp"].shape[-2]
    return layer_cache["k"].shape[-3]


def _at(layer) -> tuple:
    """Index prefix addressing one layer of a stacked leaf (none unstacked)."""
    return () if layer is None else (layer,)


def layer_view(layer_cache: Dict, layer) -> Dict:
    """One layer's (unstacked) slice of a stacked layer cache; ``layer=None``
    passes an unstacked cache through."""
    if layer is None:
        return layer_cache
    return {k: v[layer] for k, v in layer_cache.items()}


def cache_update_layer(layer_cache: Dict, k_new: jnp.ndarray, v_new: jnp.ndarray,
                       pos: jnp.ndarray, layer=None) -> Dict:
    """Insert S_new tokens at absolute position ``pos``.

    Ring layout scatters into per-row ring slots (``pos % T``); paged layout
    routes each token through the page table into the shared pool.
    layer_cache k/v or kp/vp as documented above; k_new/v_new: (B, S, Hkv, D).
    ``pos`` scalar (lockstep batch) or (B,) (per-slot continuous batching).
    ``layer`` (int or traced scalar) scatters into that layer's rows of a
    stacked cache and returns the whole stacked leaves.
    """
    if is_paged(layer_cache):
        return _paged_update_layer(layer_cache, k_new, v_new, pos, layer)
    k, v, positions = layer_cache["k"], layer_cache["v"], layer_cache["positions"]
    T = k.shape[-3]
    B, S = k_new.shape[0], k_new.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    if S > T:
        # prefill longer than the (windowed) cache: only the trailing T
        # tokens can ever be attended to — drop the rest (static slice, and
        # it keeps the ring scatter free of duplicate slots).
        k_new, v_new = k_new[:, -T:], v_new[:, -T:]
        pos = pos + (S - T)
        S = T
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    abs_pos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]      # (B, S)
    at = _at(layer) + (jnp.arange(B, dtype=jnp.int32)[:, None], abs_pos % T)
    return {"k": k.at[at].set(k_new.astype(k.dtype)),
            "v": v.at[at].set(v_new.astype(v.dtype)),
            "positions": positions.at[at].set(abs_pos)}


def _paged_update_layer(layer_cache: Dict, k_new: jnp.ndarray, v_new: jnp.ndarray,
                        pos: jnp.ndarray, layer) -> Dict:
    kp, vp, pt = layer_cache["kp"], layer_cache["vp"], layer_cache["page_table"]
    n_pages, page_size = kp.shape[-3], kp.shape[-2]
    max_pages = pt.shape[-1]
    B, S = k_new.shape[0], k_new.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    abs_pos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]      # (B, S)
    page_idx = abs_pos // page_size
    offset = abs_pos % page_size
    row = pt if layer is None else pt[layer]
    pid = jnp.take_along_axis(row, jnp.clip(page_idx, 0, max_pages - 1), axis=-1)
    # unmapped / out-of-table positions are pushed out of bounds: JAX drops
    # OOB scatter updates, so stale traffic from freed or admitting slots can
    # never land in a page it does not own.
    pid = jnp.where((page_idx < max_pages) & (pid >= 0), pid, n_pages)
    at = _at(layer) + (pid, offset)
    kp = kp.at[at].set(k_new.reshape(B, S, -1).astype(kp.dtype))
    vp = vp.at[at].set(v_new.reshape(B, S, -1).astype(vp.dtype))
    return {"kp": kp, "vp": vp, "page_table": pt}


def cache_kv_view(layer_cache: Dict, upto: Optional[jnp.ndarray] = None,
                  layer=None, n_kv: Optional[int] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (k, v, kv_positions, kv_valid) for sdpa().

    Ring layout reads the buffers directly (``positions`` doubles as the
    validity mask).  Paged layout gathers the slot's pages from the pool in
    logical order; ``upto`` (scalar or (B,) live length) and ``n_kv`` (the
    heads a token's Hkv*D values split into) are required there — lanes at
    or past ``upto``, and lanes on unmapped pages, are masked invalid.
    ``layer`` reads that layer of a stacked cache (the paged gather indexes
    ``(layer, page)`` on the stacked pool).
    """
    if is_paged(layer_cache):
        if upto is None or n_kv is None:
            raise ValueError("paged cache view needs `upto` (the live length) "
                             "and `n_kv`")
        return _paged_kv_view(layer_cache, upto, layer, n_kv)
    ring = layer_view(layer_cache, layer)
    pos = ring["positions"]
    return ring["k"], ring["v"], pos, pos >= 0


def paged_attn_decode(layer_cache: Dict, q: jnp.ndarray, pos, *,
                      window: Optional[int] = None,
                      k_new: Optional[jnp.ndarray] = None,
                      v_new: Optional[jnp.ndarray] = None,
                      include_new: bool = False) -> jnp.ndarray:
    """Fused table-indirect decode attention over the paged pool.

    The ``attn_backend='paged_kernel'`` alternative to
    ``cache_kv_view`` + ``sdpa_append``/``sdpa``: the Pallas kernel streams
    the slot's K/V pages straight from the pool via the scalar-prefetched
    page table — the gathered (B, T, Hkv, D) cache never materializes in
    HBM.  Read-only: ``_prepare_write_span`` / ``cache_update_layer`` still
    own every pool write, so CoW splits and ``mask_slot_rows`` freezing are
    untouched.  ``layer_cache`` is one layer's (unstacked).

    ``pos`` is the slot's live length pre-write (scalar or (B,)).  With
    ``k_new``/``v_new`` the just-projected token is appended in fp32 on top
    of the streamed softmax (the ``sdpa_append`` contract: attend the
    PRE-update pool + a rank-1 new-token term).  With ``include_new`` the
    token was already written into the pool (hybrid local-attention layers)
    and lane ``pos`` itself is attended instead.  q: (B, 1, H, D).
    """
    from ..dist.sharding import constrain, current_policy
    from ..kernels.paged_attention import paged_attention

    kp, vp, pt = layer_cache["kp"], layer_cache["vp"], layer_cache["page_table"]
    # the kernel reads (n_pages, page_size, Hkv, D) pages
    kp, vp = (x.reshape(x.shape[:-1] + (-1, q.shape[-1])) for x in (kp, vp))
    B = q.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    lengths = pos + 1 if include_new else pos
    policy = current_policy()
    if policy is not None and getattr(policy, "shard_map_pool", False):
        # shard_map decomposition over the lane-sharded pool: GSPMD cannot
        # partition the table-indirect pallas_call without all-gathering the
        # pool, so the per-shard kernel + softmax merge runs explicitly
        from ..kernels.paged_attention.ops import sharded_paged_attention
        out = sharded_paged_attention(q, kp, vp, pt, lengths, policy=policy,
                                      q_pos=pos, window=window, k_new=k_new,
                                      v_new=v_new)
    else:
        out = paged_attention(q, kp, vp, pt, lengths, q_pos=pos,
                              window=window, k_new=k_new, v_new=v_new)
    return constrain(out, "attn_out")


def _paged_kv_view(layer_cache: Dict, upto, layer, n_kv: int) -> Tuple[jnp.ndarray, ...]:
    kp, vp, pt = layer_cache["kp"], layer_cache["vp"], layer_cache["page_table"]
    n_pages, page_size, width = kp.shape[-3:]
    B, max_pages = pt.shape[-2:]
    T = max_pages * page_size
    row = pt if layer is None else pt[layer]
    at = _at(layer) + (jnp.clip(row, 0, n_pages - 1),)
    k = kp[at].reshape(B, T, n_kv, width // n_kv)
    v = vp[at].reshape(B, T, n_kv, width // n_kv)
    kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    upto = jnp.asarray(upto, jnp.int32)
    if upto.ndim == 0:
        upto = jnp.broadcast_to(upto, (B,))
    mapped = jnp.repeat(row >= 0, page_size, axis=-1)                     # (B, T)
    return k, v, kv_pos, mapped & (kv_pos < upto[:, None])


# ---------------------------------------------------------------------------
# Host-side page allocator (free list over the shared pool's page ids)
# ---------------------------------------------------------------------------


class PageAllocator:
    """Refcounted free-list allocator for the paged pool.

    Pure host-side bookkeeping: the device only ever sees the page table.
    Pages are a *shared* resource: :meth:`alloc` hands a page out with
    refcount 1, :meth:`share` takes an additional reference (a second slot
    mapping the page read-only, the prefix index publishing it, a parked
    session retaining it), and :meth:`release` drops one reference — the
    page only returns to the free list when its last reference dies.

    Invariants (pinned by the property tests and the scheduler's
    ``audit()``): ``free_count + in_use == n_pages`` at every point, every
    in-use page has refcount >= 1, no free page carries a refcount, no page
    is ever handed out twice, and :meth:`reset` returns the pool to fully
    free.
    """

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self._free: List[int] = list(range(n_pages - 1, -1, -1))  # pop() -> 0 first
        self._rc: Dict[int, int] = {}       # page -> reference count (mapped only)
        self.high_water = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return len(self._rc)

    @property
    def total_refs(self) -> int:
        """Sum of refcounts over mapped pages (== total mappings held by
        slots + prefix index + parked sessions; the audit cross-checks)."""
        return sum(self._rc.values())

    def refcount(self, page: int) -> int:
        return self._rc.get(page, 0)

    def alloc(self, n: int = 1) -> List[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"KV pool exhausted: need {n} pages, {len(self._free)} free "
                f"of {self.n_pages}")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._rc[p] = 1
        self.high_water = max(self.high_water, len(self._rc))
        return pages

    def share(self, pages: Sequence[int]) -> None:
        """Take one extra reference on each (already mapped) page."""
        for p in pages:
            if p not in self._rc:
                raise ValueError(f"sharing unmapped page {p}")
            self._rc[p] += 1

    def release(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; a page with no references left goes
        back to the free list."""
        for p in pages:
            rc = self._rc.get(p)
            if rc is None:
                raise ValueError(f"releasing unmapped page {p}")
            if rc == 1:
                del self._rc[p]
                self._free.append(p)
            else:
                self._rc[p] = rc - 1

    # Back-compat name: before refcounts, completion-time frees called this.
    free = release

    def check(self) -> None:
        """Raise if the allocator invariants do not hold."""
        if len(self._free) + len(self._rc) != self.n_pages:
            raise AssertionError(
                f"page leak: {len(self._free)} free + {len(self._rc)} mapped "
                f"!= {self.n_pages}")
        if any(rc < 1 for rc in self._rc.values()):
            raise AssertionError(f"mapped page with refcount < 1: {self._rc}")
        overlap = set(self._free) & set(self._rc)
        if overlap:
            raise AssertionError(f"pages both free and mapped: {overlap}")

    def reset(self) -> None:
        """Back to fully free; the high-water gauge restarts too, so
        post-crash stats describe the replayed run, not the aborted one."""
        self._free = list(range(self.n_pages - 1, -1, -1))
        self._rc.clear()
        self.high_water = 0


# ---------------------------------------------------------------------------
# Prefix index: content-addressed full pages for cross-request sharing
# ---------------------------------------------------------------------------


def page_hashes(tokens, page_size: int) -> List[bytes]:
    """Chain hashes of the *full* pages of a token sequence.

    ``h_i = H(h_{i-1} || tokens[i*ps : (i+1)*ps])`` — keyed on the whole
    token prefix, not the page content alone, so two sequences share a chain
    entry iff they agree on every token up to that page boundary.  Only full
    pages get a hash: a partial page's content is still growing and cannot
    be content-addressed.
    """
    arr = np.asarray(tokens, np.int32).reshape(-1)
    out: List[bytes] = []
    h = b"\x00" * 16
    for i in range(len(arr) // page_size):
        m = hashlib.blake2b(digest_size=16)
        m.update(h)
        m.update(arr[i * page_size:(i + 1) * page_size].tobytes())
        h = m.digest()
        out.append(h)
    return out


INDEX_JOURNAL_PREFIX = "index/"


class PrefixIndex:
    """Content-addressed map from token-chain hashes to resident pool pages.

    The FaaSKeeper/FaaSFS move applied to KV state: a full page whose tokens
    are fixed is an immutable journal entry, so it can be shared read-only by
    any request whose prompt carries the same token prefix.  The index holds
    **one allocator reference per published page** (taken via
    :meth:`PageAllocator.share` at publish time), which is what keeps a page
    alive after the slot that wrote it completes.  Pages are immutable once
    published: appends only ever write at ``pos >= length``, and a writer
    that must touch a shared page first copy-on-write splits it.

    Eviction is LRU over publish/hit order and only reclaims the *index's*
    reference — a page another slot or parked session still maps survives
    with its remaining refcount.

    The index itself is worker-local (physical page ids mean nothing outside
    one pool), but its *entries* are durable content: :meth:`journal` pushes
    each published page to a blob store under its chain hash, and
    :meth:`rebuild` re-adopts journaled pages into a fresh pool on worker
    start — how shared prefixes survive a fleet worker's death.
    """

    def __init__(self):
        self._pages: Dict[bytes, int] = {}       # chain hash -> physical page

    def __len__(self) -> int:
        return len(self._pages)

    @property
    def pages(self) -> List[int]:
        return list(self._pages.values())

    def has(self, h: bytes) -> bool:
        return h in self._pages

    @staticmethod
    def journal_key(h: bytes) -> str:
        """Blob-store key of one journaled entry: content-addressed by the
        token-chain hash, so concurrent workers journaling the same prefix
        write the same key with the same bytes (idempotent by construction —
        decode-written KV is bitwise prefill KV)."""
        return INDEX_JOURNAL_PREFIX + h.hex()

    def journal(self, pairs, blob_store, extract) -> int:
        """Persist ``(hash, physical page)`` entries to ``blob_store``: each
        page's contents are gathered via ``extract(page_ids) -> blob`` and
        PUT under :meth:`journal_key`.  Entries whose key is already stored
        are skipped (content-addressed — same hash, same bytes).  Returns
        how many blobs were written."""
        n = 0
        for h, pid in pairs:
            key = self.journal_key(h)
            if key in blob_store.blobs:
                continue
            blob = extract([int(pid)])
            blob_store.put(key, blob, blob_nbytes(blob))
            n += 1
        return n

    def adopt(self, h: bytes, pid: int) -> None:
        """Record an entry whose allocator reference the caller *transfers*
        (vs :meth:`publish`, which takes its own): the rebuild path allocates
        a fresh page, scatters journaled contents into it, and hands the
        alloc-time reference straight to the index."""
        self._pages[h] = int(pid)

    def rebuild(self, blob_store, allocator, budget, install) -> int:
        """Re-adopt journaled entries into a fresh pool (worker cold start).

        For every ``index/<hash>`` blob in the store not already indexed,
        while ``budget()`` pages remain adoptable: allocate one page, call
        ``install(pid, blob)`` to scatter the journaled contents into it,
        and adopt the entry.  Entries that do not fit stay in the store —
        adoption is an optimization; a missed entry just re-prefills.
        Returns the number of pages adopted."""
        n = 0
        for key in list(blob_store.blobs):
            if not key.startswith(INDEX_JOURNAL_PREFIX):
                continue
            h = bytes.fromhex(key[len(INDEX_JOURNAL_PREFIX):])
            if h in self._pages:
                continue
            if budget() < 1:
                break
            pid = allocator.alloc(1)[0]
            install(pid, blob_store.get(key))
            self.adopt(h, pid)
            n += 1
        return n

    def publish(self, hashes: Sequence[bytes], page_ids: Sequence[int],
                allocator: PageAllocator) -> int:
        """Publish (hash, page) pairs not already indexed; the index takes
        one reference per page it actually adopts.  Returns how many."""
        n = 0
        for h, pid in zip(hashes, page_ids, strict=True):
            if h in self._pages:
                continue
            allocator.share([pid])
            self._pages[h] = int(pid)
            n += 1
        return n

    def lookup(self, hashes: Sequence[bytes]) -> List[int]:
        """Longest indexed chain prefix: physical pages for ``hashes[:k]``
        where ``k`` is the first miss.  Hits are re-marked most recent."""
        out: List[int] = []
        for h in hashes:
            pid = self._pages.get(h)
            if pid is None:
                break
            self._pages[h] = self._pages.pop(h)   # LRU bump
            out.append(pid)
        return out

    def evict(self, allocator: PageAllocator, need_free: int,
              pinned: Sequence[int] = ()) -> int:
        """Drop LRU entries (releasing the index's reference) until the
        allocator has ``need_free`` free pages or every unpinned entry is
        gone.  ``pinned`` pages are skipped — the admission driving the
        eviction may be about to map them.  Returns the number dropped."""
        keep = set(pinned)
        n = 0
        for h in list(self._pages):               # LRU first
            if allocator.free_count >= need_free:
                break
            pid = self._pages[h]
            if pid in keep:
                continue
            allocator.release([self._pages.pop(h)])
            n += 1
        return n

    def clear(self, allocator: Optional[PageAllocator] = None) -> None:
        if allocator is not None:
            for pid in self._pages.values():
                allocator.release([pid])
        self._pages.clear()


# ---------------------------------------------------------------------------
# Batched-cache construction & slot-level surgery (scheduler support)
# ---------------------------------------------------------------------------


def batched_cache(model, n_slots: int, seq_len: int) -> Dict:
    """A decode cache for ``n_slots`` independent sequences: the model's
    normal batch cache with the shared scalar ``length`` widened to a
    per-slot ``(n_slots,)`` vector."""
    cache = dict(model.init_cache(n_slots, seq_len))
    cache["length"] = jnp.zeros((n_slots,), jnp.int32)
    return cache


def paged_cache(model, n_slots: int, *, page_size: int, n_pages: int,
                max_pages: int) -> Dict:
    """A paged decode cache: every KV ring of the model's batch cache is
    replaced by a shared ``(n_pages, page_size, Hkv*D)`` pool plus a
    per-slot ``(n_slots, max_pages)`` page table (replicated across the
    stacked layer dim so the decode scan carries one pytree).  Ring-free
    state (SSM/RG-LRU recurrences, conv tails) keeps its per-slot layout."""

    def transform(tree):
        if isinstance(tree, dict) and {"k", "v", "positions"} <= set(tree):
            k = tree["k"]                       # (..., B, T, Hkv, D)
            lead = k.shape[:-4]
            out = {kk: transform(vv) for kk, vv in tree.items()
                   if kk not in ("k", "v", "positions")}
            pool = lead + (n_pages, page_size, k.shape[-2] * k.shape[-1])
            out["kp"] = jnp.zeros(pool, k.dtype)
            out["vp"] = jnp.zeros(pool, k.dtype)
            out["page_table"] = -jnp.ones(lead + (n_slots, max_pages), jnp.int32)
            return out
        if isinstance(tree, dict):
            return {kk: transform(vv) for kk, vv in tree.items()}
        return tree

    cache = transform(dict(model.init_cache(n_slots, page_size)))
    cache["length"] = jnp.zeros((n_slots,), jnp.int32)
    return cache


def _path_keys(path) -> Tuple[str, ...]:
    return tuple(str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))
                 for k in path)


def _slot_axis_of(keys: Tuple[str, ...]) -> int:
    """Axis carrying the slot (batch) dim for a per-slot cache leaf.

    Stacked leaves (dense/moe/ssm top-level arrays, hybrid ``blocks``) carry
    a leading layer dim, so B sits at axis 1; the hybrid ``tail`` layers and
    the per-slot ``length`` vector are unstacked (axis 0)."""
    return 0 if keys[0] in ("tail", "length") else 1


def mask_slot_rows(new_cache: Dict, old_cache: Dict, keep: jnp.ndarray) -> Dict:
    """Keep a decode step's updates only for slots where ``keep`` is True.

    Inactive rows (freed slots, slots mid-chunked-admission) are restored to
    their pre-step state so a batched decode step cannot advance their
    lengths or evolve their recurrent states.  Shared pool leaves have no
    slot axis and pass through — unmapped page tables already drop their
    writes at the scatter."""

    def sel(path, new, old):
        keys = _path_keys(path)
        if keys[-1] in POOL_KEYS:
            return new
        ax = _slot_axis_of(keys)
        shape = [1] * new.ndim
        shape[ax] = keep.shape[0]
        return jnp.where(keep.reshape(shape), new, old)

    return jax.tree_util.tree_map_with_path(sel, new_cache, old_cache)


def cache_slot_view(batch_cache: Dict, slot) -> Dict:
    """The B=1 view of one slot: per-slot leaves sliced at ``slot`` (kept
    dim), shared pool leaves passed through whole.  ``slot`` may be traced —
    the chunked-prefill step jits over it."""

    def slice_leaf(path, leaf):
        keys = _path_keys(path)
        if keys[-1] in POOL_KEYS:
            return leaf
        return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=_slot_axis_of(keys))

    return jax.tree_util.tree_map_with_path(slice_leaf, batch_cache)


def cache_clear_slot(batch_cache: Dict, slot) -> Dict:
    """Zero one slot's rows (page table and ring positions to -1): fresh
    state for an admission, and — on completion — an unmapped page table so
    the freed slot's residual decode writes are dropped, never landing in
    pages that now belong to another slot."""

    def clear(path, leaf):
        keys = _path_keys(path)
        if keys[-1] in POOL_KEYS:
            return leaf
        ax = _slot_axis_of(keys)
        idx = [slice(None)] * leaf.ndim
        idx[ax] = slot
        fill = -1 if keys[-1] in ("page_table", "positions") else 0
        return leaf.at[tuple(idx)].set(jnp.asarray(fill, leaf.dtype))

    return jax.tree_util.tree_map_with_path(clear, batch_cache)


def set_page_row(batch_cache: Dict, slot: int, row) -> Dict:
    """Install a slot's (max_pages,) page-table row on every replicated
    page-table leaf (no-op for ring or ring-free caches)."""
    row = jnp.asarray(row, jnp.int32)

    def upd(path, leaf):
        if _path_keys(path)[-1] != "page_table":
            return leaf
        idx = [slice(None)] * leaf.ndim
        idx[-2] = slot
        return leaf.at[tuple(idx)].set(row)

    return jax.tree_util.tree_map_with_path(upd, batch_cache)


# ---------------------------------------------------------------------------
# Page-level offload: extract / inject pool pages (storage-backed preemption)
# ---------------------------------------------------------------------------

# Axis of the page dim in a pool leaf (..., n_pages, page_size, Hkv*D).
_PAGE_AXIS = -3


def gather_pages(cache: Dict, page_ids) -> Dict:
    """Extract physical pages ``page_ids`` from every pool leaf.

    Returns a pytree with the cache's structure restricted to pool leaves:
    each ``kp``/``vp`` leaf becomes ``(..., len(page_ids), page_size,
    Hkv*D)`` — the staging buffer a preemption ships to the object store.  The
    caller supplies ``page_ids`` in *logical* order (the slot's page-table
    order), so a blob is position-ordered regardless of how scrambled the
    physical table is.  Exact inverse of :func:`scatter_pages` through any
    page table: ``gather(scatter(cache, ids, blob), ids) == blob``.
    """
    ids = jnp.asarray(page_ids, jnp.int32)

    def pick(path, leaf):
        if _path_keys(path)[-1] in POOL_KEYS:
            return jnp.take(leaf, ids, axis=_PAGE_AXIS)
        return None

    tree = jax.tree_util.tree_map_with_path(pick, cache)
    return _prune_none(tree)


def scatter_pages(cache: Dict, page_ids, blob: Dict) -> Dict:
    """Inject a page blob back into the pool at physical pages ``page_ids``
    (the restore half of offload; the new ids need not match the ids the
    blob was extracted from — the page table re-maps them).  Non-pool leaves
    pass through untouched."""
    ids = jnp.asarray(page_ids, jnp.int32)
    flat = dict(_iter_pool_leaves(blob))

    def put(path, leaf):
        keys = _path_keys(path)
        if keys[-1] not in POOL_KEYS:
            return leaf
        src = flat[keys]
        idx = [slice(None)] * leaf.ndim
        idx[leaf.ndim + _PAGE_AXIS] = ids
        return leaf.at[tuple(idx)].set(src.astype(leaf.dtype))

    return jax.tree_util.tree_map_with_path(put, cache)


def copy_pages(cache: Dict, src_ids, dst_ids) -> Dict:
    """Copy pool pages ``src_ids`` onto ``dst_ids`` (the copy-on-write
    split: a writer about to mutate a page with refcount > 1 duplicates it
    onto a fresh page and remaps its own table; every other reference keeps
    reading the original bytes).  Non-pool leaves pass through untouched."""
    src = jnp.asarray(src_ids, jnp.int32)
    dst = jnp.asarray(dst_ids, jnp.int32)

    def cp(path, leaf):
        if _path_keys(path)[-1] not in POOL_KEYS:
            return leaf
        vals = jnp.take(leaf, src, axis=_PAGE_AXIS)
        idx = [slice(None)] * leaf.ndim
        idx[leaf.ndim + _PAGE_AXIS] = dst
        return leaf.at[tuple(idx)].set(vals)

    return jax.tree_util.tree_map_with_path(cp, cache)


def gather_slot_state(cache: Dict, slot) -> Dict:
    """Snapshot one slot's per-slot rows (lengths, recurrent conv/SSM/RG-LRU
    state — everything except the shared pool and the page table, which the
    scheduler mirrors on the host).  The snapshot is what a parked session
    carries after its slot is reclaimed; :func:`scatter_slot_state` is the
    exact inverse into any slot index."""

    def pick(path, leaf):
        keys = _path_keys(path)
        if keys[-1] in POOL_KEYS or keys[-1] == "page_table":
            return None
        return jax.lax.dynamic_slice_in_dim(leaf, slot, 1,
                                            axis=_slot_axis_of(keys))

    return _prune_none(jax.tree_util.tree_map_with_path(pick, cache))


def scatter_slot_state(cache: Dict, slot, state: Dict) -> Dict:
    """Install a :func:`gather_slot_state` snapshot into row ``slot`` (the
    restore half of parked-slot eviction; the target slot need not be the
    one the snapshot came from).  Pool and page-table leaves pass through."""
    flat = dict(_iter_pool_leaves(state))

    def put(path, leaf):
        keys = _path_keys(path)
        src = flat.get(keys)
        if src is None:
            return leaf
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, src.astype(leaf.dtype), slot, axis=_slot_axis_of(keys))

    return jax.tree_util.tree_map_with_path(put, cache)


def slice_page_blob(blob: Dict, lo: int, hi: int) -> Dict:
    """Pages ``[lo, hi)`` of a blob — the unit of a chunked restore."""
    def cut(leaf):
        idx = [slice(None)] * leaf.ndim
        idx[leaf.ndim + _PAGE_AXIS] = slice(lo, hi)
        return leaf[tuple(idx)]

    return jax.tree_util.tree_map(cut, blob)


def blob_nbytes(blob: Dict) -> int:
    """Serialized size of a page blob (drives the storage billing)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(blob))


def _iter_pool_leaves(tree, prefix: Tuple[str, ...] = ()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _iter_pool_leaves(v, prefix + (str(k),))
    elif tree is not None:
        yield prefix, tree


def _prune_none(tree):
    """Drop None-valued subtrees (non-pool leaves filtered by gather)."""
    if isinstance(tree, dict):
        out = {k: _prune_none(v) for k, v in tree.items()}
        return {k: v for k, v in out.items()
                if v is not None and not (isinstance(v, dict) and not v)}
    return tree


def kv_bytes_per_token(cache: Dict) -> int:
    """Bytes of KV state per stored token, summed over layers (ring k/v or
    pool kp/vp leaves; recurrent state excluded — it is O(1) per slot)."""
    total = 0

    def visit(path, leaf):
        nonlocal total
        key = _path_keys(path)[-1]
        if key in POOL_KEYS:            # (..., Np, ps, H*D)
            tokens = leaf.shape[-3] * leaf.shape[-2]
            total += leaf.size * leaf.dtype.itemsize // tokens
        elif key in ("k", "v"):         # (..., B, T, H, D)
            per_row_tokens = leaf.shape[-3]
            total += (leaf.size * leaf.dtype.itemsize
                      // (leaf.shape[-4] * per_row_tokens))
        return leaf

    jax.tree_util.tree_map_with_path(visit, cache)
    return total


def _slot_axis(batch_shape: Tuple[int, ...], one_shape: Tuple[int, ...]) -> Optional[int]:
    """The axis along which a B=1 cache leaf scatters into the batch leaf.

    Cache trees from ``init_cache(B, T)`` and ``init_cache(1, T)`` are
    structurally identical, so the slot axis is the unique axis where the
    shapes disagree (stacked leaves carry a leading layer dim, tail leaves do
    not — shape matching handles both without per-family knowledge).
    """
    diffs = [i for i, (a, b)
             in enumerate(zip(batch_shape, one_shape, strict=False)) if a != b]
    if not diffs:
        return None  # identical shapes: pool leaves / n_slots == 1 — replace wholesale
    if len(diffs) > 1 or one_shape[diffs[0]] != 1:
        raise ValueError(
            f"cannot locate slot axis: batch {batch_shape} vs one {one_shape}")
    return diffs[0]


def cache_insert_slot(batch_cache: Dict, one_cache: Dict, slot) -> Dict:
    """Scatter a B=1 cache into row ``slot`` of a batched cache (prefill-on-
    admit, and the write-back half of the chunked-prefill step).  Leaves with
    identical shapes — the shared page pool, or everything when n_slots == 1
    — are replaced wholesale.  ``batch_cache['length']`` must be per-slot
    (see :func:`batched_cache`); the admitted sequence keeps its own length."""
    length = batch_cache["length"].at[slot].set(
        jnp.asarray(one_cache["length"], jnp.int32).reshape(()))
    rest = {k: v for k, v in batch_cache.items() if k != "length"}
    one_rest = {k: v for k, v in one_cache.items() if k != "length"}

    def ins(b, o):
        ax = _slot_axis(tuple(b.shape), tuple(o.shape))
        if ax is None:
            return o.astype(b.dtype)
        idx = [slice(None)] * b.ndim
        idx[ax] = slot
        return b.at[tuple(idx)].set(jnp.squeeze(o, axis=ax).astype(b.dtype))

    out = jax.tree_util.tree_map(ins, rest, one_rest)
    out["length"] = length
    return out
