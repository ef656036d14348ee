"""Serving steps: prefill + single-token decode against a KV/state cache.

``make_decode_step`` is what the decode_* / long_* dry-run cells lower: one
new token per sequence with a cache of ``seq_len`` (per the assignment, these
cells lower ``serve_step``, not ``train_step``).

Every factory takes optional ``policy`` / ``cache_specs`` keywords for
mesh-sharded execution (the scheduler's ``mesh=`` mode): ``policy`` is a
:class:`repro.dist.sharding.ShardingPolicy` installed *inside* the traced
body — jit executes the Python function once per trace, so the context
manager is live exactly while the model constrains activations — and
``cache_specs`` is the cache's PartitionSpec pytree, re-asserted on the
returned cache so the carried decode state never drifts off its storage
layout between steps.  Both default to None: the single-device call sites
are byte-for-byte the old factories.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp


def _policy_scope(policy):
    """Context installing ``policy`` for the trace; ambient pass-through when
    the caller has no policy (None must not *clear* an outer policy here —
    dry-run traces under an outer ``activation_sharding``)."""
    if policy is None:
        return contextlib.nullcontext()
    from ..dist import sharding as shd

    return shd.activation_sharding(policy)


def _constrain_cache(cache, cache_specs):
    """Pin the returned cache pytree to its storage PartitionSpecs (identity
    without specs or without an active policy mesh)."""
    if cache_specs is None:
        return cache
    from ..dist import sharding as shd

    return shd.constrain_tree(cache, cache_specs)


def init_serving_params(model, key):
    """Random serving weights from ``key``, in the dtypes serving reads
    them in (:func:`repro.models.layers.to_compute_dtype`).

    Initialisation and cast are one jitted program, so the float32 masters
    are never whole in device memory: at published widths they would not
    fit next to the bf16 copy.  The values are those of ``model.init(key)``
    cast, so serving outputs do not change."""
    from ..models.layers import to_compute_dtype

    return jax.jit(lambda k: to_compute_dtype(model.init(k)))(key)


def make_decode_step(model, *, policy=None, cache_specs=None) -> Callable:
    def serve_step(params, cache, tokens):
        with _policy_scope(policy):
            logits, new_cache = model.decode_step(params, cache, tokens)
            next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            new_cache = _constrain_cache(new_cache, cache_specs)
        return next_token, logits, new_cache

    return serve_step


def make_chunk_step(model, *, policy=None, cache_specs=None) -> Callable:
    """Prefill one prompt chunk for a *single slot* of a batched paged cache.

    The chunk runs as a B=1 forward against the shared page pool: per-slot
    leaves (lengths, recurrent states, page-table rows) are sliced at
    ``slot``, the pool is passed through whole (the slot exclusively owns the
    pages its table maps, so the scatter is race-free against the other
    slots' decode traffic), and the updated row is scattered back.  ``slot``
    is traced, so one compile covers every slot at a given chunk length.
    """
    from ..models import kvcache

    def chunk_step(params, cache, tokens, slot):
        with _policy_scope(policy):
            one = kvcache.cache_slot_view(cache, slot)
            logits, one_new = model.decode_step(params, one, tokens)
            new_cache = kvcache.cache_insert_slot(cache, one_new, slot)
            new_cache = _constrain_cache(new_cache, cache_specs)
        return logits, new_cache

    return chunk_step


def make_draft_step(model, *, policy=None, cache_specs=None) -> Callable:
    """Batched S=1 greedy step for the *draft* model of a speculative
    decoder: one proposed token per masked-in slot against the draft's own
    per-slot ring cache.  Inactive rows keep their state and their last
    token — same masking contract as the target's decode step."""
    from ..models import kvcache

    def draft_step(params, cache, last_tokens, active):
        with _policy_scope(policy):
            logits, new_cache = model.decode_step(params, cache, last_tokens[:, None])
            new_cache = kvcache.mask_slot_rows(new_cache, cache, active)
            new_cache = _constrain_cache(new_cache, cache_specs)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return new_cache, jnp.where(active, tok, last_tokens)

    return draft_step


def make_draft_catchup_step(model, *, policy=None, cache_specs=None) -> Callable:
    """Batched draft catch-up on the canonical token stream: every masked-in
    slot replays the canonical tokens its draft ring has not consumed — ONE
    dispatch per verify round instead of one B=1 chunk per slot.

    ``tokens`` (B, W) is back-padded to the round's widest pending span and
    ``counts`` (B,) holds each row's real span (>= 1 for active rows).  The
    whole padded chunk runs through ``decode_step``; then each active row's
    length advances by its *own* count, so the pad positions land past the
    canonical length.  Pad-position KV is garbage but never observable: the
    ring path writes every chunk's KV before attending (post-update view),
    so a later dispatch overwrites a pad lane's position before any query's
    causal mask could admit it — provided the ring is deep enough that a pad
    write never wraps onto a live lane (the scheduler sizes the draft ring
    for the padded worst case).  The returned last token is row ``counts-1``
    of the greedy argmax — exactly the B=1 chunk's final-position token.
    """
    from ..models import kvcache

    def catchup(params, cache, tokens, counts, active):
        with _policy_scope(policy):
            logits, new_cache = model.decode_step(params, cache, tokens)
            # decode_step advanced every row by the padded width W; the
            # canonical advance is each row's own pending count
            new_cache["length"] = jnp.where(
                active, cache["length"] + counts, cache["length"])
            new_cache = kvcache.mask_slot_rows(new_cache, cache, active)
            new_cache = _constrain_cache(new_cache, cache_specs)
            y = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # (B, W)
            last = jnp.take_along_axis(
                y, jnp.maximum(counts - 1, 0)[:, None], axis=1)[:, 0]
        return new_cache, last

    return catchup


def make_spec_verify_step(model, *, max_seq: int, policy=None,
                          cache_specs=None) -> Callable:
    """One draft-and-verify round's target half: score ``spec_k + 1`` tokens
    per slot in a single chunked decode step and accept the longest prefix
    of drafts that matches the target's own greedy argmax.

    ``verify_tokens[:, 0]`` is each slot's newest canonical token (the
    sampled-but-unconsumed one) and ``verify_tokens[:, 1:]`` the draft's
    proposals.  Position ``j``'s argmax ``y[:, j]`` is what the target would
    have sampled after consuming ``verify_tokens[:, :j+1]`` — so draft
    ``j+1`` is accepted iff it equals ``y[:, j]``, and ``a`` (the accepted
    count, clamped per-slot by ``k_eff`` so a slot never overruns its
    ``max_new`` budget) emits ``a + 1`` tokens: the accepted drafts plus the
    target's own bonus/correction token.  Exactness is structural, not
    statistical: every emitted token is the target's argmax conditioned on
    a fully canonical prefix, so the output stream is token-for-token what
    S=1 non-speculative decode produces (the S=1 decode path *is* the chunk
    path at S=1 — the bitwise KV contract this feature stands on).

    The cache write runs ahead: the chunk writes KV for all ``S`` positions,
    so rejected positions hold non-canonical KV — the returned lengths are
    rewound to the canonical ``old + a + 1``, which puts those positions
    past every later read's validity mask until the next round's chunk
    overwrites them (write-before-read, same contract as prefill chunks).
    Recurrent (non-KV) rows advance through all ``S`` tokens and cannot be
    rewound here — hybrid callers snapshot rows before the round and
    replay the accepted span through the chunk path on partial accepts.
    """
    from ..models import kvcache

    def verify(params, cache, verify_tokens, active, k_eff, out_buf, out_pos,
               last_tokens):
        B, S = verify_tokens.shape
        with _policy_scope(policy):
            logits, new_cache = model.decode_step(params, cache, verify_tokens)
            new_cache = kvcache.mask_slot_rows(new_cache, cache, active)
            new_cache = _constrain_cache(new_cache, cache_specs)
        y = jnp.argmax(logits, axis=-1).astype(jnp.int32)          # (B, S)
        match = (verify_tokens[:, 1:] == y[:, :-1]).astype(jnp.int32)
        a = jnp.minimum(jnp.cumprod(match, axis=1).sum(axis=1), k_eff)
        b = jnp.arange(B, dtype=jnp.int32)
        for j in range(S):
            # emitted tokens y[:, :a+1] land on the output ring; masked-out
            # rows and rejected columns scatter out of bounds -> dropped
            ok = active & (j <= a)
            col = jnp.where(ok, out_pos + j, max_seq)
            out_buf = out_buf.at[b, col].set(y[:, j])
        last = jnp.take_along_axis(y, a[:, None], axis=1)[:, 0]
        last_tokens = jnp.where(active, last, last_tokens)
        out_pos = out_pos + jnp.where(active, a + 1, 0)
        new_cache["length"] = jnp.where(
            active, new_cache["length"] - (S - 1 - a), new_cache["length"])
        return new_cache, y, a, out_buf, out_pos, last_tokens

    return verify


def make_offload_steps(*, policy=None, cache_specs=None,
                       stage_specs=None) -> tuple:
    """Jitted staging steps for storage-backed preemption.

    ``extract(cache, page_ids)`` gathers the victim's pool pages (in the
    page table's logical order) into the staging buffer the scheduler ships
    to the object store; ``inject(cache, page_ids, blob)`` scatters a blob
    chunk back onto freshly allocated pages during a chunked restore (it
    donates the cache it is given).  Both are pure pool-pytree programs
    (:func:`repro.models.kvcache.gather_pages` / :func:`scatter_pages`)
    jitted once and re-traced only per distinct chunk length, so a restore
    step costs one dispatch — same budget as a prefill chunk.

    With a concrete-mesh ``policy`` plus the cache/stage PartitionSpec
    pytrees, both run under ``jax.shard_map``: the page dim of the pool is
    unsharded, so the per-page take/scatter is local to each lane shard and
    the staged chunk comes out in :func:`offload_stage_shardings`' layout —
    no reshuffle of the pool, no gather of anything but the page ids.
    """
    from ..models import kvcache

    mesh = getattr(policy, "mesh", None)
    if mesh is None or cache_specs is None or stage_specs is None:
        return (jax.jit(kvcache.gather_pages),
                jax.jit(kvcache.scatter_pages, donate_argnums=(0,)))
    from jax.sharding import PartitionSpec as P

    def extract_body(cache, ids):
        return kvcache.gather_pages(cache, ids)

    def inject_body(cache, ids, blob):
        return kvcache.scatter_pages(cache, ids, blob)

    extract = jax.jit(jax.shard_map(
        extract_body, mesh=mesh, in_specs=(cache_specs, P()),
        out_specs=stage_specs, check_vma=False))
    inject = jax.jit(jax.shard_map(
        inject_body, mesh=mesh, in_specs=(cache_specs, P(), stage_specs),
        out_specs=cache_specs, check_vma=False), donate_argnums=(0,))
    return extract, inject


def make_prefill(model, seq_len: Optional[int] = None, *,
                 policy=None) -> Callable:
    """``seq_len`` sizes the cache for the *total* sequence (prompt + decode
    budget): without it the legacy prompt-sized ring silently evicts the
    oldest prompt tokens once decode wraps it."""

    def prefill(params, tokens, *extra):
        with _policy_scope(policy):
            if seq_len is None:
                logits, cache = model.prefill(params, tokens, *extra)
            else:
                logits, cache = model.prefill(params, tokens, *extra,
                                              seq_len=seq_len)
            next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_token, cache

    return prefill


def generate(model, params, prompt: jnp.ndarray, max_new: int, *extra,
             seq_len: Optional[int] = None) -> jnp.ndarray:
    """Greedy autoregressive generation (examples / integration tests).

    Pass ``seq_len >= prompt + max_new`` for an eviction-free decode — the
    layout the continuous-batching scheduler uses, and the reference the
    paged parity suite compares against."""
    prefill = jax.jit(make_prefill(model, seq_len))
    step = jax.jit(make_decode_step(model))
    tok, cache = prefill(params, prompt, *extra)
    out = [tok]
    for _ in range(max_new - 1):
        tok, _, cache = step(params, cache, tok[:, None])
        out.append(tok)
    return jnp.stack(out, axis=1)
