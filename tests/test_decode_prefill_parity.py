"""Bitwise decode/prefill KV parity on the paged pool.

The contract everything in this PR stands on: **an S=1 decode step is the
chunk path at S=1** — same gathered attention view, same pool scatter, same
recurrent-state fold — so the bytes a decode step writes into the page pool
are bitwise identical to what a chunked prefill of the same tokens writes.
Prefix sharing (generated-span publishing), session parking (consumed-span
reuse) and speculative verify-rollback all assume this; these tests prove it
at two levels:

* **model level** — one fixed token stream fed through three different
  chunkings (single chunk, mixed chunks, pure S=1 steps) of
  ``decode_step`` must leave every cache leaf (pool bytes, lengths,
  recurrent rows, conv tails) bitwise identical and emit bitwise-identical
  per-position logits.  Dense, MoE and hybrid archetypes, plus a
  sliding-window dense variant (the window is mask-only on the paged pool —
  no eviction — so parity must survive it).
* **scheduler level** — after a real request completes and parks, the KV
  pages its parked journal owns must hold, byte for byte, what a fresh
  chunked prefill of the consumed history writes.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model, kvcache
from repro.serve.scheduler import DecodeScheduler

ARCH_VARIANTS = [
    ("minicpm-2b", None),
    ("minicpm-2b", 8),                 # sliding-window dense
    ("moonshot-v1-16b-a3b", None),
    ("recurrentgemma-2b", None),
]


def _build(arch, window=None):
    cfg = configs.get(arch).reduced()
    if window is not None:
        cfg = dataclasses.replace(cfg, sliding_window=window)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def _one_slot_paged(model, *, n_pages, page_size):
    """B=1 paged cache with an identity page table (logical == physical)."""

    def ident(tree):
        if not isinstance(tree, dict):
            return tree
        return {k: (jnp.broadcast_to(
                        jnp.arange(v.shape[-1], dtype=jnp.int32), v.shape)
                    if k == "page_table" else ident(v))
                for k, v in tree.items()}

    return ident(kvcache.paged_cache(model, 1, page_size=page_size,
                                     n_pages=n_pages, max_pages=n_pages))


def _feed(model, params, cache, toks, chunks):
    """Run ``toks`` through ``decode_step`` in the given chunking; returns
    the concatenated per-position logits and the final cache."""
    assert sum(chunks) == len(toks)
    step = jax.jit(model.decode_step)
    out, i = [], 0
    for c in chunks:
        logits, cache = step(params, cache,
                             jnp.asarray(toks[None, i:i + c], jnp.int32))
        out.append(np.asarray(logits[0]))
        i += c
    return np.concatenate(out, axis=0), cache


def _assert_trees_bitwise(ca, cb, ctx):
    la = jax.tree_util.tree_leaves_with_path(ca)
    lb = jax.tree_util.tree_leaves(cb)
    assert len(la) == len(lb)
    for (path, a), b in zip(la, lb):
        a, b = np.asarray(a), np.asarray(b)
        if a.tobytes() != b.tobytes():
            # fall back for a readable diff; the raise below catches the
            # +0.0/-0.0 and NaN cases == would paper over
            np.testing.assert_array_equal(
                a, b, err_msg=f"{ctx}: leaf {jax.tree_util.keystr(path)}")
            raise AssertionError(
                f"{ctx}: leaf {jax.tree_util.keystr(path)} differs bitwise "
                f"(signed zero or NaN payload)")


@pytest.mark.parametrize(
    "arch,window", ARCH_VARIANTS,
    ids=[f"{a}{'' if w is None else f'-win{w}'}" for a, w in ARCH_VARIANTS])
def test_pool_bytes_s1_equals_chunked(arch, window):
    """One token stream, three chunkings — single chunk, mixed chunk sizes,
    and an S=1 tail after a prompt-sized chunk (exactly what the scheduler's
    decode loop does) — must agree bitwise on every cache leaf and every
    per-position logit row."""
    cfg, model, params = _build(arch, window)
    L, ps = 13, 4
    rng = np.random.default_rng(42)
    toks = rng.integers(0, cfg.vocab, size=L).astype(np.int32)

    def run(chunks):
        cache = _one_slot_paged(model, n_pages=5, page_size=ps)
        return _feed(model, params, cache, toks, chunks)

    la, ca = run([L])                       # one prefill chunk
    lb, cb = run([5, 4, 4])                 # mixed chunked prefill
    lc, cc = run([5] + [1] * (L - 5))       # prefill chunk + S=1 decode steps

    ctx = f"{arch} window={window}"
    assert la.tobytes() == lb.tobytes() == lc.tobytes(), \
        f"{ctx}: per-position logits diverged across chunkings"
    _assert_trees_bitwise(ca, cb, ctx + " [single vs mixed]")
    _assert_trees_bitwise(ca, cc, ctx + " [single vs S=1]")


@pytest.mark.parametrize("arch", [a for a, w in ARCH_VARIANTS if w is None])
def test_parked_pages_are_prefill_bytes(arch):
    """End-to-end form of the same claim: a parked session's journal pages —
    written partly by chunked prefill, partly by live S=1 decode steps —
    hold bitwise what one fresh prefill of the consumed history writes.
    This is the exactness that lets the prefix index publish generated-span
    pages and lets parked sessions reuse the full consumed span."""
    cfg, model, params = _build(arch)
    ps, P, N = 4, 9, 4
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, size=P).astype(np.int32)
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=24,
                            kv_mode="paged", page_size=ps, prefill_chunk=5,
                            prefix_sharing=True, park_sessions=True)
    sched.submit("s", "r0", prompt, N)
    n = 0
    while sched.busy():
        sched.step()
        sched.audit()
        n += 1
        assert n < 100
    rec = sched._parked["s"]
    assert rec.consumed == P + N - 1        # last sampled token: no KV yet
    n_pages = -(-rec.consumed // ps)
    assert len(rec.pages) == n_pages

    ref_cache = _one_slot_paged(model, n_pages=n_pages + 1, page_size=ps)
    _, ref_cache = _feed(model, params, ref_cache,
                         np.asarray(rec.history[:rec.consumed], np.int32),
                         [rec.consumed])

    got = kvcache.gather_pages(sched.cache,
                               [int(p) for p in rec.page_row[:n_pages]])
    exp = kvcache.gather_pages(ref_cache, list(range(n_pages)))
    gl = jax.tree_util.tree_leaves_with_path(got)
    el = jax.tree_util.tree_leaves(exp)
    for (path, g), e in zip(gl, el):
        g, e = np.asarray(g), np.asarray(e)
        # (..., n_pages, ps, H*D) -> (..., tokens, H*D); the tail of the
        # last page is unwritten scratch, compared only up to `consumed`
        g = g.reshape(g.shape[:-3] + (n_pages * ps,) + g.shape[-1:])
        e = e.reshape(e.shape[:-3] + (n_pages * ps,) + e.shape[-1:])
        sl = (Ellipsis, slice(0, rec.consumed), slice(None))
        assert g[sl].tobytes() == e[sl].tobytes(), \
            f"{arch}: parked pages differ from prefill bytes at " \
            f"{jax.tree_util.keystr(path)}"
