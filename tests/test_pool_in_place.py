"""The paged pool is updated in place.

Two halves, both checked here on the CPU:

* **the layer scan carries the stacked cache** and addresses each layer by
  index (one scatter at ``(layer, page, lane)``, one gather at ``(layer,
  page)``) instead of slicing each layer's cache out of the scan's input and
  stacking a fresh output.  The arithmetic is unchanged, so logits and every
  cache byte must equal the earlier formulation, kept below as the reference
  (``_ref_*``: a layer scan over the cache as ``xs``/``ys``).
* **the scheduler's steps donate the cache**: after a dispatch the previous
  cache's pool leaves are deleted, the ``pool_*_steps`` counters say so, and
  the compiled decode and chunk programs alias the pool input to the output.
"""

from __future__ import annotations

import dataclasses
import operator

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model, kvcache, layers
from repro.models.moe import moe_ffn
from repro.serve.scheduler import DecodeScheduler

B, PAGE, MAX_PAGES, N_PAGES, RING = 4, 4, 8, 24, 32
CHUNK = 16
LENGTHS = np.array([5, 11, 8, 17], np.int32)
UNMAPPED = 2          # decode: this slot's write lands on an unmapped page
MASKED = 3            # decode: masked out of the step


# -- the reference: the earlier slice-per-layer scan (or unrolled loop) -----


def _ref_update(lc, k_new, v_new, pos):
    S = k_new.shape[1]
    abs_pos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    if "kp" in lc:
        kp, vp, pt = lc["kp"], lc["vp"], lc["page_table"]
        n_pages, page_size = kp.shape[-4], kp.shape[-3]
        max_pages = pt.shape[-1]
        page_idx = abs_pos // page_size
        pid = jnp.take_along_axis(pt, jnp.clip(page_idx, 0, max_pages - 1), axis=-1)
        pid = jnp.where((page_idx < max_pages) & (pid >= 0), pid, n_pages)
        off = abs_pos % page_size
        return {"kp": kp.at[pid, off].set(k_new.astype(kp.dtype)),
                "vp": vp.at[pid, off].set(v_new.astype(vp.dtype)),
                "page_table": pt}
    T = lc["k"].shape[1]
    slots = abs_pos % T
    b = jnp.arange(k_new.shape[0], dtype=jnp.int32)[:, None]
    return {"k": lc["k"].at[b, slots].set(k_new.astype(lc["k"].dtype)),
            "v": lc["v"].at[b, slots].set(v_new.astype(lc["v"].dtype)),
            "positions": lc["positions"].at[b, slots].set(abs_pos)}


def _ref_view(lc, upto):
    if "k" in lc:
        return lc["k"], lc["v"], lc["positions"], lc["positions"] >= 0
    kp, vp, pt = lc["kp"], lc["vp"], lc["page_table"]
    n_pages, page_size, n_kv, hd = kp.shape
    nb, max_pages = pt.shape
    T = max_pages * page_size
    pid = jnp.clip(pt, 0, n_pages - 1)
    kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (nb, T))
    mapped = jnp.repeat(pt >= 0, page_size, axis=-1)
    return (kp[pid].reshape(nb, T, n_kv, hd), vp[pid].reshape(nb, T, n_kv, hd),
            kv_pos, mapped & (kv_pos < upto[:, None]))


def _ref_layer(cfg, p, x, lc, pos):
    rs = jnp.asarray(cfg.residual_scale, x.dtype)
    nb, S = x.shape[0], x.shape[1]
    positions = kvcache.decode_positions(pos, nb, S)
    h = layers.apply_norm(cfg.norm, p["attn_norm"], x)
    q, k, v = layers.qkv_project(p["attn"], cfg, h, positions)
    new = _ref_update(lc, k, v, pos)
    ck, cv, kv_pos, kv_valid = _ref_view(new, pos + S)
    o = layers.sdpa(q, ck, cv, causal=True, window=cfg.sliding_window,
                    q_positions=positions, kv_positions=kv_pos, kv_valid=kv_valid)
    o = o.reshape(nb, S, cfg.n_heads * cfg.the_head_dim())
    x = x + jnp.einsum("bsq,qd->bsd", o, layers.wcast(p["attn"]["wo"], "row")) * rs
    h = layers.apply_norm(cfg.norm, p["mlp_norm"], x)
    if cfg.family == "moe":
        h, _ = moe_ffn(p["moe"], cfg, h, no_drop=True)
    else:
        h = layers.apply_mlp(p["mlp"], cfg, h)
    return x + h * rs, new


def _ref_decode_step(cfg, params, cache, tokens):
    """The earlier decode step, on a pool stored ``(..., ps, Hkv, D)``."""
    x = layers.embed_tokens(params["embedding"], cfg, tokens)
    pos = cache["length"]
    keys = ("kp", "vp", "page_table") if "kp" in cache else ("k", "v", "positions")

    def body(h, layer_in):
        p, lc = layer_in
        return _ref_layer(cfg, p, h, lc, pos)

    xs = (params["layers"], {k: cache[k] for k in keys})
    if cfg.scan_layers:
        x, new = jax.lax.scan(body, x, xs)
    else:
        outs = []
        for i in range(cfg.n_layers):
            x, lc = body(x, jax.tree_util.tree_map(operator.itemgetter(i), xs))
            outs.append(lc)
        new = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *outs)
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    new = dict(new)
    new["length"] = cache["length"] + tokens.shape[1]
    return layers.lm_head(params["embedding"], cfg, x), new


def _ref_on_merged_pool(cfg, params, cache, tokens):
    """``_ref_decode_step`` on today's ``(..., ps, Hkv*D)`` pool: split each
    token's values into heads on the way in, merge them on the way out."""
    def split(x):
        return x.reshape(x.shape[:-1] + (cfg.n_kv_heads, -1))

    def merge(x):
        return x.reshape(x.shape[:-2] + (-1,))

    pool = kvcache.POOL_KEYS & set(cache)
    logits, new = _ref_decode_step(
        cfg, params, {k: split(v) if k in pool else v for k, v in cache.items()},
        tokens)
    return logits, {k: merge(v) if k in pool else v for k, v in new.items()}


# -- the cache states both formulations start from ---------------------------


def _filled_cache(model, layout, rng):
    """Four slots at LENGTHS with random K/V under every live position; in
    the paged pool each slot maps shuffled pages for its live span and the
    chunk's, except UNMAPPED, whose next page stays unmapped."""
    if layout == "paged":
        cache = kvcache.paged_cache(model, B, page_size=PAGE, n_pages=N_PAGES,
                                    max_pages=MAX_PAGES)
        L = cache["kp"].shape[0]
        table = np.full((B, MAX_PAGES), -1, np.int32)
        free = list(rng.permutation(N_PAGES))
        for b, n in enumerate(LENGTHS):
            live = -(-n // PAGE) if b == UNMAPPED else -(-(n + CHUNK) // PAGE)
            for i in range(min(live, MAX_PAGES)):
                table[b, i] = free.pop()
        cache["page_table"] = jnp.asarray(np.broadcast_to(table, (L, B, MAX_PAGES)))
        pool = ("kp", "vp")
    else:
        cache = kvcache.batched_cache(model, B, RING)
        L = cache["k"].shape[0]
        pos = np.where(np.arange(RING)[None] < LENGTHS[:, None],
                       np.arange(RING)[None], -1).astype(np.int32)
        cache["positions"] = jnp.asarray(np.broadcast_to(pos, (L, B, RING)))
        pool = ("k", "v")
    for k in pool:
        shape, dt = cache[k].shape, cache[k].dtype
        cache[k] = jnp.asarray(rng.standard_normal(shape), dt)
    cache["length"] = jnp.asarray(LENGTHS)
    return cache


def _bytes(tree):
    return jax.tree_util.tree_map(lambda x: np.asarray(x).tobytes(), tree)


ARCHS = {"dense": "minicpm-2b-smoke", "moe": "qwen3-moe-235b-a22b-smoke"}


@pytest.mark.parametrize("mode", ["decode", "chunk"])
@pytest.mark.parametrize("layout", ["paged", "ring"])
@pytest.mark.parametrize("family,scan", [("dense", True), ("moe", True),
                                         ("dense", False)],
                         ids=["dense", "moe", "dense-unrolled"])
def test_carried_scan_matches_sliced_scan(family, scan, layout, mode):
    cfg = dataclasses.replace(configs.get(ARCHS[family]), scan_layers=scan)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(7)
    cache = _filled_cache(model, layout, rng)

    if mode == "decode":
        active = jnp.asarray(np.arange(B) != MASKED)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)

        def run(decode):
            def step(params, cache, tokens):
                logits, new = decode(params, cache, tokens)
                return logits, kvcache.mask_slot_rows(new, cache, active)
            return jax.jit(step)(params, cache, tokens)
    else:
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, (1, CHUNK)), jnp.int32)

        def run(decode):
            def step(params, cache, tokens):
                logits, one = decode(params, kvcache.cache_slot_view(cache, 1), tokens)
                return logits, kvcache.cache_insert_slot(cache, one, 1)
            return jax.jit(step)(params, cache, tokens)

    got = run(model.decode_step)
    want = run(lambda p, c, t: _ref_on_merged_pool(cfg, p, c, t))
    assert _bytes(got) == _bytes(want)

    if layout == "paged" and mode == "decode":
        # UNMAPPED's write was dropped: pool bytes changed only on the pages
        # the mapped slots' tokens landed on
        table = np.asarray(cache["page_table"][0])
        written = {int(table[b, LENGTHS[b] // PAGE]) for b in range(B)
                   if b != UNMAPPED}
        assert table[UNMAPPED, LENGTHS[UNMAPPED] // PAGE] == -1
        for k in ("kp", "vp"):
            old, new = np.asarray(cache[k]), np.asarray(got[1][k])
            changed = {int(p) for p in np.nonzero((old != new).any(
                axis=(0, 2, 3)))[0]}
            assert changed and changed <= written


# -- donation ------------------------------------------------------------------


def _sched(**kw):
    cfg = configs.get("minicpm-2b-smoke")
    model = build_model(cfg)
    return DecodeScheduler(model, model.init(jax.random.key(0)), n_slots=2,
                           max_seq=32, page_size=4, prefill_chunk=8, **kw)


def _pool_leaves(cache):
    return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
            if path[-1].key in kvcache.POOL_KEYS]


def test_steps_donate_the_pool():
    sched = _sched()
    rng = np.random.default_rng(0)
    for i in range(3):
        sched.submit(f"s{i}", f"r{i}", rng.integers(0, 256, 10 + 3 * i), 5)
    dispatches = 0
    while sched.busy():
        before = _pool_leaves(sched.cache)
        chunks, steps = sched.prefill_chunks, sched.steps
        sched.step()
        n = sched.prefill_chunks - chunks + sched.steps - steps
        if n:
            assert all(leaf.is_deleted() for leaf in before)
        dispatches += n
    st = sched.stats()
    assert st["completed"] == 3
    assert st["pool_copied_steps"] == 0
    assert st["pool_donated_steps"] == dispatches == st["steps"] + st["prefill_chunks"]


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_compiled_steps_alias_the_pool(program):
    sched = _sched()
    pool = sum(leaf.nbytes for leaf in _pool_leaves(sched.cache))
    if program == "decode":
        lowered = sched._decode.lower(
            sched.params, sched.cache, sched.last_tokens, sched.out_buf,
            sched.out_pos, jnp.ones((sched.n_slots,), bool), jax.random.key(0))
    else:
        lowered = sched._chunk.lower(sched.params, sched.cache,
                                     jnp.zeros((1, 8), jnp.int32), 0)
    assert lowered.compile().memory_analysis().alias_size_in_bytes >= pool


@pytest.mark.parametrize("program", ["copy_pages", "scatter_state", "inject"])
def test_pool_programs_donate(program):
    sched = _sched()
    before = _pool_leaves(sched.cache)
    ids = jnp.asarray([0, 1], jnp.int32)
    if program == "copy_pages":
        sched.cache = sched._copy_pages(sched.cache, ids, ids + 2)
    elif program == "scatter_state":
        state = sched._gather_state(sched.cache, 0)
        sched.cache = sched._scatter_state(sched.cache, 1, state)
    else:
        blob = sched._extract(sched.cache, ids)
        sched.cache = sched._inject(sched.cache, ids + 2, blob)
    assert all(leaf.is_deleted() for leaf in before)
    assert not any(leaf.is_deleted() for leaf in _pool_leaves(sched.cache))
