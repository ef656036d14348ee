"""Paged-attention decode kernel, wired end to end: the fused Pallas
table-indirect path must be token-for-token identical to the gather
reference through the scheduler (dense / MoE / hybrid, staggered chunked
admissions, CoW-shared rc>1 prefixes), lane-exact at the kvcache helper
level on scrambled and partially-mapped tables, rejected on configurations
it cannot serve, and strictly cheaper than gather in HBM bytes."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import reference_paged_attention
from repro.models import kvcache
from repro.serve.engine import generate
from repro.serve.scheduler import DecodeScheduler
from test_paged_kvcache import run_all, tiny

# sdpa_append now keeps softmax probs and the value accumulation in fp32
# like the fused kernel does, which shrank the gather-vs-fused attention
# divergence from ~1 ulp of bf16 (the old prob rounding) down to fp32
# summation-order noise.  Dense and hybrid parity is seed-robust after the
# change (each arch previously needed a hand-picked seed where greedy
# argmax had headroom).  The attention *output* still rounds to bf16,
# though, and the MoE router's discreteness can amplify that last bit on
# unlucky prompts — so the MoE seed below still wants headroom, it is just
# no longer knife-edge (most small seeds pass).
PARITY_CASES = [("minicpm-2b", 0), ("moonshot-v1-16b-a3b", 0),
                ("recurrentgemma-2b", 0)]


# ---------------------------------------------------------------------------
# Scheduler-level token parity: fused == gather == solo decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,seed", PARITY_CASES)
def test_paged_kernel_parity_staggered_multichunk(arch, seed):
    """Prompts spanning 1..3 pages, admitted at different steps, prefilled
    in chunks smaller than a page: with ``attn_backend='paged_kernel'``
    every request's tokens must equal both the gather scheduler's and an
    eviction-free solo B=1 decode.  The fused path streams the same pool
    through the page table the gather path materializes, so any divergence
    is a kernel masking/indexing bug."""
    cfg, model, params = tiny(arch)
    page = 8
    lengths = [6, 12, 20]                 # 1, 2 and 3 pages of 8
    N = 4
    max_seq = max(lengths) + N
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, size=L).astype(np.int32)
               for L in lengths]
    ref = {i: np.asarray(generate(model, params, jnp.asarray(p)[None], N,
                                  seq_len=max_seq))[0]
           for i, p in enumerate(prompts)}

    submits = {0: [("a", "r0", prompts[0], N)],
               2: [("b", "r1", prompts[1], N)],
               3: [("c", "r2", prompts[2], N)]}
    kw = dict(n_slots=3, max_seq=max_seq, kv_mode="paged", page_size=page,
              prefill_chunk=5)
    gather = run_all(DecodeScheduler(model, params, **kw), submits)
    fused_sched = DecodeScheduler(model, params, attn_backend="paged_kernel",
                                  **kw)
    fused = run_all(fused_sched, submits)
    assert fused_sched.stats()["attn_backend"] == "paged_kernel"
    assert sorted(gather) == sorted(fused) == [0, 1, 2]
    for i in range(3):
        np.testing.assert_array_equal(
            fused[i], gather[i],
            err_msg=f"{arch} r{i}: paged_kernel != gather scheduler")
        np.testing.assert_array_equal(
            fused[i], ref[i],
            err_msg=f"{arch} r{i}: paged_kernel != solo decode")
    # the gather-mode scheduler must not have been flipped by the fused
    # one's config rebind (they share the model object)
    assert model.cfg.attn_backend == "gather"


def test_paged_kernel_parity_over_shared_cow_prefix():
    """Three requests decode concurrently over the same page-aligned system
    prefix (rc>1 on the shared pages): the fused kernel reads those pages
    through each slot's own table row and must match gather token for
    token — including after the CoW split when a writer lands on a shared
    page."""
    cfg, model, params = tiny()
    ps, N = 8, 4
    rng = np.random.default_rng(7)
    sys_p = rng.integers(0, cfg.vocab, size=2 * ps).astype(np.int32)
    tails = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
             for n in (3, 6, 10)]
    prompts = [np.concatenate([sys_p, t]) for t in tails]
    max_seq = max(len(p) for p in prompts) + N
    kw = dict(n_slots=3, max_seq=max_seq, kv_mode="paged", page_size=ps,
              prefill_chunk=5, prefix_sharing=True)

    def drive(**extra):
        sched = DecodeScheduler(model, params, **kw, **extra)
        # phase 1: r0 completes and publishes its full sys pages to the
        # prefix index (one index reference per page)
        got = run_all(sched, {0: [("a", "r0", prompts[0], N)]})
        # phase 2: r1 and r2 admit concurrently over the indexed pages —
        # rc = index + r1 + r2 on the shared prefix while both decode
        sched.submit("b", "r1", prompts[1], N)
        sched.submit("c", "r2", prompts[2], N)
        shared_seen, step = False, 0
        while sched.busy():
            for fin in sched.step():
                got[int(fin.request_id[1:])] = fin.tokens
            a = sched.allocator
            shared_seen |= any(a.refcount(p) > 2 for p in range(a.n_pages))
            step += 1
            assert step < 500
        assert shared_seen, "harness never exercised an rc>1 shared page"
        assert sched.stats()["shared_prefix_tokens"] >= 2 * len(sys_p)
        return got

    fused = drive(attn_backend="paged_kernel")
    gather = drive()
    for i in range(3):
        np.testing.assert_array_equal(
            fused[i], gather[i],
            err_msg=f"r{i}: paged_kernel != gather over shared prefix")


# ---------------------------------------------------------------------------
# kvcache-level: scrambled / partially-mapped tables through the helper
# ---------------------------------------------------------------------------


def _scrambled_layer_cache(rng, *, n_pages, ps, Hkv, D, table):
    shape = (n_pages, ps, Hkv * D)
    return {"kp": jnp.asarray(rng.standard_normal(shape), jnp.float32),
            "vp": jnp.asarray(rng.standard_normal(shape), jnp.float32),
            "page_table": jnp.asarray(table, jnp.int32)}


def _pages(pool, D):
    """A pool leaf as the kernel's oracle reads it: (n_pages, ps, Hkv, D)."""
    return pool.reshape(pool.shape[:-1] + (-1, D))


def test_paged_attn_decode_scrambled_and_holey_table():
    """The model-facing helper on a handcrafted pool: physical pages out of
    logical order, one slot with an unmapped (-1) hole below its length, and
    ragged positions — both call modes must match the gather oracle."""
    Hkv, G, D, ps = 2, 3, 8, 4
    rng = np.random.default_rng(3)
    # slot 0: pages scrambled; slot 1: hole at logical page 1 (its tokens
    # 4..7 were dropped by offload) but still decoding at pos 9
    table = [[5, 2, 7, -1], [1, 6, -1, 3]]
    lc = _scrambled_layer_cache(rng, n_pages=9, ps=ps, Hkv=Hkv, D=D,
                                table=table)
    q = jnp.asarray(rng.standard_normal((2, 1, Hkv * G, D)), jnp.float32)
    pos = jnp.asarray([7, 9], jnp.int32)
    k_new = jnp.asarray(rng.standard_normal((2, 1, Hkv, D)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((2, 1, Hkv, D)), jnp.float32)

    for hkw in ({"k_new": k_new, "v_new": v_new}, {"include_new": True}):
        out = kvcache.paged_attn_decode(lc, q, pos, window=None, **hkw)
        rkw = (dict(k_new=k_new, v_new=v_new) if "k_new" in hkw
               else dict(q_pos=pos))
        lengths = pos if "k_new" in hkw else pos + 1
        ref = reference_paged_attention(q, _pages(lc["kp"], D), _pages(lc["vp"], D),
                                        lc["page_table"], lengths, **rkw)
        assert out.shape == q.shape and out.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6,
                                   err_msg=f"mode {sorted(hkw)}")
        assert np.isfinite(np.asarray(out)).all()

    # sliding window through the helper trims the same lanes as the oracle
    out = kvcache.paged_attn_decode(lc, q, pos, window=5, k_new=k_new,
                                    v_new=v_new)
    ref = reference_paged_attention(q, _pages(lc["kp"], D), _pages(lc["vp"], D),
                                    lc["page_table"], pos, window=5,
                                    k_new=k_new, v_new=v_new)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


# ---------------------------------------------------------------------------
# Configuration validation + stats surface
# ---------------------------------------------------------------------------


def test_paged_kernel_backend_validation():
    cfg, model, params = tiny()
    with pytest.raises(ValueError, match="needs kv_mode='paged'"):
        DecodeScheduler(model, params, n_slots=2, max_seq=16,
                        kv_mode="ring", attn_backend="paged_kernel")
    with pytest.raises(ValueError, match="attn_backend must be"):
        DecodeScheduler(model, params, n_slots=2, max_seq=16,
                        attn_backend="flash")
    _, ssm_model, ssm_params = tiny("mamba2-1.3b")
    with pytest.raises(ValueError, match="SSM decode has no KV pool"):
        DecodeScheduler(ssm_model, ssm_params, n_slots=2, max_seq=16,
                        kv_mode="paged", page_size=4,
                        attn_backend="paged_kernel")
    # default surface unchanged
    sched = DecodeScheduler(model, params, n_slots=2, max_seq=16)
    assert sched.stats()["attn_backend"] == "gather"


# ---------------------------------------------------------------------------
# HBM bytes gate: fused must read strictly less than gather
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-14b", "recurrentgemma-2b"])
def test_paged_decode_cell_fused_reads_fewer_bytes(arch):
    """The roofline cell the bench-smoke gate asserts on: at the same pool
    config the fused table-indirect scan touches only the mapped pages,
    while gather materializes the full per-slot span and re-reads it —
    strictly more traffic, also on hybrids where only the attention layers
    carry a pool."""
    roofline = pytest.importorskip(
        "benchmarks.roofline",
        reason="benchmarks package needs the repo root on sys.path")
    cell = roofline.paged_decode_cell(arch, n_slots=4, page_size=8,
                                      max_pages=16, fill=0.5)
    assert cell["status"] == "OK"
    assert cell["fused_hbm_bytes"] < cell["gather_hbm_bytes"], cell
    assert cell["fused_lt_gather"] and cell["bytes_ratio"] > 1.0
    assert cell["mapped_pages"] * 8 >= cell["live_tokens"]
