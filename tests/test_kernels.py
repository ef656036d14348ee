"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, reference_attention
from repro.kernels.paged_attention import (paged_attention,
                                           reference_paged_attention)
from repro.kernels.rglru_scan import reference_rglru, rglru_scan
from repro.kernels.ssd_scan import reference_ssd, ssd_scan

ATOL = {jnp.float32: 2e-4, jnp.bfloat16: 3e-2}


def _tol(dtype):
    return dict(atol=ATOL[dtype], rtol=ATOL[dtype])


def paged_inputs(seed, B, Hkv, G, D, ps, mp, n_pages, dtype,
                 fill=0.8, holes=0):
    """Random pool + per-slot tables: scrambled physical pages, ragged live
    lengths, optional unmapped (-1) holes punched below the live length."""
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (B, 1, Hkv * G, D), dtype)
    kp = jax.random.normal(ks[1], (n_pages, ps, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (n_pages, ps, Hkv, D), dtype)
    k_new = jax.random.normal(ks[3], (B, 1, Hkv, D), dtype)
    v_new = jax.random.normal(ks[4], (B, 1, Hkv, D), dtype)
    lengths = rng.integers(1, max(2, int(mp * ps * fill)), size=B)
    pt = np.full((B, mp), -1, np.int32)
    for b in range(B):
        need = -(-int(lengths[b]) // ps)
        pt[b, :need] = rng.choice(n_pages, size=need, replace=False)
        for _ in range(holes):
            pt[b, rng.integers(0, mp)] = -1
    return (q, kp, vp, jnp.asarray(pt), jnp.asarray(lengths, jnp.int32),
            k_new, v_new)


# -- flash attention ------------------------------------------------------------


@pytest.mark.parametrize("B,S,T,H,Hkv,D", [
    (1, 16, 16, 4, 4, 8),      # MHA square
    (2, 32, 32, 8, 2, 16),     # GQA
    (1, 24, 40, 4, 1, 32),     # MQA, S != T, non-multiples of block
    (2, 128, 128, 4, 4, 64),   # block-aligned
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 8])
def test_flash_attention_sweep(B, S, T, H, Hkv, D, dtype, window):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=True, window=window, bq=16, bk=16)
    G = H // Hkv
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, 1).reshape(B * H, T, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, 1).reshape(B * H, T, D)
    ref = reference_attention(qf, kf, vf, causal=True, window=window)
    ref = ref.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_matches_model_sdpa():
    """Kernel agrees with the model-layer attention used by the XLA path."""
    from repro.models.layers import sdpa

    ks = jax.random.split(jax.random.key(1), 3)
    B, S, H, Hkv, D = 2, 24, 8, 4, 16
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    got = flash_attention(q, k, v, causal=True, bq=8, bk=8)
    want = sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                               atol=2e-4, rtol=2e-4)


# -- paged attention -------------------------------------------------------------


@pytest.mark.parametrize("B,Hkv,G,D,ps,mp,n_pages,holes", [
    (1, 1, 1, 8, 4, 4, 8, 0),      # MQA/MHA minimal
    (3, 2, 3, 16, 8, 6, 32, 1),    # GQA, scrambled pages + a hole per slot
    (2, 4, 2, 32, 16, 8, 64, 2),   # wider pool, more holes
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 12])
def test_paged_attention_append_sweep(B, Hkv, G, D, ps, mp, n_pages, holes,
                                      dtype, window):
    q, kp, vp, pt, lengths, k_new, v_new = paged_inputs(
        B * 7 + mp, B, Hkv, G, D, ps, mp, n_pages, dtype, holes=holes)
    out = paged_attention(q, kp, vp, pt, lengths, k_new=k_new, v_new=v_new,
                          window=window)
    ref = reference_paged_attention(q, kp, vp, pt, lengths, k_new=k_new,
                                    v_new=v_new, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_post_update_sweep(dtype):
    """No-append mode (hybrid layers: the token is already in the pool) —
    the query sits at the last live lane."""
    B, Hkv, G, D, ps, mp = 3, 2, 2, 16, 8, 5
    q, kp, vp, pt, lengths, _, _ = paged_inputs(
        11, B, Hkv, G, D, ps, mp, 24, dtype, holes=1)
    out = paged_attention(q, kp, vp, pt, lengths, q_pos=lengths - 1,
                          window=8)
    ref = reference_paged_attention(q, kp, vp, pt, lengths,
                                    q_pos=lengths - 1, window=8)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_paged_attention_matches_model_gather_path():
    """Kernel == the gather formulation the decode paths use today:
    ``cache_kv_view`` (logical-order page gather) + ``sdpa_append``."""
    from repro.models import kvcache
    from repro.models.layers import sdpa_append

    B, Hkv, G, D, ps, mp = 2, 2, 4, 16, 4, 6
    q, kp, vp, pt, lengths, k_new, v_new = paged_inputs(
        3, B, Hkv, G, D, ps, mp, 16, jnp.float32, holes=1)
    got = paged_attention(q, kp, vp, pt, lengths, k_new=k_new, v_new=v_new)
    lc = {"kp": kp.reshape(kp.shape[:2] + (-1,)),
          "vp": vp.reshape(vp.shape[:2] + (-1,)), "page_table": pt}
    ck, cv, kv_pos, kv_valid = kvcache.cache_kv_view(lc, upto=lengths, n_kv=Hkv)
    want = sdpa_append(q, ck, cv, k_new, v_new, window=None,
                       q_positions=kvcache.decode_positions(lengths, B, 1),
                       kv_positions=kv_pos, kv_valid=kv_valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                               atol=2e-4, rtol=2e-4)


def test_paged_attention_fully_unmapped_slot():
    """A slot with zero mapped pages must fall back to the new token alone
    (softmax over one logit), not NaN."""
    B, Hkv, G, D, ps, mp = 2, 1, 2, 8, 4, 3
    q, kp, vp, pt, _, k_new, v_new = paged_inputs(
        5, B, Hkv, G, D, ps, mp, 8, jnp.float32)
    pt = pt.at[1].set(-1)
    lengths = jnp.asarray([6, 0], jnp.int32)
    out = paged_attention(q, kp, vp, pt, lengths, k_new=k_new, v_new=v_new)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(
        np.asarray(out)[1, 0].reshape(Hkv, G, D),
        np.broadcast_to(np.asarray(v_new)[1, 0][:, None, :], (Hkv, G, D)),
        atol=1e-6, rtol=1e-6)


# -- ssd scan --------------------------------------------------------------------


@pytest.mark.parametrize("B,L,H,P,N,chunk,bh", [
    (1, 16, 2, 4, 8, 8, 2),
    (2, 37, 6, 8, 16, 8, 2),    # ragged L, H % bh != 0
    (1, 64, 4, 16, 32, 16, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, L, H, P, N, chunk, bh, dtype):
    ks = jax.random.split(jax.random.key(2), 4)
    x = jax.random.normal(ks[0], (B, L, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, L, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[0], (B, L, N)) * 0.5).astype(dtype)
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, bh=bh)
    yr = reference_ssd(x.astype(jnp.float32), dt.astype(jnp.float32), A,
                       Bm.astype(jnp.float32), Cm.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=ATOL[dtype] * 5, rtol=ATOL[dtype] * 5)


def test_ssd_kernel_matches_model_chunked():
    """Kernel == the model's chunked SSD == the sequential recurrence."""
    from repro.models.mamba2 import ssd_chunked

    ks = jax.random.split(jax.random.key(3), 5)
    B, L, H, P, N = 2, 24, 4, 8, 16
    x = jax.random.normal(ks[0], (B, L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, L, N)) * 0.5
    y_kernel = ssd_scan(x, dt, A, Bm, Cm, chunk=8, bh=2)
    y_model, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=8)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               atol=1e-4, rtol=1e-4)


# -- rg-lru scan -------------------------------------------------------------------


@pytest.mark.parametrize("B,L,W,bq,bw", [
    (1, 16, 8, 8, 8),
    (2, 29, 24, 8, 8),          # ragged both dims
    (1, 128, 64, 32, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_sweep(B, L, W, bq, bw, dtype):
    ks = jax.random.split(jax.random.key(4), 2)
    a = (jax.nn.sigmoid(jax.random.normal(ks[0], (B, L, W))) * 0.98 + 0.01).astype(dtype)
    b = jax.random.normal(ks[1], (B, L, W), dtype)
    h = rglru_scan(a, b, block_q=bq, block_w=bw)
    hr = reference_rglru(a.astype(jnp.float32), b.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(hr, np.float32),
                               atol=ATOL[dtype] * 5, rtol=ATOL[dtype] * 5)


def test_rglru_kernel_matches_model_scan():
    from repro.models.rglru import rglru_scan as model_scan

    ks = jax.random.split(jax.random.key(5), 2)
    B, L, W = 2, 20, 16
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, L, W))) * 0.9 + 0.05
    b = jax.random.normal(ks[1], (B, L, W))
    got = rglru_scan(a, b, block_q=8, block_w=8)
    want = model_scan(b, a)  # model takes (x_in, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_rglru_near_one_decay_stability():
    """a ~ 0.999^c as in trained RG-LRU; long block, no drift."""
    B, L, W = 1, 256, 8
    a = jnp.full((B, L, W), 0.999, jnp.float32)
    b = jnp.ones((B, L, W), jnp.float32) * 0.01
    h = rglru_scan(a, b, block_q=128, block_w=8)
    hr = reference_rglru(a, b)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4, rtol=1e-4)
