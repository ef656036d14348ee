"""Paged-attention decode (S=1) Pallas TPU kernel.

The table-indirect analogue of ``kernels/flash_attention``: instead of
gathering a slot's pages into a contiguous (B, T, Hkv, D) tensor in HBM and
running dense attention over it, the kernel streams K/V **pages** straight
out of the shared ``(n_pages, page_size, Hkv, D)`` pool.  The per-slot page
table rides in as a *scalar-prefetch* operand
(:class:`~jax.experimental.pallas.tpu.PrefetchScalarGridSpec`), so the
k/v ``index_map`` can resolve logical kv block ``j`` of slot ``b`` to its
physical page ``page_table[b, j]`` before the grid step runs — the DMA
engine fetches pages by table lookup and the gathered cache never exists in
HBM.

Tiling: grid ``(B, max_pages)`` with the kv-page index innermost
(sequential on TPU), one page **of all kv heads** per kv block: the K/V
block is ``(1, page_size, Hkv, D)`` and the S=1 query block is the whole
``(Hkv, G, D)`` head set of one slot.  The last two dims of every block are
then full array dims, which is what Mosaic requires of a block whose dims
are not multiples of the (8, 128) tile — a ``(1, page_size, 1, D)`` block
over a 36-head pool is refused.  The online-softmax running max /
normalizer / accumulator live in VMEM scratch across the page sweep,
exactly as in the flash kernel, and GQA is a contraction batched over the
kv heads instead of a materialized ``jnp.repeat``.

Masking: lane ``t`` of page ``j`` is attendable iff its page is mapped
(``page_table[b, j] >= 0``), ``t < lengths[b]`` (the slot's live length
bounds the scan), and — for sliding-window archs — ``t > q_pos[b] -
window``.  Unmapped blocks clamp their index_map to page 0 (a benign fetch,
fully masked in compute; on TPU revisiting an already-resident block index
skips the re-fetch).  Masked lanes are zeroed in ``p`` *after* the running
max update, so a fully-masked page contributes nothing even while the
running max is still ``NEG_INF`` — the flash kernel can lean on causal
ordering to dodge that corner; a scrambled page table cannot.

The kernel returns the **unnormalized** accumulator plus the running
``(m, l)`` softmax state instead of the normalized output: ops.py folds the
just-projected decode token in as a rank-1 fp32 update (the paged analogue
of ``layers.sdpa_append``), which needs ``m``/``l`` to splice one more
logit into the streamed softmax.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import resolve_interpret

NEG_INF = -1e30


def _paged_attn_kernel(len_ref, qpos_ref, pt_ref, base_ref, q_ref, k_ref,
                       v_ref, acc_out, m_out, l_out, acc_ref, m_ref, l_ref, *,
                       pos_stride: int, n_blocks: int, scale: float,
                       window: Optional[int]):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale                 # (Hkv, G, D)
    k = k_ref[0].astype(jnp.float32)                         # (ps, Hkv, D)
    v = v_ref[0].astype(jnp.float32)                         # (ps, Hkv, D)
    s = jnp.einsum("hgd,thd->hgt", q, k)                     # (Hkv, G, ps)

    t_pos = (j * pos_stride + base_ref[0]
             + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2))
    mask = (t_pos < len_ref[b]) & (pt_ref[b, j] >= 0)
    if window is not None:
        mask &= t_pos > qpos_ref[b] - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    # zero masked lanes explicitly: while every page so far is masked the
    # running max is still NEG_INF and exp(s - m) == 1 there, which would
    # leak phantom weight into l/acc (scrambled tables hit this; the causal
    # flash sweep never does)
    p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
        "hgt,thd->hgd", p, v)
    m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finish():
        acc_out[0] = acc_ref[...]
        m_out[0] = m_ref[...]
        l_out[0] = l_ref[...]


def paged_attention_kernel(q: jnp.ndarray, kp: jnp.ndarray, vp: jnp.ndarray,
                           page_table: jnp.ndarray, lengths: jnp.ndarray,
                           q_pos: jnp.ndarray, *,
                           lane_base: Optional[jnp.ndarray] = None,
                           pos_stride: Optional[int] = None,
                           window: Optional[int] = None,
                           interpret: Optional[bool] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """q: (B, Hkv, G, D); kp/vp: (n_pages, page_size, Hkv, D);
    page_table: (B, max_pages) int32, -1 = unmapped; lengths/q_pos: (B,).

    Returns ``(acc, m, l)`` — acc ``(B, Hkv, G, D)`` fp32 unnormalized
    accumulator, m/l ``(B, Hkv, G)`` running max / normalizer.  Rows with no
    attendable lane come out as ``(0, NEG_INF, 0)``; ops.py owns both the
    normalization and the new-token append.

    ``lane_base``/``pos_stride`` exist for the shard_map lane decomposition
    (ops.py): a pool lane-sharded on ``model`` hands each shard a
    ``(n_pages, ps_local, Hkv, D)`` slice holding contiguous lanes
    ``[lane_base, lane_base + ps_local)`` of every *global* page of size
    ``pos_stride``, so lane ``t`` of block ``j`` sits at global position
    ``j * pos_stride + lane_base + t``.  ``lane_base`` is a traced ``(1,)``
    int32 (a fourth scalar-prefetch operand — it depends on
    ``axis_index``); ``pos_stride`` is static.  The defaults (0, local page
    size) reproduce the unsharded positions bitwise.
    """
    B, Hkv, G, D = q.shape
    page_size = kp.shape[1]
    max_pages = page_table.shape[1]
    grid = (B, max_pages)
    if pos_stride is None:
        pos_stride = page_size
    if lane_base is None:
        lane_base = jnp.zeros((1,), jnp.int32)

    kernel = functools.partial(
        _paged_attn_kernel, pos_stride=pos_stride, n_blocks=max_pages,
        scale=1.0 / math.sqrt(D), window=window)

    def q_map(b, j, lens, qp, pt, base):
        return (b, 0, 0, 0)

    def kv_map(b, j, lens, qp, pt, base):
        # unmapped blocks clamp to page 0: a benign (masked) fetch, and on
        # TPU a revisited block index skips the DMA entirely
        return (jnp.maximum(pt[b, j], 0), 0, 0, 0)

    def o_map(b, j, lens, qp, pt, base):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), q_map),
            pl.BlockSpec((1, page_size, Hkv, D), kv_map),
            pl.BlockSpec((1, page_size, Hkv, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, Hkv, G, D), q_map),
            pl.BlockSpec((1, Hkv, G), o_map),
            pl.BlockSpec((1, Hkv, G), o_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, D), jnp.float32),   # acc
            pltpu.VMEM((Hkv, G), jnp.float32),      # running max
            pltpu.VMEM((Hkv, G), jnp.float32),      # running normalizer
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(lengths, jnp.int32), jnp.asarray(q_pos, jnp.int32),
      jnp.asarray(page_table, jnp.int32), jnp.asarray(lane_base, jnp.int32),
      q, kp, vp)
