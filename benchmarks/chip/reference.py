"""The plain float32 reference of the benchmark's decoders.

Independent of the program: it imports nothing of ``repro`` and reads its
weights from :mod:`weights`, made again from the seed.  A full causal
forward over each sequence, no cache, no batching of decode steps, every
matrix product at ``Precision.HIGHEST`` in float32.  What every
configuration shares is here:

- embedding times ``scale_emb``; residual branches times
  ``scale_depth / sqrt(layers)`` (MiniCPM; the block applies it);
- RMSNorm or LayerNorm with the published eps;
- rotary embedding on the two halves of each head (``rotate_half``);
- grouped-query causal attention, with the sliding window where the
  configuration has one, in blocks of query rows so that the score matrix
  of a long sequence fits;
- the scan over the layers, each the configuration's block
  (``blocks/<name>.py``, its ``block``), and the final norm;
- the head (the transposed embedding when tied) times
  ``dim_model_base / hidden_size`` (MiniCPM).

``quant="fp8"`` is the correctness control: every matrix product (the
projections, the attention scores and values, the MLP and the head) takes
its operands through float8 e4m3, scaled per row or column of the
contracted dimension, and accumulates in float32."""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from . import blocks

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(x, w, quant: Optional[str]):
    """``x (..., k) @ w (k, n)`` in float32, or through fp8 for the control."""
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def norm(x, p, dm):
    if dm["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + dm["eps"]) * p["scale"] + p["bias"]
    ms = (x * x).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + dm["eps"]) * p["scale"]


def rope(x, theta):
    """x (T, H, D): rotate the halves ``x1, x2`` of every head."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, dm, quant):
    """One sequence: q (T, H, D), k/v (T, Hkv, D) -> (T, H, D)."""
    T, H, D = q.shape
    G = H // dm["Hkv"]
    if quant == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    kpos = jnp.arange(T)
    nb = -(-T // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - T), (0, 0), (0, 0)))
    qb = qb.reshape(nb, Q_BLOCK, H, D)

    def block(args):
        i, qq = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,thd->hqt", qq, k, precision=HI) / math.sqrt(D)
        mask = kpos[None, :] <= qpos[:, None]
        if dm["window"]:
            mask &= kpos[None, :] > qpos[:, None] - dm["window"]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            p = _fp8(p, -1)
        return jnp.einsum("hqt,thd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(nb * Q_BLOCK, H, D)[:T]


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("dm_items", "quant"))
def _forward(params, tokens, pos, targets, *, dm_items, quant):
    dm = dict(dm_items)
    x = params["embedding"]["embed"][tokens].astype(jnp.float32) * dm["emb_scale"]

    block = blocks.load(dm["block"]).block

    def layer(x, lp):
        return block(x, _f32(lp), dm, quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = norm(x, _f32(params["final_norm"]), dm)
    xg = jnp.take_along_axis(x, pos[..., None], axis=1)             # (K, N, d)
    if dm["tied"]:
        head = params["embedding"]["embed"][: dm["vocab"]].T
    else:
        head = params["embedding"]["head"][:, : dm["vocab"]]
    logits = mm(xg, head.astype(jnp.float32), quant) * dm["logit_scale"]
    best = logits.max(-1)
    at = jnp.take_along_axis(logits[None], targets[..., None], axis=-1)[..., 0]
    return best, at, logits.argmax(-1).astype(jnp.int32)


def forward(params: Dict, dm: Dict, tokens, pos, targets, *,
            quant: Optional[str] = None):
    """Logit statistics at the positions ``pos`` (K, N) of the sequences
    ``tokens`` (K, T): the best logit (K, N), the logits of ``targets``
    (M, K, N), and the argmax (K, N).  ``params`` in the program's layout
    (bf16 values, read in float32)."""
    return _forward(params, tokens, pos, targets,
                    dm_items=tuple(sorted(dm.items())), quant=quant)
