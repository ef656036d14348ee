"""Shared neural-net building blocks (pure JAX, functional).

Parameters are plain nested dicts of ``jnp.ndarray``; initialization takes an
explicit PRNG key.  Everything here is shape-polymorphic over a leading batch
dim and differentiable; models compose these into scanned layer stacks.

Compute dtype discipline: parameters are stored in ``param_dtype`` (fp32
masters) and cast to ``compute_dtype`` (bf16) at use — the usual mixed
precision recipe, and what the roofline's bf16 peak assumes.  Training keeps
the masters; serving casts once at set-up (:func:`to_compute_dtype`).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any

COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32


# Keys of the parameter leaves (or subtrees) whose uses compute in float32:
# per-head qk norms applied to fp32 statistics, the MoE router, the SSM
# decay/skip/gated-norm leaves and the RG-LRU gates.  Every other floating
# leaf is only ever read through a cast to COMPUTE_DTYPE.
F32_PARAM_KEYS = frozenset({
    "q_norm", "k_norm", "router", "dt_bias", "A_log", "D", "norm",
    "gate_w_a", "gate_b_a", "gate_w_x", "gate_b_x", "a_param",
})


def to_compute_dtype(params: PyTree) -> PyTree:
    """Serving weights: every leaf that each use casts to COMPUTE_DTYPE is
    cast once, here, so the values every matmul sees are unchanged and the
    weights take half the memory.  Leaves under :data:`F32_PARAM_KEYS`
    keep their dtype."""
    def leaf(path, x):
        if (not jnp.issubdtype(x.dtype, jnp.floating)
                or any(getattr(k, "key", None) in F32_PARAM_KEYS
                       for k in path)):
            return x
        return x.astype(COMPUTE_DTYPE)

    return jax.tree_util.tree_map_with_path(leaf, params)


def cast(x: jnp.ndarray) -> jnp.ndarray:
    return x.astype(COMPUTE_DTYPE)


def wcast(x: jnp.ndarray, orient: str) -> jnp.ndarray:
    """Cast a weight to compute dtype and (under an explicit-blocks policy)
    constrain the *cast* result so the ZeRO-3 dp-gather moves bf16, not the
    fp32 master.  orient: 'col' (out-dim on model) | 'row' (in-dim on model).
    """
    from ..dist.sharding import constrain

    return constrain(x.astype(COMPUTE_DTYPE), f"w_{orient}")


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, scale: float = 1.0) -> jnp.ndarray:
    std = scale / math.sqrt(d_in)
    return (std * jax.random.normal(key, (d_in, d_out))).astype(PARAM_DTYPE)


def embed_init(key, vocab: int, d: int) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(PARAM_DTYPE)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(kind: str, d: int) -> Dict[str, jnp.ndarray]:
    p = {"scale": jnp.ones((d,), PARAM_DTYPE)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), PARAM_DTYPE)
    return p


def apply_norm(kind: str, p: Dict[str, jnp.ndarray], x: jnp.ndarray,
               eps: float = 1e-6) -> jnp.ndarray:
    """Normalization with f32 *statistics* but a bf16 *tensor* path.

    Upcasting the whole (B,S,D) tensor to f32 (the naive recipe) doubles the
    bytes of every activation reshard GSPMD places near a norm — measured as
    the dominant wire term on qwen1.5-110b (§Perf iteration 2).  Only the
    (B,S,1) moment statistics are f32."""
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(ms + eps).astype(x.dtype)
        return x * inv * p["scale"].astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return ((x - mu.astype(x.dtype)) * inv * p["scale"].astype(x.dtype)
            + p["bias"].astype(x.dtype))


def rms_norm_head(p_scale: jnp.ndarray, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """Per-head RMSNorm over the trailing head_dim (qwen3 qk_norm)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * p_scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    freqs = rope_frequencies(x.shape[-1], theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs          # (..., S, D/2)
    angles = angles[..., None, :]                                      # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, causal / sliding-window / cross, shared by all families)
# ---------------------------------------------------------------------------


def init_attention(key, cfg) -> Dict[str, jnp.ndarray]:
    d, hd = cfg.d_model, cfg.the_head_dim()
    q_dim, kv_dim = cfg.n_heads * hd, cfg.n_kv_heads * hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, q_dim),
        "wk": dense_init(ks[1], d, kv_dim),
        "wv": dense_init(ks[2], d, kv_dim),
        "wo": dense_init(ks[3], q_dim, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((q_dim,), PARAM_DTYPE)
        p["bk"] = jnp.zeros((kv_dim,), PARAM_DTYPE)
        p["bv"] = jnp.zeros((kv_dim,), PARAM_DTYPE)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), PARAM_DTYPE)
        p["k_norm"] = jnp.ones((hd,), PARAM_DTYPE)
    return p


def qkv_project(p, cfg, x: jnp.ndarray, positions: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> q (B,S,H,D), k/v (B,S,Hkv,D) with RoPE applied."""
    from ..dist.sharding import constrain

    hd = cfg.the_head_dim()
    x = constrain(x, "block_in")
    q = jnp.einsum("bsd,dq->bsq", x, wcast(p["wq"], "col"))
    k = jnp.einsum("bsd,dq->bsq", x, wcast(p["wk"], "col"))
    v = jnp.einsum("bsd,dq->bsq", x, wcast(p["wv"], "col"))
    if cfg.qkv_bias:
        q = q + cast(p["bq"])
        k = k + cast(p["bk"])
        v = v + cast(p["bv"])
    B, S = x.shape[0], x.shape[1]
    # explicit head-layout constraints: without these, GSPMD propagates the
    # 16-way projection sharding through the reshape and splits head_dim (the
    # attention *contraction* dim), all-reducing full score tensors.
    q = constrain(q.reshape(B, S, cfg.n_heads, hd), "q_heads")
    k = constrain(k.reshape(B, S, cfg.n_kv_heads, hd), "kv_heads")
    v = constrain(v.reshape(B, S, cfg.n_kv_heads, hd), "kv_heads")
    if cfg.qk_norm:
        q = rms_norm_head(p["q_norm"], q)
        k = rms_norm_head(p["k_norm"], k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# Above this many kv positions, sdpa switches to a streaming-softmax scan
# over kv blocks (flash attention expressed in XLA): the full (S, T) score
# tensor is never materialized, which is what makes the 32k-prefill cells fit
# HBM without the Pallas kernel.  The Pallas kernel implements the same
# algorithm with explicit VMEM tiles for real-TPU runs.
STREAM_KV_THRESHOLD = 4096
STREAM_KV_BLOCK = 1024


def sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
         causal: bool = True,
         window: Optional[int] = None,
         q_positions: Optional[jnp.ndarray] = None,
         kv_positions: Optional[jnp.ndarray] = None,
         kv_valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Grouped-query scaled-dot-product attention.

    q: (B, S, H, D); k, v: (B, T, Hkv, D).  H must be a multiple of Hkv.
    ``q_positions``/``kv_positions`` (B, S)/(B, T) define the mask when the
    query block is not aligned with the kv block (decode with a cache).
    ``kv_valid`` (B, T) masks unfilled cache slots.
    """
    from ..dist.sharding import constrain

    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

    if S > 1 and T >= STREAM_KV_THRESHOLD and T % STREAM_KV_BLOCK == 0:
        out = _sdpa_streaming(q, k, v, causal=causal, window=window,
                              q_positions=q_positions,
                              kv_positions=kv_positions, kv_valid=kv_valid)
        return constrain(out, "attn_out")

    G = H // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(D)
    scores = jnp.where(_attn_mask(q_positions, kv_positions, kv_valid,
                                  causal, window), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v)
    return constrain(out.reshape(B, S, H, D), "attn_out")


def sdpa_append(q: jnp.ndarray, ck: jnp.ndarray, cv: jnp.ndarray,
                k_new: jnp.ndarray, v_new: jnp.ndarray, *,
                window: Optional[int] = None,
                q_positions: jnp.ndarray,
                kv_positions: jnp.ndarray,
                kv_valid: jnp.ndarray) -> jnp.ndarray:
    """Single-token decode attention over (old cache || new token).

    Scores against the *pre-update* cache plus an explicit rank-1 term for
    the new token, combined in one softmax — the reference semantics of the
    fused paged kernel (which streams the pre-update pool and appends the
    new token in fp32).  No longer on the gather decode path: S=1 decode
    rides the chunked ``sdpa`` formulation so decode-written KV is bitwise
    prefill KV.  q/k_new/v_new: (B, 1, H*, D).
    """
    B, S, H, D = q.shape
    Hkv = ck.shape[2]
    G = H // Hkv
    # round the new token through the cache dtype so results are
    # bit-consistent with the read-back-after-update formulation
    k_new = k_new.astype(ck.dtype)
    v_new = v_new.astype(cv.dtype)
    qg = q.reshape(B, S, Hkv, G, D)
    s_old = jnp.einsum("bshgd,bthd->bhgst", qg, ck).astype(jnp.float32)
    s_old = s_old / math.sqrt(D)
    mask = _attn_mask(q_positions, kv_positions, kv_valid, True, window)
    s_old = jnp.where(mask, s_old, -1e30)
    s_new = jnp.einsum("bshgd,bthd->bhgst", qg, k_new).astype(jnp.float32)
    s_new = s_new / math.sqrt(D)   # self-attention of the new token: always valid
    s = jnp.concatenate([s_old, s_new], axis=-1)
    # probs and the value accumulation stay fp32, cast once on the way out —
    # matching the fused paged kernel's fp32 VMEM online-softmax state.
    # Rounding probs to the activation dtype here gave ~1-ulp logit skew vs
    # the kernel, which the MoE router's discreteness could amplify into a
    # token flip (the seed-pinned parity cases test_paged_kernel.py carried).
    p = jax.nn.softmax(s, axis=-1)
    p_old, p_new = p[..., :-1], p[..., -1:]
    out = jnp.einsum("bhgst,bthd->bshgd", p_old, cv.astype(jnp.float32))
    out = out + jnp.einsum("bhgst,bthd->bshgd", p_new,
                           v_new.astype(jnp.float32))
    out = out.astype(q.dtype)
    from ..dist.sharding import constrain

    return constrain(out.reshape(B, S, H, D), "attn_out")


def _attn_mask(q_positions, kv_positions, kv_valid, causal, window):
    qp = q_positions[:, None, None, :, None]      # (B,1,1,S,1)
    kp = kv_positions[:, None, None, None, :]     # (B,1,1,1,T)
    mask = jnp.ones(qp.shape[:-1] + (kp.shape[-1],), dtype=bool)
    if causal:
        mask = mask & (kp <= qp)
    if window is not None:
        mask = mask & (kp > qp - window)
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, None, None, :]
    return mask


def _sdpa_streaming(q, k, v, *, causal, window, q_positions, kv_positions,
                    kv_valid, block: int = STREAM_KV_BLOCK) -> jnp.ndarray:
    """Numerically exact streaming softmax over kv blocks (lax.scan)."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    nb = T // block
    scale = 1.0 / math.sqrt(D)
    qg = (q.astype(jnp.float32) * scale).reshape(B, S, Hkv, G, D)

    kb = jnp.moveaxis(k.reshape(B, nb, block, Hkv, D), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, block, Hkv, D), 1, 0)
    pb = jnp.moveaxis(kv_positions.reshape(B, nb, block), 1, 0)
    valb = (jnp.moveaxis(kv_valid.reshape(B, nb, block), 1, 0)
            if kv_valid is not None else jnp.ones((nb, B, block), bool))

    def step(carry, inp):
        m, l, acc = carry                                # (B,h,g,S), (…), (B,h,g,S,D)
        kc, vc, pc, vac = inp
        s = jnp.einsum("bshgd,bthd->bhgst", qg, kc.astype(jnp.float32))
        mask = _attn_mask(q_positions, pc, vac, causal, window)
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgst,bthd->bhgsd", p, vc.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, G, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, S), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, G, S, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kb, vb, pb, valb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1)                        # (B,S,Hkv,G,D)
    return out.reshape(B, S, H, D).astype(q.dtype)


def attention_block(p, cfg, x: jnp.ndarray, positions: jnp.ndarray, *,
                    window: Optional[int] = None, causal: bool = True) -> jnp.ndarray:
    q, k, v = qkv_project(p, cfg, x, positions)
    o = sdpa(q, k, v, causal=causal, window=window)
    B, S = x.shape[0], x.shape[1]
    o = o.reshape(B, S, cfg.n_heads * cfg.the_head_dim())
    return jnp.einsum("bsq,qd->bsd", o, wcast(p["wo"], "row"))


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, cfg, d_ff: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], d, f),
            "w_up": dense_init(ks[1], d, f),
            "w_down": dense_init(ks[2], f, d),
        }
    return {
        "w_up": dense_init(ks[0], d, f),
        "b_up": jnp.zeros((f,), PARAM_DTYPE),
        "w_down": dense_init(ks[1], f, d),
        "b_down": jnp.zeros((d,), PARAM_DTYPE),
    }


def apply_mlp(p, cfg, x: jnp.ndarray) -> jnp.ndarray:
    from ..dist.sharding import constrain

    x = constrain(x, "block_in")   # gather S at block entry (Megatron-SP)
    if cfg.mlp in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp == "swiglu" else jax.nn.gelu
        g = act(jnp.einsum("bsd,df->bsf", x, wcast(p["w_gate"], "col")))
        u = jnp.einsum("bsd,df->bsf", x, wcast(p["w_up"], "col"))
        h = constrain(g * u, "mlp_hidden")
        return jnp.einsum("bsf,fd->bsd", h, wcast(p["w_down"], "row"))
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, wcast(p["w_up"], "col")) + cast(p["b_up"]))
    h = constrain(h, "mlp_hidden")
    return jnp.einsum("bsf,fd->bsd", h, wcast(p["w_down"], "row")) + cast(p["b_down"])


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def init_embedding(key, cfg) -> Dict[str, jnp.ndarray]:
    ks = jax.random.split(key, 2)
    vp = cfg.padded_vocab
    p = {"embed": embed_init(ks[0], vp, cfg.d_model)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], cfg.d_model, vp)
    return p


def embed_tokens(p, cfg, tokens: jnp.ndarray) -> jnp.ndarray:
    x = cast(p["embed"])[tokens]
    return x * jnp.asarray(cfg.emb_scale, x.dtype)


def lm_head(p, cfg, x: jnp.ndarray) -> jnp.ndarray:
    from ..dist.sharding import constrain

    if cfg.tie_embeddings:
        # re-shard the tied table from (gather-friendly) d-sharded to
        # (matmul-friendly) vocab-sharded before the projection: a small
        # weight all-to-all instead of a huge logits all-reduce.
        w = constrain(cast(p["embed"]).T, "head_weight")
    else:
        w = cast(p["head"])
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    if cfg.padded_vocab != cfg.vocab:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype), logits)
    return logits * jnp.asarray(cfg.logit_scale, logits.dtype)


def sinusoidal_positions(S: int, d: int, offset=0) -> jnp.ndarray:
    """Length-agnostic absolute embeddings (whisper stub-fidelity).

    ``offset`` may be a traced scalar (decode position)."""
    pos = (jnp.arange(S, dtype=jnp.float32) + offset)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d)
    pe = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)
    return pe.astype(COMPUTE_DTYPE)
