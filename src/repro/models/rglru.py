"""RecurrentGemma / Griffin hybrid (arXiv:2402.19427).

Layer pattern ``rra`` (two RG-LRU recurrent blocks, one local-attention MQA
block) repeated over 26 layers.  The RG-LRU linear recurrence

    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t),
    a_t = exp(-c * softplus(L) * r_t)

is evaluated with ``lax.associative_scan`` (log-depth — the TPU-native way to
run a diagonal linear recurrence; kernels/rglru_scan gives the Pallas version).
Sub-quadratic (local attention window 2048 + O(1) recurrent state), so this
arch runs the ``long_500k`` cell.

The layer stack scans over *super-blocks* (one ``rra`` group), with the
non-divisible tail unrolled — HLO stays O(1) in depth.
"""

from __future__ import annotations

import operator
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import constrain
from . import kvcache, layers
from .config import ArchConfig, layer_pattern
from .layers import cast, wcast
from .transformer import DenseLM, remat_wrap

C_RGLRU = 8.0

# Chunks up to this length run the recurrence as a strict left fold
# (``lax.scan``) instead of the log-depth ``associative_scan``.  The left
# fold computes h_t = a_t*h_{t-1} + b_t in exactly the order a sequence of
# S=1 decode steps would, so a short chunk (spec-decode verify, chunked
# prefill tail) is bitwise-identical to stepping token by token — the
# invariant accept/reject speculation relies on.  associative_scan happens
# to be left-fold-exact for S <= 3 but reassociates (and drifts in low fp32
# bits) from S = 4; long prefill keeps the log-depth form for perf.
RGLRU_LEFT_FOLD_MAX = 16


# ---------------------------------------------------------------------------
# RG-LRU core
# ---------------------------------------------------------------------------


def rglru_scan(x_in: jnp.ndarray, a: jnp.ndarray,
               h0: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Diagonal linear recurrence h_t = a_t h_{t-1} + b_t over axis 1.

    x_in (=b), a: (B, S, W) fp32.  h0: (B, W) initial state.
    """
    if h0 is not None:
        x_in = x_in.at[:, 0].add(a[:, 0] * h0)

    if x_in.shape[1] <= RGLRU_LEFT_FOLD_MAX:
        # sequential fold from zero state (h0 already folded into b_0):
        # a_0*0 + b_0 == b_0 bitwise, and each a_t*h + b_t matches the
        # fold-in an S=1 step performs, so chunk == token-by-token exactly.
        def step(h, ab):
            a_t, b_t = ab
            h = a_t * h + b_t
            return h, h

        _, hs = jax.lax.scan(step, jnp.zeros_like(x_in[:, 0]),
                             (jnp.moveaxis(a, 1, 0), jnp.moveaxis(x_in, 1, 0)))
        return jnp.moveaxis(hs, 0, 1)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, x_in), axis=1)
    return h


def rglru_gates(p: Dict, x: jnp.ndarray, n_blocks: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Block-diagonal gate projections (Griffin): returns (a, gated_input)."""
    B, S, W = x.shape
    Wb = W // n_blocks
    xb = x.reshape(B, S, n_blocks, Wb).astype(jnp.float32)
    r = jax.nn.sigmoid(jnp.einsum("bskw,kwv->bskv", xb, p["gate_w_a"].astype(jnp.float32))
                       + p["gate_b_a"].astype(jnp.float32))
    i = jax.nn.sigmoid(jnp.einsum("bskw,kwv->bskv", xb, p["gate_w_x"].astype(jnp.float32))
                       + p["gate_b_x"].astype(jnp.float32))
    r = r.reshape(B, S, W)
    i = i.reshape(B, S, W)
    log_a = -C_RGLRU * jax.nn.softplus(p["a_param"].astype(jnp.float32)) * r
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.clip(1.0 - a * a, 1e-12)) * (i * x.astype(jnp.float32))
    return a, gated


def init_rec_mixer(key, cfg: ArchConfig) -> Dict:
    h = cfg.hybrid
    W = h.lru_width or cfg.d_model
    nb = cfg.n_heads
    Wb = W // nb
    ks = jax.random.split(key, 6)
    # a_param init so that a^(1/c) ~ U(0.9, 0.999) at r=1 (Griffin App. A)
    a0 = jax.random.uniform(ks[0], (W,), minval=0.9, maxval=0.999)
    a_param = jnp.log(jnp.expm1(-jnp.log(a0) / C_RGLRU))
    return {
        "w_x": layers.dense_init(ks[1], cfg.d_model, W),
        "w_y": layers.dense_init(ks[2], cfg.d_model, W),
        "conv_w": (0.1 * jax.random.normal(ks[3], (h.d_conv, W))).astype(layers.PARAM_DTYPE),
        "conv_b": jnp.zeros((W,), layers.PARAM_DTYPE),
        "gate_w_a": (jax.random.normal(ks[4], (nb, Wb, Wb)) / math.sqrt(Wb)).astype(layers.PARAM_DTYPE),
        "gate_b_a": jnp.zeros((nb, Wb), layers.PARAM_DTYPE),
        "gate_w_x": (jax.random.normal(ks[5], (nb, Wb, Wb)) / math.sqrt(Wb)).astype(layers.PARAM_DTYPE),
        "gate_b_x": jnp.zeros((nb, Wb), layers.PARAM_DTYPE),
        "a_param": a_param.astype(layers.PARAM_DTYPE),
        "w_out": layers.dense_init(ks[0], W, cfg.d_model),
    }


def rec_mix(p: Dict, cfg: ArchConfig, x: jnp.ndarray,
            state: Optional[Dict] = None, want_state: bool = False
            ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Griffin recurrent block mixer.  state={'h': (B,W), 'conv': (B,K-1,W)}."""
    h_cfg = cfg.hybrid
    K = h_cfg.d_conv
    y_branch = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, wcast(p["w_y"], "col")))
    xw = jnp.einsum("bsd,dw->bsw", x, wcast(p["w_x"], "col"))

    # a state with S > 1 is a *continuation* (chunked prefill): the conv
    # carry and h0 thread the recurrence across chunk boundaries exactly as
    # S == 1 decode does — from a zero state this reduces bitwise to the
    # zero-padded monolithic prefill.
    continuing = state is not None
    carry = state["conv"] if continuing else None
    conv_in = xw
    # causal depthwise conv (no activation in griffin conv)
    if carry is None:
        padded = jnp.concatenate(
            [jnp.zeros((xw.shape[0], K - 1, xw.shape[2]), xw.dtype), xw], axis=1)
    else:
        padded = jnp.concatenate([carry.astype(xw.dtype), xw], axis=1)
    xc = sum(padded[:, i:i + xw.shape[1], :] * cast(p["conv_w"][i]) for i in range(K))
    xc = xc + cast(p["conv_b"])

    new_state: Optional[Dict] = None
    if continuing or want_state:
        prev = (carry if carry is not None
                else jnp.zeros((xw.shape[0], K - 1, xw.shape[2]), conv_in.dtype))
        tail = jnp.concatenate([prev.astype(conv_in.dtype), conv_in], axis=1)[:, -(K - 1):]
        new_state = {"conv": tail}

    # shard channels (not seq) across model for the scan: the associative
    # scan is sequential in S, so S must be local; W/16 keeps its log-depth
    # intermediate buffers small.
    xc = constrain(xc, "lru_channels")
    a, gated = rglru_gates(p, xc, cfg.n_heads)
    a = constrain(a, "lru_channels")
    gated = constrain(gated, "lru_channels")
    h0 = state["h"] if continuing else None
    h = rglru_scan(gated, a, h0=h0)
    if continuing or want_state:
        new_state["h"] = h[:, -1]
    h = h.astype(x.dtype) * y_branch
    return jnp.einsum("bsw,wd->bsd", h, wcast(p["w_out"], "row")), new_state


# ---------------------------------------------------------------------------
# Layer / super-block structure
# ---------------------------------------------------------------------------


def init_hybrid_layer(key, cfg: ArchConfig, kind: str) -> Dict:
    ks = jax.random.split(key, 2)
    p = {
        "norm": layers.init_norm(cfg.norm, cfg.d_model),
        "mlp_norm": layers.init_norm(cfg.norm, cfg.d_model),
        "mlp": layers.init_mlp(ks[1], cfg),
    }
    if kind == "r":
        p["rec"] = init_rec_mixer(ks[0], cfg)
    else:
        p["attn"] = layers.init_attention(ks[0], cfg)
    return p


def _layer_step(p: Dict, cfg: ArchConfig, kind: str, x: jnp.ndarray,
                positions: jnp.ndarray, lc: Optional[Dict], pos,
                want_state: bool) -> Tuple[jnp.ndarray, Optional[Dict]]:
    h = layers.apply_norm(cfg.norm, p["norm"], x)
    new_lc: Optional[Dict] = None
    if kind == "r":
        h, new_lc = rec_mix(p["rec"], cfg, h,
                            state=lc if lc is not None else None,
                            want_state=want_state)
    else:
        if lc is None:
            h = layers.attention_block(p["attn"], cfg, h, positions,
                                       window=cfg.hybrid.local_window)
        else:
            B, S = h.shape[0], h.shape[1]
            q, k, v = layers.qkv_project(p["attn"], cfg, h, positions)
            new_lc = kvcache.cache_update_layer(lc, k, v, pos)
            if S > kvcache.cache_capacity(lc):  # prefill longer than the ring window
                o = layers.sdpa(q, k, v, causal=True, window=cfg.hybrid.local_window,
                                q_positions=positions, kv_positions=positions)
            elif (S == 1 and cfg.attn_backend == "paged_kernel"
                  and kvcache.is_paged(lc)):
                # fused table-indirect kernel over the POST-update pool (the
                # token is already written; lane ``pos`` itself is attended)
                o = kvcache.paged_attn_decode(new_lc, q, pos,
                                              window=cfg.hybrid.local_window,
                                              include_new=True)
            else:
                ck, cv, kv_pos, kv_valid = kvcache.cache_kv_view(
                    new_lc, upto=pos + S, n_kv=cfg.n_kv_heads)
                o = layers.sdpa(q, ck, cv, causal=True, window=cfg.hybrid.local_window,
                                q_positions=positions, kv_positions=kv_pos, kv_valid=kv_valid)
            o = o.reshape(B, S, cfg.n_heads * cfg.the_head_dim())
            h = jnp.einsum("bsq,qd->bsd", o, layers.wcast(p["attn"]["wo"], "row"))
    x = x + h
    x = constrain(x, "activation")
    h = layers.apply_norm(cfg.norm, p["mlp_norm"], x)
    x = x + layers.apply_mlp(p["mlp"], cfg, h)
    return constrain(x, "activation"), new_lc


class RecurrentLM(DenseLM):
    def __init__(self, cfg: ArchConfig):
        super().__init__(cfg)
        self.pattern = cfg.hybrid.pattern
        self.full_pattern = layer_pattern(cfg)
        self.n_sb = cfg.n_layers // len(self.pattern)
        self.tail_pattern = self.full_pattern[self.n_sb * len(self.pattern):]

    # -- init -------------------------------------------------------------------

    def init(self, key) -> Dict:
        cfg = self.cfg
        k_emb, k_blocks, k_tail = jax.random.split(key, 3)

        def one_sb(k):
            ks = jax.random.split(k, len(self.pattern))
            return {f"l{j}": init_hybrid_layer(ks[j], cfg, kind)
                    for j, kind in enumerate(self.pattern)}

        params = {
            "embedding": layers.init_embedding(k_emb, cfg),
            "blocks": jax.vmap(one_sb)(jax.random.split(k_blocks, self.n_sb)),
            "final_norm": layers.init_norm(cfg.norm, cfg.d_model),
        }
        if self.tail_pattern:
            ks = jax.random.split(k_tail, len(self.tail_pattern))
            params["tail"] = {f"t{j}": init_hybrid_layer(ks[j], cfg, kind)
                              for j, kind in enumerate(self.tail_pattern)}
        return params

    # -- fwd ---------------------------------------------------------------------

    def apply(self, params: Dict, batch: Dict) -> jnp.ndarray:
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = layers.embed_tokens(params["embedding"], cfg, tokens)
        x = constrain(x, "activation")
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

        def sb_body(carry, p):
            h = carry
            for j, kind in enumerate(self.pattern):
                h, _ = _layer_step(p[f"l{j}"], cfg, kind, h, positions, None, None, False)
            return h, None

        fn = remat_wrap(sb_body, cfg.remat)
        if cfg.scan_layers:
            x, _ = jax.lax.scan(fn, x, params["blocks"])
        else:
            for i in range(self.n_sb):
                p = jax.tree_util.tree_map(operator.itemgetter(i), params["blocks"])
                x, _ = fn(x, p)
        for j, kind in enumerate(self.tail_pattern):
            x, _ = _layer_step(params["tail"][f"t{j}"], cfg, kind, x,
                               positions, None, None, False)
        x = layers.apply_norm(cfg.norm, params["final_norm"], x)
        logits = layers.lm_head(params["embedding"], cfg, x)
        return constrain(logits, "logits")

    # -- decode ------------------------------------------------------------------

    def cache_len(self, seq_len: int) -> int:
        return min(seq_len, self.cfg.hybrid.local_window)

    def _empty_caches(self, B: int, seq_len: int):
        cfg = self.cfg
        W = cfg.hybrid.lru_width or cfg.d_model
        K = cfg.hybrid.d_conv
        T = self.cache_len(seq_len)
        hd = cfg.the_head_dim()

        def one(kind):
            if kind == "r":
                return {"h": jnp.zeros((B, W), jnp.float32),
                        "conv": jnp.zeros((B, K - 1, W), layers.COMPUTE_DTYPE)}
            return {"k": jnp.zeros((B, T, cfg.n_kv_heads, hd), layers.COMPUTE_DTYPE),
                    "v": jnp.zeros((B, T, cfg.n_kv_heads, hd), layers.COMPUTE_DTYPE),
                    "positions": -jnp.ones((B, T), jnp.int32)}

        block = {f"l{j}": one(kind) for j, kind in enumerate(self.pattern)}
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (self.n_sb,) + a.shape).copy(), block)
        cache = {"blocks": stacked}
        if self.tail_pattern:
            cache["tail"] = {f"t{j}": one(kind) for j, kind in enumerate(self.tail_pattern)}
        return cache

    def init_cache(self, B: int, seq_len: int) -> Dict:
        cache = self._empty_caches(B, seq_len)
        cache["length"] = jnp.zeros((), jnp.int32)
        return cache

    def _step_with_cache(self, params, cache, tokens, want_state: bool):
        cfg = self.cfg
        B, S = tokens.shape
        x = layers.embed_tokens(params["embedding"], cfg, tokens)
        pos = cache["length"]
        positions = kvcache.decode_positions(pos, B, S)

        def sb_body(carry, pc):
            h = carry
            p, lc = pc
            new_lcs = {}
            for j, kind in enumerate(self.pattern):
                h, nlc = _layer_step(p[f"l{j}"], cfg, kind, h, positions,
                                     lc[f"l{j}"], pos, want_state)
                new_lcs[f"l{j}"] = nlc if nlc is not None else lc[f"l{j}"]
            return h, new_lcs

        if cfg.scan_layers:
            x, new_blocks = jax.lax.scan(sb_body, x, (params["blocks"], cache["blocks"]))
        else:
            outs = []
            for i in range(self.n_sb):
                p = jax.tree_util.tree_map(operator.itemgetter(i), params["blocks"])
                lc = jax.tree_util.tree_map(operator.itemgetter(i), cache["blocks"])
                x, nc = sb_body(x, (p, lc))
                outs.append(nc)
            new_blocks = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)

        new_cache = {"blocks": new_blocks, "length": cache["length"] + S}
        if self.tail_pattern:
            new_tail = {}
            for j, kind in enumerate(self.tail_pattern):
                x, nlc = _layer_step(params["tail"][f"t{j}"], cfg, kind, x, positions,
                                     cache["tail"][f"t{j}"], pos, want_state)
                new_tail[f"t{j}"] = nlc if nlc is not None else cache["tail"][f"t{j}"]
            new_cache["tail"] = new_tail
        x = layers.apply_norm(cfg.norm, params["final_norm"], x)
        logits = layers.lm_head(params["embedding"], cfg, x)
        return constrain(logits, "logits"), new_cache

    def decode_step(self, params, cache, tokens):
        return self._step_with_cache(params, cache, tokens, want_state=False)

    def prefill(self, params, tokens, *, seq_len=None):
        cache = self.init_cache(tokens.shape[0], seq_len or tokens.shape[1])
        return self._step_with_cache(params, cache, tokens, want_state=True)
