"""Dense decoder-only transformer (starcoder2 / qwen3 / qwen1.5 / minicpm,
and the LM backbone of internvl2).

Layer stack is a ``lax.scan`` over stacked per-layer params (HLO size is
O(1) in depth — mandatory for the 80/94-layer archs), with configurable
rematerialization.  The same block is reused by moe.py (which swaps the MLP)
and whisper.py (which adds cross-attention).
"""

from __future__ import annotations

import operator
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import constrain
from . import kvcache, layers
from .config import ArchConfig


def remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def init_dense_layer(key, cfg: ArchConfig) -> Dict:
    ks = jax.random.split(key, 2)
    return {
        "attn_norm": layers.init_norm(cfg.norm, cfg.d_model),
        "attn": layers.init_attention(ks[0], cfg),
        "mlp_norm": layers.init_norm(cfg.norm, cfg.d_model),
        "mlp": layers.init_mlp(ks[1], cfg),
    }


def dense_layer_fwd(p: Dict, cfg: ArchConfig, x: jnp.ndarray,
                    positions: jnp.ndarray) -> jnp.ndarray:
    rs = jnp.asarray(cfg.residual_scale, x.dtype)
    h = layers.apply_norm(cfg.norm, p["attn_norm"], x)
    h = layers.attention_block(p["attn"], cfg, h, positions,
                               window=cfg.sliding_window)
    x = x + h * rs
    x = constrain(x, "activation")
    h = layers.apply_norm(cfg.norm, p["mlp_norm"], x)
    h = layers.apply_mlp(p["mlp"], cfg, h)
    x = x + h * rs
    return constrain(x, "activation")


def attention_decode(p: Dict, cfg: ArchConfig, h: jnp.ndarray, layer_cache: Dict,
                     pos: jnp.ndarray, layer=None) -> Tuple[jnp.ndarray, Dict]:
    """Attention half of a decode layer against a ring or paged cache:
    project, write the new K/V, attend, project out.  Returns (output,
    updated cache).  ``layer`` addresses one layer of a stacked cache (see
    :mod:`repro.models.kvcache`); None takes one unstacked layer.
    """
    B, S = h.shape[0], h.shape[1]
    positions = kvcache.decode_positions(pos, B, S)
    q, k, v = layers.qkv_project(p, cfg, h, positions)
    new_cache = kvcache.cache_update_layer(layer_cache, k, v, pos, layer)
    if S > kvcache.cache_capacity(layer_cache):
        # prefill-from-scratch longer than the (windowed) ring: the ring only
        # keeps the trailing window, so attend the fresh full-sequence k/v.
        o = layers.sdpa(q, k, v, causal=True, window=cfg.sliding_window,
                        q_positions=positions, kv_positions=positions)
    elif S == 1 and cfg.attn_backend == "paged_kernel" and kvcache.is_paged(layer_cache):
        # fused path: stream the slot's pages via the table-indirect Pallas
        # kernel (pre-update pool + fp32 new-token append) — the gathered
        # cache never materializes in HBM.
        o = kvcache.paged_attn_decode(kvcache.layer_view(layer_cache, layer), q, pos,
                                      window=cfg.sliding_window,
                                      k_new=k, v_new=v)
    else:
        # S=1 steady-state decode is the S-chunk path at S=1: attend the
        # POST-update view so a decode step computes bit-identically to a
        # prefill chunk covering the same token.  (The old pre-update
        # ``sdpa_append`` formulation saved the read-after-write but made
        # decode-written KV diverge from prefill KV in low bf16 bits,
        # blocking generated-tail reuse and accept/reject speculation.)
        ck, cv, kv_pos, kv_valid = kvcache.cache_kv_view(
            new_cache, upto=pos + S, layer=layer, n_kv=cfg.n_kv_heads)
        o = layers.sdpa(q, ck, cv, causal=True, window=cfg.sliding_window,
                        q_positions=positions, kv_positions=kv_pos, kv_valid=kv_valid)
    o = o.reshape(B, S, cfg.n_heads * cfg.the_head_dim())
    return jnp.einsum("bsq,qd->bsd", o, layers.wcast(p["wo"], "row")), new_cache


def dense_layer_decode(p: Dict, cfg: ArchConfig, x: jnp.ndarray,
                       layer_cache: Dict, pos: jnp.ndarray, layer=None
                       ) -> Tuple[jnp.ndarray, Dict]:
    """One-token (or short-S) step against a ring or paged cache.

    ``pos`` scalar (lockstep batch) or (B,) per-slot (continuous batching).
    """
    rs = jnp.asarray(cfg.residual_scale, x.dtype)
    h = layers.apply_norm(cfg.norm, p["attn_norm"], x)
    h, new_cache = attention_decode(p["attn"], cfg, h, layer_cache, pos, layer)
    x = x + h * rs
    h = layers.apply_norm(cfg.norm, p["mlp_norm"], x)
    h = layers.apply_mlp(p["mlp"], cfg, h)
    x = x + h * rs
    return x, new_cache


class DenseLM:
    """Functional model object; params are plain pytrees."""

    family_layer_init = staticmethod(init_dense_layer)

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    # -- init ------------------------------------------------------------------

    def init(self, key) -> Dict:
        cfg = self.cfg
        k_emb, k_layers = jax.random.split(key)
        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        stacked = jax.vmap(lambda k: self._init_layer(k))(layer_keys)
        return {
            "embedding": layers.init_embedding(k_emb, cfg),
            "layers": stacked,
            "final_norm": layers.init_norm(cfg.norm, cfg.d_model),
        }

    def _init_layer(self, key) -> Dict:
        return init_dense_layer(key, self.cfg)

    def _layer_fwd(self, p, x, positions):
        return dense_layer_fwd(p, self.cfg, x, positions)

    def _layer_decode(self, p, x, layer_cache, pos, layer=None):
        return dense_layer_decode(p, self.cfg, x, layer_cache, pos, layer)

    # -- stack runner ------------------------------------------------------------

    def _run_stack(self, stacked: Dict, x: jnp.ndarray, positions: jnp.ndarray,
                   aux_init: Any = None):
        """Scan (or unroll) the layer stack.  Returns (x, aux)."""
        cfg = self.cfg

        def body(carry, p):
            h, aux = carry
            h2, aux2 = self._layer_fwd_aux(p, h, positions, aux)
            return (h2, aux2), None

        fn = remat_wrap(body, cfg.remat)
        if cfg.scan_layers:
            (x, aux), _ = jax.lax.scan(fn, (x, aux_init), stacked)
        else:
            aux = aux_init
            for i in range(cfg.n_layers):
                p = jax.tree_util.tree_map(operator.itemgetter(i), stacked)
                (x, aux), _ = fn((x, aux), p)
        return x, aux

    def _layer_fwd_aux(self, p, x, positions, aux):
        return self._layer_fwd(p, x, positions), aux

    # -- public API ----------------------------------------------------------------

    def apply(self, params: Dict, batch: Dict) -> jnp.ndarray:
        """Training/prefill forward over full sequences -> logits."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = layers.embed_tokens(params["embedding"], cfg, tokens)
        x = constrain(x, "activation")
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        x, _ = self._run_stack(params["layers"], x, positions)
        x = layers.apply_norm(cfg.norm, params["final_norm"], x)
        logits = layers.lm_head(params["embedding"], cfg, x)
        return constrain(logits, "logits")

    def loss_aux(self, params: Dict, batch: Dict):
        """Hook: families may add auxiliary losses (MoE load balance)."""
        return self.apply(params, batch), 0.0

    # -- decode ------------------------------------------------------------------

    def cache_len(self, seq_len: int) -> int:
        w = self.cfg.sliding_window
        return min(seq_len, w) if w else seq_len

    def init_cache(self, B: int, seq_len: int) -> Dict:
        cfg = self.cfg
        return kvcache.init_attn_cache(
            cfg.n_layers, B, self.cache_len(seq_len), cfg.n_kv_heads, cfg.the_head_dim()
        )

    def decode_step(self, params: Dict, cache: Dict, tokens: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, Dict]:
        """tokens: (B, S_new) — one (or a few) new tokens per sequence.

        The stacked cache rides the layer loop as carry, beside the layer
        index: each layer writes its tokens at ``(layer, ...)`` and reads its
        view back through one indexed gather.  No layer's slice of the cache
        is copied out and no stacked copy is built, so a donated cache is
        updated in place."""
        cfg = self.cfg
        x = layers.embed_tokens(params["embedding"], cfg, tokens)
        pos = cache["length"]

        def body(carry, layer_in):
            h, kv = carry
            p, i = layer_in
            return self._layer_decode(p, h, kv, pos, i), None

        kv_keys = (("kp", "vp", "page_table") if "kp" in cache
                   else ("k", "v", "positions"))
        kv = {k: cache[k] for k in kv_keys}
        if cfg.scan_layers:
            (x, kv), _ = jax.lax.scan(
                body, (x, kv),
                (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        else:
            for i in range(cfg.n_layers):
                p = jax.tree_util.tree_map(operator.itemgetter(i), params["layers"])
                (x, kv), _ = body((x, kv), (p, i))
        x = layers.apply_norm(cfg.norm, params["final_norm"], x)
        logits = layers.lm_head(params["embedding"], cfg, x)
        new_cache = dict(kv)
        new_cache["length"] = cache["length"] + tokens.shape[1]
        return constrain(logits, "logits"), new_cache

    def prefill(self, params: Dict, tokens: jnp.ndarray, *,
                seq_len: Optional[int] = None) -> Tuple[jnp.ndarray, Dict]:
        """Full-sequence forward that also fills the cache (kind='prefill').

        ``seq_len`` sizes the ring for the *total* sequence (prompt + decode
        budget) so the scheduler can prefill straight into a slot-shaped
        cache; default is the prompt length (legacy behaviour).
        """
        cache = self.init_cache(tokens.shape[0], seq_len or tokens.shape[1])
        logits, cache = self.decode_step(params, cache, tokens)
        return logits, cache
