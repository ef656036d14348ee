"""Token-choice MoE transformer (moonshot-v1-16b-a3b, qwen3-moe-235b-a22b).

Dispatch strategy (TPU adaptation, DESIGN.md §5): activations are replicated
across the ``model`` mesh axis, so each model-rank owns ``E / |model|``
experts and *locally* gathers the tokens routed to them — no all-to-all is
needed; the combine is a single ``psum`` over ``model``, the same collective
a dense TP MLP pays.  Capacity-based dropping (factor ``capacity_factor``)
keeps every shape static.  FLOPs are the *active*-expert FLOPs (each rank
computes E_local experts x capacity tokens), so the roofline's
MODEL_FLOPS/HLO_FLOPs ratio stays honest — no dense-all-experts fakery.

On a single device (smoke tests) the identical dispatch math runs without
shard_map.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..dist import sharding as shd
from . import layers
from .config import ArchConfig
from .layers import cast
from .transformer import DenseLM, attention_decode


# ---------------------------------------------------------------------------
# Expert dispatch core (runs per data-shard; E_local experts per model-rank)
# ---------------------------------------------------------------------------


def _rank_within_expert(e_flat: jnp.ndarray, n_experts: int) -> jnp.ndarray:
    """Position of each routing pair within its expert's arrival order.

    Sort-based: O(TK log TK) time, O(TK) memory — the one-hot-cumsum
    formulation costs O(TK * E) memory ((TK, E) int32 tensors measured as a
    dominant §Perf memory term for the 128-expert arch)."""
    TK = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)                  # (TK,)
    e_sorted = e_flat[order]
    # index of the first occurrence of each pair's expert in sorted order
    first = jnp.searchsorted(e_sorted, jnp.arange(n_experts, dtype=e_flat.dtype),
                             side="left")                     # (E,)
    rank_sorted = jnp.arange(TK, dtype=jnp.int32) - first[e_sorted]
    return jnp.zeros((TK,), jnp.int32).at[order].set(rank_sorted)


def _dispatch_ffn(xf: jnp.ndarray, w_flat: jnp.ndarray, e_flat: jnp.ndarray,
                  experts: Dict, mlp: str, e_lo, E_local: int,
                  n_experts: int, capacity: int) -> jnp.ndarray:
    """xf: (T, D) tokens; (w|e)_flat: (T*k,) routing pairs; experts: stacked
    weights for the E_local experts starting at ``e_lo`` (``e_lo`` may be a
    traced axis_index value; ``E_local`` must be static).  Returns this
    rank's partial (T, D)."""
    T, D = xf.shape
    TK = e_flat.shape[0]
    k = TK // T
    e_hi = e_lo + E_local

    # rank of each pair within its expert (capacity-based dropping)
    rank = _rank_within_expert(e_flat, n_experts)                     # (TK,)
    local = (e_flat >= e_lo) & (e_flat < e_hi) & (rank < capacity)
    slot = jnp.where(local, (e_flat - e_lo) * capacity + rank, E_local * capacity)

    # single gather->scatter dispatch.  (A per-slot k-loop variant was tried
    # in §Perf cell-2 iteration 3 and REFUTED: each of the k scatters
    # rewrites the whole (E_local*cap, D) buffer, +10% bytes accessed.)
    tok_idx = jnp.arange(TK, dtype=jnp.int32) // k
    buf = jnp.zeros((E_local * capacity + 1, D), xf.dtype)
    buf = buf.at[slot].add(xf[tok_idx])
    buf = buf[: E_local * capacity].reshape(E_local, capacity, D)

    if mlp == "swiglu":
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, cast(experts["w_gate"])))
        u = jnp.einsum("ecd,edf->ecf", buf, cast(experts["w_up"]))
        y = jnp.einsum("ecf,efd->ecd", g * u, cast(experts["w_down"]))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, cast(experts["w_up"])))
        y = jnp.einsum("ecf,efd->ecd", h, cast(experts["w_down"]))

    # combine: gather + weighted sum over the k slots.  (Per-slot combine
    # loop also REFUTED in §Perf cell-2: +9% bytes accessed.)  Weights cast
    # to compute dtype — an f32 multiply here would promote the whole (TK, D)
    # buffer to f32.
    y_flat = y.reshape(E_local * capacity, D)
    picked = y_flat[jnp.minimum(slot, E_local * capacity - 1)]        # (TK, D)
    picked = picked * (local & (slot < E_local * capacity))[:, None]
    picked = picked * w_flat[:, None].astype(xf.dtype)
    return picked.reshape(T, k, D).sum(axis=1)


# Below this many tokens, the shard_map EP path switches to the stationary-
# weights formulation: gathering every expert's weights to process a handful
# of decode tokens dominated the decode collective term (§Perf cell 3).
DECODE_TOKEN_THRESHOLD = 2048


def _moe_decode_stationary(xf, w_flat, e_flat, p, cfg, mesh, rules, cap):
    """Decode-time MoE: weights stay in their (EP x FSDP) storage sharding;
    the (tiny) token set is replicated across dp and only (E_loc, C, *)
    partials cross the wire.  Expert weight bytes moved: zero."""
    m_cfg = cfg.moe
    e_per = m_cfg.n_experts // mesh.shape[rules.model]

    def body(xf_l, w_l, e_l, wg, wu, wd):
        e_lo = jax.lax.axis_index(rules.model) * e_per
        T, D_full = xf_l.shape
        TK = e_l.shape[0]
        k = TK // T
        rank = _rank_within_expert(e_l, m_cfg.n_experts)
        local = (e_l >= e_lo) & (e_l < e_lo + e_per) & (rank < cap)
        slot = jnp.where(local, (e_l - e_lo) * cap + rank, e_per * cap)
        tok_idx = jnp.arange(TK, dtype=jnp.int32) // k
        buf = jnp.zeros((e_per * cap + 1, D_full), xf_l.dtype)
        buf = buf.at[slot].add(xf_l[tok_idx])[: e_per * cap]
        buf = buf.reshape(e_per, cap, D_full)
        # wg/wu blocks: (e_per, D/|dp|, F) -> contract the local D slice,
        # psum the (e_per, cap, F) partial over dp (tiny at decode sizes)
        d_idx = jnp.zeros((), jnp.int32)
        for a in rules.dp:
            d_idx = d_idx * mesh.shape[a] + jax.lax.axis_index(a)
        d_lo = d_idx * wg.shape[1]
        buf_d = jax.lax.dynamic_slice_in_dim(buf, d_lo, wg.shape[1], axis=2)
        g = jax.lax.psum(jnp.einsum("ecd,edf->ecf", buf_d, wg.astype(xf_l.dtype)),
                         rules.dp)
        u = jax.lax.psum(jnp.einsum("ecd,edf->ecf", buf_d, wu.astype(xf_l.dtype)),
                         rules.dp)
        h = jax.nn.silu(g) * u if cfg.mlp == "swiglu" else jax.nn.gelu(g)
        # wd block: (e_per, F, D/|dp|) -> local D slice, all-gather D (tiny)
        y_part = jnp.einsum("ecf,efd->ecd", h, wd.astype(xf_l.dtype))
        y = jax.lax.all_gather(y_part, rules.dp, axis=2, tiled=True)
        y_flat = y.reshape(e_per * cap, D_full)
        picked = y_flat[jnp.minimum(slot, e_per * cap - 1)]
        picked = picked * (local & (slot < e_per * cap))[:, None]
        picked = picked * w_l[:, None].astype(xf_l.dtype)
        return jax.lax.psum(picked.reshape(T, k, D_full).sum(1), rules.model)

    P_ = P
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P_(), P_(), P_(),
                  P_(rules.model, rules.dp, None),   # w_gate storage sharding
                  P_(rules.model, rules.dp, None),   # w_up
                  P_(rules.model, None, rules.dp)),  # w_down
        out_specs=P_(),
        check_vma=False,
    )(xf, w_flat, e_flat, p["experts"]["w_gate"], p["experts"]["w_up"],
      p["experts"]["w_down"])


def moe_ffn(p: Dict, cfg: ArchConfig, x: jnp.ndarray, *, no_drop: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output (B,S,D), aux load-balance loss scalar).

    ``no_drop=True`` (the decode/serving path) sizes capacity to the T*k
    worst case so routing never drops a token: capacity dropping is a
    training-throughput trade, and at serve time it would make outputs
    depend on what else shares the batch — chunked prefill must produce the
    same tokens as a monolithic prefill regardless of chunk boundaries.
    """
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"]["w"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)                           # (T, E)
    weights, idx = jax.lax.top_k(gates, m.top_k)                      # (T, k)
    weights = weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9)

    # switch-style load-balance aux: E * sum_e f_e * P_e
    pe = gates.mean(axis=0)
    fe = jax.nn.one_hot(idx, m.n_experts).sum(axis=(0, 1)) / (T * m.top_k)
    aux = m.n_experts * jnp.sum(fe * pe)

    e_flat = idx.reshape(-1).astype(jnp.int32)
    w_flat = weights.reshape(-1)

    policy = shd.current_policy()
    if policy is None:
        out = _dispatch_ffn(
            xf, w_flat, e_flat, p["experts"], cfg.mlp, 0, m.n_experts,
            m.n_experts, _capacity(T, m, no_drop=no_drop),
        )
    else:
        mesh = policy.mesh
        rules = shd.MeshRules.for_mesh(mesh)
        dp_size = int(math.prod(mesh.shape[a] for a in rules.dp))
        model_size = mesh.shape[rules.model]
        if (T <= DECODE_TOKEN_THRESHOLD and cfg.mlp == "swiglu"
                and m.n_experts % model_size == 0 and D % dp_size == 0):
            # decode: weights stay put; only tiny partials cross the wire
            out = _moe_decode_stationary(xf, w_flat, e_flat, p, cfg, mesh,
                                         rules, _capacity(T, m, no_drop=no_drop))
        elif T % dp_size != 0 or m.n_experts % model_size != 0:
            out = _dispatch_ffn(xf, w_flat, e_flat, p["experts"], cfg.mlp,
                                0, m.n_experts, m.n_experts,
                                _capacity(T, m, no_drop=no_drop))
        else:
            cap = _capacity(T // dp_size, m, no_drop=no_drop)
            e_per = m.n_experts // model_size  # static experts-per-rank

            def body(xf_l, w_l, e_l, experts_l):
                e_lo = jax.lax.axis_index(rules.model) * e_per  # traced offset
                partial = _dispatch_ffn(xf_l, w_l, e_l, experts_l, cfg.mlp,
                                        e_lo, e_per, m.n_experts, cap)
                return jax.lax.psum(partial, rules.model)

            # tokens split over dp; experts split over model; inside the body
            # each (dp, model) cell sees its token block and its expert block.
            out = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(rules.dp, None), P(rules.dp), P(rules.dp),
                          P(rules.model, None, None)),
                out_specs=P(rules.dp, None),
                check_vma=False,
            )(xf, w_flat, e_flat, p["experts"])

    if "shared" in p:
        out = out + layers.apply_mlp(p["shared"], cfg, xf[None])[0]
    return out.reshape(B, S, D), aux.astype(jnp.float32)


def _capacity(tokens: int, m, *, no_drop: bool = False) -> int:
    if no_drop:      # serving: cover the all-to-one-expert worst case
        return tokens * m.top_k
    return max(4, int(math.ceil(tokens * m.top_k / m.n_experts * m.capacity_factor)))


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def init_moe_layer(key, cfg: ArchConfig) -> Dict:
    m = cfg.moe
    ks = jax.random.split(key, 6)
    ek = jax.random.split(ks[1], m.n_experts)

    def one_expert(k):
        kk = jax.random.split(k, 3)
        e = {
            "w_gate": layers.dense_init(kk[0], cfg.d_model, m.d_expert),
            "w_up": layers.dense_init(kk[1], cfg.d_model, m.d_expert),
            "w_down": layers.dense_init(kk[2], m.d_expert, cfg.d_model),
        }
        if cfg.mlp != "swiglu":
            del e["w_gate"]
        return e

    p = {
        "attn_norm": layers.init_norm(cfg.norm, cfg.d_model),
        "attn": layers.init_attention(ks[0], cfg),
        "mlp_norm": layers.init_norm(cfg.norm, cfg.d_model),
        "moe": {
            "router": {"w": layers.dense_init(ks[2], cfg.d_model, m.n_experts)},
            "experts": jax.vmap(one_expert)(ek),
        },
    }
    if cfg.d_ff > 0 and cfg.name.startswith("moonshot"):
        # moonlight/deepseek-style shared expert alongside routed experts
        p["moe"]["shared"] = layers.init_mlp(ks[3], cfg, d_ff=2 * m.d_expert)
    return p


class MoELM(DenseLM):
    def __init__(self, cfg: ArchConfig):
        super().__init__(cfg)
        self._aux_weight = cfg.moe.router_aux_weight

    def _init_layer(self, key):
        return init_moe_layer(key, self.cfg)

    def _layer_fwd_aux(self, p, x, positions, aux):
        cfg = self.cfg
        rs = jnp.asarray(cfg.residual_scale, x.dtype)
        h = layers.apply_norm(cfg.norm, p["attn_norm"], x)
        h = layers.attention_block(p["attn"], cfg, h, positions,
                                   window=cfg.sliding_window)
        x = x + h * rs
        x = shd.constrain(x, "activation")
        h = layers.apply_norm(cfg.norm, p["mlp_norm"], x)
        h, layer_aux = moe_ffn(p["moe"], cfg, h)
        x = x + h * rs
        return shd.constrain(x, "activation"), (aux + layer_aux if aux is not None else layer_aux)

    def _layer_fwd(self, p, x, positions):
        y, _ = self._layer_fwd_aux(p, x, positions, jnp.zeros((), jnp.float32))
        return y

    def apply(self, params, batch):
        logits, _ = self.loss_aux(params, batch)
        return logits

    def loss_aux(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = layers.embed_tokens(params["embedding"], cfg, tokens)
        x = shd.constrain(x, "activation")
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        x, aux = self._run_stack(params["layers"], x, positions,
                                 aux_init=jnp.zeros((), jnp.float32))
        x = layers.apply_norm(cfg.norm, params["final_norm"], x)
        logits = layers.lm_head(params["embedding"], cfg, x)
        return shd.constrain(logits, "logits"), aux * self._aux_weight

    def _layer_decode(self, p, x, layer_cache, pos, layer=None):
        cfg = self.cfg
        rs = jnp.asarray(cfg.residual_scale, x.dtype)
        h = layers.apply_norm(cfg.norm, p["attn_norm"], x)
        h, new_cache = attention_decode(p["attn"], cfg, h, layer_cache, pos, layer)
        x = x + h * rs
        h = layers.apply_norm(cfg.norm, p["mlp_norm"], x)
        h, _ = moe_ffn(p["moe"], cfg, h, no_drop=True)
        x = x + h * rs
        return x, new_cache
