"""Named-rule sharding registry: the single place state placement is decided.

FaaSKeeper's core lesson — a coordination service only scales when state
placement is explicit and cheap to reason about — applied to the data plane:
every tensor class (weights, optimizer moments, activations, batches, decode
caches) resolves its placement through a *named rule*, and the model code
never mentions mesh axes.

Three layers:

* **Mesh vocabulary** — :class:`MeshRules` maps a mesh's axis names onto the
  two logical roles: ``dp`` (the data-parallel axes, ``("data",)`` single-pod
  or ``("pod", "data")`` multi-pod, always a tuple so hierarchical DP is one
  PartitionSpec entry) and ``model`` (the tensor-parallel axis).

* **Storage rules** — :func:`param_shardings` / :func:`batch_shardings` /
  :func:`cache_shardings` walk abstract pytrees and assign
  ``NamedSharding``s.  Parameter placement goes through the
  :data:`PARAM_RULES` registry: ordered ``(match, spec)`` pairs keyed on the
  pytree path, with a shape-driven ``auto`` fallback that shards the largest
  divisible dim on ``model`` and the next on ``dp`` (FSDP x TP).  Every rule
  is divisibility-guarded: an axis that does not evenly divide a dim is
  dropped rather than failing, so the same rules resolve on a 1x1 CPU smoke
  mesh, the 16x16 production pod, and the 2x16x16 multi-pod mesh.

* **Activation policy** — :class:`ShardingPolicy` carries a dict of named
  activation PartitionSpecs; :func:`activation_sharding` installs it for the
  current trace and :func:`constrain` (the only hook model code calls) looks
  the rule name up, fits it to the tensor's rank/shape, and applies
  ``jax.lax.with_sharding_constraint``.  With no policy installed
  ``constrain`` is the identity, so eager smoke tests and benchmarks run the
  exact same model code with zero sharding machinery.

Adding a rule for a new architecture: give the weight a distinctive pytree
key and append a ``ParamRule`` before ``auto`` in :data:`PARAM_RULES`
(storage), and/or add a named entry to :meth:`ShardingPolicy.default`'s spec
table plus a ``constrain(x, "<name>")`` call at the use site (compute
layout).  Rules are pure functions of abstract shapes + mesh — unit-test
them with ``AbstractMesh``, no devices needed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from contextvars import ContextVar
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

PyTree = Any


# ---------------------------------------------------------------------------
# Mesh vocabulary
# ---------------------------------------------------------------------------


# The full mesh-axis vocabulary.  Every axis name a PartitionSpec (or a
# mesh constructor) may spell out literally lives here: ``pod`` (inter-pod
# hierarchical DP, multi-pod only), ``data`` (intra-pod DP), ``model``
# (tensor parallel).  The static-analysis sharding pass parses this tuple
# from the AST and flags any literal axis name outside it — register a new
# axis here before using it in a spec.
MESH_AXES: Tuple[str, ...] = ("pod", "data", "model")


@dataclasses.dataclass(frozen=True)
class MeshRules:
    """Logical axis roles for a (possibly abstract) mesh."""

    axis_names: Tuple[str, ...]
    dp: Tuple[str, ...]      # data-parallel axes (hierarchical on multi-pod)
    model: str               # tensor-parallel axis

    @classmethod
    def for_mesh(cls, mesh) -> "MeshRules":
        names = tuple(mesh.axis_names)
        if "model" in names:
            model = "model"
        else:
            model = names[-1]
        dp = tuple(a for a in names if a != model)
        return cls(axis_names=names, dp=dp, model=model)

    def dp_size(self, mesh) -> int:
        return int(math.prod(mesh.shape[a] for a in self.dp)) if self.dp else 1

    def model_size(self, mesh) -> int:
        return int(mesh.shape[self.model])


def _axes_size(entry, mesh) -> int:
    axes = entry if isinstance(entry, tuple) else (entry,)
    return int(math.prod(mesh.shape[a] for a in axes))


def _fit_spec(spec: P, shape: Tuple[int, ...], mesh) -> P:
    """Divisibility guard: drop any spec entry whose axes do not evenly
    divide the corresponding dim (rules stay total over shapes/meshes)."""
    entries = [*spec] + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries, strict=True):
        if entry is None:
            out.append(None)
        else:
            out.append(entry if dim % _axes_size(entry, mesh) == 0 else None)
    return P(*out)


# ---------------------------------------------------------------------------
# Parameter storage rules (the registry)
# ---------------------------------------------------------------------------

# Top-level pytree keys whose children carry a leading scan-over-layers dim
# that storage rules must skip.
STACKED_PREFIXES = ("layers", "blocks", "enc_layers")


@dataclasses.dataclass(frozen=True)
class ParamRule:
    """One named storage rule: ``match`` on the pytree path decides
    applicability, ``spec`` produces the (unfitted) PartitionSpec."""

    name: str
    match: Callable[[Tuple[str, ...], Tuple[int, ...]], bool]
    spec: Callable[[Tuple[str, ...], Tuple[int, ...], MeshRules, Any], P]


def _nspec(ndim: int, at: Dict[int, Any]) -> P:
    """PartitionSpec with entries at the given (possibly negative) dims."""
    entries = [None] * ndim
    for pos, axes in at.items():
        entries[pos] = axes
    return P(*entries)


def _rule_head(keys, shape, rules, mesh) -> P:
    # (d_model, padded_vocab): vocab on model, contraction dim UNSHARDED —
    # sharding d would all-reduce the full logits tensor (the 40 GB/device
    # whisper incident pinned by tests/test_sharding.py).
    return _nspec(len(shape), {-1: rules.model})


def _rule_embed(keys, shape, rules, mesh) -> P:
    # (padded_vocab, d_model): rows on dp (ZeRO-style), d on model —
    # gather-friendly for embed lookups; lm_head re-shards the tied table
    # via the "head_weight" activation rule.
    return _nspec(len(shape), {-2: tuple(rules.dp) or None, -1: rules.model})


def _rule_expert_in(keys, shape, rules, mesh) -> P:
    # (..., E, D, F): experts on model (EP), D on dp (FSDP) — exactly the
    # storage layout the stationary-decode shard_map consumes.
    return _nspec(len(shape), {-3: rules.model, -2: tuple(rules.dp) or None})


def _rule_expert_out(keys, shape, rules, mesh) -> P:
    # (..., E, F, D): experts on model, output D on dp.
    return _nspec(len(shape), {-3: rules.model, -1: tuple(rules.dp) or None})


def _auto_spec(keys, shape, rules, mesh) -> P:
    """Fallback: greedy largest-divisible assignment (model first, then dp).

    Skips the leading scan dim for stacked trees.  Breaks size ties toward
    the trailing dim for ``model``, which lands matmul weights in the
    (dp, model) FSDP x TP layout.
    """
    sp = 1 if keys and keys[0] in STACKED_PREFIXES and len(shape) > 1 else 0
    entries: list = [None] * len(shape)
    candidates = sorted(range(sp, len(shape)),
                        key=lambda i: (shape[i], i), reverse=True)
    picked_model = None
    model_size = rules.model_size(mesh)
    for i in candidates:
        if shape[i] > 1 and shape[i] % model_size == 0:
            entries[i] = rules.model
            picked_model = i
            break
    if rules.dp:
        dp_size = rules.dp_size(mesh)
        for i in candidates:
            if i != picked_model and shape[i] > 1 and shape[i] % dp_size == 0:
                entries[i] = tuple(rules.dp)
                break
    return P(*entries)


PARAM_RULES: Tuple[ParamRule, ...] = (
    ParamRule("head",
              lambda keys, shape: keys[-1:] == ("head",) and len(shape) >= 2,
              _rule_head),
    ParamRule("embed",
              lambda keys, shape: keys[-1:] == ("embed",) and len(shape) >= 2,
              _rule_embed),
    ParamRule("expert_ffn_in",
              lambda keys, shape: "experts" in keys and len(shape) >= 3
              and keys[-1] in ("w_gate", "w_up"),
              _rule_expert_in),
    ParamRule("expert_ffn_out",
              lambda keys, shape: "experts" in keys and len(shape) >= 3
              and keys[-1] == "w_down",
              _rule_expert_out),
    ParamRule("auto", lambda keys, shape: len(shape) >= 2, _auto_spec),
)


def _path_keys(path) -> Tuple[str, ...]:
    return tuple(str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))
                 for k in path)


def resolve_param_rule(keys: Tuple[str, ...], shape: Tuple[int, ...]
                       ) -> Optional[ParamRule]:
    """First registry rule matching this (path, shape); None -> replicate."""
    for rule in PARAM_RULES:
        if rule.match(keys, shape):
            return rule
    return None


def _resolve_param_spec(keys, shape, rules: MeshRules, mesh) -> P:
    rule = resolve_param_rule(keys, shape)
    if rule is None:
        return P()
    return _fit_spec(rule.spec(keys, shape, rules, mesh), shape, mesh)


def param_shardings(p_abs: PyTree, mesh) -> PyTree:
    """NamedShardings for a parameter pytree (abstract or concrete leaves).

    Guarantees every >=2-dim weight leaf is sharded on at least one axis
    whenever any of its dims divides an axis — the 110B/235B configs cannot
    afford replicated matrices in 16 GB HBM.
    """
    rules = MeshRules.for_mesh(mesh)

    def assign(path, leaf):
        spec = _resolve_param_spec(_path_keys(path), tuple(leaf.shape), rules, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(assign, p_abs)


# ---------------------------------------------------------------------------
# Batch / cache storage rules
# ---------------------------------------------------------------------------


def batch_shardings(batch_abs: PyTree, mesh) -> PyTree:
    """Leading (global-batch) dim on the full dp tuple; replicated when the
    batch does not divide (e.g. the B=1 long-context cell)."""
    rules = MeshRules.for_mesh(mesh)
    dp_size = rules.dp_size(mesh)

    def assign(leaf):
        shape = tuple(leaf.shape)
        if rules.dp and shape and shape[0] % dp_size == 0:
            return NamedSharding(mesh, P(tuple(rules.dp), *([None] * (len(shape) - 1))))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(assign, batch_abs)


# decode-cache kv-ring leaf keys; dims are indexed from the right so stacked
# (leading layer dim) and unstacked leaves share one rule
_CACHE_KV_KEYS = frozenset({"k", "v", "xk", "xv"})
# paged-pool leaf keys: (..., n_pages, page_size, H*D) shared across slots
_CACHE_POOL_KEYS = frozenset({"kp", "vp"})


def cache_shardings(cache_abs: PyTree, mesh) -> PyTree:
    """Decode-state placement.

    kv rings (..., B, T, H, D): batch on dp; heads on model when the head
    count divides, else fall back to the time dim (GQA archs with few kv
    heads — the divisibility guard the sharding tests pin).  Paged pools
    (..., n_pages, page_size, H*D) have no slot axis — every slot's page
    table indexes one shared pool, so the pool stays *replicated over dp*
    and shards its within-page lane dim on model (heads x head_dim, then
    pages, as fallbacks — the paged-attention kernel slices one page per grid step
    by table index, which head- or page-sharded pools can only serve by
    all-gathering the pool);
    page tables (..., n_slots, max_pages) follow the slot batch onto dp.
    Refcounted prefix sharing / session parking never changes pool
    placement: a shared page is just extra table rows pointing at it, and a
    copy-on-write split lands on another page of the same pool — the lane
    shard stays on ``model`` throughout (pinned by the prefix-sharing spec
    test).
    SSM states shard their head dim, conv tails and RG-LRU states their
    channel dim.
    """
    rules = MeshRules.for_mesh(mesh)
    dp = tuple(rules.dp) or None

    def assign(path, leaf):
        keys = _path_keys(path)
        key = keys[-1] if keys else ""
        shape = tuple(leaf.shape)
        nd = len(shape)
        entries: list = [None] * nd

        def put(dim: int, axes) -> bool:
            i = nd + dim if dim < 0 else dim
            if 0 <= i < nd and axes is not None and shape[i] % _axes_size(axes, mesh) == 0:
                entries[i] = axes
                return True
            return False

        if key in _CACHE_POOL_KEYS and nd >= 3:  # (..., Np, ps, H*D) shared pool
            # within-page lane dim first, then heads, then pages.  The paged
            # decode kernel streams the pool one page block per grid step,
            # so a pool sharded across heads or pages turns every
            # block slice into a cross-shard read XLA answers by
            # all-gathering the whole pool each step (measured on the 16x16
            # decode_32k cells: 73 GB/device wire page-sharded, 65 GB
            # head-sharded, 93 MB lane-sharded).  Lane shards keep block
            # slicing local and partition the softmax like the ring cells'
            # seq-sharded attention; the gather path is layout-indifferent.
            put(-2, rules.model) or put(-1, rules.model) or put(-3, rules.model)
        elif key == "page_table" and nd >= 2:    # (..., n_slots, max_pages)
            put(-2, dp)
        elif key in _CACHE_KV_KEYS and nd >= 4:  # (..., B, T, H, D)
            put(-4, dp)
            put(-2, rules.model) or put(-3, rules.model)
        elif key == "ssm" and nd >= 4:           # (..., B, H, P, N)
            put(-4, dp)
            put(-3, rules.model)
        elif key == "conv" and nd >= 3:          # (..., B, K-1, C)
            put(-3, dp)
            put(-1, rules.model)
        elif key == "h" and nd >= 2:             # (..., B, W) rg-lru state
            put(-2, dp)
            put(-1, rules.model)
        elif key == "positions" and nd >= 2:     # (..., B, T)
            put(-2, dp)
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map_with_path(assign, cache_abs)


def offload_stage_shardings(stage_abs: PyTree, mesh) -> PyTree:
    """Placement for KV offload staging buffers.

    A staging buffer is a gathered page chunk ``(..., n_chunk_pages,
    page_size, H*D)`` in flight between the shared pool and host memory
    (``kvcache.gather_pages`` / ``scatter_pages``).  Unlike the resident
    pool, the chunk is about to cross the device boundary, so the only
    useful partitioning is the one that matches the pool's own sharding —
    each shard DMAs its own pool slice and no reshuffle happens before the
    transfer.  That means the *same* fallback order as
    :func:`cache_shardings`' pool rule: within-page lane dim on ``model``
    first, then heads x head_dim; everything else (including the gathered-page dim —
    chunks are a handful of pages, far too small to amortize a collective)
    stays replicated.
    """
    rules = MeshRules.for_mesh(mesh)

    def assign(path, leaf):
        keys = _path_keys(path)
        shape = tuple(leaf.shape)
        nd = len(shape)
        entries: list = [None] * nd
        if keys and keys[-1] in _CACHE_POOL_KEYS and nd >= 3:
            msize = _axes_size(rules.model, mesh)
            for dim in (nd - 2, nd - 1):    # (..., n, ps, H*D): lane, heads
                if shape[dim] % msize == 0:
                    entries[dim] = rules.model
                    break
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map_with_path(assign, stage_abs)


# ---------------------------------------------------------------------------
# Activation policy
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Named activation-layout rules for one mesh, installed for a trace via
    :func:`activation_sharding` and consumed by :func:`constrain`."""

    mesh: Any
    specs: Dict[str, P]
    rules: MeshRules
    batch_shardable: bool = True
    attn_mode: str = "head"              # "head" | "seq"
    decode_stationary: bool = False      # stationary-weights MoE decode
    shard_map_pool: bool = False         # shard_map the fused paged gather

    @classmethod
    def default(cls, mesh, *, batch_shardable: bool = True,
                attn_mode: str = "head", decode_stationary: bool = False,
                shard_map_pool: bool = False,
                overrides: Optional[Dict[str, P]] = None) -> "ShardingPolicy":
        """The standard rule table.

        ``attn_mode="head"`` shards attention heads on ``model`` (needs the
        head counts to divide); ``"seq"`` falls back to sequence sharding for
        q with replicated kv (GQA/MQA archs whose kv heads don't divide).
        """
        rules = MeshRules.for_mesh(mesh)
        dp = tuple(rules.dp) if (batch_shardable and rules.dp) else None
        mdl = rules.model
        specs: Dict[str, P] = {
            # residual stream: Megatron-SP — sequence on model between blocks
            "activation": P(dp, mdl, None),
            # block entry: gather S, keep D whole for the TP projections
            "block_in": P(dp, None, None),
            "mlp_hidden": P(dp, None, mdl),
            "logits": P(dp, None, mdl),
            # matmul-layout (bf16, post-cast) weights: the ZeRO-3 dp-gather
            # moves the compute dtype, not the fp32 master
            "w_col": P(None, mdl),
            "w_row": P(mdl, None),
            # tied lm head: re-shard d-sharded table to vocab-sharded
            "head_weight": P(None, mdl),
            "ssm_heads": P(dp, None, mdl, None),
            "ssm_dt": P(dp, None, mdl),
            "lru_channels": P(dp, None, mdl),
        }
        if attn_mode == "head":
            specs["q_heads"] = P(dp, None, mdl, None)
            specs["kv_heads"] = P(dp, None, mdl, None)
            specs["attn_out"] = P(dp, None, mdl, None)
        else:
            specs["q_heads"] = P(dp, mdl, None, None)
            specs["kv_heads"] = P(dp, None, None, None)
            specs["attn_out"] = P(dp, mdl, None, None)
        if overrides:
            specs.update(overrides)
        return cls(mesh=mesh, specs=specs, rules=rules,
                   batch_shardable=batch_shardable, attn_mode=attn_mode,
                   decode_stationary=decode_stationary,
                   shard_map_pool=shard_map_pool)


_ACTIVE_POLICY: ContextVar[Optional[ShardingPolicy]] = ContextVar(
    "repro_dist_sharding_policy", default=None)


def current_policy() -> Optional[ShardingPolicy]:
    return _ACTIVE_POLICY.get()


@contextlib.contextmanager
def activation_sharding(policy: Optional[ShardingPolicy]):
    """Install ``policy`` for the enclosed trace (None -> force no policy)."""
    token = _ACTIVE_POLICY.set(policy)
    try:
        yield policy
    finally:
        _ACTIVE_POLICY.reset(token)


def constrain(x, rule_name: str):
    """Apply the active policy's named layout rule to ``x``.

    Identity when no policy is installed, when the policy has no such rule,
    or when no entry of the fitted spec survives the divisibility guard —
    model code can call this unconditionally.
    """
    policy = current_policy()
    if policy is None:
        return x
    spec = policy.specs.get(rule_name)
    if spec is None or len(spec) > x.ndim:
        return x
    fitted = _fit_spec(spec, tuple(x.shape), policy.mesh)
    if all(e is None for e in fitted):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(policy.mesh, fitted))


def constrain_tree(tree: PyTree, specs: Optional[PyTree], mesh=None):
    """Constrain every array leaf of ``tree`` to the matching leaf of a
    PartitionSpec pytree (e.g. the scheduler's ``cache_specs``).

    Identity when ``specs`` is None or no mesh is resolvable; leaves whose
    spec is the empty/replicated ``P()`` pass through untouched so the
    compiler keeps its freedom where the registry expressed no opinion.
    """
    if specs is None:
        return tree
    if mesh is None:
        policy = current_policy()
        mesh = policy.mesh if policy is not None else None
    if mesh is None:
        return tree

    def one(leaf, spec):
        if spec is None or all(e is None for e in spec):
            return leaf
        return jax.lax.with_sharding_constraint(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(one, tree, specs)
