"""Random weights from a seed, in the layout the program serves them in.

One jitted call makes every weight on the device, in bfloat16: the layers as
a ``lax.map`` over the layer index, so each layer's float32 draw is a
temporary of one layer.  The reference makes them again with the same call
after the program's state is freed, so it takes nothing the program made.

Each leaf draws from its own key, ``fold_in(k, crc32(path))``, where ``k``
is the seed's key, or for a layer's leaf ``fold_in(seed key, 1 + layer)``.
Values:

- matrices ``(d_in, d_out)``: normal, std ``1/sqrt(d_in)``;
- the embedding: normal, std ``0.02 / scale_emb``, so that every
  configuration's residual stream starts at std 0.02.  (At std 0.02 times
  MiniCPM's ``scale_emb`` of 12 the current token's own row dominated the
  tied head, every served token copied its predecessor with a wide margin,
  and no rounding could show in the served tokens.)
- norm scales: ``1 + 0.1 * normal``; norm and projection biases:
  ``0.02 * normal``, so that every term of the block is exercised;
- a tied configuration whose program holds a separate head gets the
  transposed embedding there, so the served model is the published one.
"""

from __future__ import annotations

import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], str]


def _norm_leaves(prefix: Tuple[str, ...], d: int, dm: Dict) -> List[Leaf]:
    out = [((*prefix, "scale"), (d,), "scale")]
    if dm["norm"] == "layernorm":
        out.append(((*prefix, "bias"), (d,), "bias"))
    return out


def layer_leaves(dm: Dict) -> List[Leaf]:
    """One layer's leaves (unstacked shapes), in the program's layout."""
    d, hd = dm["d"], dm["hd"]
    q, kv = dm["H"] * hd, dm["Hkv"] * hd
    out = _norm_leaves(("attn_norm",), d, dm)
    out += [(("attn", "wq"), (d, q), "matrix"), (("attn", "wk"), (d, kv), "matrix"),
            (("attn", "wv"), (d, kv), "matrix"), (("attn", "wo"), (q, d), "matrix")]
    if dm["bias"]:
        out += [(("attn", "bq"), (q,), "bias"), (("attn", "bk"), (kv,), "bias"),
                (("attn", "bv"), (kv,), "bias")]
    out += _norm_leaves(("mlp_norm",), d, dm)
    f = dm["ff"]
    if dm["gated"]:
        out += [(("mlp", "w_gate"), (d, f), "matrix"), (("mlp", "w_up"), (d, f), "matrix"),
                (("mlp", "w_down"), (f, d), "matrix")]
    else:
        out += [(("mlp", "w_up"), (d, f), "matrix"), (("mlp", "b_up"), (f,), "bias"),
                (("mlp", "w_down"), (f, d), "matrix"), (("mlp", "b_down"), (d,), "bias")]
    return out


def _key(seed_lo, seed_hi):
    return jax.random.fold_in(jax.random.key(seed_lo), seed_hi)


def _draw(key, path: Tuple[str, ...], shape, kind: str, emb_scale: float = 1.0
          ) -> jnp.ndarray:
    k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "matrix":
        return z / jnp.sqrt(jnp.float32(shape[0]))
    if kind == "embed":
        return (0.02 / emb_scale) * z
    if kind == "scale":
        return 1.0 + 0.1 * z
    return 0.02 * z                                      # bias


def _set(tree: Dict, path: Tuple[str, ...], value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def make(dm: Dict, *, program_head: bool):
    """``make(seed_lo, seed_hi) -> params``, jitted: every weight, bf16.

    ``program_head``: the program holds a separate ``(d, vocab_pad)`` head
    leaf (untied layout); for a tied configuration it is filled with the
    transposed embedding."""
    leaves = layer_leaves(dm)

    def one_layer(key, layer):
        lk = jax.random.fold_in(key, 1 + layer)
        out: Dict = {}
        for path, shape, kind in leaves:
            _set(out, path, _draw(lk, ("layers", *path), shape, kind)
                 .astype(jnp.bfloat16))
        return out

    def build(seed_lo, seed_hi):
        key = _key(seed_lo, seed_hi)
        layers = jax.lax.map(lambda i: one_layer(key, i),
                             jnp.arange(dm["L"], dtype=jnp.int32))
        embed = _draw(key, ("embedding", "embed"), (dm["vocab_pad"], dm["d"]), "embed",
                      dm["emb_scale"])
        params: Dict = {"embedding": {"embed": embed.astype(jnp.bfloat16)},
                        "layers": layers, "final_norm": {}}
        if program_head:
            head = (embed.T if dm["tied"]
                    else _draw(key, ("embedding", "head"),
                               (dm["d"], dm["vocab_pad"]), "matrix"))
            params["embedding"]["head"] = head.astype(jnp.bfloat16)
        for path, shape, kind in _norm_leaves(("final_norm",), dm["d"], dm):
            _set(params, path, _draw(key, path, shape, kind).astype(jnp.bfloat16))
        return params

    return jax.jit(build)


def split_seed(seed: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A seed of any size as two uint32 arguments, so one compiled program
    serves every seed."""
    return (jnp.asarray(seed & 0xFFFFFFFF, jnp.uint32),
            jnp.asarray((seed >> 32) & 0xFFFFFFFF, jnp.uint32))

