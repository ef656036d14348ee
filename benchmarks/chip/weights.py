"""Random weights from a seed, in the layout the program serves them in.

One jitted call makes every weight on the device, in bfloat16: the layers as
a ``lax.map`` over the layer index, so each layer's float32 draw is a
temporary of one layer.  A layer's leaves are its block's
(``blocks/<name>.py``).  On a mesh the call makes each leaf in the
program's placement, so no chip holds more than its share.  The reference
makes them again with the same call, in a placement of its own
(:func:`spread`), after the program's state is freed, so it takes nothing
the program made.

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

from . import blocks

# (path, shape, kind), and the dtype where the program serves the leaf in
# another than bfloat16
Leaf = Tuple


def norm_leaves(prefix: Tuple[str, ...], d: int, dm: Dict) -> List[Leaf]:
    out = [((*prefix, "scale"), (d,), "scale")]
    if dm["norm"] == "layernorm":
        out.append(((*prefix, "bias"), (d,), "bias"))
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


def make(dm: Dict, *, program_head: bool, shardings=None):
    """``make(seed_lo, seed_hi) -> params``, jitted: every weight, bf16 (or
    the dtype its leaf names).

    ``program_head``: the program holds a separate ``(d, vocab_pad)`` head
    leaf (untied layout); for a tied configuration it is filled with the
    transposed embedding.  ``shardings``: the params' placement over several
    chips, as the jitted call's ``out_shardings``; each chip then draws its
    share of a leaf, and the values are those of one chip."""
    leaves = blocks.load(dm["block"]).layer_leaves(dm)

    def one_layer(key, layer):
        lk = jax.random.fold_in(key, 1 + layer)
        out: Dict = {}
        for path, shape, kind, *dtype in leaves:
            _set(out, path, _draw(lk, ("layers", *path), shape, kind)
                 .astype(dtype[0] if dtype else jnp.bfloat16))
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
        for path, shape, kind in norm_leaves(("final_norm",), dm["d"], dm):
            _set(params, path, _draw(key, path, shape, kind).astype(jnp.bfloat16))
        return params

    return jax.jit(build, out_shardings=shardings)


def spread(dm: Dict, devices, *, program_head: bool):
    """The harness's own placement of the weights over ``devices``, for the
    reference: a 1-D mesh of the chips, each leaf split on its largest axis
    that the chip count divides (the layer axis of the stacked layers
    excepted, which the reference's scan walks), replicated where none
    does.  ``None`` on one chip."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    n = len(devices)
    if n == 1:
        return None
    mesh = Mesh(list(devices), ("chips",))

    def place(path, leaf):
        first = 1 if getattr(path[0], "key", None) == "layers" else 0
        axes = [i for i in range(first, len(leaf.shape)) if leaf.shape[i] % n == 0]
        spec = [None] * len(leaf.shape)
        if axes:
            spec[max(axes, key=lambda i: (leaf.shape[i], i))] = "chips"
        return NamedSharding(mesh, PartitionSpec(*spec))

    shapes = jax.eval_shape(make(dm, program_head=program_head), *split_seed(0))
    return jax.tree_util.tree_map_with_path(place, shapes)


def split_seed(seed: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A seed of any size as two uint32 arguments, so one compiled program
    serves every seed."""
    return (jnp.asarray(seed & 0xFFFFFFFF, jnp.uint32),
            jnp.asarray((seed >> 32) & 0xFFFFFFFF, jnp.uint32))

