"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single-pod = (16, 16) = 256 chips (one TPU v5e pod slice);
multi-pod = (2, 16, 16) = 512 chips, with the leading ``pod`` axis used for
hierarchical data parallelism (reduce-scatter intra-pod over ICI, all-reduce
inter-pod over DCI).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

from ..dist.sharding import MESH_AXES


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with ``Auto`` axes on every dimension.

    The sharding layer is written for GSPMD: activations are steered with
    ``with_sharding_constraint`` and the pool-indexed paths drop into
    ``shard_map``.  ``jax.make_mesh`` now defaults to ``Explicit`` axes,
    under which those constraints are refused and eager updates of a
    sharded array need a ``jax.set_mesh`` context, so every mesh of this
    repo is built here.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = MESH_AXES if multi_pod else MESH_AXES[1:]
    return make_mesh(shape, axes)


# TPU v5e hardware constants (roofline denominators; EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link (~4 links/chip on a 2d torus)
HBM_PER_CHIP = 16 * 1024**3    # 16 GB
