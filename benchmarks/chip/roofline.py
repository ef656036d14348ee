"""What a decode step has to do, counted from shapes, and the peaks of the
cell's chips.

The counts are of the work the algorithm needs, not of what the current code
does, so a change of kernel or fusion leaves the yardstick as it is:

- bytes: every weight a decode step reads once (all blocks, each counted by
  its ``blocks/<name>.py``; the final norm and the head; the embedding only
  for the rows of the new tokens), the K/V of the valid positions of the
  active slots, and the K/V written for each new token;
- FLOPs per new token: 2 x the blocks' matrix parameters, 4 x layers x heads
  x head_dim x context for attention (scores and values), and 2 x d_model x
  vocab for the head.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

from . import blocks

BF16 = 2
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, chips: int = 1) -> Dict[str, float]:
    """The published peaks of ``chips`` chips of a kind together: one
    chip's FLOP/s and bandwidth times the count, so a mesh's work reads
    against the whole mesh.  An unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    one = table[device_kind]
    return dict(one, bf16_flops_per_s=chips * one["bf16_flops_per_s"],
                hbm_bytes_per_s=chips * one["hbm_bytes_per_s"])


def block_matrix_params(dm: Dict) -> int:
    return dm["L"] * blocks.load(dm["block"]).matrix_params(dm)


def block_vector_params(dm: Dict) -> int:
    return dm["L"] * blocks.load(dm["block"]).vector_params(dm)


def kv_bytes_per_token(dm: Dict) -> int:
    return dm["L"] * 2 * dm["Hkv"] * dm["hd"] * BF16


def weight_bytes(dm: Dict) -> int:
    """Weights one decode step reads: blocks, final norm and the head."""
    final_norm = dm["d"] * (2 if dm["norm"] == "layernorm" else 1)
    head = dm["d"] * dm["vocab"]
    return (block_matrix_params(dm) + block_vector_params(dm) + final_norm
            + head) * BF16


def decode_flops(dm: Dict, tokens: int, context: int) -> float:
    """``tokens`` new tokens (one per active slot) attending ``context``
    positions in all (the sum over the active slots, new token included)."""
    per_token = 2 * block_matrix_params(dm) + 2 * dm["d"] * dm["vocab"]
    attn = 4 * dm["L"] * dm["H"] * dm["hd"] * context
    return float(per_token * tokens + attn)


def decode_bytes(dm: Dict, tokens: int, context: int) -> float:
    """Weights once, the K/V of the ``context - tokens`` positions already
    held, the K/V written for the ``tokens`` new ones, and their embedding
    rows."""
    kv = kv_bytes_per_token(dm)
    return float(weight_bytes(dm) + kv * context + tokens * dm["d"] * BF16)


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
