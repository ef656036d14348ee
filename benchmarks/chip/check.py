"""How ``correct`` is decided: served greedy tokens against the reference.

Once the window has closed and the program's state is freed, a sample of the
finished requests (the one with the longest output, then others drawn from
the seed) goes through the float32 reference once, over each prompt with its
served tokens, on the cell's chips.  At every served position the number
read is how far the served token's reference logit lies below the
reference's best logit; the run's numbers are the widest such gap and their mean over the served
tokens (a configuration's ``correctness.limits`` says which are compared,
each with its limit: :func:`judge`).  A greedy server computing the
configuration's arithmetic picks the reference's best token up to rounding,
so its gaps stay small; one that serves another token does not.

The control (``control=True``) reads, at the same positions, the gap of the
token that the fp8 reference (``reference.forward(quant="fp8")``) puts
first: the precision step below the bf16 that the configuration serves in.
Its numbers go through the same :func:`judge` in the program's place.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from . import reference, weights


def sample(finished: List, k: int, seed: int) -> List:
    """``k`` finished requests: the longest output first, then a draw."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.tokens), r.rid))
    rest = order[1:]
    rng = np.random.default_rng(seed + 1)
    pick = rng.permutation(len(rest))[: max(0, k - 1)]
    return [order[0], *(rest[i] for i in sorted(pick))]


def pack(reqs: List, k: int, t_ref: int, n_ref: int) -> Dict[str, np.ndarray]:
    """Fixed shapes (one compile for every run of a cell): ``tokens`` (k,
    t_ref), the positions whose logits chose each served token ``pos`` (k,
    n_ref), the served tokens ``served`` and a ``valid`` mask."""
    tokens = np.zeros((k, t_ref), np.int32)
    pos = np.zeros((k, n_ref), np.int32)
    served = np.zeros((k, n_ref), np.int32)
    valid = np.zeros((k, n_ref), bool)
    for i, r in enumerate(reqs):
        P, out = len(r.prompt), np.asarray(r.tokens, np.int32)
        n = len(out)
        seq = np.concatenate([np.asarray(r.prompt, np.int32), out[:-1]])
        tokens[i, : len(seq)] = seq
        pos[i, :n] = P - 1 + np.arange(n)
        served[i, :n] = out
        valid[i, :n] = True
    return {"tokens": tokens, "pos": pos, "served": served, "valid": valid}


def gaps(dm: Dict, seed: int, reqs: List, *, k: int, t_ref: int, n_ref: int,
         program_head: bool, devices, control: bool = False) -> Dict[str, float]:
    """Widest and mean logit gap of the served tokens and, with ``control``,
    of the fp8 reference's first choices (keys ``control_...``).  The
    reference runs over the cell's ``devices``, its weights spread over them
    (``weights.spread``)."""
    import jax.numpy as jnp

    b = pack(reqs, k, t_ref, n_ref)
    params = weights.make(
        dm, program_head=program_head,
        shardings=weights.spread(dm, devices, program_head=program_head),
    )(*weights.split_seed(seed))
    tok, pos = jnp.asarray(b["tokens"]), jnp.asarray(b["pos"])
    targets = [b["served"]]
    if control:
        _, _, c_arg = reference.forward(params, dm, tok, pos,
                                        jnp.zeros((1, *pos.shape), jnp.int32),
                                        quant="fp8")
        targets.append(np.asarray(c_arg))
    best, at, _ = reference.forward(params, dm, tok, pos,
                                    jnp.asarray(np.stack(targets)))
    best, at = np.asarray(best), np.asarray(at)
    del params
    valid = b["valid"]
    out = {"tokens": int(valid.sum())}
    for prefix, a in (("", at[0]), ("control_", at[1] if control else None)):
        if a is None or not valid.any():
            continue
        gap = (best - a)[valid]
        out[prefix + "widest_logit_gap"] = float(gap.max())
        out[prefix + "mean_logit_gap"] = float(gap.mean())
        out[prefix + "gaps"] = gap
    return out


def judge(g: Dict, limits: Dict[str, float], *, bad: int, checked: int,
          prefix: str = "") -> Tuple[bool, Dict[str, List]]:
    """``correct`` and the numbers compared, each as ``[value, limit]``:
    every number the configuration names (read from ``g`` under ``prefix``)
    within its limit, no finished request with a wrong count or id, and at
    least one request checked.  A number that was not read fails."""
    checks = {n: [g.get(prefix + n, float("inf")), lim] for n, lim in limits.items()}
    checks["bad_requests"] = [bad, 0]
    checks["checked_requests"] = [checked, 1]
    correct = checked >= 1 and all(
        v <= lim for n, (v, lim) in checks.items() if n != "checked_requests")
    return correct, checks
