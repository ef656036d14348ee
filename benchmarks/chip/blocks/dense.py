"""The dense decoder block of minicpm-2b and starcoder2-3b.

Pre-norm attention and MLP, each added to the residual stream times
``residual_scale``: grouped-query attention with q/k/v/o projections (q, k
and v biased where the configuration has ``use_bias``), then a SwiGLU
(``silu``) MLP or the biased tanh-GELU one.  Written from Hugging Face's
``modeling_minicpm`` and ``modeling_starcoder2``.

StarCoder2's attention output bias is part of the published block; the
benchmark's weights set it to zero because the program has none, so it adds
nothing here.
"""

from __future__ import annotations

from typing import Dict, List

import jax

from .. import reference as ref
from ..weights import Leaf, norm_leaves


def dims(cfg: Dict) -> Dict:
    act = cfg["hidden_act"]
    return {"ff": cfg["intermediate_size"], "act": act, "gated": act == "silu",
            "bias": bool(cfg.get("use_bias", False))}


def check_program(arch, dm: Dict) -> Dict:
    bad = {}
    if arch.d_ff != dm["ff"]:
        bad["ff"] = (dm["ff"], arch.d_ff)
    if (arch.mlp == "swiglu") != dm["gated"]:
        bad["gated"] = (dm["gated"], arch.mlp)
    return bad


def layer_leaves(dm: Dict) -> List[Leaf]:
    d, hd = dm["d"], dm["hd"]
    q, kv = dm["H"] * hd, dm["Hkv"] * hd
    out = norm_leaves(("attn_norm",), d, dm)
    out += [(("attn", "wq"), (d, q), "matrix"), (("attn", "wk"), (d, kv), "matrix"),
            (("attn", "wv"), (d, kv), "matrix"), (("attn", "wo"), (q, d), "matrix")]
    if dm["bias"]:
        out += [(("attn", "bq"), (q,), "bias"), (("attn", "bk"), (kv,), "bias"),
                (("attn", "bv"), (kv,), "bias")]
    out += norm_leaves(("mlp_norm",), d, dm)
    f = dm["ff"]
    if dm["gated"]:
        out += [(("mlp", "w_gate"), (d, f), "matrix"), (("mlp", "w_up"), (d, f), "matrix"),
                (("mlp", "w_down"), (f, d), "matrix")]
    else:
        out += [(("mlp", "w_up"), (d, f), "matrix"), (("mlp", "b_up"), (f,), "bias"),
                (("mlp", "w_down"), (f, d), "matrix"), (("mlp", "b_down"), (d,), "bias")]
    return out


def matrix_params(dm: Dict) -> int:
    d, hd = dm["d"], dm["hd"]
    q, kv = dm["H"] * hd, dm["Hkv"] * hd
    attn = d * (q + 2 * kv) + q * d
    return attn + d * dm["ff"] * (3 if dm["gated"] else 2)


def vector_params(dm: Dict) -> int:
    d, hd = dm["d"], dm["hd"]
    norms = 2 * d * (2 if dm["norm"] == "layernorm" else 1)
    bias = (dm["H"] * hd + 2 * dm["Hkv"] * hd + dm["ff"] + d) if dm["bias"] else 0
    return norms + bias


def qkv(h, a, dm, quant):
    """The projections of ``h (K, T, d)``: q ``(K, T, H, D)``, k and v
    ``(K, T, Hkv, D)``."""
    K, T, _ = h.shape
    H, Hkv, D = dm["H"], dm["Hkv"], dm["hd"]
    q = ref.mm(h, a["wq"], quant)
    k = ref.mm(h, a["wk"], quant)
    v = ref.mm(h, a["wv"], quant)
    if dm["bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    return q.reshape(K, T, H, D), k.reshape(K, T, Hkv, D), v.reshape(K, T, Hkv, D)


def attend(q, k, v, dm, quant):
    """Rope on q and k, then causal attention over each sequence: ``(K, T,
    H * D)``."""
    K, T, H, D = q.shape

    def one(args):
        qq, kk, vv = args
        return ref.attention(ref.rope(qq, dm["theta"]), ref.rope(kk, dm["theta"]), vv,
                             dm, quant)

    return jax.lax.map(one, (q, k, v)).reshape(K, T, H * D)


def mlp(h, m, dm, quant):
    if dm["gated"]:
        u = jax.nn.silu(ref.mm(h, m["w_gate"], quant)) * ref.mm(h, m["w_up"], quant)
        return ref.mm(u, m["w_down"], quant)
    u = jax.nn.gelu(ref.mm(h, m["w_up"], quant) + m["b_up"], approximate=True)
    return ref.mm(u, m["w_down"], quant) + m["b_down"]


def block(x, lp, dm, quant):
    a = lp["attn"]
    q, k, v = qkv(ref.norm(x, lp["attn_norm"], dm), a, dm, quant)
    x = x + ref.mm(attend(q, k, v, dm, quant), a["wo"], quant) * dm["residual_scale"]
    h = ref.norm(x, lp["mlp_norm"], dm)
    return x + mlp(h, lp["mlp"], dm, quant) * dm["residual_scale"]
