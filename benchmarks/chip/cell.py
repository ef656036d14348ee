"""Finding a cell's pieces by name, and the program's side of a cell.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``configs/<config>.json``) under a traffic mix (``traffic/<traffic>.json``).
Its metrics are the ``end_to_end`` and ``per_layer`` entries that apply to
it, each read by ``metrics/<name>.py``.  A configuration names its block
(``blocks/<block>.py``, ``dense`` by default) and, on several chips, its
mesh (``serving.mesh``).  Adding a configuration, a block, a traffic mix or
a metric is adding its file and its entry; nothing here changes.

Only :func:`program_config`, :func:`mesh` and :func:`build_scheduler` touch
the program, through ``repro.configs``, ``repro.launch.mesh.make_mesh`` and
``repro.serve.scheduler.DecodeScheduler``.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCHMARK = ROOT / "BENCHMARK.json"


class CellError(Exception):
    """A cell, configuration, traffic mix or metric that cannot be found or
    does not match what the program runs."""


def load_json(path: Path) -> Dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise CellError(f"missing file {path}") from e


def benchmark() -> Dict:
    return load_json(BENCHMARK)


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise CellError(f"no workload {name!r} in BENCHMARK.json; have "
                    f"{[w['name'] for w in bench['workloads']]}")


def metrics_for(bench: Dict, cell: str, *, traced: bool) -> List[Dict]:
    """The metric entries a run of ``cell`` reports: the end-to-end ones
    untraced, the per-layer ones traced.  An entry without ``workloads``
    applies to every cell (a per-layer one: every cell that reports the
    end-to-end metric it moves)."""
    def applies(m: Dict) -> bool:
        return "workloads" not in m or cell in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if applies(m) and ("workloads" in m or m["moves"] in names)]


def config(name: str) -> Dict:
    return load_json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> Dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def block(cfg: Dict):
    """The configuration's block file, ``blocks/<block>.py``."""
    from . import blocks

    name = cfg.get("block", "dense")
    try:
        return blocks.load(name)
    except FileNotFoundError as e:
        raise CellError(f"{cfg.get('name')}: {e}") from e


def dims(cfg: Dict) -> Dict:
    """The sizes the yardstick needs, from a configuration's published keys
    (Hugging Face names): those every block shares, and its block's own."""
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    hd = cfg.get("head_dim", d // H)
    norm = "layernorm" if cfg.get("norm_type") == "layer_norm" else "rmsnorm"
    eps = cfg.get("layer_norm_epsilon", cfg.get("rms_norm_eps"))
    L = cfg["num_hidden_layers"]
    blk = block(cfg)
    return {
        "block": cfg.get("block", "dense"),
        "L": L, "d": d, "H": H, "Hkv": cfg["num_key_value_heads"], "hd": hd,
        "vocab": cfg["vocab_size"],
        "vocab_pad": -(-cfg["vocab_size"] // cfg["program"]["vocab_pad"])
        * cfg["program"]["vocab_pad"],
        "norm": norm, "eps": eps,
        "tied": bool(cfg.get("tie_word_embeddings", False)),
        "theta": cfg["rope_theta"],
        "window": cfg.get("sliding_window"),
        "emb_scale": float(cfg.get("scale_emb", 1.0)),
        "residual_scale": (cfg["scale_depth"] / L ** 0.5
                           if "scale_depth" in cfg else 1.0),
        "logit_scale": (cfg["dim_model_base"] / d
                        if "dim_model_base" in cfg else 1.0),
        **blk.dims(cfg),
    }


# published key -> the program's ArchConfig attribute
_PROGRAM_FIELDS = {
    "L": "n_layers", "d": "d_model", "H": "n_heads", "Hkv": "n_kv_heads",
    "vocab": "vocab", "theta": "rope_theta", "window": "sliding_window",
    "norm": "norm",
}


def program_config(cfg: Dict):
    """The program's config for this configuration, checked against every
    published size the benchmark relies on, its block's among them: a cell
    runs at published widths or not at all."""
    from repro import configs

    arch = configs.get(cfg["program"]["arch"])
    dm = dims(cfg)
    bad = {k: (dm[k], getattr(arch, a)) for k, a in _PROGRAM_FIELDS.items()
           if dm[k] != getattr(arch, a)}
    if arch.the_head_dim() != dm["hd"]:
        bad["hd"] = (dm["hd"], arch.the_head_dim())
    if arch.padded_vocab != dm["vocab_pad"]:
        bad["vocab_pad"] = (dm["vocab_pad"], arch.padded_vocab)
    scales = {"emb_scale": arch.emb_scale, "residual_scale": arch.residual_scale,
              "logit_scale": arch.logit_scale}
    for k, v in scales.items():
        if abs(dm[k] - v) > 1e-9 * max(1.0, abs(v)):
            bad[k] = (dm[k], v)
    bad.update(block(cfg).check_program(arch, dm))
    if bad:
        raise CellError(f"{cfg['name']}: the program's {arch.name} differs "
                        f"from the configuration (want, have): {bad}")
    return arch


def mesh(cfg: Dict, devices, chips: int):
    """The cell's mesh over ``devices[:chips]``, named by the configuration's
    ``serving.mesh`` (axis name to size, e.g. ``{"data": 1, "model": 4}``);
    ``None`` for a configuration without one, which runs on one chip."""
    shape = cfg["serving"].get("mesh")
    if shape is None:
        if chips != 1:
            raise CellError(f"{cfg['name']}: a cell on {chips} chips needs "
                            "serving.mesh in its configuration")
        return None
    size = math.prod(shape.values())
    if size != chips:
        raise CellError(f"{cfg['name']}: serving.mesh {shape} holds {size} "
                        f"chips, the cell asks for {chips}")
    if len(devices) < chips:
        raise CellError(f"{cfg['name']}: {chips} chips asked for, "
                        f"{len(devices)} given")
    from repro.launch.mesh import make_mesh

    return make_mesh(tuple(shape.values()), tuple(shape), devices=devices[:chips])


def build_scheduler(model, params, cfg: Dict, mesh=None):
    """The timed entry, built with the serving defaults of
    ``repro.launch.serve``: only the sizes a cell needs, and its mesh, are
    set."""
    from repro.serve.scheduler import DecodeScheduler

    s = cfg["serving"]
    return DecodeScheduler(model, params, n_slots=s["slots"],
                           max_seq=s["max_seq"], page_size=s["page_size"],
                           kv_pages=s["kv_pages"],
                           prefill_chunk=s["prefill_chunk"], mesh=mesh)
