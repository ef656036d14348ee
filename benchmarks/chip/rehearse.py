"""Compile rehearsal for a described TPU v5e: does a configuration's serving
fit its chips, and how large can its KV pool be?

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py minicpm-2b [--pages N ...]

Run by hand, not as a test (a second file that describes the topology can
land on another test worker and skip in silence).  For each configuration
and pool size it compiles, for the chips of a described ``v5e:2x2`` that
the configuration's ``serving.mesh`` names (one chip without a mesh), the
scheduler's own decode step and its prefill-chunk step at every chunk length
the configuration's cells use, the benchmark's weight program and the
reference of the correctness check, and prints ``memory_analysis()`` of
each, per chip, the padded bytes of one pool leaf on a chip, and the
pool-size arithmetic, per chip:

    weights + pool + largest step temporaries + 0.5 GiB <= 15.75 GiB

One pool: every step that writes the pool is given it donated and updates
it in place, so no step holds a second one.  Nothing runs, so nothing here
is a chip measurement.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

HBM = 15.75 * 2**30          # what XLA lets one v5e program use
SPARE = 0.5 * 2**30
GiB = 2**30


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"args": m.argument_size_in_bytes, "out": m.output_size_in_bytes,
            "temp": m.temp_size_in_bytes, "alias": m.alias_size_in_bytes}


@contextlib.contextmanager
def placed_as_shapes():
    """``DecodeScheduler`` places its state on a mesh with
    ``jax.device_put``, and a described chip holds no array: hand it the
    shapes with their shardings instead, which is all a compile needs."""
    import jax

    real = jax.device_put

    def put(x, sharding=None, **_):
        if isinstance(sharding, jax.sharding.Sharding):
            sharding = jax.tree_util.tree_map(lambda _: sharding, x)
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), x, sharding)

    jax.device_put = put
    try:
        yield
    finally:
        jax.device_put = real


def rehearse(name: str, pages: list) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from repro.dist.sharding import param_shardings
    from repro.models import build_model
    from repro.models.layers import to_compute_dtype

    from benchmarks.chip import cell, check, reference, traffic, weights

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_PALLAS_INTERPRET"] = "0"
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")

    cfg = cell.config(name)
    s = cfg["serving"]
    chips = math.prod(s.get("mesh", {}).values())
    devices = topo.devices[:chips]
    mesh = cell.mesh(cfg, devices, chips)
    dm = cell.dims(cfg)
    arch = cell.program_config(cfg)
    model = build_model(arch)
    program_head = not arch.tie_embeddings
    if mesh is None:
        chip = SingleDeviceSharding(devices[0])
        shardings = None
    else:
        chip = NamedSharding(mesh, PartitionSpec())
        shardings = param_shardings(
            jax.eval_shape(lambda k: to_compute_dtype(model.init(k)), jax.random.key(0)),
            mesh)

    def sds(shape, dt, sharding=chip):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def on(tree, placement=None):
        if placement is None:
            placement = jax.tree_util.tree_map(lambda _: chip, tree)
        return jax.tree_util.tree_map(lambda a, p: sds(a.shape, a.dtype, p), tree,
                                      placement)

    make = weights.make(dm, program_head=program_head, shardings=shardings)
    seed = (sds((), jnp.uint32), sds((), jnp.uint32))
    out = {"config": name, "chips": chips, "weights": mem(make.lower(*seed).compile())}
    w_bytes = out["weights"]["out"]
    params = on(jax.eval_shape(make, *weights.split_seed(0)), shardings)
    cells = [w for w in cell.benchmark()["workloads"] if w["config"] == name]
    if not cells:
        raise cell.CellError(f"no cell of BENCHMARK.json runs {name}: its traffic "
                             "sets the chunk lengths and the check's sizes")
    tails = sorted({t for w in cells
                    for t in traffic.shapes(cell.traffic(w["traffic"]), seconds=51,
                                            chunk=s["prefill_chunk"])["tails"]}
                   | {s["prefill_chunk"]})
    out["pools"] = []
    for n in pages:
        with placed_as_shapes():
            sched = cell.build_scheduler(model, params, dict(cfg, serving=dict(
                s, kv_pages=n)), mesh)
        cache, state = sched.cache, (sched.last_tokens, sched.out_buf, sched.out_pos)
        if mesh is None:          # the scheduler placed nothing: put it on the chip
            cache, state = on((cache, state))
        pool = 2 * mem(jax.jit(lambda x: x * 1).lower(cache["kp"]).compile())["args"]
        dec = mem(sched._decode.lower(
            sched.params, cache, *state, sds((s["slots"],), jnp.bool_),
            sds((), jax.random.key(0).dtype)).compile())
        chunks = {t: mem(sched._chunk.lower(sched.params, cache, sds((1, t), jnp.int32),
                                            sds((), jnp.int32)).compile())
                  for t in tails}
        temp = max(dec["temp"], *(c["temp"] for c in chunks.values()))
        total = w_bytes + pool + temp + SPARE
        raw = n * s["page_size"] * 2 * dm["L"] * dm["Hkv"] * dm["hd"] * 2 // chips
        row = {"pages": n, "pool_padded": pool, "pool_raw": raw, "decode": dec,
               "chunks": chunks, "need": total, "fits": total <= HBM,
               "largest_pages": int(n * (HBM - w_bytes - temp - SPARE) / pool)}
        out["pools"].append(row)
        print(json.dumps({"config": name, "chips": chips, "pages": n,
                          "pool_GiB_per_chip": pool / GiB,
                          "pool_raw_GiB_per_chip": raw / GiB,
                          "weights_GiB_per_chip": w_bytes / GiB, "decode": dec,
                          "temp_max_GiB": temp / GiB, "need_GiB_per_chip": total / GiB,
                          "fits": total <= HBM,
                          "largest_pages": row["largest_pages"]}), flush=True)
        del sched
    spec_k = max(cell.traffic(w["traffic"])["check_requests"] for w in cells)
    n_ref = max(traffic.shapes(cell.traffic(w["traffic"]), seconds=51, chunk=1)
                ["output_max"] for w in cells)
    b = check.pack([], spec_k, s["max_seq"], n_ref)
    spread = weights.spread(dm, devices, program_head=program_head)
    ref_params = on(jax.eval_shape(make, *weights.split_seed(0)), spread)
    rep = chip if spread is None else NamedSharding(     # the reference's 1-D mesh
        jax.tree_util.tree_leaves(spread)[0].mesh, PartitionSpec())
    ref_args = (sds(b["tokens"].shape, jnp.int32, rep), sds(b["pos"].shape, jnp.int32, rep),
                sds((2, *b["pos"].shape), jnp.int32, rep))
    for quant in (None, "fp8"):
        f = jax.jit(lambda p, t, q, g, quant=quant: reference.forward(
            p, dm, t, q, g, quant=quant))
        out[f"reference_{quant}"] = mem(f.lower(ref_params, *ref_args).compile())
        print(json.dumps({"config": name, "chips": chips, f"reference_{quant}":
                          out[f"reference_{quant}"]}), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="+")
    ap.add_argument("--pages", type=int, nargs="+", default=None,
                    help="pool sizes to try (default: the configuration's)")
    ap.add_argument("--out", default=None, help="write the results as JSON here")
    args = ap.parse_args(argv)
    from benchmarks.chip import cell

    res = [rehearse(c, args.pages or [cell.config(c)["serving"]["kv_pages"]])
           for c in args.configs]
    if args.out:
        Path(args.out).write_text(json.dumps(res, indent=1, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
