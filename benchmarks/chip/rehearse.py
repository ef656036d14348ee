"""Compile rehearsal for a described TPU v5e: does a configuration's serving
fit one chip, and how large can its KV pool be?

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py minicpm-2b [--pages N ...]

Run by hand, not as a test (a second file that describes the topology can
land on another test worker and skip in silence).  For each configuration
and pool size it compiles, for one chip of a described ``v5e:2x2``, the
scheduler's own decode step and its prefill-chunk step at every chunk length
the configuration's cells use, the benchmark's weight program and the
reference of the correctness check, and prints ``memory_analysis()`` of
each, the padded bytes of one pool leaf, and the pool-size arithmetic:

    weights + 3 x pool + largest step temporaries + 0.5 GiB <= 15.75 GiB

Three pools, because no step donates its cache: inside one scheduler step the
chunk's input, its output (the decode's input) and the decode's output can
all be alive at once.  Nothing runs, so nothing here is a chip measurement.
"""

from __future__ import annotations

import argparse
import json
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


def rehearse(name: str, pages: list) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models import build_model

    from benchmarks.chip import cell, check, reference, traffic, weights

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_PALLAS_INTERPRET"] = "0"
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)

    cfg = cell.config(name)
    dm = cell.dims(cfg)
    arch = cell.program_config(cfg)
    model = build_model(arch)
    make = weights.make(dm, program_head=not arch.tie_embeddings)
    seed = tuple(jax.ShapeDtypeStruct((), jnp.uint32, sharding=chip) for _ in range(2))
    out = {"config": name, "weights": mem(make.lower(*seed).compile())}
    w_bytes = out["weights"]["out"]
    params = on(jax.eval_shape(make, *weights.split_seed(0)))
    s = cfg["serving"]
    tails = sorted({t for w in cell.benchmark()["workloads"] if w["config"] == name
                    for t in traffic.shapes(cell.traffic(w["traffic"]), seconds=51,
                                            chunk=s["prefill_chunk"])["tails"]}
                   | {s["prefill_chunk"]})
    out["pools"] = []
    for n in pages:
        sched_cfg = dict(cfg, serving=dict(s, kv_pages=n))
        sched = cell.build_scheduler(model, params, sched_cfg)
        cache = on(jax.eval_shape(lambda c=sched.cache: c))
        leaf = cache["kp"]
        pool = 2 * mem(jax.jit(lambda x: x * 1).lower(leaf).compile())["args"]
        sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
        B = s["slots"]
        dec = mem(sched._decode.lower(
            params, cache, sds((B,), jnp.int32), sds((B, s["max_seq"]), jnp.int32),
            sds((B,), jnp.int32), sds((B,), jnp.bool_),
            jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=chip)).compile())
        chunks = {t: mem(sched._chunk.lower(params, cache, sds((1, t), jnp.int32),
                                            sds((), jnp.int32)).compile())
                  for t in tails}
        temp = max(dec["temp"], *(c["temp"] for c in chunks.values()))
        total = w_bytes + 3 * pool + temp + SPARE
        raw = n * s["page_size"] * 2 * dm["L"] * dm["Hkv"] * dm["hd"] * 2
        row = {"pages": n, "pool_padded": pool, "pool_raw": raw, "decode": dec,
               "chunks": chunks, "need": total, "fits": total <= HBM,
               "largest_pages": int(n * (HBM - w_bytes - temp - SPARE) / (3 * pool))}
        out["pools"].append(row)
        print(json.dumps({"config": name, "pages": n,
                          "pool_GiB": pool / GiB, "pool_raw_GiB": raw / GiB,
                          "weights_GiB": w_bytes / GiB, "decode": dec,
                          "temp_max_GiB": temp / GiB, "need_GiB": total / GiB,
                          "fits": total <= HBM,
                          "largest_pages": row["largest_pages"]}), flush=True)
        del sched
    spec_k = max(cell.traffic(w["traffic"])["check_requests"]
                 for w in cell.benchmark()["workloads"] if w["config"] == name)
    n_ref = max(traffic.shapes(cell.traffic(w["traffic"]), seconds=51, chunk=1)
                ["output_max"] for w in cell.benchmark()["workloads"]
                if w["config"] == name)
    b = check.pack([], spec_k, s["max_seq"], n_ref)
    ref_args = (params, dm, sds(b["tokens"].shape, jnp.int32),
                sds(b["pos"].shape, jnp.int32), sds((2, *b["pos"].shape), jnp.int32))
    for quant in (None, "fp8"):
        f = jax.jit(lambda p, t, q, g, quant=quant: reference.forward(
            p, dm, t, q, g, quant=quant))
        out[f"reference_{quant}"] = mem(f.lower(ref_args[0], *ref_args[2:]).compile())
        print(json.dumps({"config": name, f"reference_{quant}":
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
