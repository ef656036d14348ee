"""Sharded-decode smoke: the same serving workload on one device and on an
8-device (2 data x 4 model) host mesh, asserted token-identical.

It needs 8 devices.  On a CPU host the caller spoofs them, before JAX
starts: ``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``.

For each mode the driver runs a small multi-session workload through
:class:`repro.serve.scheduler.DecodeScheduler` (chunked admission, paged
pool, the fused paged-attention backend by default — on the mesh that is
the shard_map lane/head decomposition), then measures

  * steady-state decode-step wall latency (post-warm, timed solo),
  * per-step collective wire bytes from the compiled decode step's HLO
    (``launch/hlo_analysis.py``) — the lane-sharded budget gate: the
    shard_map merge ships per-head softmax statistics, whose size is
    independent of the pool, so the decode step is compiled twice (default
    pool and 4x pool) and the wire bytes must NOT grow with the pool.  At
    this reduced scale fixed collectives (logits, embeddings) dominate the
    absolute number, so the growth — not the total — is what catches a
    full-pool all-gather regressing in.

The default arch is dense (``minicpm-2b-smoke``): dense holds the *strict*
1-device == 8-device token-parity claim (cross-shard bf16 reduction drift
stays inside its argmax margins; see tests/test_sched_differential.py's
sharded section for why moe/hybrid compare mesh-vs-mesh instead).

Usage:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.sharded_smoke --out smoke.json
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs
from ..models import build_model
from ..serve.scheduler import DecodeScheduler
from . import hlo_analysis
from .mesh import make_mesh

PAGE_SIZE = 4            # divides the mesh's model axis -> lane decomposition
N_SLOTS = 4              # divides the mesh's data axis


def _drive(sched, cfg, *, n_requests, sessions, prompt_len, max_new, seed=0):
    rng = np.random.default_rng(seed)
    for i in range(n_requests):
        sched.submit(f"s{i % sessions}", f"r{i}",
                     rng.integers(0, cfg.vocab,
                                  size=prompt_len).astype(np.int32),
                     max_new)
    outputs = {}
    steps = 0
    while sched.busy():
        for fin in sched.step():
            outputs[fin.request_id] = np.asarray(fin.tokens).tolist()
        steps += 1
        assert steps < 2000, "sharded smoke failed to drain"
    return outputs, steps


def _decode_args(sched):
    return (sched.params, sched.cache, sched.last_tokens, sched.out_buf,
            sched.out_pos, jnp.ones((sched.n_slots,), bool),
            jax.random.key(0))


def _decode_wire_bytes(sched):
    stats = hlo_analysis.collective_stats(
        sched._decode.lower(*_decode_args(sched)).compile().as_text())
    return int(stats.wire_bytes), dict(stats.count_by_kind)


def _cache_bytes(sched) -> int:
    return int(sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(sched.cache)))


def _decode_step_stats(sched, *, reps=20):
    """Steady-state decode dispatch: wall latency (solo, post-warm) and the
    compiled step's per-device collective wire bytes."""
    params, _, *rest = _decode_args(sched)
    # the step donates its cache: each call takes the one the last returned
    sched.cache = jax.block_until_ready(
        sched._decode(params, sched.cache, *rest))[0]     # warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        sched.cache = jax.block_until_ready(
            sched._decode(params, sched.cache, *rest))[0]
        times.append((time.perf_counter() - t0) * 1e3)
    wire, kinds = _decode_wire_bytes(sched)
    return {
        "decode_ms_p50": round(float(np.percentile(times, 50)), 3),
        "decode_ms_mean": round(float(np.mean(times)), 3),
        "wire_bytes_per_step": wire,
        "collectives_by_kind": kinds,
    }


def run_smoke(arch="minicpm-2b-smoke", *, attn_backend="paged_kernel",
              n_requests=6, sessions=3, prompt_len=12, max_new=6):
    if jax.device_count() < 8:
        raise SystemExit(
            f"sharded smoke needs 8 devices, have {jax.device_count()} (CPU: "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    cfg = configs.get(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    max_seq = prompt_len + max_new

    result = {"arch": arch, "backend": attn_backend,
              "requests": n_requests, "sessions": sessions,
              "prompt_len": prompt_len, "max_new": max_new}
    modes = {"single": None,
             "sharded": make_mesh((2, 4), ("data", "model"))}
    outputs = {}
    for name, mesh in modes.items():
        sched = DecodeScheduler(model, params, n_slots=N_SLOTS,
                                max_seq=max_seq, page_size=PAGE_SIZE,
                                prefill_chunk=PAGE_SIZE, mesh=mesh,
                                attn_backend=attn_backend)
        outs, steps = _drive(sched, cfg, n_requests=n_requests,
                             sessions=sessions, prompt_len=prompt_len,
                             max_new=max_new)
        outputs[name] = outs
        row = {"steps": steps, "devices": 1 if mesh is None else mesh.size,
               **_decode_step_stats(sched)}
        if mesh is not None:
            row["mesh"] = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
            result["pool_bytes"] = _cache_bytes(sched)
            # lane-sharded wire budget: recompile against a 4x pool — the
            # merge ships softmax statistics (pool-size-independent), so
            # wire bytes growing with the pool means pages on the wire
            big = DecodeScheduler(model, params, n_slots=N_SLOTS,
                                  max_seq=max_seq, page_size=PAGE_SIZE,
                                  prefill_chunk=PAGE_SIZE, mesh=mesh,
                                  attn_backend=attn_backend,
                                  kv_pages=4 * sched.n_pages)
            wire_big, _ = _decode_wire_bytes(big)
            row["wire_bytes_per_step_4x_pool"] = wire_big
            result["pool_bytes_4x"] = _cache_bytes(big)
        result[name] = row

    result["identical_outputs"] = outputs["single"] == outputs["sharded"]
    sh = result["sharded"]
    pool_growth = result["pool_bytes_4x"] - result["pool_bytes"]
    wire_growth = (sh["wire_bytes_per_step_4x_pool"]
                   - sh["wire_bytes_per_step"])
    result["wire_growth_bytes"] = wire_growth
    result["wire_growth_budget_bytes"] = pool_growth // 2
    result["wire_within_budget"] = wire_growth < pool_growth // 2
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b-smoke",
                    choices=configs.names())
    ap.add_argument("--attn-backend", default="paged_kernel",
                    choices=["gather", "paged_kernel"])
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--sessions", type=int, default=3)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    res = run_smoke(args.arch, attn_backend=args.attn_backend,
                    n_requests=args.requests, sessions=args.sessions,
                    prompt_len=args.prompt_len, max_new=args.max_new)
    print(f"{res['arch']} [{res['backend']}]: "
          f"1-dev {res['single']['decode_ms_p50']} ms/step vs "
          f"{res['sharded']['devices']}-dev ({res['sharded']['mesh']}) "
          f"{res['sharded']['decode_ms_p50']} ms/step, "
          f"{res['sharded']['wire_bytes_per_step']} wire B/step "
          f"(growth over 4x pool {res['wire_growth_bytes']} B, "
          f"budget {res['wire_growth_budget_bytes']}), "
          f"identical_outputs={res['identical_outputs']}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
        print(f"wrote {args.out}")
    if not (res["identical_outputs"] and res["wire_within_budget"]):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
