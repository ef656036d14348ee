"""Chip smoke: serve minicpm-2b at its published widths on a TPU.

One process drives the normal serving path once, through the entry points a
user calls (``build_frontend`` / ``spawn_workload`` of ``repro.launch.serve``):
``ServingFrontend`` -> ``DecodeScheduler`` (paged KV pool, chunked prefill)
-> the jitted ``serve/engine.py`` steps -> the paged-attention kernel.  The
model is ``minicpm-2b`` as published (40 layers, d_model 2304, 36 MHA heads
of 64, d_ff 5760, vocab 122753) with random bf16 weights from ``--seed``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # 2x2 (data, model) mesh vs one chip

One chip: 16 requests over 4 sessions, 8 slots, prompt 512, 32 new tokens,
page 16, prefill chunk 256, first with the gather backend and then with the
Pallas kernel; checks what was served, that the kernel was compiled (a
``tpu_custom_call`` in the decode step) and that both backends give the same
logits on one cache state.  ``--chips 4`` runs only the mesh phase: the same
workload on a 2x2 mesh (slots on ``data``, pool lanes and heads on
``model``, the kernel under ``shard_map``) and on device 0, with the logits
of the two compared.

Any failed check exits non-zero.  The script refuses to run without a TPU:
a CPU run would measure nothing this script exists for.  Every number it
prints before its last line is a smoke-run reading, not a benchmark metric.
The last line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ARCH = "minicpm-2b"
N_REQUESTS = 16
SESSIONS = 4
SLOTS = 8
PROMPT = 512
MAX_NEW = 32
PAGE = 16
CHUNK = 256
# Largest relative L2 distance allowed between two logit rows of the same
# requests.  Weights, activations and the KV pool are bf16 on every path; the
# paths differ in where they round.  The gather path rounds attention scores,
# softmax probabilities and the attention output to bf16 where the kernel
# keeps fp32, and a 2x2 mesh sums matmul partials in another order.  On a
# CPU, minicpm-2b's blocks at d_model 576 put the two backends 0.8e-2 apart
# at 4 layers and 2.1e-2 to 2.2e-2 apart at 40.  A current token left out
# of its own attention (its page unmapped) moved a 4-layer model's logits
# by 9.7e-2; the bound sits between the two.
LOGIT_RTOL = 0.08


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report_memory(what: str, devices) -> None:
    """HBM held after a phase, and the process's peak so far, per device."""
    rows = [d.memory_stats() or {} for d in devices]
    print(f"smoke: memory after {what}: bytes_in_use "
          + ", ".join(f"{r.get('bytes_in_use', 0) / 2**30:.2f}" for r in rows)
          + " GiB; peak_bytes_in_use "
          + ", ".join(f"{r.get('peak_bytes_in_use', 0) / 2**30:.2f}"
                      for r in rows) + " GiB", flush=True)


def release(fe) -> None:
    """Free a frontend's scheduler state now: the frontend and its cloud
    reference each other, so dropping the name alone leaves the KV pool on
    the device until the cycle collector runs."""
    fe.scheduler = None
    gc.collect()


def tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def serve(cfg, model, params, *, seed: int, attn_backend: str = "gather",
          mesh=None):
    """Serve the smoke workload; returns the frontend after checking it."""
    from repro.core import SimCloud
    from repro.launch.serve import build_frontend, spawn_workload

    cloud = SimCloud(seed=seed)
    fe = build_frontend(cloud, cfg, model, params, mode="continuous",
                        batch_size=SLOTS, max_new=MAX_NEW, prompt_len=PROMPT,
                        page_size=PAGE, prefill_chunk=CHUNK,
                        attn_backend=attn_backend, mesh=mesh)
    spawn_workload(cloud, fe, vocab=cfg.vocab, n_requests=N_REQUESTS,
                   sessions=SESSIONS, prompt_len=PROMPT, max_new=MAX_NEW,
                   seed=seed)
    t0 = time.perf_counter()
    cloud.run()
    wall = time.perf_counter() - t0
    label = f"{attn_backend}{'' if mesh is None else ' on 2x2 mesh'}"

    served = sum(len(v) for v in fe.completions.values())
    check(served == N_REQUESTS, f"{label}: served {served}/{N_REQUESTS}")
    for s in range(SESSIONS):
        sess = f"s{s}"
        want = [f"r{i}" for i in range(N_REQUESTS) if i % SESSIONS == s]
        check(fe.completions.get(sess) == want,
              f"{label}: session {sess} completed {fe.completions.get(sess)}, "
              f"want FIFO order {want}")
        for toks in fe.results[sess]:
            check(len(toks) == MAX_NEW
                  and all(0 <= int(t) < cfg.vocab for t in toks),
                  f"{label}: a request in {sess} returned {len(toks)} tokens "
                  f"(want {MAX_NEW} in [0, {cfg.vocab}))")
    check(fe.dead_letter_ids() == [],
          f"{label}: dead letters {fe.dead_letter_ids()}")
    stats = fe.serving_stats()
    check(stats["kv_pages_high_water"] > 0, f"{label}: KV pool never used")
    print(f"smoke: served {served} requests ({label}) in {wall:.2f} s wall, "
          f"compilation included; kv pool high-water "
          f"{stats['kv_pages_high_water']}/{stats['kv_pages']} pages",
          flush=True)
    return fe


def admit_round(sched, *, seed: int) -> None:
    """Admit one request per slot, with prompts of different lengths so the
    slots end on different pages and lanes, and step until every slot has
    finished its prefill and is decoding."""
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    for i in range(SLOTS):
        prompt = rng.integers(0, sched.model.cfg.vocab, size=PROMPT - 37 * i)
        sched.submit(f"cmp{i}", f"cmp{i}", prompt.astype(np.int32), MAX_NEW)
    steps = 0
    while sched.pending or sched.admitting_slots():
        sched.step()
        steps += 1
        check(steps < 200, "comparison admissions did not finish")
    check(sched.active_slots() == SLOTS, "not every slot is decoding")
    # map the page each slot's next token lands in, as a scheduler step
    # does before its decode: a token written to an unmapped page is
    # dropped, and the gather backend would then not attend to it
    for slot in sched.slots:
        sched._prepare_write_span(slot, slot.len, 1)


def decode_logits(sched, model):
    """One engine decode step of ``model`` on the cache ``sched`` holds;
    float32 logits ``(SLOTS, vocab)``.  The step binds the scheduler's mesh
    policy, so on a mesh it runs sharded like the scheduler's own step."""
    import jax
    import numpy as np

    from repro.serve.engine import make_decode_step

    step = jax.jit(make_decode_step(model, policy=sched._policy,
                                    cache_specs=sched.cache_specs))
    _, logits, _ = step(sched.params, sched.cache, sched.last_tokens[:, None])
    out = np.asarray(logits[:, -1, :model.cfg.vocab], np.float32)
    check(bool(np.isfinite(out).all()), "non-finite logits")
    return out


def compare_logits(got, ref, what: str) -> float:
    import numpy as np

    rel = float(np.max(np.linalg.norm(got - ref, axis=-1)
                       / np.linalg.norm(ref, axis=-1)))
    agree = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
    print(f"smoke: logits {what}: max relative L2 distance {rel:.3e} "
          f"(bound {LOGIT_RTOL}), argmax agreement {agree:.3f}", flush=True)
    check(rel <= LOGIT_RTOL, f"logits {what} differ: {rel:.3e} > {LOGIT_RTOL}")
    return rel


def build(seed: int):
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.models import build_model
    from repro.serve.engine import init_serving_params

    cfg = configs.get(ARCH)
    check((cfg.n_layers, cfg.d_model, cfg.n_kv_heads) == (40, 2304, 36),
          f"{ARCH} is not at its published widths: {cfg}")
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_serving_params(model, jax.random.key(seed)))
    dtypes = {x.dtype for x in jax.tree_util.tree_leaves(params)}
    check(dtypes == {jnp.dtype(jnp.bfloat16)},
          f"serving weights are not all bf16: {dtypes}")
    print(f"smoke: {ARCH} {cfg.n_layers} layers d_model {cfg.d_model}, "
          f"weights {tree_bytes(params) / 2**30:.2f} GiB bf16, made in "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    return cfg, model, params


def one_chip(seed: int) -> None:
    import jax

    dev = jax.devices()[:1]
    cfg, model, params = build(seed)
    report_memory("weights", dev)
    fe = serve(cfg, model, params, seed=seed)
    kv_bytes = fe.scheduler.kv_memory_stats()["kv_pool_bytes"]
    print(f"smoke: kv pool {kv_bytes / 2**30:.2f} GiB", flush=True)
    report_memory("gather serving", dev)
    release(fe)

    fe = serve(cfg, model, params, seed=seed, attn_backend="paged_kernel")
    sched = fe.scheduler
    report_memory("paged_kernel serving", dev)
    assert_kernel_compiled(sched)
    admit_round(sched, seed=seed)
    compare_logits(decode_logits(sched, sched.model),
                   decode_logits(sched, model),
                   "paged_kernel vs gather, same cache state")
    report_memory("logit comparison", dev)


def assert_kernel_compiled(sched) -> None:
    import jax
    import jax.numpy as jnp

    hlo = sched._decode.lower(
        sched.params, sched.cache, sched.last_tokens, sched.out_buf,
        sched.out_pos, jnp.ones((SLOTS,), bool),
        jax.random.key(0)).compile().as_text()
    check("tpu_custom_call" in hlo,
          "paged_kernel decode step holds no tpu_custom_call: the kernel "
          "was not compiled for the chip")


def four_chips(seed: int) -> None:
    """The mesh serves with the kernel backend (its pool lane-sharded on
    ``model``, the kernel under ``shard_map``); both mesh backends' logits
    are compared with one chip's gather logits for the same requests."""
    import jax

    from repro.launch.mesh import make_mesh

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, have "
          f"{len(jax.devices())}")
    cfg, model, params = build(seed)
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    fe = serve(cfg, model, params, seed=seed, attn_backend="paged_kernel",
               mesh=mesh)
    report_memory("2x2 mesh serving", mesh.devices.flat)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in mesh.devices.flat]
    share = tree_bytes(params) / 8
    check(all(b >= share for b in in_use),
          f"a mesh device holds less than {share / 2**30:.2f} GiB: {in_use}")
    sched = fe.scheduler
    assert_kernel_compiled(sched)
    admit_round(sched, seed=seed)
    mesh_kernel = decode_logits(sched, sched.model)
    mesh_gather = decode_logits(sched, model)
    del sched
    release(fe)

    fe = serve(cfg, model, params, seed=seed)
    admit_round(fe.scheduler, seed=seed)
    ref = decode_logits(fe.scheduler, model)
    report_memory("one-chip serving", mesh.devices.flat)
    compare_logits(mesh_gather, ref, "gather, 2x2 mesh vs one chip")
    compare_logits(mesh_kernel, ref,
                   "paged_kernel on 2x2 mesh vs gather on one chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip smoke: no TPU (JAX sees {devices[0].platform} "
              f"devices); nothing was served", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"smoke: compile cache at {enable_compile_cache()}", flush=True)
    try:
        if args.chips == 4:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
