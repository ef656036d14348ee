"""Compiles for a described TPU v5e chip, at minicpm-2b's published widths.

Nothing here runs on a chip: the TPU compiler, installed beside JAX, compiles
for a ``v5e:2x2`` topology that is described, not attached.  That catches
what interpret mode cannot — a kernel block Mosaic refuses, a program that
does not fit HBM — at no chip time.  The topology is described inside a
module fixture, never at import: only one process at a time may load the
TPU library, and every test worker imports this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.models import build_model, kvcache
from repro.models.layers import to_compute_dtype
from repro.serve.engine import make_chunk_step, make_decode_step

HBM_LIMIT = int(15.75 * 2**30)      # what XLA lets one v5e program use
# chip_smoke.py's pool: 8 slots x ceil((512 + 32) / 16) pages of 16 tokens
SLOTS, PAGE, MAX_PAGES = 8, 16, 34
N_PAGES = SLOTS * MAX_PAGES
CHUNK = 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with JAX's persistent cache off: a compile for a
    described device is written to the cache but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total <= HBM_LIMIT, (
        f"{total / 2**30:.2f} GiB > {HBM_LIMIT / 2**30:.2f} GiB: {m}")
    return total


@pytest.mark.parametrize("hkv,g,d,page", [
    (36, 1, 64, PAGE),          # minicpm-2b: MHA, 36 kv heads
    (36, 1, 64, PAGE // 2),     # its pool lane-sharded over model=2
    (2, 12, 128, PAGE),         # starcoder2-3b: GQA
], ids=["minicpm-2b", "minicpm-2b-lane-shard", "starcoder2-3b"])
def test_paged_kernel_compiles_for_v5e(one_chip, hkv, g, d, page):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = (S((SLOTS, hkv, g, d), jnp.bfloat16),
            S((N_PAGES, page, hkv, d), jnp.bfloat16),
            S((N_PAGES, page, hkv, d), jnp.bfloat16),
            S((SLOTS, MAX_PAGES), jnp.int32),
            S((SLOTS,), jnp.int32), S((SLOTS,), jnp.int32))
    kernel = jax.jit(lambda *a: paged_attention_kernel(*a, interpret=False))
    hlo = kernel.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.fixture(scope="module")
def minicpm():
    cfg = configs.get("minicpm-2b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_kv_heads) == (40, 2304, 36)
    return cfg


def test_serving_init_never_holds_fp32_masters(one_chip, minicpm):
    """Initialisation and cast compile to one program whose peak stays far
    below the 10.15 GiB the float32 masters alone would take."""
    model = build_model(minicpm)
    init = jax.jit(lambda k: to_compute_dtype(model.init(k)))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    compiled = init.lower(key).compile()
    out = jax.eval_shape(init, jax.random.key(0))
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(out))
    assert {x.dtype for x in jax.tree_util.tree_leaves(out)} == {
        jnp.dtype(jnp.bfloat16)}
    assert _fits(compiled) < 2 * weights


@pytest.mark.parametrize("step,backend", [
    ("decode", "gather"), ("decode", "paged_kernel"), ("chunk", "gather"),
])
def test_minicpm_serving_step_fits_one_v5e(one_chip, minicpm, monkeypatch,
                                           step, backend):
    """The scheduler's paged decode and prefill-chunk steps at chip_smoke's
    pool size, with bf16 weights: arguments, outputs and temporaries fit
    one chip's HBM, and the kernel backend holds the compiled kernel."""
    import dataclasses

    # kernels pick interpret mode from the backend JAX runs on (the CPU
    # here); this program is for the chip
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    model = build_model(dataclasses.replace(minicpm, attn_backend=backend))
    params = _on(one_chip, jax.eval_shape(
        lambda k: to_compute_dtype(model.init(k)), jax.random.key(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: kvcache.paged_cache(model, SLOTS, page_size=PAGE,
                                    n_pages=N_PAGES, max_pages=MAX_PAGES)))
    if step == "decode":
        fn = jax.jit(make_decode_step(model))
        args = (params, cache, jax.ShapeDtypeStruct(
            (SLOTS, 1), jnp.int32, sharding=one_chip))
    else:
        fn = jax.jit(make_chunk_step(model))
        args = (params, cache,
                jax.ShapeDtypeStruct((1, CHUNK), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    compiled = fn.lower(*args).compile()
    _fits(compiled)
    assert ("tpu_custom_call" in compiled.as_text()) == (
        backend == "paged_kernel")


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_minicpm_scheduler_steps_alias_pool_on_v5e(one_chip, minicpm, step):
    """The scheduler's own jitted decode and chunk steps, compiled for the
    chip at chip_smoke's pool size, donate the cache: every pool byte they
    are given is aliased to the cache they return, and they fit HBM."""
    from repro.serve.scheduler import DecodeScheduler

    model = build_model(minicpm)
    params = _on(one_chip, jax.eval_shape(
        lambda k: to_compute_dtype(model.init(k)), jax.random.key(0)))
    # the steps depend on the shapes they are lowered with, not on the
    # scheduler's own pool, which is the smallest it accepts
    sched = DecodeScheduler(model, params, n_slots=SLOTS,
                            max_seq=MAX_PAGES * PAGE, page_size=PAGE,
                            kv_pages=MAX_PAGES, prefill_chunk=CHUNK)
    cache = _on(one_chip, jax.eval_shape(
        lambda: kvcache.paged_cache(model, SLOTS, page_size=PAGE,
                                    n_pages=N_PAGES, max_pages=MAX_PAGES)))
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    if step == "decode":
        lowered = sched._decode.lower(
            params, cache, S((SLOTS,), jnp.int32),
            S((SLOTS, sched.max_seq), jnp.int32), S((SLOTS,), jnp.int32),
            S((SLOTS,), jnp.bool_),
            jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                 sharding=one_chip))
    else:
        lowered = sched._chunk.lower(params, cache, S((1, CHUNK), jnp.int32),
                                     S((), jnp.int32))
    compiled = lowered.compile()
    pool = sum(leaf.size * leaf.dtype.itemsize for key, leaf in cache.items()
               if key in kvcache.POOL_KEYS)
    assert compiled.memory_analysis().alias_size_in_bytes >= pool
    _fits(compiled)
