"""Per-architecture smoke tests: reduced configs of every assigned arch run
one forward + one train step on CPU, asserting shapes and finiteness; decode
parity is asserted per family (the full configs are exercised only through
the dry-run)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model
from repro.models.layers import F32_PARAM_KEYS, to_compute_dtype
from repro.train import AdamWConfig, make_train_step
from repro.train.step import TrainStepConfig, init_train_state

ARCHS = configs.list_archs()


def _smoke_batch(cfg, B=2, S=16, rng=None):
    rng = rng or np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
    }
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.encdec.n_frames, cfg.encdec.frame_dim or cfg.d_model)),
            jnp.float32)
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.vlm.n_patches, cfg.vlm.patch_dim or cfg.d_model)),
            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_train_step(arch):
    cfg = configs.get(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _smoke_batch(cfg)
    logits = jax.jit(model.apply)(params, batch)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all()), f"{arch}: NaNs in fwd"

    step_cfg = TrainStepConfig()
    state = init_train_state(model, params, step_cfg)
    step = jax.jit(make_train_step(model, AdamWConfig(lr=1e-3, total_steps=10), step_cfg))
    params2, state2, metrics = step(params, state, batch)
    assert bool(jnp.isfinite(metrics["loss"])), f"{arch}: NaN loss"
    assert float(metrics["grad_norm"]) > 0, f"{arch}: zero grads"
    # params must actually change
    delta = jax.tree_util.tree_reduce(
        lambda a, b: a + float(jnp.abs(b).sum()),
        jax.tree_util.tree_map(lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
                               params, params2), 0.0)
    assert delta > 0, f"{arch}: train step was a no-op"


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_decode_step(arch):
    cfg = configs.get(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B, ctx = 2, 12
    cache = model.init_cache(B, ctx)
    tok = jnp.asarray(np.random.default_rng(1).integers(0, cfg.vocab, (B, 1)), jnp.int32)
    logits, cache2 = jax.jit(model.decode_step)(params, cache, tok)
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert int(cache2["length"]) == 1


def test_smoke_names_resolve_to_reduced_configs():
    """``<arch>-smoke`` is the name ``reduced()`` gives; the registry
    resolves it, and the plain name stays the published config."""
    for arch in ARCHS:
        small = configs.get(arch + "-smoke")
        assert small == configs.get(arch).reduced()
        assert configs.get(small.name) == small
        assert configs.get(arch).name == arch
    assert set(configs.names()) == {a + s for a in ARCHS
                                    for s in ("", configs.SMOKE_SUFFIX)}
    with pytest.raises(KeyError):
        configs.get("no-such-arch-smoke")


@pytest.mark.parametrize("arch", ARCHS)
def test_serving_weights_keep_logits_bitwise(arch):
    """Serving casts once, at set-up, what every use casts anyway: the bf16
    serving weights give bitwise the logits of the float32 masters, in the
    forward pass and in a decode step."""
    cfg = configs.get(arch + "-smoke")
    model = build_model(cfg)
    p32 = model.init(jax.random.key(0))
    p16 = to_compute_dtype(p32)
    for path, leaf in jax.tree_util.tree_leaves_with_path(p16):
        keep = any(getattr(k, "key", None) in F32_PARAM_KEYS for k in path)
        want = jnp.float32 if keep else jnp.bfloat16
        assert leaf.dtype == want, (arch, jax.tree_util.keystr(path))
    batch = _smoke_batch(cfg)
    fwd = jax.jit(model.apply)
    assert (np.asarray(fwd(p32, batch)).tobytes()
            == np.asarray(fwd(p16, batch)).tobytes()), arch
    cache = model.init_cache(2, 12)
    tok = batch["tokens"][:, :1]
    step = jax.jit(model.decode_step)
    assert (np.asarray(step(p32, cache, tok)[0]).tobytes()
            == np.asarray(step(p16, cache, tok)[0]).tobytes()), arch


@pytest.mark.parametrize("arch", ["qwen3-14b", "starcoder2-3b", "mamba2-1.3b",
                                  "recurrentgemma-2b", "moonshot-v1-16b-a3b"])
def test_decode_matches_full_forward(arch):
    """Step-by-step decode reproduces the training forward logits."""
    cfg = configs.get(arch).reduced()
    if cfg.moe is not None:  # avoid capacity drops in the parity check
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=4.0))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    toks = jnp.asarray(np.random.default_rng(2).integers(0, cfg.vocab, (2, 10)), jnp.int32)
    full = model.apply(params, {"tokens": toks})
    cache = model.init_cache(2, 10)
    outs = []
    step = jax.jit(model.decode_step)
    for i in range(10):
        lg, cache = step(params, cache, toks[:, i:i + 1])
        outs.append(lg[:, 0])
    stepped = jnp.stack(outs, 1)
    # tolerance: bf16 eps at logit magnitudes ~10 is ~0.08; the append-
    # attention decode (write-only cache, §Perf cell 3) adds one extra bf16
    # rounding where the old-cache and new-token outputs combine.
    np.testing.assert_allclose(np.asarray(full, np.float32),
                               np.asarray(stepped, np.float32), atol=8e-2, rtol=8e-2)


@pytest.mark.parametrize("arch", ["qwen3-14b", "recurrentgemma-2b", "mamba2-1.3b"])
def test_prefill_matches_forward(arch):
    cfg = configs.get(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    toks = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab, (2, 10)), jnp.int32)
    full = model.apply(params, {"tokens": toks})
    pre, cache = jax.jit(model.prefill)(params, toks)
    np.testing.assert_allclose(np.asarray(full, np.float32),
                               np.asarray(pre, np.float32), atol=5e-2, rtol=5e-2)


def test_param_counts_match_known_sizes():
    """Config fidelity: derived parameter counts land on the published sizes."""
    expect = {
        "qwen3-14b": (14.8e9, 0.08), "qwen1.5-110b": (111e9, 0.05),
        "starcoder2-3b": (3.0e9, 0.15), "mamba2-1.3b": (1.3e9, 0.2),
        "qwen3-moe-235b-a22b": (235e9, 0.05), "minicpm-2b": (2.4e9, 0.2),
        "recurrentgemma-2b": (2.7e9, 0.15), "whisper-base": (74e6, 0.25),
        "internvl2-2b": (1.8e9, 0.25),
    }
    for arch, (target, tol) in expect.items():
        n = configs.get(arch).param_count()
        assert abs(n - target) / target < tol, f"{arch}: {n/1e9:.2f}B vs {target/1e9:.2f}B"
    active = configs.get("qwen3-moe-235b-a22b").param_count(active_only=True)
    assert abs(active - 22e9) / 22e9 < 0.1  # the A22B in the name


def test_vocab_padding_masked():
    cfg = configs.get("whisper-base").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _smoke_batch(cfg)
    logits = model.apply(params, batch)
    assert cfg.padded_vocab % 256 == 0
    if cfg.padded_vocab > cfg.vocab:
        pad = logits[..., cfg.vocab:]
        assert float(pad.max()) < -1e29, "padded vocab columns must be masked"
