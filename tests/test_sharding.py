"""Sharding-rule unit tests (no multi-device needed: rules are pure functions
of abstract shapes + mesh; a 1x1 mesh exercises the jit path)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import configs
from repro.dist import sharding as shd
from repro.models import build_model


def fake_mesh_16x16():
    """AbstractMesh stands in for the 256-chip mesh: rule resolution only
    needs axis names/sizes, never real devices."""
    from jax.sharding import AbstractMesh

    return AbstractMesh((16, 16), ("data", "model"))


def fake_mesh_multipod():
    from jax.sharding import AbstractMesh

    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _abstract_params(arch):
    cfg = configs.get(arch)
    model = build_model(cfg)
    return jax.eval_shape(model.init, jax.random.key(0)), cfg


@pytest.mark.parametrize("arch", configs.list_archs())
def test_param_rules_cover_all_weights(arch):
    """Every >=2-dim weight leaf gets at least one sharded dim (16 GB HBM has
    no room for replicated matrices at 110B/235B scale)."""
    p_abs, cfg = _abstract_params(arch)
    mesh = fake_mesh_16x16()
    sh = shd.param_shardings(p_abs, mesh)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    shapes = {tuple(k for k in path): leaf
              for path, leaf in jax.tree_util.tree_flatten_with_path(p_abs)[0]}
    replicated_big = []
    for path, s in flat:
        leaf = shapes[tuple(k for k in path)]
        if leaf.ndim >= 2 and np.prod(leaf.shape) > 1_000_000:
            if all(ax is None for ax in s.spec):
                replicated_big.append("/".join(str(getattr(k, "key", k)) for k in path))
    assert not replicated_big, f"{arch}: big replicated weights: {replicated_big}"


def test_divisibility_guard_degrades_to_replication():
    mesh = fake_mesh_16x16()
    # kv-head dim 8 does not divide 16 -> cache rule falls back to time dim
    cache = {"k": jax.ShapeDtypeStruct((4, 16, 4096, 8, 128), jnp.bfloat16)}
    sh = shd.cache_shardings(cache, mesh)
    spec = sh["k"].spec
    assert spec[2] == "model" and spec[3] is None  # time sharded, heads not
    # kv=16 divides -> heads sharded
    cache = {"k": jax.ShapeDtypeStruct((4, 16, 4096, 16, 128), jnp.bfloat16)}
    spec = shd.cache_shardings(cache, mesh)["k"].spec
    assert spec[3] == "model"


def test_multipod_dp_axes():
    mesh = fake_mesh_multipod()
    rules = shd.MeshRules.for_mesh(mesh)
    assert rules.dp == ("pod", "data")
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    spec = shd.batch_shardings(batch, mesh)["tokens"].spec
    assert spec[0] == ("pod", "data")


def test_head_weight_not_contraction_sharded():
    """Regression: sharding the head's contraction dim all-reduces the full
    logits tensor (the 40 GB/device whisper incident)."""
    p_abs, _ = _abstract_params("qwen3-14b")
    sh = shd.param_shardings(p_abs, fake_mesh_16x16())
    spec = sh["embedding"]["head"].spec
    assert spec[0] is None and spec[1] == "model"


def test_constrain_identity_without_policy():
    x = jnp.ones((4, 8))
    assert shd.constrain(x, "activation") is x


def test_constrain_applies_with_policy_on_single_device():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    policy = shd.ShardingPolicy.default(mesh)

    def f(x):
        with shd.activation_sharding(policy):
            return shd.constrain(x, "activation") * 2

    out = jax.jit(f)(jnp.ones((2, 4, 8)))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((2, 4, 8)))


def test_attn_mode_specs():
    mesh = fake_mesh_16x16()
    head = shd.ShardingPolicy.default(mesh, attn_mode="head")
    seq = shd.ShardingPolicy.default(mesh, attn_mode="seq")
    assert head.specs["q_heads"][2] == "model"
    assert seq.specs["q_heads"][1] == "model"
    assert seq.specs["kv_heads"] == P(("data",), None, None, None)


def test_repo_meshes_have_auto_axes():
    """The sharding layer steers GSPMD with sharding constraints, which
    ``Explicit`` mesh axes (``jax.make_mesh``'s default) refuse."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    assert mesh.axis_names == ("data", "model")
