"""Sharding rules + elastic remesh tests (divisibility over all archs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_configs, smoke_variant
from repro.distributed.sharding import base_rules, spec_for, use_rules
from repro.launch import shardings as sh
from repro.launch import specs as specs_mod

MESH_SINGLE = AbstractMesh((16, 16), ("data", "model"))
MESH_MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


@pytest.mark.parametrize("arch", list_configs())
@pytest.mark.parametrize("mesh", [MESH_SINGLE, MESH_MULTI],
                         ids=["16x16", "2x16x16"])
def test_param_shardings_divide(arch, mesh):
    """Every param leaf's spec must evenly divide its dims (pjit contract)."""
    cfg = get_config(arch)
    rules = sh.build_rules(cfg, mesh)
    params = specs_mod.params_shape(cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for pathkeys, leaf in flat:
        path = "/".join(str(getattr(p, "key", "")) for p in pathkeys)
        spec = sh.param_spec(path, leaf.ndim, cfg, rules)
        spec = sh._sanitize(spec, leaf.shape, mesh)
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert leaf.shape[i] % size == 0, (arch, path, leaf.shape, spec)


@pytest.mark.parametrize("arch", ["yi-34b", "kimi-k2-1t-a32b", "grok-1-314b"])
def test_big_arch_params_fit_hbm(arch):
    """Sharded param bytes per chip must fit v5e HBM (16 GiB) with headroom
    for activations; checked analytically from specs."""
    cfg = get_config(arch)
    mesh = MESH_SINGLE
    rules = sh.build_rules(cfg, mesh)
    params = specs_mod.params_shape(cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    per_device = 0
    for pathkeys, leaf in flat:
        path = "/".join(str(getattr(p, "key", "")) for p in pathkeys)
        spec = sh._sanitize(sh.param_spec(path, leaf.ndim, cfg, rules),
                            leaf.shape, mesh)
        shards = 1
        for ax in spec:
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            shards *= int(np.prod([mesh.shape[a] for a in axes]))
        per_device += int(np.prod(leaf.shape)) * leaf.dtype.itemsize // shards
    assert per_device < 16 * 2**30, f"{arch}: {per_device/2**30:.1f} GiB"


def test_moe_expert_sharding_strategy():
    mesh = MESH_SINGLE
    kimi = sh.build_rules(get_config("kimi-k2-1t-a32b"), mesh)
    grok = sh.build_rules(get_config("grok-1-314b"), mesh)
    assert kimi["experts"] == ("model",)      # 384 experts -> EP
    assert kimi["moe_ffn"] is None
    assert grok["experts"] is None            # 8 experts -> shard ffn instead
    assert grok["moe_ffn"] == ("model",)


def test_spec_for_and_rules():
    rules = base_rules(multi_pod=True, fsdp=True)
    assert spec_for("batch", None, "heads", rules=rules) == \
        P(("pod", "data"), None, "model")
    assert spec_for("batch", rules=base_rules()) == P("data")


def test_shard_noop_outside_mesh():
    from repro.distributed.sharding import shard
    x = jnp.ones((4, 4))
    np.testing.assert_array_equal(np.asarray(shard(x, "batch", None)),
                                  np.asarray(x))


def test_elastic_remesh_single_device(tmp_path):
    """Save params, restore them onto a different (1x1) mesh sharding."""
    from repro.checkpoint.ckpt import save_checkpoint
    from repro.distributed.elastic import elastic_remesh
    cfg = smoke_variant("smollm-360m")
    from repro.models import lm as lm_mod
    params = lm_mod.init_lm(jax.random.PRNGKey(0), cfg)
    save_checkpoint(tmp_path, 42, {"params": params})
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    p_shape = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                           params)
    step, restored, _ = elastic_remesh(tmp_path, cfg, mesh, p_shape)
    assert step == 42
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cell_status_skips():
    cfg = get_config("hubert-xlarge")
    assert specs_mod.cell_status("hubert-xlarge", "decode_32k", cfg)
    assert specs_mod.cell_status("hubert-xlarge", "train_4k", cfg) is None
    yi = get_config("yi-34b")
    assert specs_mod.cell_status("yi-34b", "long_500k", yi)
    mam = get_config("mamba2-1.3b")
    assert specs_mod.cell_status("mamba2-1.3b", "long_500k", mam) is None
