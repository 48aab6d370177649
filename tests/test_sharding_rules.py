"""Sharding-rule unit tests (no multi-device requirement: specs only)."""

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.distributed import sharding
from repro.models.transformer import Model


def _fake_mesh(data=16, model=16, pod=None):
    """AbstractMesh stands in for the production mesh (no devices needed)."""
    from jax.sharding import AbstractMesh
    if pod:
        sizes, names = (pod, data, model), ("pod", "data", "model")
    else:
        sizes, names = (data, model), ("data", "model")
    return AbstractMesh(sizes, names)


def _specs_for(arch, layout="tp", mesh=None):
    cfg = registry.get_config(arch, smoke=False)
    mesh = mesh or _fake_mesh()
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return cfg, params, sharding.param_specs(cfg, mesh, params, layout)


def _flat(params, specs):
    out = {}
    for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]):
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out[key] = (leaf, spec)
    return out


def test_every_sharded_dim_divides(monkeypatch):
    mesh = _fake_mesh()
    for arch in registry.list_archs():
        cfg, params, specs = _specs_for(arch, mesh=mesh)
        for key, (leaf, spec) in _flat(params, specs).items():
            for d, ax in enumerate(spec):
                if ax is None:
                    continue
                size = 1
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    size *= dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
                assert leaf.shape[d] % size == 0, (arch, key, spec, leaf.shape)


def test_tp_layout_uses_model_axis():
    _, params, specs = _specs_for("yi-6b", layout="tp")
    flat = _flat(params, specs)
    mlp_spec = flat["stack/b0/mlp/wi_up/w"][1]
    assert "model" in jax.tree_util.tree_leaves(
        [a for a in mlp_spec if a is not None]) or "model" in str(mlp_spec)


def test_fsdp_layout_has_no_model_tp():
    """fsdp layout: weights sharded over all axes but never TP on 'model' alone."""
    _, params, specs = _specs_for("yi-6b", layout="fsdp")
    for key, (leaf, spec) in _flat(params, specs).items():
        for ax in spec:
            if ax == "model":
                raise AssertionError(f"{key} still TP-sharded: {spec}")


def test_fsdp_layout_shards_big_weights():
    _, params, specs = _specs_for("yi-6b", layout="fsdp")
    flat = _flat(params, specs)
    leaf, spec = flat["embed/table"]
    assert any(a is not None for a in spec), spec


def test_moe_experts_on_model_axis():
    _, params, specs = _specs_for("deepseek-moe-16b")
    flat = _flat(params, specs)
    leaf, spec = flat["stack/b0/mlp/experts/wi_up"]
    assert spec[1] == "model"       # leading periods axis, then experts


def test_whisper_vocab_not_sharded():
    """51865 is not divisible by 16: vocab sharding must be dropped."""
    _, params, specs = _specs_for("whisper-medium")
    flat = _flat(params, specs)
    leaf, spec = flat["embed/table"]
    assert spec[0] is None
    assert leaf.shape[0] == 51865


def test_logical_rules_head_fallback():
    mesh = _fake_mesh()
    r_ok = sharding.logical_rules(registry.get_config("yi-6b"), mesh)
    assert r_ok["heads"] == "model" and r_ok["aseq"] is None
    r_fb = sharding.logical_rules(registry.get_config("minitron-4b"), mesh)
    assert r_fb["heads"] is None and r_fb["aseq"] == "model"  # context-parallel


def test_cache_specs_decode():
    cfg = registry.get_config("yi-6b")
    mesh = _fake_mesh()
    model = Model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(128, 1024))
    specs = sharding.cache_specs(cfg, mesh, cache, batch_size=128)
    flat = _flat(cache, specs)
    leaf, spec = flat["stack/b0/kv/k"]
    assert spec[1] == "data"        # batch on data (after stacked periods axis)
    # kv=4 not divisible by 16 -> head_dim sharded
    assert spec[4] == "model"
