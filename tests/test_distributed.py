"""Distribution tests: sharding rules, sharded train step, elastic reshard."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config, reduced
from repro.distributed.elastic import reshard_tree
from repro.distributed.sharding import (cache_specs, data_specs,
                                        param_specs, simple_batch_spec)
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import (abstract_params, input_specs,
                                make_train_step)
from repro.models import init_params
from repro.train.optimizer import AdamW


def _mesh11():
    return make_local_mesh(1, 1)


def test_param_specs_divisibility():
    cfg = get_config("grok-1-314b")
    params_abs = abstract_params(cfg)
    mesh = _mesh11()

    # on a 1x1 mesh every dim divides: specs exist for all leaves
    specs = param_specs(params_abs, mesh)
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert leaves and all(isinstance(s, P) for s in leaves)


def test_grok_experts_not_sharded_on_16():
    """grok has 8 experts: EP on a 16-wide model axis must NOT apply."""
    import os
    cfg = get_config("grok-1-314b")
    params_abs = abstract_params(cfg)
    devs = np.array(jax.devices() * 256)[:256].reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    specs = param_specs(params_abs, mesh)
    moe_spec = specs["blocks"]["p0"]["moe"].w_gate  # (np, E, d, f)
    # expert dim (8) cannot take the 16-wide axis; d_ff (32768) can
    assert moe_spec[1] != "model"
    assert "model" in tuple(moe_spec)


def test_arctic_experts_ep_sharded():
    cfg = get_config("arctic-480b")
    params_abs = abstract_params(cfg)
    devs = np.array(jax.devices() * 256)[:256].reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    specs = param_specs(params_abs, mesh)
    moe_spec = specs["blocks"]["p0"]["moe"].w_gate
    assert moe_spec[1] == "model"      # 128 experts over 16 => EP


def test_batch_spec_divisibility():
    devs = np.array(jax.devices() * 512)[:512].reshape(2, 16, 16)
    mesh = Mesh(devs, ("pod", "data", "model"))
    assert simple_batch_spec(mesh, 256) == P(("pod", "data"))
    assert simple_batch_spec(mesh, 2) == P(("pod",))
    assert simple_batch_spec(mesh, 1) == P()


def test_cache_specs_structure():
    cfg = get_config("jamba-v0.1-52b")
    devs = np.array(jax.devices() * 256)[:256].reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    specs = cache_specs(cfg, mesh, 128)
    for pi, spec in enumerate(cfg.block_pattern):
        entry = specs[f"p{pi}"]
        if spec.mixer == "attn":
            assert isinstance(entry, tuple) and len(entry) == 2
        else:
            assert isinstance(entry, P)


def test_sharded_train_step_runs():
    cfg = reduced(get_config("deepseek-7b"), n_layers=2, d_model=64,
                  d_ff=128, vocab=256)
    mesh = _mesh11()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    opt = AdamW(lr=1e-3)
    opt_state = opt.init(params)
    pspecs = param_specs(params, mesh)
    sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs,
                                is_leaf=lambda x: isinstance(x, P))
    params = jax.tree_util.tree_map(jax.device_put, params, sh)
    step = jax.jit(make_train_step(cfg, opt))
    batch = {"inputs": jnp.zeros((2, 32), jnp.int32),
             "labels": jnp.zeros((2, 32), jnp.int32)}
    with jax.set_mesh(mesh):
        p2, o2, loss = step(params, opt_state, batch)
    assert np.isfinite(float(loss))


def test_embed_gather_under_explicit_mesh():
    """With explicit mesh axes the vocab-sharded embedding gather needs
    an output sharding; the rows follow the tokens' batch sharding."""
    from jax.sharding import AxisType
    from repro.models.transformer import _embed

    cfg = reduced(get_config("deepseek-7b"), n_layers=2, d_model=64,
                  d_ff=128, vocab=256)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Explicit,) * 2)
    tokens = jnp.arange(64, dtype=jnp.int32).reshape(2, 32)
    with jax.set_mesh(mesh):
        table = jax.device_put(params["embed"],
                               NamedSharding(mesh, P("model", None)))
        toks = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
        rows = jax.jit(lambda t, i: _embed(cfg, {"embed": t}, i))(table,
                                                                  toks)
    assert jax.typeof(rows).sharding.spec == P("data", None, None)
    np.testing.assert_array_equal(np.asarray(rows),
                                  np.asarray(params["embed"])[tokens])


def test_elastic_reshard_roundtrip(tmp_path):
    """Checkpoint on one mesh, restore re-placed on another."""
    from repro.distributed.elastic import resume_on_mesh
    from repro.train import checkpoint as ck

    cfg = reduced(get_config("deepseek-7b"), n_layers=2, d_model=64,
                  d_ff=128, vocab=256)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ck.save(str(tmp_path), 7, params)
    mesh2 = _mesh11()                      # the "new" mesh after failure
    restored, step = resume_on_mesh(str(tmp_path), params, mesh2)
    assert step == 7
    a = jax.tree_util.tree_leaves(params)[0]
    b = jax.tree_util.tree_leaves(restored)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_input_specs_all_cells():
    """input_specs must produce pure ShapeDtypeStructs for every cell."""
    from repro.configs import SHAPES, all_configs

    for arch, cfg in all_configs().items():
        for sname in cfg.shapes:
            spec = input_specs(cfg, SHAPES[sname])
            for leaf in jax.tree_util.tree_leaves(spec):
                assert isinstance(leaf, jax.ShapeDtypeStruct), (arch, sname)
