"""repro.dist unit tests: rule resolution, spec validation, context binding.

Single-device (CPU) by design — multi-device behaviour is covered by
test_distribution.py's subprocess cases.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist.api import (axis_rules, constrain, current_rules,
                            logical_to_spec, validate_spec)
from repro.launch.mesh import make_mesh
from repro.dist.sharding import (DEFAULT_RULES, batch_spec, cache_shardings,
                                 make_rules, param_shardings)


def test_logical_to_spec_resolution():
    rules = dict(DEFAULT_RULES)
    # tuple rules stay tuples, string rules stay strings, None stays None
    assert logical_to_spec(("batch", "seq", "ff"), rules) \
        == P(("pod", "data"), None, "model")
    # logical names without a rule resolve to replicated, not an error
    assert logical_to_spec(("no_such_axis", "vocab"), rules) \
        == P(None, "model")
    assert logical_to_spec((None, None), rules) == P(None, None)


def test_validate_spec_unknown_mesh_axis_drops():
    mesh = make_mesh((1,), ("data",))
    assert validate_spec(P("model"), (8,), mesh) in (P(), P(None))
    # unknown axis inside a tuple truncates the kept prefix
    assert validate_spec(P(("data", "model")), (8,), mesh) == P(("data",))


def test_validate_spec_duplicate_axis_drops_second_use():
    mesh = make_mesh((1,), ("data",))
    spec = validate_spec(P("data", "data"), (4, 4), mesh)
    assert spec in (P("data"), P("data", None))
    spec = validate_spec(P(("data",), ("data",)), (4, 4), mesh)
    assert spec in (P(("data",)), P(("data",), None))


def test_validate_spec_truncates_to_rank():
    mesh = make_mesh((1,), ("data",))
    assert validate_spec(P("data", None, None), (4,), mesh) == P("data")


def test_constrain_noop_outside_context():
    assert current_rules() is None
    x = jnp.ones((4, 8))
    y = constrain(x, ("batch", "seq"))
    assert y is x  # literally untouched, not a copy


def test_axis_rules_binds_and_nests():
    mesh = make_mesh((1,), ("data",))
    outer = make_rules(mesh)
    inner = dict(outer, batch=None)
    with axis_rules(mesh, outer):
        got_mesh, got_rules = current_rules()
        assert got_mesh is mesh and got_rules["batch"] == ("data",)
        with axis_rules(mesh, inner):
            assert current_rules()[1]["batch"] is None
        assert current_rules()[1]["batch"] == ("data",)
    assert current_rules() is None


def test_constrain_inside_context_and_jit():
    mesh = make_mesh((1,), ("data",))
    rules = make_rules(mesh)

    def fn(x):
        with axis_rules(mesh, rules):
            return constrain(x, ("batch", None)) * 2.0

    x = jnp.ones((4, 8))
    np.testing.assert_allclose(np.asarray(jax.jit(fn)(x)),
                               np.asarray(x) * 2.0)


def test_make_rules_filters_to_mesh_and_knobs():
    mesh = make_mesh((1,), ("data",))
    r = make_rules(mesh)
    assert r["heads"] is None and r["batch"] == ("data",)
    assert r["act_seq"] is None and r["kv_seq"] is None and r["embed"] is None
    r = make_rules(mesh, fsdp=True, seq_activations=True, long_context=True)
    assert r["embed"] == ("data",)
    assert r["act_seq"] is None        # no 'model' axis on this mesh
    assert r["kv_seq"] is None
    mesh2 = make_mesh((1, 1), ("data", "model"))
    r2 = make_rules(mesh2, seq_activations=True, long_context=True)
    assert r2["act_seq"] == "model" and r2["kv_seq"] == "model"


def test_batch_spec_shards_leading_dim():
    mesh = make_mesh((1,), ("data",))
    shard = batch_spec(mesh, make_rules(mesh))
    sh = shard(jax.ShapeDtypeStruct((4, 16), jnp.int32))
    assert sh.spec in (P(("data",)), P(("data",), None))
    # scalars replicate
    assert shard(jax.ShapeDtypeStruct((), jnp.int32)).spec == P()


def test_param_and_cache_shardings_cover_every_arch():
    from repro.configs import ARCHS, get_config
    from repro.models import init_cache, init_params
    mesh = make_mesh((1,), ("data",))
    rules = make_rules(mesh, fsdp=True)
    for arch in sorted(ARCHS.keys()):
        cfg = get_config(arch, smoke=True)
        p_spec = jax.eval_shape(
            lambda c=cfg: init_params(c, jax.random.PRNGKey(0)))
        ps = param_shardings(cfg, p_spec, mesh, rules)
        assert len(jax.tree.leaves(ps)) == len(jax.tree.leaves(p_spec)), arch
        c_spec = jax.eval_shape(lambda c=cfg: init_cache(c, 2, 32))
        cs = cache_shardings(cfg, c_spec, mesh, rules)
        assert len(jax.tree.leaves(cs)) == len(jax.tree.leaves(c_spec)), arch


def test_param_and_cache_shardings_bind_expected_axes():
    """Concrete spec values on a (data, model) mesh with FSDP: the tables
    must actually shard, not silently fall through to replication."""
    from repro.configs import get_config
    from repro.models import init_cache, init_params
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = make_rules(mesh, fsdp=True)
    cfg = get_config("gemma-2b", smoke=True)
    p_spec = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    ps = param_shardings(cfg, p_spec, mesh, rules)
    # embed (V, D): vocab over model, d_model over the FSDP data axes
    assert ps["embed"].spec == P("model", ("data",))
    # stacked MLP w_gate (L, D, F): layer dim replicated, D fsdp, F model
    assert ps["stack"]["layers"]["mlp"]["w_gate"].spec \
        == P(None, ("data",), "model")
    assert ps["stack"]["layers"]["attn"]["wo"].spec \
        == P(None, "model", ("data",))
    # norm scales fall through to replication
    assert ps["ln_f"].spec == P()
    # stacked KV cache (L, B, S, n_kv, hd): batch over data, heads over model
    c_spec = jax.eval_shape(lambda: init_cache(cfg, 2, 32))
    cs = cache_shardings(cfg, c_spec, mesh, rules)
    assert cs.k.spec == P(None, ("data",), None, "model", None)
    assert cs.pos.spec == P(None)  # stacked (L,) scalar-per-layer counter
    # MoE expert tensors carry the leading 'expert' -> model dim
    moe_cfg = get_config("olmoe-1b-7b", smoke=True)
    mp_spec = jax.eval_shape(
        lambda: init_params(moe_cfg, jax.random.PRNGKey(0)))
    mps = param_shardings(moe_cfg, mp_spec, mesh, rules)
    assert mps["stack"]["layers"]["moe"]["w_down"].spec \
        == P(None, "model", None, ("data",))
