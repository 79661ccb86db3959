"""repro.dist.sharding unit coverage: rule resolution, the context stack,
no-op behavior outside a mesh, param_shardings on a small pytree, and the
fault-free helpers (barrier, unroll switch). Single-device, fast."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.sharding import (
    LM_RULES, SP_RULES, axis_rules, barrier, current_mesh, current_rules,
    enforce_divisible, logical_spec, make_mesh, param_shardings, param_spec,
    shard, unroll_active, unroll_loops)


# ---------------------------------------------------------------------------
# axis-rule resolution
# ---------------------------------------------------------------------------

def test_rules_resolve_known_logical_axes():
    mesh = make_mesh((1, 1), ("data", "model"))
    with axis_rules(LM_RULES, mesh):
        assert logical_spec(("batch", "seq", "mlp")) == P("data", None, "model")
        assert logical_spec(("tokens", "embed")) == P("data", None)
        # subjects are subject-wide: every mesh axis
        assert logical_spec(("subjects", None)) == P(("data", "model"), None)
        # unknown logical names replicate
        assert logical_spec(("no_such_axis",)) == P(None)
        # explicit None entries replicate
        assert logical_spec((None, "heads")) == P(None, "model")


def test_rules_drop_axes_missing_from_mesh():
    mesh = make_mesh((1,), ("data",))
    with axis_rules(LM_RULES, mesh):
        # "pod" and "model" don't exist on a 1-axis mesh
        assert logical_spec(("batch", "heads")) == P("data", None)


def test_sp_rules_shard_residual_seq():
    mesh = make_mesh((1, 1), ("data", "model"))
    with axis_rules(SP_RULES, mesh):
        assert logical_spec(("batch", "seq_res", "embed")) == P(
            "data", "model", None)
    with axis_rules(LM_RULES, mesh):
        assert logical_spec(("batch", "seq_res", "embed")) == P(
            "data", None, None)


def test_context_stack_nests_and_restores():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert current_rules() is None and current_mesh() is None
    with axis_rules(LM_RULES, mesh):
        assert current_rules() is LM_RULES and current_mesh() is mesh
        with axis_rules(SP_RULES, None):
            assert current_rules() is SP_RULES and current_mesh() is None
        assert current_rules() is LM_RULES and current_mesh() is mesh
    assert current_rules() is None and current_mesh() is None


# ---------------------------------------------------------------------------
# shard: no-op outside a mesh, constraint inside
# ---------------------------------------------------------------------------

def test_shard_is_noop_outside_mesh():
    x = jnp.arange(12.0).reshape(3, 4)
    assert shard(x, ("batch", "embed")) is x            # no context at all
    with axis_rules(LM_RULES, None):                    # rules but no mesh
        assert shard(x, ("batch", "embed")) is x


def test_shard_applies_constraint_under_mesh():
    mesh = make_mesh((1, 1), ("data", "model"))
    x = jnp.arange(8.0).reshape(2, 4)
    with axis_rules(LM_RULES, mesh):
        y = shard(x, ("batch", "mlp"))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert y.sharding.is_equivalent_to(
        NamedSharding(mesh, P("data", "model")), 2)


class _MeshShape:
    """Stand-in with the two attributes divisibility checks read (the test
    process owns a single real device, so no true multi-device mesh)."""

    def __init__(self, shape, names):
        self.devices = np.zeros(shape)
        self.axis_names = names


def test_enforce_divisible_keeps_exact_and_drops_uneven():
    mesh = _MeshShape((2,), ("data",))
    assert enforce_divisible(P("data"), (8,), mesh) == P("data")
    assert enforce_divisible(P("data"), (7,), mesh) == P(None)
    # short specs are padded with None up to the array rank
    assert enforce_divisible(P("data"), (8, 3), mesh) == P("data", None)
    # multi-axis entries drop only when the combined size doesn't divide
    mesh2 = _MeshShape((2, 2), ("data", "model"))
    assert enforce_divisible(P(("data", "model")), (8,), mesh2) == P(
        ("data", "model"))
    assert enforce_divisible(P(("data", "model")), (6,), mesh2) == P(None)


# ---------------------------------------------------------------------------
# param_shardings on a small pytree
# ---------------------------------------------------------------------------

def test_param_shardings_small_pytree():
    mesh = make_mesh((1, 1), ("data", "model"))
    tree = {
        "embed": {"tokens": jax.ShapeDtypeStruct((8, 4), jnp.float32)},
        "layers": {"groups": {"p0_attn_mlp": {
            "attn": {"wq": jax.ShapeDtypeStruct((3, 4, 4), jnp.float32)},
            "ln1_scale": jax.ShapeDtypeStruct((3, 4), jnp.float32),
            "mlp": {"w_down": jax.ShapeDtypeStruct((3, 4, 4), jnp.float32)},
        }}},
        "final_norm_scale": jax.ShapeDtypeStruct((4,), jnp.float32),
    }
    sh = param_shardings(tree, mesh)
    assert sh["embed"]["tokens"].spec == P("model", "data")
    grp = sh["layers"]["groups"]["p0_attn_mlp"]
    # stacked leading layer dim never sharded
    assert grp["attn"]["wq"].spec == P(None, "data", "model")
    assert grp["mlp"]["w_down"].spec == P(None, "model", "data")
    assert grp["ln1_scale"].spec == P()
    assert sh["final_norm_scale"].spec == P()
    assert all(isinstance(s, NamedSharding)
               for s in jax.tree_util.tree_leaves(
                   sh, is_leaf=lambda x: isinstance(x, NamedSharding)))


def test_param_spec_respects_divisibility():
    mesh = _MeshShape((2, 2), ("data", "model"))
    # 7 not divisible by data=2 -> replicated; 6 divisible by model=2 -> kept
    assert enforce_divisible(param_spec("attn/wq", 2), (7, 6), mesh) == P(
        None, "model")


def test_param_spec_optimizer_state_matches_params():
    for prefix in ("", "m/", "v/", "1/"):
        assert param_spec(prefix + "layers/rem/0/attn/wo", 2) == P(
            "model", "data")
    assert param_spec("experts/w_gate", 3) == P("model", None, None)
    assert param_spec("m/experts/w_gate", 4, stacked=True) == P(
        None, "model", None, None)


# ---------------------------------------------------------------------------
# barrier + unroll switch
# ---------------------------------------------------------------------------

def test_barrier_identity_and_differentiable():
    x = jnp.arange(4.0)
    np.testing.assert_array_equal(np.asarray(barrier(x)), np.asarray(x))
    g = jax.grad(lambda a: (barrier(a) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g), 2.0 * np.asarray(x))


def test_unroll_loops_nesting():
    assert not unroll_active()
    with unroll_loops():
        assert unroll_active()
        with unroll_loops():
            assert unroll_active()
        assert unroll_active()
    assert not unroll_active()
