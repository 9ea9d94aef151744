"""Unit tests for repro.dist.sharding (single process, no subprocess).

The 8-device integration counterpart lives in test_distributed.py; this
file covers the pure resolution logic: fit_pspec's divisibility fallback,
the per-mode rule tables, init determinism, and shard_act's no-op contract
outside a sharding_ctx.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (
    MODES,
    ParamSpec,
    ShardingRules,
    abstract_params,
    current_ctx,
    fit_pspec,
    init_params,
    init_params_sharded,
    logical_to_pspec,
    rules_for_mode,
    shard_act,
    sharding_ctx,
    specs_to_shardings,
)

KEY = jax.random.PRNGKey(0)


class FakeMesh:
    """Duck-typed stand-in: axis_names + devices.shape, no real devices."""

    def __init__(self, shape=(4, 8), axes=("data", "model")):
        self.axis_names = axes
        self.devices = type("D", (), {"shape": shape})()


# ---------------------------------------------------------------------------
# fit_pspec
# ---------------------------------------------------------------------------


def test_fit_pspec_drops_indivisible_dims():
    m = FakeMesh((4, 8))
    assert fit_pspec((3, 16), P("data", "model"), m) == P(None, "model")
    assert fit_pspec((12, 24), P("data", "model"), m) == P("data", "model")
    # nothing fits -> fully replicated
    assert fit_pspec((3, 5), P("data", "model"), m) == P(None, None)


def test_fit_pspec_composite_keeps_divisible_prefix():
    m = FakeMesh((4, 8))
    assert fit_pspec((8,), P(("data", "model"),), m) == P(("data",))
    assert fit_pspec((32,), P(("data", "model"),), m) == P(("data", "model"))
    assert fit_pspec((2,), P(("data", "model"),), m) == P(None)


def test_fit_pspec_deduplicates_first_dim_wins():
    m = FakeMesh((4, 8))
    assert fit_pspec((32, 32), P("model", "model"), m) == P("model", None)
    # the seq-parallel case: seq takes model, act_heads loses it
    assert fit_pspec((16, 8), P("model", "model"), m) == P("model", None)


def test_fit_pspec_ignores_axes_missing_from_mesh():
    m = FakeMesh((4, 8))
    assert fit_pspec((16, 16), P("pod", "model"), m) == P(None, "model")


def test_fit_pspec_pads_short_pspec_with_replication():
    m = FakeMesh((4, 8))
    assert fit_pspec((4, 8, 16), P("data"), m) == P("data", None, None)
    assert fit_pspec((), P(), m) == P()


# ---------------------------------------------------------------------------
# rule tables
# ---------------------------------------------------------------------------


def test_rules_for_mode_megatron_table():
    r = rules_for_mode("megatron")
    assert isinstance(r, ShardingRules) and r.mode == "megatron"
    assert r["col_out"] == "model"
    assert r["row_in"] == "model"
    assert r["vocab"] == "model"
    assert r["act_heads"] == "model"
    assert r["batch"] == ("pod", "data")
    assert r["fsdp"] == ("pod", "data")
    assert r["seq"] is None          # no sequence parallelism
    assert r["act_embed"] is None    # activations replicated on model
    assert r["layers"] is None       # scan dim never sharded
    assert r["experts"] == "model"
    assert r["expert_cap"] == "data"


def test_rules_for_mode_cascade_table():
    r = rules_for_mode("cascade")
    # contraction dim on model = the west->east cascade psum
    assert r["cascade_in"] == "model"
    # output features FSDP across (pod, data)
    assert r["cascade_out"] == ("pod", "data")
    # activations keep their feature dim on model to match cascade_in
    assert r["act_embed"] == "model"
    assert r["batch"] == ("pod", "data")


def test_rules_for_mode_megatron_sp_and_unknown():
    r = rules_for_mode("megatron_sp")
    assert r["seq"] == "model"       # the only delta vs megatron
    assert r["col_out"] == "model"
    with pytest.raises(ValueError):
        rules_for_mode("zigzag")
    assert set(MODES) == {"cascade", "megatron", "megatron_sp"}


def test_logical_to_pspec_resolves_through_rules():
    r = rules_for_mode("megatron")
    m2 = FakeMesh((4, 8))
    assert logical_to_pspec(("batch", "seq", "act_heads"), m2, r) == \
        P(("data",), None, "model")
    m3 = FakeMesh((2, 4, 8), ("pod", "data", "model"))
    assert logical_to_pspec(("batch", None, "vocab"), m3, r) == \
        P(("pod", "data"), None, "model")
    # unknown logical names replicate rather than raise
    assert logical_to_pspec(("no_such_axis",), m2, r) == P(None)


# ---------------------------------------------------------------------------
# ParamSpec / init_params / abstract_params
# ---------------------------------------------------------------------------


def test_param_spec_defaults_and_rank_check():
    s = ParamSpec((16, 8), ("row_in", "fsdp"))
    assert s.dtype == jnp.bfloat16 and s.init == "normal" and s.scale is None
    with pytest.raises(ValueError):
        ParamSpec((16, 8), ("row_in",))


SPECS = {
    "w": ParamSpec((8, 4), ("row_in", "fsdp")),
    "b": ParamSpec((4,), (None,), jnp.float32, init="zeros"),
    "g": ParamSpec((4,), (None,), jnp.float32, init="ones"),
    "emb": ParamSpec((16, 8), ("vocab", "embed"), jnp.float32, init="embed"),
}


def test_init_params_deterministic_per_key():
    a = init_params(jax.random.PRNGKey(7), SPECS)
    b = init_params(jax.random.PRNGKey(7), SPECS)
    c = init_params(jax.random.PRNGKey(8), SPECS)
    for k in SPECS:
        np.testing.assert_array_equal(np.asarray(a[k], np.float32),
                                      np.asarray(b[k], np.float32))
    assert not np.array_equal(np.asarray(a["w"], np.float32),
                              np.asarray(c["w"], np.float32))


def test_init_params_splits_rng_per_leaf():
    p = init_params(KEY, SPECS)
    assert p["w"].shape == (8, 4) and p["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(p["b"]), 0.0)
    np.testing.assert_array_equal(np.asarray(p["g"]), 1.0)
    # distinct leaves get distinct keys
    assert not np.array_equal(np.asarray(p["emb"][:8], np.float32),
                              np.asarray(p["w"], np.float32))


@pytest.mark.parametrize("arch", ["yi_6b", "zamba2_2_7b", "rwkv6_7b",
                                  "phi3_5_moe_42b"])
def test_sharded_init_is_bit_identical_to_eager(arch):
    """The plan's one-program init (jit + out_shardings) reproduces the
    eager ``init_params`` bit for bit and lands in the requested
    shardings. zamba2 and rwkv6 hold leaves where an unguarded jit folds
    the stddev into the normal's sqrt(2) and rounds one bf16 ulp off."""
    from repro.configs import reduced_config
    from repro.models import build_model

    model = build_model(reduced_config(arch))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    for specs in (model.param_specs(), model.decode_state_specs(2, 16)):
        sh = specs_to_shardings(specs, mesh, rules_for_mode("megatron"))
        got = init_params_sharded(KEY, specs, sh)
        want = init_params(KEY, specs)
        for g, w, s in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                           jax.tree.leaves(sh)):
            assert g.dtype == w.dtype and g.sharding == s
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))


def test_abstract_params_shapes_and_dtypes():
    av = abstract_params(SPECS)
    assert av["w"] == jax.ShapeDtypeStruct((8, 4), jnp.bfloat16)
    assert av["b"] == jax.ShapeDtypeStruct((4,), jnp.float32)


# ---------------------------------------------------------------------------
# sharding_ctx / shard_act
# ---------------------------------------------------------------------------


def test_shard_act_noop_outside_ctx():
    assert current_ctx() is None
    x = jnp.ones((4, 8, 16), jnp.float32)
    y = shard_act(x, "batch", "seq", "act_embed")
    assert y is x  # literally the identity, not just equal


def test_sharding_ctx_installs_and_restores():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    rules = rules_for_mode("megatron")
    assert current_ctx() is None
    with sharding_ctx(mesh, rules) as (m, r):
        assert current_ctx() == (mesh, rules) and m is mesh and r is rules
        x = jnp.ones((4, 8), jnp.float32)
        y = shard_act(x, "batch", "act_heads")   # constraint applies on 1x1
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert current_ctx() is None


def test_specs_to_shardings_real_mesh():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    sh = specs_to_shardings(SPECS, mesh, rules_for_mode("megatron"))
    assert sh["w"].spec == P("model", ("data",))
    assert sh["b"].spec == P(None)
    params = jax.device_put(init_params(KEY, SPECS), sh)
    assert params["w"].shape == (8, 4)
