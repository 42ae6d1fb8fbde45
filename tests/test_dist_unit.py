"""Single-process unit tests for repro.dist (the subprocess suite in
test_dist.py is the multi-device oracle; these cover the contracts that
don't need fake devices)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist import checkpoint as ckpt
from repro.dist.compression import compressed_psum
from repro.dist.sharding import (
    DEFAULT_RULES,
    RULE_SETS,
    ShardCtx,
    rules_without_axis,
)


# -- ShardCtx: no-mesh defaults ---------------------------------------------


def test_shardctx_no_mesh_is_inert():
    sh = ShardCtx()
    x = jnp.ones((4, 8))
    assert sh.constrain(x, "batch", None) is x
    assert sh.sharding(("batch", None), (4, 8)) is None
    assert sh.param_sharding(
        type("S", (), {"axes": ("embed",), "shape": (8,)})()
    ) is None
    assert sh.axis_size("data") == 0
    assert sh.axis_size("data", "model") == 0
    assert not sh.heads_shardable(16)
    assert sh.data_axes == ()
    assert sh.model_axes == ()


def test_shardctx_constructor_forms():
    # the three forms the consumers use: (), (mesh), (mesh, rules)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    inner = rules_without_axis(DEFAULT_RULES, "pod")
    assert ShardCtx().mesh is None
    assert ShardCtx(mesh).rules == DEFAULT_RULES
    assert dict(ShardCtx(mesh, inner).rules)["batch"] == ("data",)


# -- RULE_SETS ---------------------------------------------------------------


def test_rule_sets_registry():
    assert set(RULE_SETS) >= {"default", "no_fsdp"}
    default = dict(RULE_SETS["default"])
    no_fsdp = dict(RULE_SETS["no_fsdp"])
    assert default["batch"] == ("pod", "data")
    assert default["mlp"] == ("model",)
    assert default["embed"] == ("data",)  # FSDP param sharding
    assert no_fsdp["embed"] == ()
    # every logical axis the models annotate has a rule in both sets
    for name in ("batch", "embed", "heads_flat", "heads", "kv_heads", "mlp",
                 "vocab", "qseq", "seq_kv", "experts", "layers"):
        assert name in default and name in no_fsdp, name


class _StubMesh:
    """spec() only reads .shape/.axis_names, so resolution semantics can be
    tested against multi-device geometries on a 1-device host."""

    def __init__(self, **shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_spec_resolution_drops_absent_and_non_dividing_axes():
    sh = ShardCtx(_StubMesh(data=2, model=4))
    # "pod" is not in this mesh: batch resolves to ("data",) alone
    assert sh.spec(("batch", None), (4, 8)) == P("data", None)
    # a dim the assignment can't divide falls back to unsharded
    assert sh.spec(("batch", None), (3, 8)) == P(None, None)
    # a mesh axis is used at most once per tensor (first dimension wins)
    assert sh.spec(("mlp", "vocab"), (8, 8)) == P("model", None)
    # multi-axis batch peels trailing axes until the dim divides
    sh3 = ShardCtx(_StubMesh(pod=2, data=2, model=4))
    assert sh3.spec(("batch",), (8,)) == P(("pod", "data"))
    assert sh3.spec(("batch",), (6,)) == P("pod")
    assert sh3.axis_size("pod", "data") == 4
    assert sh3.heads_shardable(8) and not sh3.heads_shardable(6)


# -- checkpoint --------------------------------------------------------------


def test_latest_step_empty_and_partial(tmp_path):
    missing = str(tmp_path / "nope")
    assert ckpt.latest_step(missing) is None
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    assert ckpt.latest_step(empty) is None
    # a partial (never-committed) step dir has no meta.json and is ignored
    os.makedirs(os.path.join(empty, "step_00000007"))
    assert ckpt.latest_step(empty) is None
    ckpt.save_checkpoint(empty, 3, {"x": jnp.zeros((2,))})
    assert ckpt.latest_step(empty) == 3


def test_checkpoint_preserves_exotic_dtypes(tmp_path):
    tree = {
        "bf16": jnp.full((3,), 1.5, jnp.bfloat16),
        "i8": jnp.arange(4, dtype=jnp.int8),
        "bool": jnp.array([True, False]),
    }
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 1, tree)
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    out = ckpt.restore_checkpoint(d, 1, like)
    for k in tree:
        assert out[k].dtype == tree[k].dtype, k
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(tree[k]))


def test_restore_rejects_mismatched_trees(tmp_path):
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 1, {"x": jnp.zeros((2,))})
    try:
        ckpt.restore_checkpoint(d, 1, {"x": jnp.zeros((2,)), "y": jnp.zeros((2,))})
    except ValueError:
        pass
    else:
        raise AssertionError("leaf-count mismatch not rejected")


# -- compression -------------------------------------------------------------


def test_compressed_psum_single_device_round_trip():
    """On a 1-way axis the mean is the identity up to quantisation, and the
    residual is exactly what quantisation dropped."""
    mesh = jax.make_mesh((1,), ("pod",))
    g = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    err0 = jnp.zeros_like(g)

    def body(g, e):
        return compressed_psum({"w": g}, {"w": e}, "pod")

    out, new_err = jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        axis_names={"pod"}, check_vma=False,
    )(g, err0)
    scale = float(jnp.abs(g).max()) / 127.0
    np.testing.assert_allclose(
        np.asarray(out["w"]) + np.asarray(new_err["w"]), np.asarray(g),
        rtol=0, atol=1e-6,
    )
    assert float(jnp.abs(out["w"] - g).max()) <= scale * 0.51
    assert float(jnp.abs(new_err["w"]).max()) <= scale * 0.51
    # error feedback: feeding the residual back cancels it
    out2, err2 = jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        axis_names={"pod"}, check_vma=False,
    )(g, new_err["w"])
    np.testing.assert_allclose(
        np.asarray(out2["w"]) + np.asarray(err2["w"]),
        np.asarray(g + new_err["w"]), rtol=0, atol=1e-6,
    )
