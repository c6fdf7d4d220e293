"""Property-style coverage of the repro.dist rule engine beyond test_dist.py:
structural invariants on every arch x both MoE partition modes x both
production mesh shapes, to_named round-trips, and the paper's bit-exactness
claim for an int8 FFIP GEMM running under data-parallel sharding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core import fip
from repro.dist import context as dctx
from repro.dist import sharding as shd
from repro.kernels import ops
from repro.launch.inputs import params_specs_struct


class Mesh16x16:
    axis_names = ("data", "model")

    class devices:  # noqa: D106 — shape-only stand-in for a 256-chip pod
        shape = (16, 16)


class Mesh2x16x16:
    axis_names = ("pod", "data", "model")

    class devices:  # noqa: D106 — the 512-chip multi-pod mesh
        shape = (2, 16, 16)


PROD_MESHES = [Mesh16x16, Mesh2x16x16]


def _axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


@pytest.mark.parametrize("mesh", PROD_MESHES, ids=["16x16", "2x16x16"])
@pytest.mark.parametrize("mode", ["expert", "ffn"])
@pytest.mark.parametrize("arch", sorted(configs.ARCHS))
def test_every_arch_every_mode_specs_divisible(arch, mode, mesh):
    """Every leaf gets a full-rank spec; every assigned dim divides its axis."""
    sizes = _axis_sizes(mesh)
    cfg = configs.get_config(arch)
    params = params_specs_struct(cfg)
    specs = shd.param_specs(params, mesh, moe_partition=mode)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves)
    for (path, leaf), spec in zip(leaves, spec_leaves):
        assert len(spec) == len(leaf.shape), \
            (arch, jax.tree_util.keystr(path), leaf.shape, spec)
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            assert leaf.shape[dim] % sizes[ax] == 0, \
                (arch, mode, jax.tree_util.keystr(path), leaf.shape, spec)


def test_spec_tree_structure_mirrors_params():
    cfg = configs.get_config("mixtral-8x22b")
    params = params_specs_struct(cfg)
    specs = shd.param_specs(params, Mesh16x16, moe_partition="ffn")
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(
                specs, is_leaf=lambda x: isinstance(x, P)))


@settings(max_examples=30, deadline=None)
@given(L=st.integers(1, 8), e=st.integers(1, 128), d=st.integers(1, 512),
       f=st.integers(1, 512))
def test_property_moe_rules_divisible_and_modes_differ(L, e, d, f):
    """For ANY expert-bank shape, both modes give divisible full-rank specs;
    when dims divide, expert mode shards E and ffn mode shards d_ff."""
    sizes = _axis_sizes(Mesh16x16)
    for name in ("w_gate", "w_up", "w_down"):
        shape = (L, e, d, f) if name != "w_down" else (L, e, f, d)
        for mode in ("expert", "ffn"):
            spec = shd._match_spec(f"layers/ffn/{name}", shape, Mesh16x16, mode)
            assert len(spec) == 4
            for dim, ax in enumerate(spec):
                assert ax is None or shape[dim] % sizes[ax] == 0
    if e % 16 == 0:
        s = shd._match_spec("layers/ffn/w_gate", (L, e, d, f), Mesh16x16,
                            "expert")
        assert s[1] == "model"
    if f % 16 == 0:
        s = shd._match_spec("layers/ffn/w_gate", (L, e, d, f), Mesh16x16,
                            "ffn")
        assert s[3] == "model"


@settings(max_examples=30, deadline=None)
@given(d0=st.integers(1, 64), d1=st.integers(1, 4096), d2=st.integers(1, 4096))
def test_property_guard_never_assigns_indivisible(d0, d1, d2):
    """The divisibility guard holds for arbitrary generic-weight shapes."""
    sizes = _axis_sizes(Mesh16x16)
    spec = shd._match_spec("layers/attn/wq/w", (d0, d1, d2), Mesh16x16,
                           "expert")
    for dim, ax in zip((d0, d1, d2), spec):
        assert ax is None or dim % sizes[ax] == 0


def test_moe_partition_mode_validated():
    with pytest.raises(ValueError):
        shd._match_spec("layers/ffn/w_gate", (2, 4, 8, 16), Mesh16x16, "bogus")


def test_data_and_cache_specs_shapes():
    batch = {"tokens": jax.ShapeDtypeStruct((32, 128), jnp.int32),
             "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    bs = shd.data_specs(batch, Mesh16x16)
    assert bs["tokens"] == P("data", None)
    assert bs["pos"] == P()
    # batch of 8 does not divide the 16-way data axis -> replicated
    small = shd.data_specs(jax.ShapeDtypeStruct((8, 128), jnp.int32), Mesh16x16)
    assert small == P(None, None)
    # multi-pod: batch dim splits over ("pod", "data") jointly (32 x 32-way)
    bs3 = shd.data_specs(batch, Mesh2x16x16)
    assert bs3["tokens"] == P(("pod", "data"), None)

    # batch divides "data" (16) but not pod*data (32): degrade to data-only
    # sharding, never silent full replication
    mid = shd.data_specs(jax.ShapeDtypeStruct((16, 128), jnp.int32),
                         Mesh2x16x16)
    assert mid == P("data", None)

    # K/V leaves hold their rows in blocks: (L, B, J, KV, hd, blk)
    kv = {"k": jax.ShapeDtypeStruct((4, 32, 2, 16, 64, 128), jnp.bfloat16)}
    cs = shd.cache_specs(kv, Mesh16x16, batch=32)
    assert cs["k"] == P(None, "data", None, "model", None, None)
    # kv-heads that do not divide the model axis stay replicated
    kv8 = {"k": jax.ShapeDtypeStruct((4, 32, 2, 8, 64, 128), jnp.bfloat16)}
    assert shd.cache_specs(kv8, Mesh16x16, batch=32)["k"] \
        == P(None, "data", None, None, None, None)
    # the encoder's cross K/V are rows, (L, B, T, KV, hd)
    cross = {"cross_kv": {"k": jax.ShapeDtypeStruct((4, 32, 256, 16, 64),
                                                    jnp.bfloat16)}}
    assert shd.cache_specs(cross, Mesh16x16, batch=32)["cross_kv"]["k"] \
        == P(None, "data", None, "model", None)
    # hybrid layout (n_groups, period, B, ...): batch dim found structurally
    # even when a stack dim (period) collides with the batch size
    hyb = {"hybrid_groups": {
        "conv": jax.ShapeDtypeStruct((3, 32, 32, 3, 128), jnp.bfloat16)}}
    spec = shd.cache_specs(hyb, Mesh16x16, batch=32)
    assert spec["hybrid_groups"]["conv"] == P(None, None, "data", None, None)


def test_to_named_roundtrip_single_device():
    """device_put through to_named keeps every value bit-identical and
    attaches the requested sharding (trivially valid on a 1-device mesh)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = configs.smoke_config(configs.get_config("minicpm-2b"))
    from repro.models.model import build_model
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    specs = shd.param_specs(params, mesh)
    named = shd.to_named(specs, mesh)
    placed = jax.device_put(params, named)
    for (path, a), b, ns in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves(placed),
            jax.tree_util.tree_leaves(
                named, is_leaf=lambda x: isinstance(x, NamedSharding))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
        assert b.sharding.is_equivalent_to(ns, a.ndim), \
            (jax.tree_util.keystr(path), b.sharding, ns)


def test_sharded_ffip_gemm_bit_exact_int8():
    """Paper exactness claim under sharding: a batched int8 FFIP GEMM run
    through jit with data-parallel in_shardings equals baseline_matmul
    bit-for-bit (int32 accumulators; sharding never splits the K
    contraction of a kernel invocation)."""
    n = jax.device_count()
    mesh = jax.make_mesh((n, 1), ("data", "model"))
    ka, kb = jax.random.split(jax.random.PRNGKey(7))
    a = jax.random.randint(ka, (2 * n, 24, 32), -128, 128,
                           dtype=jnp.int32).astype(jnp.int8)
    b = jax.random.randint(kb, (32, 40), -128, 128,
                           dtype=jnp.int32).astype(jnp.int8)
    aspec = shd.data_specs(a, mesh)
    fn = jax.jit(
        lambda a_, b_: ops.matmul(a_, b_, algo="ffip", interpret=True),
        in_shardings=(shd.to_named(aspec, mesh), NamedSharding(mesh, P())))
    with dctx.mesh_context(mesh):
        got = fn(a, b)
    want = fip.baseline_matmul(a.astype(jnp.int32).reshape(-1, 32),
                               b.astype(jnp.int32)).reshape(2 * n, 24, 40)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mesh_context_nests_and_clears():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    inner = jax.make_mesh((1, 1), ("data", "model"))
    assert dctx.get_mesh() is None
    with dctx.mesh_context(mesh):
        assert dctx.get_mesh() is mesh
        with dctx.mesh_context(inner):
            assert dctx.get_mesh() is inner
        assert dctx.get_mesh() is mesh
    assert dctx.get_mesh() is None
