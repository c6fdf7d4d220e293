"""Compile-only checks of the main-path Pallas kernels and serving programs
for a TPU v5e chip.

Each test compiles one kernel at minicpm-2b widths (d_model 2304, d_ff 5760,
head dim 64, a 256-row prefill tile) with the TPU compiler against a
described, not attached, v5e chip, and asserts the compiled program holds the
Mosaic kernel (``tpu_custom_call``) — interpret mode would lower to plain HLO
instead; the int8 dense layer is XLA code and is only compiled. The
serving programs are compiled through the server at test widths. Nothing
runs. The topology is described inside a fixture, so only the
test worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import quant
from repro.core.gemm import GemmConfig, use_gemm
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.models import layers

M, D_MODEL, D_FF = 256, 2304, 5760
HEADS, HEAD_DIM = 36, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be written to the persistent cache
    # but not read back without one: keep the cache off for these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_fwd_compiles(one_chip):
    qkv = ((8 * HEADS, 256, HEAD_DIM), jnp.bfloat16)
    text = _compile(
        lambda q, k, v: flash_attention(q, k, v, 0, True, False),
        one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("algo", ["baseline", "fip", "ffip"])
def test_gemm_compiles(one_chip, algo, dtype):
    text = _compile(
        lambda a, b: ops.matmul(a, b, algo=algo, interpret=False),
        one_chip, ((M, D_MODEL), dtype), ((D_MODEL, D_FF), dtype))
    assert "tpu_custom_call" in text


def test_int8_dense_layer_compiles(one_chip):
    """The quantized serving dense layer (the int8 FFIP tier): XLA's closed
    form, so no Mosaic kernel; it must compile and keep its integer
    arithmetic (s32) on the chip."""
    q = jax.eval_shape(quant.prepare_quantized_dense,
                       jax.ShapeDtypeStruct((D_FF, D_MODEL), jnp.bfloat16))
    cfg = GemmConfig(algo="ffip", quantized=True)

    def layer(x, *leaves):
        with use_gemm(cfg):
            return layers.dense(x, {"q": dict(zip(sorted(q), leaves))})

    text = _compile(layer, one_chip, ((M, D_FF), jnp.bfloat16),
                    *((q[k].shape, q[k].dtype) for k in sorted(q)))
    assert "s32[" in text


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_tensor_parallel_prefill_keeps_layout(topo, quantized):
    """The served bucketed prefill of one minicpm-2b layer on a 1x4 ("data",
    "model") mesh of v5e chips stays tensor-parallel: flash attention runs in
    the heads-over-"model" layout the projections produce, so nothing is
    all-gathered or moved all-to-all — only row-parallel partial sums are
    all-reduced. Sharding flash's fused batch*head rows instead split the
    batch across chips: every weight was then all-gathered each layer, and
    the per-row float steps ran on other shapes than on one chip (int8
    tokens parted from one chip's on v5e)."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro import configs
    from repro.dist import context as dctx
    from repro.dist import sharding as ds
    from repro.models.model import build_model

    cfg = dataclasses.replace(configs.get_config("minicpm-2b"), n_layers=1)
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    if quantized:
        params = jax.eval_shape(quant.attach_quantized_weights, params)
    cache = jax.eval_shape(lambda: model.init_cache(8, 512))
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    rep = NamedSharding(mesh, P())

    def placed(tree, specs):
        return jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            tree, ds.to_named(specs, mesh))

    args = (placed(params, ds.param_specs(params, mesh)),
            jax.ShapeDtypeStruct((8, 64), jnp.int32, sharding=rep),
            placed(cache, ds.cache_specs(cache, mesh, batch=8)),
            jax.ShapeDtypeStruct((8,), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((8,), bool, sharding=rep))
    with dctx.mesh_context(mesh), \
            use_gemm(GemmConfig(algo="ffip", quantized=quantized,
                                interpret=False)):
        text = jax.jit(model.prefill_sample).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text                  # flash, compiled
    assert "all-reduce" in text                       # row-parallel sums
    assert "all-gather" not in text
    assert "all-to-all" not in text


# minicpm-2b's attention (36 heads of 64, one K/V head each) at a small
# model width; MLA at its smoke widths
SERVING_CASES = {
    "gqa": ("minicpm-2b", dict(n_heads=36, n_kv_heads=36, head_dim=64)),
    "mla": ("deepseek-v2-lite-16b", {}),
}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("case", sorted(SERVING_CASES))
def test_serving_programs_update_the_cache_in_place(one_chip, case, program):
    """The server's decode and bucketed prefill, compiled for one v5e: the
    donated cache aliases the output whole, no cache leaf or layer of one is
    copied, and the program needs less scratch than one cache leaf. With
    head dim 64 the chip lays a leaf's rows along its lanes; a program that
    slices layers out of the stack and stacks them back, or writes single
    rows there, copies or relayouts whole leaves instead."""
    import dataclasses
    import re

    import numpy as np

    from repro import configs
    from repro.models.model import build_model
    from repro.serve.batcher import BatchServer

    arch, widths = SERVING_CASES[case]
    cfg = dataclasses.replace(
        configs.smoke_config(configs.get_config(arch)), d_model=256,
        n_layers=4, param_dtype="bfloat16", **widths)
    model = build_model(cfg)
    b, max_len = 8, 2048
    srv = BatchServer(model, batch_slots=b, max_len=max_len)
    place = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(b, max_len)))
    vec = lambda dt: jax.ShapeDtypeStruct((b,), dt, sharding=one_chip)
    if program == "decode":
        lowered = srv._decode.lower(params, vec(jnp.int32), cache,
                                    vec(jnp.int32), vec(jnp.bool_),
                                    vec(jnp.int32), vec(jnp.int32))
    else:
        toks = jax.ShapeDtypeStruct((b, 64), jnp.int32, sharding=one_chip)
        lowered = srv._prefill_bucket.lower(params, toks, cache,
                                            vec(jnp.int32), vec(jnp.bool_))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    leaves = jax.tree.leaves(cache)
    nbytes = [int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves]
    assert mem.alias_size_in_bytes == sum(nbytes)
    assert mem.temp_size_in_bytes < max(nbytes), (mem.temp_size_in_bytes,
                                                  nbytes)
    sized = {",".join(map(str, dims)) for x in leaves
             for dims in (x.shape, (1,) + x.shape[1:], x.shape[1:])}
    copies = [m.group(1) for m in re.finditer(
        r"= bf16\[([\d,]+)\]\S* copy\(", compiled.as_text())]
    assert not [c for c in copies if c in sized], copies
