"""Compile a cell's serving programs at full size for a described TPU v5e
host and print what each needs of a chip's memory.

    JAX_PLATFORMS=cpu python bench/aot_fit.py <cell> [<cell> ...]

No chip is needed: the TPU compiler compiles for chips that are described,
not attached. For each cell, on as many chips as the cell asks for (a
``("data", "model")`` mesh of ``(1, chips)`` on a described 2x2 host, the
weights and the cache placed by the program's sharding rules, as the
benchmark serves a cell on several chips), it compiles the weights' making,
the bucketed prefill at the traffic's largest bucket, the decode program and
the reference at the cell's sizes, with the model's own functions at the
server's shapes, and prints ``memory_analysis()`` per chip and a reckoning
in bytes per chip: weights, K/V cache, the program's temporaries.
"""
from __future__ import annotations

import contextlib
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def fit(cell_name: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding
    from repro import prepare
    from repro.core.gemm import GemmConfig, use_gemm
    from repro.dist import context as dist_context
    from repro.dist import sharding as dist_sharding
    from repro.models.model import build_model
    from harness import serving, spec, traffic

    cell = spec.load_cell(cell_name)
    fam = cell.family
    srv_cfg = cell.config["serving"]
    model = build_model(fam.model_config(cell.config))
    shapes = fam.ModelShapes.from_model(cell.config["model"])
    b, max_len = int(srv_cfg["slots"]), int(srv_cfg["max_len"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = serving.make_mesh(topo.devices, cell.chips)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    quantized = cell.tier == "int8"
    if quantized:
        params = jax.eval_shape(lambda p: prepare.prepare_lm(
            p, quantized=True, y_deltas=False).params, params)
    cache = jax.eval_shape(lambda: model.init_cache(b, max_len))

    if mesh is None:
        chip = SingleDeviceSharding(topo.devices[0])
        param_sh = jax.tree.map(lambda _: chip, params)
        cache_sh = jax.tree.map(lambda _: chip, cache)
        rep = chip
    else:
        param_sh = dist_sharding.to_named(
            dist_sharding.param_specs(params, mesh), mesh)
        cache_sh = dist_sharding.to_named(
            dist_sharding.cache_specs(cache, mesh, batch=b), mesh)
        rep = NamedSharding(mesh, PartitionSpec())

    def place(tree, sh):
        return jax.tree.map(lambda s, h: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=h), tree, sh)

    params, cache = place(params, param_sh), place(cache, cache_sh)
    vec = lambda dt: jax.ShapeDtypeStruct((b,), dt, sharding=rep)
    bucket = max(serving.buckets(cell.traffic, max_len))
    toks = jax.ShapeDtypeStruct((b, bucket), jnp.int32, sharding=rep)
    gemm = GemmConfig(algo="ffip", quantized=True) if quantized else None
    n_out = int(max(traffic.output_lengths(cell.traffic)))
    row = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rep)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    progs = {
        f"prefill[{b}x{bucket}]": (jax.jit(model.prefill_sample,
                                            donate_argnums=(2,)),
                                    (params, toks, cache, vec(jnp.int32),
                                     vec(jnp.bool_))),
        f"decode[{b}]": (jax.jit(lambda p, t, c, pos, live, rem, eos:
                                 model.sample_steps(p, t, c, pos, live, rem,
                                                    eos, steps=1),
                                 donate_argnums=(2,)),
                         (params, vec(jnp.int32), cache, vec(jnp.int32),
                          vec(jnp.bool_), vec(jnp.int32), vec(jnp.int32))),
    }
    if not quantized:
        progs["weights"] = (serving.weights_program(
            model, fam.INITIALIZERS, mesh), (key,))
        progs[f"reference[{max_len}]"] = (
            jax.jit(lambda p, t, n, s: fam.token_gaps(
                p, t, n, s, model=fam.model_key(cell.config["model"]),
                bits=0, n_out=n_out)),
            (params, row(max_len),
             jax.ShapeDtypeStruct((), jnp.int32, sharding=rep), row(n_out)))

    def per_chip(tree):
        total = 0
        for x in jax.tree.leaves(tree):
            shard = x.sharding.shard_shape(x.shape)
            total += int(np.prod(shard)) * x.dtype.itemsize
        return total

    print(f"{cell_name}: {cell.chips} chip(s), {shapes.param_count()} params, "
          f"weights as served {per_chip(params) / 1e9:.3f} GB a chip, K/V "
          f"cache {per_chip(cache) / 1e9:.3f} GB a chip "
          f"({shapes.kv_bytes_per_token} B a token in all)", flush=True)
    for name, (fn, args) in progs.items():
        served = name.startswith(("prefill", "decode"))
        with contextlib.ExitStack() as scope:
            if served and mesh is not None:      # as the server dispatches
                scope.enter_context(dist_context.mesh_context(mesh))
            if served and gemm:
                scope.enter_context(use_gemm(gemm))
            compiled = fn.lower(*args).compile()
        ma = compiled.memory_analysis()
        print(f"  {name}: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, "
              f"outputs {ma.output_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{ma.alias_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{ma.temp_size_in_bytes / 1e9:.3f} GB a chip", flush=True)


if __name__ == "__main__":
    for name in sys.argv[1:]:
        fit(name)
