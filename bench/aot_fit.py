"""Compile a cell's serving programs at full size for a described TPU v5e
and print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python bench/aot_fit.py <cell> [<cell> ...]

No chip is needed: the TPU compiler compiles for one v5e chip that is
described, not attached. For each cell it compiles the bucketed prefill at
the traffic's largest bucket and the decode program, with the model's own
functions at the server's shapes, and prints ``memory_analysis()`` and a
reckoning in bytes: weights, K/V cache, the program's temporaries.
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
    from jax.sharding import SingleDeviceSharding
    from repro import prepare
    from repro.core.gemm import GemmConfig, use_gemm
    from repro.models.model import build_model
    from harness import serving, spec
    from harness.counts import ModelShapes

    cell = spec.load_cell(cell_name)
    srv_cfg = cell.config["serving"]
    model = build_model(spec.model_config(cell.config))
    shapes = ModelShapes.from_model(cell.config["model"])
    b, max_len = int(srv_cfg["slots"]), int(srv_cfg["max_len"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    quantized = cell.tier == "int8"
    if quantized:
        params = jax.eval_shape(lambda p: prepare.prepare_lm(
            p, quantized=True, y_deltas=False).params, params)
    cache = jax.eval_shape(lambda: model.init_cache(b, max_len))

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    params, cache = place(params), place(cache)
    vec = lambda dt: jax.ShapeDtypeStruct((b,), dt, sharding=chip)
    bucket = max(serving.buckets(cell.traffic, max_len))
    toks = jax.ShapeDtypeStruct((b, bucket), jnp.int32, sharding=chip)
    gemm = GemmConfig(algo="ffip", quantized=True) if quantized else None
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
    nbytes = lambda tree: sum(np.prod(x.shape) * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    print(f"{cell_name}: {shapes.param_count()} params, weights as served "
          f"{nbytes(params) / 1e9:.3f} GB, K/V cache {nbytes(cache) / 1e9:.3f} "
          f"GB ({shapes.kv_bytes_per_token} B a token)",
          flush=True)
    for name, (fn, args) in progs.items():
        with use_gemm(gemm) if gemm else contextlib.nullcontext():
            compiled = fn.lower(*args).compile()
        ma = compiled.memory_analysis()
        print(f"  {name}: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, "
              f"outputs {ma.output_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{ma.alias_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{ma.temp_size_in_bytes / 1e9:.3f} GB", flush=True)


if __name__ == "__main__":
    for name in sys.argv[1:]:
        fit(name)
