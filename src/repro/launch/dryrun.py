import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract memory / cost / collective analyses.

MUST be run as its own process (the two lines above must execute before any
jax import anywhere):

    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x22b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Results land in benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json;
EXPERIMENTS.md §Dry-run / §Roofline are generated from them.
"""
import argparse   # noqa: E402
import dataclasses  # noqa: E402
import json       # noqa: E402
import pathlib    # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs                    # noqa: E402
from repro.configs.base import SHAPES        # noqa: E402
from repro.dist import context as dctx       # noqa: E402
from repro.dist import sharding as shd       # noqa: E402
from repro.launch import inputs as inp       # noqa: E402
from repro.launch import costs as jcosts     # noqa: E402
from repro.launch import roofline as roof    # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.model import build_model   # noqa: E402
from repro.optim import adamw                # noqa: E402
from repro.train.step import TrainConfig, make_train_step  # noqa: E402

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               sharding_overrides=None, remat: str = ""):
    """Lower + compile one cell. Returns (compiled, lowered, meta)."""
    cfg, shape, specs = inp.input_specs(arch, shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size

    if shape.kind == "train":
        # default train remat policy: save dot outputs (cheap recompute of
        # elementwise only); --remat full for strict O(1)-activation memory
        cfg = dataclasses.replace(cfg, remat=remat or "dots")
    model = build_model(cfg)

    params_sds = inp.params_specs_struct(cfg)
    pspecs = shd.param_specs(params_sds, mesh,
                             moe_partition=cfg.moe.partition if cfg.moe else "expert")
    if sharding_overrides:
        pspecs = sharding_overrides(pspecs, cfg, mesh)

    if shape.kind == "train":
        opt_sds = jax.eval_shape(adamw.init, params_sds)
        ospecs = adamw.AdamWState(step=P(), m=pspecs, v=pspecs)
        bspecs = shd.data_specs(specs, mesh)
        step = make_train_step(model, TrainConfig())
        jitted = jax.jit(
            step,
            in_shardings=(shd.to_named(pspecs, mesh),
                          shd.to_named(ospecs, mesh),
                          shd.to_named(bspecs, mesh)),
            donate_argnums=(0, 1),
        )
        args = (params_sds, opt_sds, specs)
    elif shape.kind == "prefill":
        cspecs = shd.cache_specs(specs["cache"], mesh, batch=shape.global_batch)
        bspecs = shd.data_specs(
            {k: v for k, v in specs.items() if k != "cache"}, mesh)

        def prefill(params, cache, tokens, frames=None, patches=None):
            kw = {}
            if frames is not None:
                kw["frames"] = frames
            if patches is not None:
                kw["patches"] = patches
            return model.prefill(params, tokens, cache, **kw)

        extra = {k: specs[k] for k in ("frames", "patches") if k in specs}
        jitted = jax.jit(
            prefill,
            in_shardings=(shd.to_named(pspecs, mesh),
                          shd.to_named(cspecs, mesh),
                          shd.to_named(bspecs["tokens"], mesh),
                          *(shd.to_named(bspecs[k], mesh) for k in extra)),
            donate_argnums=(1,),
        )
        args = (params_sds, specs["cache"], specs["tokens"], *extra.values())
    else:  # decode
        cspecs = shd.cache_specs(specs["cache"], mesh, batch=shape.global_batch)
        tok_spec = shd.data_specs(specs["token"], mesh)

        def decode(params, cache, token, pos):
            return model.decode_step(params, token, cache, pos)

        jitted = jax.jit(
            decode,
            in_shardings=(shd.to_named(pspecs, mesh),
                          shd.to_named(cspecs, mesh),
                          shd.to_named(tok_spec, mesh),
                          NamedSharding(mesh, P())),
            donate_argnums=(1,),
        )
        args = (params_sds, specs["cache"], specs["token"], specs["pos"])

    t0 = time.time()
    with dctx.mesh_context(mesh):
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    meta = dict(arch=arch, shape=shape_name,
                mesh="2x16x16" if multi_pod else "16x16",
                chips=chips, kind=shape.kind,
                compile_s=round(time.time() - t0, 1))
    # un-jitted callable + abstract args for the scan-aware jaxpr cost model
    meta["_costable"] = (jitted.__wrapped__, args)
    return compiled, lowered, meta


def _model_flops(cfg, shape) -> float:
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch          # decode: 1 token each


def analyze(compiled, meta, cfg, shape) -> dict:
    # global, scan-aware FLOPs/bytes from the jaxpr (XLA's cost_analysis is
    # per-partition and counts while bodies once — see launch/costs.py)
    fn, args = meta.pop("_costable")
    jc = jcosts.fn_cost(fn, *args)
    xla_cost = compiled.cost_analysis() or {}
    if isinstance(xla_cost, (list, tuple)):   # older JAX: one dict per program
        xla_cost = xla_cost[0] if xla_cost else {}
    hlo = compiled.as_text()
    coll = roof.collective_bytes(hlo)
    mem = compiled.memory_analysis()
    report = roof.roofline_report(
        jc.flops, jc.bytes, coll, meta["chips"],
        model_flops=_model_flops(cfg, shape))
    out = dict(meta)
    out.update(
        hlo_flops=jc.flops, hlo_bytes=jc.bytes,
        xla_flops_per_device=float(xla_cost.get("flops", 0.0)),
        xla_bytes_per_device=float(xla_cost.get("bytes accessed", 0.0)),
        bytes_per_device=getattr(mem, "temp_size_in_bytes", None),
        argument_bytes=getattr(mem, "argument_size_in_bytes", None),
        output_bytes=getattr(mem, "output_size_in_bytes", None),
        **report,
    )
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: pathlib.Path,
             remat: str = ""):
    cfg = configs.get_config(arch)
    shape = configs.SHAPE_BY_NAME[shape_name]
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    path = out_dir / f"{arch}__{shape_name}__{mesh_tag}.json"
    ok, why = configs.shape_supported(cfg, shape)
    if not ok:
        path.write_text(json.dumps(dict(arch=arch, shape=shape_name,
                                        mesh=mesh_tag, status="skipped",
                                        reason=why), indent=1))
        print(f"SKIP {arch} x {shape_name} [{mesh_tag}]: {why}")
        return True
    try:
        compiled, lowered, meta = lower_cell(arch, shape_name,
                                             multi_pod=multi_pod, remat=remat)
        result = analyze(compiled, meta, cfg, shape)
        result["status"] = "ok"
        path.write_text(json.dumps(result, indent=1, default=str))
        print(f"OK   {arch} x {shape_name} [{mesh_tag}] "
              f"compile={meta['compile_s']}s bottleneck={result['bottleneck']} "
              f"roofline_frac={result['roofline_fraction']:.3f}")
        return True
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        path.write_text(json.dumps(dict(
            arch=arch, shape=shape_name, mesh=mesh_tag, status="failed",
            error=f"{type(e).__name__}: {e}",
            traceback=traceback.format_exc()[-4000:]), indent=1))
        print(f"FAIL {arch} x {shape_name} [{mesh_tag}]: {type(e).__name__}: {e}")
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--remat", default="", choices=["", "none", "dots", "full"])
    ap.add_argument("--out", default=str(RESULTS_DIR))
    args = ap.parse_args()
    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    cells = []
    archs = sorted(configs.ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in SHAPES] if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    failures = 0
    for a, s, mp in cells:
        if not run_cell(a, s, mp, out_dir, remat=args.remat):
            failures += 1
    print(f"done: {len(cells) - failures}/{len(cells)} cells ok")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
