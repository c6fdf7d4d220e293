"""Vision launcher: CNN classify smoke + fused-conv schedule tuning.

    # classify smoke: tiny AlexNet through the fused implicit-im2col kernels
    PYTHONPATH=src python -m repro.launch.vision --model alexnet --smoke \
        --gemm-impl pallas --gemm-block auto

    # quantized int8 path (offline-prepared weights, Eq. 15/20 epilogue)
    PYTHONPATH=src python -m repro.launch.vision --model alexnet --smoke \
        --quantized --gemm-block auto

    # pre-populate the repro.tune conv schedules from the model's conv set
    PYTHONPATH=src python -m repro.launch.vision --model alexnet --smoke \
        --tune --budget 3 --iters 1

The smoke asserts logits are finite and the forward is deterministic, and —
with ``--quantized`` — that the int8 logits stay within a loose relative
error of the float logits (the quantization contract, not a bit check; the
bit-exactness checks live in tests/test_conv_fused.py). ``--tune`` follows
the ``launch.tune`` warm-cache contract: ``--expect-cached`` exits non-zero
if anything had to be measured, so CI can assert cold-then-warm.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gemm import GemmConfig, use_gemm
from repro.vision import models as vm


def _smoke_defaults(args) -> None:
    if args.smoke:
        args.image_size = args.image_size or (67 if args.model == "alexnet"
                                              else 32)
        args.width_div = args.width_div or 8
        args.classes = args.classes or 10
    args.image_size = args.image_size or 0
    args.width_div = args.width_div or 1
    args.classes = args.classes or 1000


def _tune(args, model, image_size: int) -> int:
    from repro import tune
    from repro.tune import measure

    algos = [a for a in args.algos.split(",") if a]
    dtypes = [jnp.dtype(d) for d in args.dtypes.split(",") if d]
    cache = tune.get_cache()
    jobs = []
    seen = set()
    for conv, h, w in vm.conv_geometries(model, image_size):
        for algo in algos:
            for dt in dtypes:
                cin_g = conv.cin // conv.groups
                k = conv.kh * conv.kw * cin_g
                oh, ow = vm._spatial(conv, h, w)
                key = tune.conv_key(algo, dt, oh * ow, conv.cout // conv.groups,
                                    k, cin_g * conv.kw)
                if key not in seen:
                    seen.add(key)
                    jobs.append((conv, h, w, algo, dt))
    t0 = time.perf_counter()
    measured = cached = 0
    for conv, h, w, algo, dt in jobs:
        pre = measure.counters["timed_candidates"]
        entry = tune.tune_conv(
            args.batch, h, w, conv.cin, conv.cout, conv.kh, conv.kw, dt,
            stride=conv.stride, pad=conv.pad, groups=conv.groups, algo=algo,
            budget=args.budget, iters=args.iters, cache=cache, persist=False)
        fresh = measure.counters["timed_candidates"] > pre
        measured += fresh
        cached += not fresh
        b = entry["blocks"]
        status = "tuned " if fresh else "cached"
        print(f"[{status}] conv {algo:8s} {jnp.dtype(dt).name:7s} "
              f"{conv.name:12s} {h}x{w}x{conv.cin}->k{conv.kh}x{conv.kw} "
              f"g{conv.groups} -> bm={b['bm']} bn={b['bn']} bk={b['bk']} "
              f"({entry['us']}us, {entry['candidates']} candidates)")
    if measured:
        cache.save()
    print(f"{args.model}: {measured} conv buckets tuned / {cached} reused "
          f"({time.perf_counter() - t0:.1f}s) -> {cache.path}")
    if args.expect_cached and measured:
        print("--expect-cached: FAIL — warm cache still measured",
              file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="CNN classify smoke / fused-conv schedule tuning")
    ap.add_argument("--model", required=True, choices=sorted(vm.BUILDERS))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny image + width_div=8 + 10 classes")
    ap.add_argument("--image-size", type=int, default=0)
    ap.add_argument("--width-div", type=int, default=0)
    ap.add_argument("--classes", type=int, default=0)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--algo", choices=["baseline", "fip", "ffip"],
                    default="ffip")
    ap.add_argument("--gemm-impl", choices=["xla", "pallas"], default="pallas")
    ap.add_argument("--gemm-block", default=None,
                    help="'auto' (repro.tune conv schedules) or 'bm,bn,bk'")
    ap.add_argument("--quantized", action="store_true",
                    help="int8 path (offline weight quantization)")
    ap.add_argument("--tune", action="store_true",
                    help="pre-populate conv schedules instead of classifying")
    ap.add_argument("--algos", default="baseline,fip,ffip",
                    help="--tune: algos to tune")
    ap.add_argument("--dtypes", default="float32,int8",
                    help="--tune: dtypes to tune")
    ap.add_argument("--budget", type=int, default=0)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--expect-cached", action="store_true",
                    help="--tune: fail if anything had to be measured")
    ap.add_argument("--prepared", default=None, metavar="DIR",
                    help="run from a repro.prepare vision artifact "
                         "(python -m repro.launch.prepare --vision ...) "
                         "instead of quantizing in-process")
    args = ap.parse_args(argv)
    _smoke_defaults(args)

    default_size = 227 if args.model == "alexnet" else 224
    image_size = args.image_size or default_size
    model = vm.build(args.model, num_classes=args.classes,
                     image_size=image_size, width_div=args.width_div)
    if args.tune:
        return _tune(args, model, image_size)

    gemm_block = args.gemm_block
    if gemm_block and gemm_block != "auto":
        gemm_block = tuple(int(x) for x in gemm_block.split(","))
    if gemm_block and args.gemm_impl != "pallas":
        raise SystemExit("--gemm-block requires --gemm-impl pallas")

    key = jax.random.PRNGKey(0)
    params = vm.init_params(model, key)
    prepared = None
    if args.prepared:
        from repro import prepare
        prepared = prepare.load(args.prepared)
        if prepared.kind != "vision":
            raise SystemExit(f"--prepared: {args.prepared} is a "
                             f"{prepared.kind!r} artifact, not vision")
        if args.quantized and not prepared.quantized:
            raise SystemExit("--quantized with a float-only artifact — "
                             "re-run launch.prepare with --quantized")
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (args.batch, image_size, image_size, 3))
    n_convs = len(vm.conv_layers(model))
    print(f"{args.model}: image {image_size}x{image_size}, width/{args.width_div}, "
          f"{n_convs} convs, algo={args.algo} impl={args.gemm_impl} "
          f"block={args.gemm_block or 'default'} quantized={args.quantized}")

    t0 = time.perf_counter()
    float_logits = vm.apply(model, params, x)     # xla/baseline reference
    print(f"float reference forward: {time.perf_counter() - t0:.2f}s")
    assert bool(jnp.isfinite(float_logits).all()), "float logits not finite"

    cfg = GemmConfig(algo=args.algo, impl=args.gemm_impl,
                     quantized=args.quantized, block=gemm_block)
    if prepared is not None:
        run_params = prepared.params
    elif args.quantized:
        run_params = vm.attach_quantized(model, params)
    else:
        run_params = params
    with use_gemm(cfg):
        t0 = time.perf_counter()
        logits = vm.apply(model, run_params, x)
        dt1 = time.perf_counter() - t0
        logits2 = vm.apply(model, run_params, x)
    assert bool(jnp.isfinite(logits).all()), "logits not finite"
    assert (np.asarray(logits) == np.asarray(logits2)).all(), \
        "forward not deterministic"
    rel = float(jnp.linalg.norm(logits - float_logits)
                / (jnp.linalg.norm(float_logits) + 1e-9))
    top1 = jnp.argmax(logits, axis=-1)
    print(f"configured forward: {dt1:.2f}s  top1={np.asarray(top1)}  "
          f"rel_err_vs_float={rel:.4f}")
    # the fused float path is allclose-tight; the int8 path has a loose
    # quantization budget (bit-exactness is tested against the reference
    # oracle in tests/test_conv_fused.py, not against float)
    limit = 0.35 if args.quantized else 1e-3
    if rel > limit:
        print(f"FAIL: rel err {rel:.4f} > {limit}", file=sys.stderr)
        return 1
    if prepared is not None and prepared.recomputed:
        print(f"FAIL: prepared artifact recomputed offline work: "
              f"{prepared.recompute_report()}", file=sys.stderr)
        return 1
    print("OK")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
