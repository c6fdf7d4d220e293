"""Offline model-prep launcher: build and save a `repro.prepare` artifact.

    # LM artifact: int8 q entries + Eq. 9 y-deltas + tuned schedule slice
    PYTHONPATH=src python -m repro.launch.prepare --arch minicpm-2b --smoke \
        --quantized --out /tmp/minicpm.prepared

    # vision artifact (BN already folded at init; conv/FC int8 entries)
    PYTHONPATH=src python -m repro.launch.prepare --vision alexnet --smoke \
        --quantized --out /tmp/alexnet.prepared

This is the paper's §4.4 offline stage as a deployment step: everything a
serving process would otherwise compute lazily at startup — per-channel int8
quantization with Eq. 15 folded beta, Eq. 9 y-delta weight encodings, and the
device-keyed `repro.tune` schedule slice — is done HERE, once, and serialized.
`launch.serve --prepared DIR` (and `launch.vision --prepared DIR`) then load
it with the zero-recompute warm-start contract; ``--require-warm`` on the
serve side turns that contract into a hard failure.

Params are initialized from seed 0, matching the serve/vision launchers, so
an artifact prepared here is byte-compatible with their synthetic workloads.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax

from repro import configs, prepare
from repro.kernels import compat


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="build + save a repro.prepare artifact")
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--arch", choices=sorted(configs.ARCHS),
                     help="LM architecture (params from seed 0, like "
                          "launch.serve)")
    src.add_argument("--vision", metavar="MODEL",
                     help="vision model name (see launch.vision)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny smoke-sized config (matches the serve/vision "
                         "launchers' --smoke)")
    ap.add_argument("--quantized", action="store_true",
                    help="attach per-channel int8 q entries (Eq. 15/20)")
    ap.add_argument("--no-y-deltas", action="store_true",
                    help="LM only: skip the Eq. 9 y-delta precompute")
    ap.add_argument("--out", required=True, help="artifact directory")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    if args.arch:
        from repro.models.model import build_model
        cfg = configs.get_config(args.arch)
        if args.smoke:
            cfg = configs.smoke_config(cfg)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        pm = prepare.prepare_lm(params, quantized=args.quantized,
                                y_deltas=not args.no_y_deltas, name=cfg.name)
    else:
        from repro.vision import models as vm
        if args.vision not in vm.BUILDERS:
            ap.error(f"--vision must be one of {sorted(vm.BUILDERS)}")
        image_size = ((67 if args.vision == "alexnet" else 32) if args.smoke
                      else (227 if args.vision == "alexnet" else 224))
        model = vm.build(args.vision,
                         num_classes=10 if args.smoke else 1000,
                         image_size=image_size,
                         width_div=8 if args.smoke else 1)
        params = vm.init_params(model, jax.random.PRNGKey(0))
        pm = prepare.prepare_vision(model, params, quantized=args.quantized,
                                    name=args.vision)
    prep_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = pm.save(args.out)
    save_s = time.perf_counter() - t0

    n_leaves = len(jax.tree.leaves(pm.params))
    print(f"prepared {pm.kind} artifact '{pm.meta.get('name')}' -> {out}")
    print(f"  device_kind={pm.device} quantized={pm.quantized} "
          f"params_leaves={n_leaves} y_deltas={len(pm.derived)} "
          f"schedule_entries={len(pm.schedule)}")
    print(f"  offline work: quantize={prepare.counters_snapshot()['quantize']}"
          f" y_encode={compat.derived.stats['computed']} "
          f"(prep {prep_s:.2f}s, save {save_s:.2f}s)")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
