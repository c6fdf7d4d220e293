"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic mix by the names in
``BENCHMARK.json``, makes the weights from the seed on the chip, warms up the
programs the traffic uses and, for a backlog, fills every slot (all of that
is ``setup_s``), drives the program's
``BatchServer`` for ``--seconds``, checks the served tokens against the plain
reference, and prints one JSON line last on stdout. ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` takes a profiler trace of part of
the window and reports its per-layer metrics, with ``busy_s``/``window_s``
and a breakdown. Needs a TPU with as many chips as the cell asks for; exits
non-zero without printing a result otherwise.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

REFERENCE_BITS = {"float": 0, "int8": 8}


def reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def find_chips(chips: int):
    """The devices to run on, or None (with the reason on stderr) when JAX
    finds no TPU or too few chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        note(f"no TPU: JAX finds only {devs[0].platform}")
        return None
    if len(devs) < chips:
        note(f"the cell needs {chips} chips, JAX finds {len(devs)}")
        return None
    return devs[:chips]


def cache_everything() -> None:
    """JAX's persistent compilation cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), small programs included, so that
    only a cell's first run compiles."""
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def breakdown(tr) -> dict:
    from harness import trace as tr_mod
    lo, hi = tr.window
    dev = next(iter(tr.devices.values()))
    gaps = sorted(tr_mod.idle_gaps(tr, dev, lo, hi), key=lambda g: -g[1])
    return {"device_ops": tr_mod.top_ops(dev, lo, hi),
            "idle_gaps": [[lab, s] for lab, s in gaps[:10]]}


def measure(cell, seed: int, seconds: float, trace: bool, devs,
            t_start: float) -> dict:
    """Everything a run does after its look for a chip: set-up, the window,
    the check, the metrics. Returns the result line's object, with the
    numbers compared under its last key, ``checks``."""
    from harness import check, peaks, readers, serving
    from harness import trace as tr_mod

    sess = serving.Session(cell, devs)
    phases = [("weights", lambda: sess.make_weights(seed)),
              ("server", sess.build_server)]
    if sess.mesh is not None:
        # the server first: on a mesh it makes its cache on one chip before
        # sharding it, which would otherwise sit on top of that chip's weights
        phases.reverse()
    t = [time.perf_counter()]
    for _, phase in phases + [("warm-up", sess.warm_up)]:
        phase()
        t.append(time.perf_counter())
    trace_dir = pathlib.Path(tempfile.mkdtemp(prefix="bench-trace-")) \
        if trace else None
    run = sess.run(seed, seconds, trace_dir)
    setup_s = t[-1] - t_start + run["fill_s"]
    note(f"setup {setup_s:.3f}s: start {t[0] - t_start:.3f}s, "
         f"{phases[0][0]} {t[1] - t[0]:.3f}s, {phases[1][0]} "
         f"{t[2] - t[1]:.3f}s, warm-up "
         f"{t[3] - t[2]:.3f}s, fill {run['fill_s']:.3f}s; "
         f"{sess.compiles.count} compiles or cache fetches, "
         f"{sess.compiles.seconds:.3f}s; buckets {sess.buckets()}")
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)
    records = run["records"]
    failed = sum(1 for r in records if r.error)
    sess.free_server()

    steps = [s for s in run["steps"]
             if run["t0"] <= s.t0 and s.t1 <= run["t_stop"]]
    note(f"window {run['seconds']:.3f}s: {len(steps)} steps, "
         f"{sum(len(s.prefills) for s in steps)} prefills, "
         f"{sum(s.decode_tokens for s in steps)} decoded tokens, "
         f"{sum(s.tokens for s in steps)} tokens; {len(records)} requests, "
         f"{failed} failed; closed {time.perf_counter() - t_start:.3f}s; "
         f"peak {memory_peak} bytes")
    t_ref = time.perf_counter()
    picked = check.sample(records, seed)
    g = check.gaps(cell.family, sess.params, picked, cell.config["model"],
                   sess.max_len,
                   int(max(serving.traffic_mod.output_lengths(cell.traffic))),
                   REFERENCE_BITS[cell.tier])
    cks = check.checks(
        {"max_gap": float(g.max()) if g.size else None,
         "mean_gap": float(g.mean()) if g.size else None,
         "bad_outputs": check.validity(records, sess.shapes.vocab),
         "failed": failed,
         "window_compiles": run["window_compiles"]},
        {**cell.config["limits"], "bad_outputs": 0, "failed": 0,
         "window_compiles": 0})
    note(f"reference: {len(picked)} requests, {g.size} tokens, "
         f"{time.perf_counter() - t_ref:.3f}s")
    ttft = readers.ttfts_ms({"records": records, **run})
    if ttft:
        note(f"ttft over {len(ttft)} requests: p50 "
             f"{readers.percentile(ttft, 50):.1f} ms, p90 "
             f"{readers.percentile(ttft, 90):.1f} ms")
    tpot = readers.tpots_ms({"records": records, **run})
    if tpot:
        note(f"tpot over {len(tpot)} requests: p50 "
             f"{readers.percentile(tpot, 50):.2f} ms, p90 "
             f"{readers.percentile(tpot, 90):.2f} ms, max {max(tpot):.2f} ms")

    tr = None
    if trace_dir is not None:
        pb = next(trace_dir.rglob("*.xplane.pb"))
        tr = tr_mod.load(pb)
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = {"tier": cell.tier, "slots": sess.slots, "chips": cell.chips,
           "shapes": sess.shapes,
           "peaks": peaks.peaks(devs[0].device_kind) if trace else None,
           "setup_s": setup_s, "t0": run["t0"], "t_stop": run["t_stop"],
           "seconds": run["seconds"], "records": records,
           "steps": steps,
           "all_steps": {s.index: s for s in run["steps"]}, "trace": tr}
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    out = {"correct": check.passed(cks), "attempted": len(records),
           "failed": failed, "metrics": metrics, "device": device}
    if tr is not None:
        lo, hi = tr.window
        device["busy_s"] = sum(tr_mod.busy_seconds(d, lo, hi)
                               for d in tr.devices.values()) / len(tr.devices)
        device["window_s"] = hi - lo
        out["breakdown"] = breakdown(tr)
    out["checks"] = cks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import spec
    cell = spec.load_cell(args.workload, ROOT)
    devs = find_chips(cell.chips)
    if devs is None:
        return 1
    cache_everything()
    out = measure(cell, args.seed, args.seconds, bool(args.trace), devs,
                  T_START)
    sys.stdout.flush()
    for name, c in out["checks"].items():
        note(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
