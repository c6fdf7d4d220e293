"""Record the small profiler trace that the trace-reduction tests read.

    python bench/tests/record_trace.py OUT_DIR

Runs on a TPU only. Serves a few requests of a two-layer model at small
widths through ``BatchServer`` with the harness's own host annotations
around ``submit`` and ``step``, under ``jax.profiler.trace``, and copies the
``.xplane.pb`` it wrote to ``OUT_DIR/small.xplane.pb``. It also prints the
trace's planes, lines and the most frequent event names, which is how the
reduction's names were found.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib
import shutil
import sys
import tempfile

REPO = pathlib.Path(__file__).resolve().parents[2]


def main(argv) -> int:
    out = pathlib.Path(argv[1])
    sys.path.insert(0, str(REPO / "src"))
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    from repro import configs
    from repro.models.model import build_model
    from repro.serve.batcher import BatchServer, Request

    cfg = dataclasses.replace(configs.smoke_config(configs.get_config("minicpm-2b")),
                              d_model=256, n_heads=2, n_kv_heads=2, d_ff=512,
                              vocab=1024, param_dtype="bfloat16")
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    srv = BatchServer(model, batch_slots=4, max_len=256)
    rng = np.random.default_rng(0)
    lens = [40, 100, 30, 120, 60, 90]

    def run(rid0):
        for i, n in enumerate(lens):
            with jax.profiler.TraceAnnotation("bench.submit"):
                srv.submit(Request(rid=rid0 + i, prompt=rng.integers(
                    0, cfg.vocab, n).astype(np.int32), max_new_tokens=6))
        k = 0
        while srv.has_queued() or any(s.req is not None for s in srv.slots):
            with jax.profiler.TraceAnnotation(f"bench.step:{k}"):
                srv.step(params)
            k += 1
        srv.take_completed()

    run(0)                                     # compiles
    tmp = pathlib.Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp), profiler_options=opts):
        run(100)
    pb = next(tmp.rglob("*.xplane.pb"))
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(pb, out / "small.xplane.pb")
    print(f"trace bytes {pb.stat().st_size}")

    from jax.profiler import ProfileData
    prof = ProfileData.from_file(str(pb))
    for plane in prof.planes:
        print(f"PLANE {plane.name!r} stats={dict(plane.stats)}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  LINE {line.name!r} events={len(evs)}")
            for name, c in names.most_common(12):
                print(f"    {c:5d} {name[:160]!r}")
            for e in evs[:3]:
                print(f"    sample {e.name[:80]!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} stats={dict(e.stats)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
