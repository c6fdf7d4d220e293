"""Readings that set a cell's correctness limits: the program's, over many
seeds, and the control's, one precision below the configuration's.

    python3 bench/tests/control.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 3 --seconds 10

One process serves the cell and, for each seed, makes that seed's weights,
builds and warms a server, drives a window of the cell's own traffic at its
own size, samples the finished requests as a run does, and reads the widest
and the mean gap of

  * the served tokens (the program's reading), and, for the first
    ``--control-seeds`` seeds,
  * the control's tokens at the same positions of the same prompts and
    served tokens: on the float tier, the program with its own int8 path
    switched on (teacher-forced through its forward); on the int8 tier, the
    reference computed at int4.

It prints one JSON line per seed. The benchmark's own runs never run it.
Runs on a TPU only.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from run import REFERENCE_BITS, cache_everything, find_chips  # noqa: E402
from harness import check, serving, spec, traffic  # noqa: E402


@functools.lru_cache(maxsize=None)
def _int8_forward(cfg):
    """The program's forward under its int8 FFIP GEMM scope, greedy token
    at every position of one sequence."""
    import jax
    import jax.numpy as jnp
    from repro.core.gemm import GemmConfig, use_gemm
    from repro.models import transformer as T

    @jax.jit
    def fwd(p, toks):
        with use_gemm(GemmConfig(algo="ffip", quantized=True)):
            h, _, _ = T.forward(p, toks[None], cfg)
            return jnp.argmax(T.logits_fn(p, h, cfg), -1)[0]
    return fwd


def mesh_scope(mesh):
    """On a mesh, the ambient mesh the server dispatches under, so that the
    program's forward wraps its Pallas kernels for the sharded weights."""
    from repro.dist.context import mesh_context
    return contextlib.nullcontext() if mesh is None else mesh_context(mesh)


def _teacher(r, max_len: int) -> np.ndarray:
    """Prompt then served tokens but the last, padded to ``max_len``."""
    seq = np.zeros((max_len,), np.int32)
    n = r.n_prompt + len(r.out) - 1
    seq[:n] = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
    return seq


def control_tokens(sess, r, max_out: int, prepared=None) -> np.ndarray:
    """The token the control puts first at each served position of
    request ``r``. ``prepared``: the program's int8 weights for the float
    tier's control (made once per weights by the caller)."""
    import jax.numpy as jnp
    seq = _teacher(r, sess.max_len)
    if sess.tier == "float":
        with mesh_scope(sess.mesh):
            best = np.asarray(_int8_forward(sess.model_cfg)(
                prepared, jnp.asarray(seq)))
        return best[r.n_prompt - 1:r.n_prompt - 1 + len(r.out)]
    served = np.zeros((max_out,), np.int32)
    served[:len(r.out)] = r.out
    fam = sess.cell.family
    _, best = fam.token_gaps(
        sess.params, jnp.asarray(seq), jnp.asarray(r.n_prompt, jnp.int32),
        jnp.asarray(served), model=fam.model_key(sess.cell.config["model"]),
        bits=4, n_out=max_out)
    return np.asarray(best)[:len(r.out)]


def int8_weights(sess):
    """The program's own int8 preparation of the session's weights."""
    from repro import prepare
    return prepare.prepare_lm(sess.params, quantized=True,
                              y_deltas=False).params


def control_gaps(sess, picked, max_out: int) -> np.ndarray:
    prepared = int8_weights(sess) if sess.tier == "float" else None
    ctl = [serving.Record(rid=r.rid, due=r.due, n_prompt=r.n_prompt,
                          max_new=r.max_new, prompt=r.prompt,
                          out=[int(t) for t in control_tokens(
                              sess, r, max_out, prepared)])
           for r in picked]
    return check.gaps(sess.cell.family, sess.params, ctl,
                      sess.cell.config["model"], sess.max_len, max_out,
                      REFERENCE_BITS[sess.tier])


def readings(sess, seed: int, seconds: float, with_control: bool) -> dict:
    sess.free_server()
    sess.make_weights(seed)
    sess.build_server()
    sess.warm_up()
    run = sess.run(seed, seconds)
    sess.free_server()
    picked = check.sample(run["records"], seed)
    max_out = int(max(traffic.output_lengths(sess.cell.traffic)))
    g = check.gaps(sess.cell.family, sess.params, picked,
                   sess.cell.config["model"], sess.max_len, max_out,
                   REFERENCE_BITS[sess.tier])
    out = {"seed": seed, "requests": len(picked), "tokens": int(g.size),
           "max_gap": float(g.max()), "mean_gap": float(g.mean()),
           "failed": sum(1 for r in run["records"] if r.error)}
    if with_control:
        c = control_gaps(sess, picked, max_out)
        out.update(control_max_gap=float(c.max()),
                   control_mean_gap=float(c.mean()))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devs = find_chips(cell.chips)
    if devs is None:
        return 1
    cache_everything()
    sess = serving.Session(cell, devs)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(sess, seed, args.seconds,
                                  i < args.control_seeds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
