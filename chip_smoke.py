"""Bring-up smoke test of the serving path on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # tensor-parallel serving on four chips

One chip: builds minicpm-2b at its published widths (40 layers, d_model
2304, 36 heads, d_ff 5760, vocab 122753, bf16) with random weights from
``PRNGKey(0)`` and serves one seeded workload through ``BatchServer`` in three
phases:

  a. the default float path (XLA GEMMs, Pallas flash-attention prefill);
  b. ``gemm_impl="pallas"``: every projection through the compiled Pallas
     FFIP kernel (the paper's GEMM);
  c. ``quantized=True``: the int8 FFIP tier.

Each phase drains the workload twice on one server: the first drain compiles,
the second is timed and must compile nothing and reproduce the first drain's
tokens. Every request must complete with exactly its token budget and valid
ids, the prefill logits of one prompt must be finite and, for phases (b) and
(c), close to phase (a)'s (``LOGITS_REL_TOL``), and phase (b)'s compiled
prefill and decode programs must contain the Pallas kernels
(``tpu_custom_call``). A kernel check then runs the baseline / FIP / FFIP
GEMMs at minicpm widths (int8: bit-exact against an int32 matmul; bf16: within
``GEMM_BF16_TOL``) and flash attention (within ``FLASH_BF16_TOL``).

``--chips 4`` runs only the tensor-parallel path: the same model and workload
on a 1x4 ("data", "model") mesh and on one device, float and int8. Every
request must complete on both; the int8 tier's tokens and the prompt K/V its
prefill wrote must be identical, float's K/V within ``TP_FLOAT_KV_TOL``
(see :func:`tensor_parallel`).

Blocks are the kernels' static defaults; no tuning cache or prepared artifact
is read. Exits non-zero when JAX finds no TPU or any check fails; on success
the last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import time

import numpy as np

REPO = pathlib.Path(__file__).resolve().parent
ARCH = "minicpm-2b"
SLOTS = 8
MAX_LEN = 512
N_REQUESTS = 8
PROMPT_LENS = (32, 200)       # inclusive range of prompt lengths
MAX_NEW = 16
SEED = 0
# kernel check: a prefill-sized GEMM at minicpm's d_model x d_ff widths, and
# flash attention over 8 sequences x 36 heads of 256 tokens, head dim 64
GEMM_MKN = (256, 2304, 5760)
FLASH_SHAPE = (SLOTS * 36, 256, 64)
# bf16 tolerances, relative to the reference's largest magnitude: the Pallas
# GEMMs round their f32 accumulator to bf16 (2^-9 relative), flash attention
# also rounds its probabilities to bf16 before the PV product
GEMM_BF16_TOL = 1e-2
FLASH_BF16_TOL = 5e-2
# prefill logits of phases (b) and (c) against phase (a), relative L2: (b)
# accumulates its GEMMs in f32 where XLA rounds them to bf16, (c) quantizes
# activations and weights to int8 (v5e, seed 0: 0.018 and 0.092)
LOGITS_REL_TOL = {"b": 0.05, "c": 0.25}
# float prompt K/V at tensor parallelism against one device, relative L2 over
# all layers: bf16 rounding of the row-parallel partial sums (v5e, tp=4:
# 0.0183); a dropped or misplaced partial sum moves them by order 1
TP_FLOAT_KV_TOL = 0.1


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's backend-compile durations (XLA and Mosaic compiles, or
    persistent-cache fetches) while it is open."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.open = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if self.open and event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def __enter__(self):
        self.seconds, self.open = 0.0, True
        return self

    def __exit__(self, *exc):
        self.open = False


def make_prompts(vocab: int, seed: int = SEED):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [rng.integers(0, vocab, size=(int(n),)).astype(np.int32)
            for n in lens]


def drain(srv, params, prompts, rid0: int, *, prompt_kv: bool = False):
    """Serve every prompt once; returns ({rid - rid0: tokens}, wall seconds,
    prompt K/V) after checking the serve launcher's gates: nothing dropped,
    exact budgets, valid ids. With ``prompt_kv`` the server's cache is read
    back after its first step (every prompt admitted and prefilled, one
    decode step taken) and the third item holds, per cache leaf, the K/V
    rows the served prefill wrote for each slot's prompt; else it is None."""
    import jax
    from repro.serve.batcher import Request
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        srv.submit(Request(rid=rid0 + i, prompt=p, max_new_tokens=MAX_NEW))
    kv = None
    if prompt_kv:
        srv.step(params)
        n = [len(s.req.prompt) for s in srv.slots]
        check(sum(n) == sum(len(p) for p in prompts),
              "first step did not admit every prompt")
        kv = [np.concatenate([np.asarray(leaf[:, i, :k], np.float32)
                              for i, k in enumerate(n)], axis=1)
              for leaf in map(jax.device_get, jax.tree.leaves(srv.cache))]
    done = srv.run_until_drained(params)
    dt = time.perf_counter() - t0
    vocab = srv.model.cfg.vocab
    check(sorted(r.rid for r in done) == list(range(rid0, rid0 + len(prompts))),
          f"drain returned rids {sorted(r.rid for r in done)}")
    for r in done:
        check(len(r.out_tokens) == MAX_NEW,
              f"rid {r.rid}: {len(r.out_tokens)} tokens, budget {MAX_NEW}")
        check(all(0 <= t < vocab for t in r.out_tokens),
              f"rid {r.rid}: token id outside [0, {vocab})")
    return {r.rid - rid0: list(r.out_tokens) for r in done}, dt, kv


def prefill_logits(srv, params, prompt):
    """Last-position logits of one prompt through the server's own GEMM
    scope and run-ready params (batch 1)."""
    import jax
    import jax.numpy as jnp
    model = srv.model
    run_params = srv._params_for(params)
    with srv._gemm_scope():
        _, logits = jax.jit(model.prefill)(
            run_params, jnp.asarray(prompt)[None], model.init_cache(1, MAX_LEN))
    return np.asarray(jax.device_get(logits), np.float32)


def compiled_programs_text(srv, params, bucket: int):
    """Compiled HLO of the server's bucketed-prefill and decode programs at
    the shapes it serves (a persistent-cache hit when the cache is on)."""
    import jax.numpy as jnp
    b = srv.b
    run_params = srv._params_for(params)
    zeros = jnp.zeros((b,), jnp.int32)
    with srv._gemm_scope():
        prefill = srv._prefill_bucket.lower(
            run_params, jnp.zeros((b, bucket), jnp.int32), srv.cache,
            jnp.ones((b,), jnp.int32), jnp.zeros((b,), bool)).compile()
        decode = srv._decode.lower(
            run_params, zeros, srv.cache, zeros, jnp.zeros((b,), bool), zeros,
            jnp.full((b,), -1, jnp.int32)).compile()
    return prefill.as_text(), decode.as_text()


def serve_phase(name, model, params, prompts, *, expect_kernels=False,
                clock, **server_kw):
    """One serving phase; returns (tokens, last-position logits of prompt 0)."""
    from repro.serve.batcher import BatchServer
    srv = BatchServer(model, batch_slots=SLOTS, max_len=MAX_LEN, **server_kw)
    with clock:
        first, first_s, _ = drain(srv, params, prompts, rid0=0)
    compile_s = clock.seconds
    compiles = dict(srv.compiles)
    second, run_s, _ = drain(srv, params, prompts, rid0=len(prompts))
    check(srv.compiles == compiles,
          f"{name}: timed drain compiled again ({compiles} -> {srv.compiles})")
    check(second == first, f"{name}: second drain's tokens differ from the first")
    logits = prefill_logits(srv, params, prompts[0])
    check(logits.shape == (1, model.cfg.vocab), f"{name}: logits {logits.shape}")
    check(bool(np.isfinite(logits).all()), f"{name}: non-finite logits")
    line = {"phase": name, "compile_s": compile_s, "first_drain_s": first_s,
            "run_s": run_s, "tokens": sum(len(t) for t in second.values()),
            "compiles": compiles, "prefill_dispatches":
                srv.stats["prefill_dispatches"],
            "decode_dispatches": srv.stats["decode_dispatches"],
            **device_memory()}
    if expect_kernels:
        bucket = max(srv._bucket_len(len(p)) for p in prompts)
        t0 = time.perf_counter()
        for prog, text in zip(("prefill", "decode"),
                              compiled_programs_text(srv, params, bucket)):
            n = text.count("tpu_custom_call")
            check(n > 0, f"{name}: compiled {prog} has no Pallas kernel")
            line[f"{prog}_tpu_custom_calls"] = n
        line["kernel_check_compile_s"] = time.perf_counter() - t0
    print(f"phase {json.dumps(line)}", flush=True)
    del srv
    gc.collect()
    return first, logits


def device_memory() -> dict:
    """Bytes in use and peak bytes on device 0, where the backend says."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def compare_tokens(ref, got) -> float:
    same = sum(a == b for rid in ref for a, b in zip(ref[rid], got[rid]))
    return same / sum(len(t) for t in ref.values())


def first_divergence(ref, got) -> list:
    """Per request, the index of the first differing token (None if none):
    0 is the token sampled at prefill, later ones come from decode steps."""
    return [next((i for i, (a, b) in enumerate(zip(ref[rid], got[rid]))
                  if a != b), None) for rid in sorted(ref)]


def kernel_check(gemm_mkn=GEMM_MKN, flash_shape=FLASH_SHAPE) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels.flash_attention import flash_attention

    m, k, n = gemm_mkn
    ka, kb, kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED + 1), 5)
    a8 = jax.random.randint(ka, (m, k), -128, 128, jnp.int32).astype(jnp.int8)
    b8 = jax.random.randint(kb, (k, n), -128, 128, jnp.int32).astype(jnp.int8)
    want = np.asarray(jnp.matmul(a8.astype(jnp.int32), b8.astype(jnp.int32)))
    # an independent host check of the reference on a few rows
    check(np.array_equal(want[:8], np.asarray(a8[:8], np.int64)
                         @ np.asarray(b8, np.int64)),
          "int32 jnp.matmul disagrees with numpy")
    a16 = jax.random.normal(ka, (m, k), jnp.float32).astype(jnp.bfloat16)
    b16 = jax.random.normal(kb, (k, n), jnp.float32).astype(jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        ref16 = np.asarray(jnp.matmul(a16.astype(jnp.float32),
                                      b16.astype(jnp.float32)))
    scale = float(np.abs(ref16).max())
    for algo in ("baseline", "fip", "ffip"):
        got = np.asarray(ops.matmul(a8, b8, algo=algo))
        bad = int((got != want).sum())
        got16 = np.asarray(ops.matmul(a16, b16, algo=algo), np.float32)
        err16 = float(np.abs(got16 - ref16).max()) / scale
        print(f"kernel {json.dumps({'gemm': algo, 'mkn': [m, k, n], 'int8_mismatches': bad, 'bf16_max_rel_err': err16, 'bf16_tol': GEMM_BF16_TOL})}",
              flush=True)
        check(got.dtype == np.int32 and bad == 0,
              f"int8 {algo} GEMM is not bit-exact ({bad} mismatches)")
        check(err16 <= GEMM_BF16_TOL,
              f"bf16 {algo} GEMM error {err16} > {GEMM_BF16_TOL}")

    bh, s, d = flash_shape
    q, kt, v = (jax.random.normal(key, (bh, s, d), jnp.float32)
                .astype(jnp.bfloat16) for key in (kq, kk, kv))
    got = np.asarray(flash_attention(q, kt, v, 0, True), np.float32)
    with jax.default_matmul_precision("highest"):
        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, kt, v))
        sc = jnp.einsum("bqd,bkd->bqk", q32, k32) / d ** 0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        ref = np.asarray(jnp.einsum("bqk,bkd->bqd", p, v32))
    err = float(np.abs(got - ref).max()) / float(np.abs(ref).max())
    print(f"kernel {json.dumps({'flash': [bh, s, d], 'bf16_max_rel_err': err, 'bf16_tol': FLASH_BF16_TOL})}",
          flush=True)
    check(err <= FLASH_BF16_TOL, f"flash error {err} > {FLASH_BF16_TOL}")


def one_chip(cfg, *, expect_kernels: bool = True, gemm_mkn=GEMM_MKN,
             flash_shape=FLASH_SHAPE) -> None:
    import jax
    from repro.models.model import build_model
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = model.init(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {n_params} params, init {time.perf_counter() - t0:.3f}s",
          flush=True)
    prompts = make_prompts(cfg.vocab)
    print(f"workload: {len(prompts)} requests, prompt lengths "
          f"{[len(p) for p in prompts]}, {MAX_NEW} new tokens each, "
          f"{SLOTS} slots, max_len {MAX_LEN}", flush=True)
    clock = CompileClock()
    toks_a, logits_a = serve_phase("a-float-xla", model, params, prompts,
                                   clock=clock)
    toks_b, logits_b = serve_phase("b-float-pallas-ffip", model, params,
                                   prompts, clock=clock, gemm_impl="pallas",
                                   gemm_algo="ffip", expect_kernels=expect_kernels)
    toks_c, logits_c = serve_phase("c-int8-ffip", model, params, prompts,
                                   clock=clock, quantized=True)
    for name, toks, logits in (("b", toks_b, logits_b),
                               ("c", toks_c, logits_c)):
        rel = float(np.linalg.norm(logits - logits_a) / np.linalg.norm(logits_a))
        print(f"vs-a {json.dumps({'phase': name, 'token_agreement': compare_tokens(toks_a, toks), 'prefill_logits_rel_l2': rel, 'tol': LOGITS_REL_TOL[name]})}",
              flush=True)
        check(rel <= LOGITS_REL_TOL[name],
              f"phase {name} logits differ from phase a by {rel}")
    del params
    gc.collect()
    kernel_check(gemm_mkn, flash_shape)


def tensor_parallel(cfg, tp: int) -> None:
    """The workload on a 1 x ``tp`` ("data", "model") mesh and on one device,
    float and int8, compared on what the server itself produces: its tokens
    and the prompt K/V its batched prefill wrote into the cache.

    int8: tokens and prompt K/V must be identical. Its integer partial sums
    add exactly across chips, every per-row float step runs on the same
    whole-batch shapes as on one chip, and flash attention runs the same
    kernel per (batch, head) row. Float: row-parallel projections sum bf16
    partials across chips in another order than one chip's accumulator, so
    its prompt K/V are held to ``TP_FLOAT_KV_TOL`` and its greedy tokens,
    which part at near-ties once the logits move, are only reported."""
    import jax
    from jax.sharding import Mesh
    from repro.models.model import build_model
    from repro.serve.batcher import BatchServer
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    prompts = make_prompts(cfg.vocab)
    mesh = Mesh(np.array(jax.devices()[:tp]).reshape(1, tp), ("data", "model"))
    clock = CompileClock()
    results = {}
    for quantized in (False, True):
        tier = "int8-ffip" if quantized else "float"
        toks, kv = {}, {}
        for where, m in ((f"tp{tp}", mesh), ("single", None)):
            srv = BatchServer(model, batch_slots=SLOTS, max_len=MAX_LEN,
                              quantized=quantized, mesh=m)
            with clock:
                toks[where], dt, kv[where] = drain(srv, params, prompts,
                                                   rid0=0, prompt_kv=True)
            check(all(np.isfinite(x).all() for x in kv[where]),
                  f"{tier}-{where}: non-finite K/V")
            line = {"phase": f"{tier}-{where}", "compile_s": clock.seconds,
                    "drain_s": dt, "compiles": dict(srv.compiles)}
            if m is not None:
                placed = srv._params_for(params)
                leaf = next(x for x in jax.tree.leaves(placed)
                            if not x.sharding.is_fully_replicated)
                line["sharded_leaf_devices"] = sorted(
                    d.id for d in leaf.sharding.device_set)
                line["bytes_in_use"] = [
                    (d.memory_stats() or {}).get("bytes_in_use")
                    for d in mesh.devices.flat]
                check(len(leaf.sharding.device_set) == tp,
                      f"sharded leaf spans {leaf.sharding.device_set}")
            print(f"phase {json.dumps(line)}", flush=True)
            del srv
            gc.collect()
        ref, got = kv["single"], kv[f"tp{tp}"]
        results[tier] = {
            "tier": tier,
            "token_agreement": compare_tokens(toks["single"], toks[f"tp{tp}"]),
            "first_divergence": first_divergence(toks["single"],
                                                 toks[f"tp{tp}"]),
            "prompt_kv_rows": int(ref[0].shape[1]),
            "prompt_kv_rows_differing": int(sum(
                (a != b).any(axis=(-1, -2)).sum() for a, b in zip(ref, got))),
            "prompt_kv_rel_l2": float(
                np.sqrt(sum(((a - b) ** 2).sum() for a, b in zip(ref, got))
                        / sum((a ** 2).sum() for a in ref)))}
        print(f"tp-vs-single {json.dumps(results[tier])}", flush=True)
    int8 = results["int8-ffip"]
    check(int8["prompt_kv_rows_differing"] == 0,
          f"int8-ffip: tp{tp} prefill wrote different K/V in "
          f"{int8['prompt_kv_rows_differing']} (layer, token) rows")
    check(int8["token_agreement"] == 1.0,
          f"int8-ffip: tp{tp} tokens differ from one device "
          f"(first divergence {int8['first_divergence']})")
    check(results["float"]["prompt_kv_rel_l2"] <= TP_FLOAT_KV_TOL,
          f"float: tp{tp} prompt K/V differ from one device by "
          f"{results['float']['prompt_kv_rel_l2']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving phases + kernel check on one chip; "
                         "4: tensor-parallel serving vs one device only")
    args = ap.parse_args(argv)
    if not (REPO / "src" / "repro").is_dir():
        print(f"FAIL: no repro package under {REPO / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))
    import jax
    devs = jax.devices()
    dev = devs[0]
    print(f"devices: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print("FAIL: no TPU found (JAX sees only "
              f"{dev.platform}); this smoke test runs on the chip only",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"FAIL: --chips {args.chips} but {len(devs)} device(s) visible",
              file=sys.stderr)
        return 1
    from repro import configs
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # no tuning schedule is read: point the schedule cache at a file that
    # never exists so every kernel keeps its static default blocks
    os.environ["REPRO_TUNE_CACHE"] = str(REPO / ".jax_cache" / "no-schedules.json")
    cfg = configs.get_config(ARCH)
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            one_chip(cfg)
        else:
            tensor_parallel(cfg, args.chips)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(f"total {time.perf_counter() - t0:.3f}s", flush=True)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
