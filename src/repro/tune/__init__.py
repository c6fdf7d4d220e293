"""`repro.tune` — kernel autotuning with a persistent device-keyed cache.

The paper's §5 design-space sweep shows the FFIP advantage is only realized
when the array tiling matches the hardware; our Pallas kernels used to ship
ONE hardcoded block shape for every GEMM on every backend. This subsystem
closes that gap:

  * :mod:`repro.tune.space`   — legal, deterministically ordered candidates;
  * :mod:`repro.tune.measure` — compile-outside-timed-region, median-of-k;
  * :mod:`repro.tune.cache`   — persistent JSON schedule cache keyed by
    ``(kernel, algo, dtype, shape-bucket, device_kind)`` + in-process LRU.

Consumers:
  * ``GemmConfig(block="auto")`` (core/gemm.py) resolves tuned ``(bm,bn,bk)``
    for the pallas fip/ffip/baseline providers via :func:`lookup_gemm_blocks`
    at trace time — lookups only, never measurement, falling back to the
    static defaults on a miss with a one-time log + ``stats`` counter;
  * flash attention (models/attention.py) resolves tuned ``(bq, bk)`` the
    same way via :func:`lookup_flash_blocks`;
  * ``python -m repro.launch.tune`` (the offline CLI) pre-populates the cache
    for a model config's / CNN workload's GEMM shape set via :func:`tune_gemm`
    / :func:`tune_flash`.

Shape bucketing: each dim rounds up to a power of two, so one measured
schedule serves every shape in its bucket — the same bucketing the serving
prefill path already uses for prompts.
"""
from __future__ import annotations

import logging
from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from repro.kernels.compat import device_kind
from repro.tune import measure, space
from repro.tune.cache import ScheduleCache, get_cache, make_key

__all__ = [
    "ScheduleCache", "get_cache", "make_key", "device_kind",
    "gemm_key", "flash_key", "conv_key",
    "lookup_gemm_blocks", "lookup_flash_blocks", "lookup_conv_blocks",
    "tune_gemm", "tune_flash", "tune_conv", "stats", "reset_stats",
]

logger = logging.getLogger("repro.tune")

# hit/miss telemetry for the "auto" resolution path: a silent fallback to the
# hardcoded constant is exactly the failure mode this subsystem exists to
# remove, so misses are counted and logged (once per distinct key).
stats: Dict[str, int] = {"hits": 0, "misses": 0}
_warned_keys: set = set()


def reset_stats():
    stats["hits"] = 0
    stats["misses"] = 0
    _warned_keys.clear()


def _dtype_name(dtype) -> str:
    return jnp.dtype(dtype).name


def _bucket(*dims: int) -> Tuple[int, ...]:
    return tuple(space.round_up_pow2(d) for d in dims)


def gemm_key(algo: str, dtype, m: int, n: int, k: int, *,
             device: Optional[str] = None) -> str:
    mb, nb, kb = _bucket(m, n, k)
    return make_key("gemm", algo, _dtype_name(dtype), f"m{mb}n{nb}k{kb}",
                    device or device_kind())


def flash_key(dtype, bh: int, sq: int, sk: int, d: int, *,
              device: Optional[str] = None) -> str:
    bhb, sqb, skb = _bucket(bh, sq, sk)
    return make_key("flash_attention", "fwd", _dtype_name(dtype),
                    f"bh{bhb}sq{sqb}sk{skb}d{d}", device or device_kind())


def conv_key(algo: str, dtype, m: int, n: int, k: int, ckw: int, *,
             device: Optional[str] = None) -> str:
    """Schedule key for the fused implicit-im2col conv kernels. Buckets the
    per-image GEMM view (m = OH*OW, n = Cout/groups, k = KH*KW*Cin_g) like
    the GEMM keys, but keeps ``ckw`` = Cin_g*KW exact — it defines the
    bk-alignment structure of the candidate space, so shapes that bucket
    together but gather differently don't share a schedule."""
    mb, nb, kb = _bucket(m, n, k)
    return make_key("conv", algo, _dtype_name(dtype),
                    f"m{mb}n{nb}k{kb}ckw{ckw}", device or device_kind())


def _miss(key: str) -> None:
    stats["misses"] += 1
    if key not in _warned_keys:
        _warned_keys.add(key)
        logger.info(
            "no tuned schedule for %s; using static default blocks "
            "(pre-populate with `python -m repro.launch.tune`)", key)
    return None


# -- lookup (hot path: trace-time, never measures) --------------------------

def lookup_gemm_blocks(algo: str, dtype, m: int, n: int, k: int, *,
                       cache: Optional[ScheduleCache] = None,
                       ) -> Optional[Tuple[int, int, int]]:
    key = gemm_key(algo, dtype, m, n, k)
    entry = (cache if cache is not None else get_cache()).lookup(key)
    if entry is None:
        return _miss(key)
    stats["hits"] += 1
    b = entry["blocks"]
    return (b["bm"], b["bn"], b["bk"])


def lookup_flash_blocks(dtype, bh: int, sq: int, sk: int, d: int, *,
                        cache: Optional[ScheduleCache] = None,
                        ) -> Optional[Tuple[int, int]]:
    key = flash_key(dtype, bh, sq, sk, d)
    entry = (cache if cache is not None else get_cache()).lookup(key)
    if entry is None:
        return _miss(key)
    stats["hits"] += 1
    b = entry["blocks"]
    return (b["bq"], b["bk"])


def lookup_conv_blocks(algo: str, dtype, m: int, n: int, k: int, ckw: int, *,
                       cache: Optional[ScheduleCache] = None,
                       ) -> Optional[Tuple[int, int, int]]:
    key = conv_key(algo, dtype, m, n, k, ckw)
    entry = (cache if cache is not None else get_cache()).lookup(key)
    if entry is None:
        return _miss(key)
    stats["hits"] += 1
    b = entry["blocks"]
    return (b["bm"], b["bn"], b["bk"])


# -- offline tuning ---------------------------------------------------------

def tune_gemm(m: int, n: int, k: int, dtype, *, algo: str = "ffip",
              budget: int = 0, iters: int = 3,
              interpret: Optional[bool] = None,
              cache: Optional[ScheduleCache] = None,
              force: bool = False, persist: bool = True) -> dict:
    """Tune one GEMM shape bucket; returns (and persists) the cache entry.

    Measures at the BUCKET shape so the entry serves every member shape.
    ``budget`` limits how many candidates are tried (0 = all; the default
    candidate is always index 0 so even budget=1 is a valid, default-keeping
    run). A warm cache returns without any measurement unless ``force``.
    ``persist=False`` defers the file write (call ``cache.save()`` once at
    the end of a sweep — the CLI does this to avoid O(n^2) rewrites).
    """
    cache = cache if cache is not None else get_cache()
    key = gemm_key(algo, dtype, m, n, k)
    entry = None if force else cache.lookup(key)
    if entry is not None:
        return entry
    mb, nb, kb = _bucket(m, n, k)
    cands = space.gemm_candidates(mb, nb, kb, algo, jnp.dtype(dtype).itemsize)
    if budget:
        cands = cands[:budget]
    best, best_t, trace = measure.best_gemm_blocks(
        algo, mb, kb, nb, dtype, cands, interpret=interpret, iters=iters)
    default_t = next((t["us"] for t in trace
                      if tuple(t["blocks"]) == cands[0] and "us" in t), None)
    entry = {
        "blocks": {"bm": best[0], "bn": best[1], "bk": best[2]},
        "us": round(best_t * 1e6, 1),
        "default_blocks": {"bm": cands[0][0], "bn": cands[0][1],
                           "bk": cands[0][2]},
        "default_us": default_t,
        "candidates": len(trace),
        "iters": iters,
    }
    cache.put(key, entry, persist=persist)
    logger.info("tuned %s -> %s (%.1fus over %d candidates)", key,
                entry["blocks"], entry["us"], entry["candidates"])
    return entry


def tune_conv(batch: int, h: int, w: int, cin: int, cout: int, kh: int,
              kw: int, dtype, *, stride=1, pad=0, groups: int = 1,
              algo: str = "ffip", budget: int = 0, iters: int = 3,
              interpret: Optional[bool] = None,
              cache: Optional[ScheduleCache] = None,
              force: bool = False, persist: bool = True) -> dict:
    """Tune one fused-conv geometry; same contract as :func:`tune_gemm`.

    Measures the fused implicit-im2col kernel at the REAL geometry (the
    gather pattern is part of the cost), keyed by the bucketed per-image GEMM
    view + the exact ``ckw`` alignment — shapes sharing a bucket reuse the
    first-measured member's schedule (the CLI dedupes by key before tuning).
    """
    from repro.core.im2col import as_pair, conv_out_hw
    cache = cache if cache is not None else get_cache()
    sh, sw = as_pair(stride)
    ph, pw = as_pair(pad)
    cin_g = cin // groups
    k = kh * kw * cin_g
    ckw = cin_g * kw
    oh, ow = conv_out_hw(h, w, kh, kw, (sh, sw), (ph, pw))
    m, n = oh * ow, cout // groups
    key = conv_key(algo, dtype, m, n, k, ckw)
    entry = None if force else cache.lookup(key)
    if entry is not None:
        return entry
    cands = space.conv_candidates(m, n, k, ckw, algo,
                                  jnp.dtype(dtype).itemsize)
    if budget:
        cands = cands[:budget]
    best, best_t, trace = measure.best_conv_blocks(
        algo, batch, h, w, cin, kh, kw, cout, dtype, cands,
        stride=(sh, sw), pad=(ph, pw), groups=groups, interpret=interpret,
        iters=iters)
    default_t = next((t["us"] for t in trace
                      if tuple(t["blocks"]) == cands[0] and "us" in t), None)
    entry = {
        "blocks": {"bm": best[0], "bn": best[1], "bk": best[2]},
        "us": round(best_t * 1e6, 1),
        "default_blocks": {"bm": cands[0][0], "bn": cands[0][1],
                           "bk": cands[0][2]},
        "default_us": default_t,
        "candidates": len(trace),
        "iters": iters,
        "geometry": {"batch": batch, "h": h, "w": w, "cin": cin, "cout": cout,
                     "kh": kh, "kw": kw, "stride": [sh, sw], "pad": [ph, pw],
                     "groups": groups},
    }
    cache.put(key, entry, persist=persist)
    logger.info("tuned %s -> %s (%.1fus over %d candidates)", key,
                entry["blocks"], entry["us"], entry["candidates"])
    return entry


def tune_flash(bh: int, sq: int, sk: int, d: int, dtype=jnp.float32, *,
               budget: int = 0, iters: int = 3,
               interpret: Optional[bool] = None,
               cache: Optional[ScheduleCache] = None,
               force: bool = False, persist: bool = True) -> dict:
    """Tune one flash-attention forward shape bucket; same contract as
    :func:`tune_gemm`."""
    cache = cache if cache is not None else get_cache()
    key = flash_key(dtype, bh, sq, sk, d)
    entry = None if force else cache.lookup(key)
    if entry is not None:
        return entry
    bhb, sqb, skb = _bucket(bh, sq, sk)
    cands = space.flash_candidates(sqb, skb)
    if budget:
        cands = cands[:budget]
    best, best_t, trace = measure.best_flash_blocks(
        bhb, sqb, skb, d, dtype, cands, interpret=interpret, iters=iters)
    default_t = next((t["us"] for t in trace
                      if tuple(t["blocks"]) == cands[0] and "us" in t), None)
    entry = {
        "blocks": {"bq": best[0], "bk": best[1]},
        "us": round(best_t * 1e6, 1),
        "default_blocks": {"bq": cands[0][0], "bk": cands[0][1]},
        "default_us": default_t,
        "candidates": len(trace),
        "iters": iters,
    }
    cache.put(key, entry, persist=persist)
    logger.info("tuned %s -> %s (%.1fus over %d candidates)", key,
                entry["blocks"], entry["us"], entry["candidates"])
    return entry
