"""repro.tune — autotuner subsystem tests.

Covers the ISSUE 4 contract:
  * every generated candidate is legal (divisibility-free by design, bk even
    for the FIP family, VMEM-bounded) and the ordering is deterministic with
    the static default first;
  * cache round-trip: write -> fresh instance reload -> identical schedule
    with ZERO re-measurement;
  * corrupted cache file recovers to empty (moved aside, next save clean);
  * tuned blocks are BIT-identical to default blocks for the int8 path and
    for integer-valued float32 inputs (every product/sum exact in f32, so any
    block partitioning must produce the same bits — a real-valued float test
    would only prove allclose, which is not the paper's claim);
  * GemmConfig(block="auto") resolves schedules from the cache inside the
    provider (hit) and falls back to defaults with a counted miss.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.core.gemm import GemmConfig, gemm, use_gemm
from repro.kernels import ops
from repro.tune import measure, space
from repro.tune.cache import ScheduleCache


def _int_inputs(m, k, n, dtype, lo=-8, hi=8, seed=0):
    """Integer-valued operands: for float32 every FIP/FFIP pre-add, product,
    and partial sum is exactly representable, so results are order-invariant
    and block choice cannot change a single bit."""
    rng = np.random.RandomState(seed)
    a = rng.randint(lo, hi, size=(m, k)).astype(np.float32)
    b = rng.randint(lo, hi, size=(k, n)).astype(np.float32)
    if dtype == jnp.int8:
        return jnp.asarray(a, jnp.int8), jnp.asarray(b, jnp.int8)
    return jnp.asarray(a, dtype), jnp.asarray(b, dtype)


# --- search space -----------------------------------------------------------

@pytest.mark.parametrize("algo", ["baseline", "fip", "ffip"])
@pytest.mark.parametrize("m,k,n", [(2, 64, 512), (100, 60, 36), (256, 1024, 256)])
def test_candidates_legal_and_deterministic(algo, m, k, n):
    c1 = space.gemm_candidates(m, n, k, algo)
    c2 = space.gemm_candidates(m, n, k, algo)
    assert c1 == c2, "candidate ordering must be deterministic"
    assert c1[0] == tuple(ops.choose_blocks(m, n, k, algo)), \
        "static default must be candidate 0"
    assert len(c1) == len(set(c1)), "duplicate candidates"
    for bm, bn, bk in c1:
        assert space.gemm_block_legal(bm, bn, bk, algo, m, n, k), (bm, bn, bk)
        assert bm % 8 == 0 or bm >= m, "sublane tile or whole dim"
        assert bn % 128 == 0 or bn >= n, "lane tile or whole dim"
        if algo in ("fip", "ffip"):
            assert bk % 2 == 0, "FIP pair algebra needs even bk"
            assert (bk // 2) % 128 == 0 or bk >= k, "pair half: lane tile"
        else:
            assert bk % 128 == 0 or bk >= k
        assert ops.tile_vmem_bytes(bm, bn, bk, algo) <= ops._VMEM_BUDGET
        assert bm <= space.round_up_pow2(m)
        assert bn <= space.round_up_pow2(n)
        assert bk <= space.round_up_pow2(k)


def test_flash_candidates_default_first():
    cands = space.flash_candidates(512, 512)
    assert cands[0] == (128, 128)
    assert cands == space.flash_candidates(512, 512)
    assert all(bq in space.FLASH_BQ and bk in space.FLASH_BK
               for bq, bk in cands)


# --- cache ------------------------------------------------------------------

def test_cache_roundtrip_zero_remeasure(tmp_path):
    path = tmp_path / "sched.json"
    c1 = ScheduleCache(path)
    before = measure.counters["timed_candidates"]
    e1 = tune.tune_gemm(16, 32, 32, jnp.int8, algo="ffip", budget=2, iters=1,
                        cache=c1)
    assert measure.counters["timed_candidates"] > before, "cold run measures"
    assert path.exists()

    c2 = ScheduleCache(path)                 # fresh instance = fresh process
    mid = measure.counters["timed_candidates"]
    e2 = tune.tune_gemm(16, 32, 32, jnp.int8, algo="ffip", budget=2, iters=1,
                        cache=c2)
    assert e2["blocks"] == e1["blocks"]
    assert measure.counters["timed_candidates"] == mid, \
        "warm cache must not re-measure"
    # same bucket, different member shape -> same schedule, still no measure
    got = tune.lookup_gemm_blocks("ffip", jnp.int8, 13, 30, 27, cache=c2)
    assert got == (e1["blocks"]["bm"], e1["blocks"]["bn"], e1["blocks"]["bk"])
    assert measure.counters["timed_candidates"] == mid


def test_cache_lru_bounded(tmp_path):
    c = ScheduleCache(tmp_path / "s.json", lru_size=2)
    for i in range(5):
        c.put(f"k{i}", {"blocks": {"bm": 8, "bn": 32, "bk": 8}},
              persist=False)
    assert len(c._lru) == 2, "LRU must stay bounded"
    assert len(c) == 5, "persisted entries must NOT be evicted"
    assert c.lookup("k0") is not None, "evicted-from-LRU keys still resolve"


def test_corrupted_cache_recovers(tmp_path):
    path = tmp_path / "sched.json"
    path.write_text("{ this is not json !!!")
    c = ScheduleCache(path)
    assert c.lookup("anything") is None
    assert c.recovered, "corruption must be flagged"
    assert path.with_name(path.name + ".corrupt").exists(), \
        "corrupt file kept aside for debugging"
    # cache still fully functional: tune, persist, reload cleanly
    e = tune.tune_gemm(16, 16, 16, jnp.int8, algo="fip", budget=1, iters=1,
                       cache=c)
    raw = json.loads(path.read_text())
    assert raw["version"] == 1
    c2 = ScheduleCache(path)
    assert not c2.recovered
    key = tune.gemm_key("fip", jnp.int8, 16, 16, 16)
    assert c2.lookup(key)["blocks"] == e["blocks"]


def test_corrupted_cache_quarantined_at_save_time(tmp_path):
    """A cache instance that loaded a CLEAN file, then finds the on-disk file
    corrupted at save() time (crashed concurrent writer, hand edit), must
    quarantine the evidence exactly like the load-time path — not silently
    overwrite it."""
    path = tmp_path / "sched.json"
    c = ScheduleCache(path)
    c.lookup("warm")            # load: file absent, nothing to recover
    assert not c.recovered
    path.write_text("{ trashed between load and save !!!")
    c.put("a|f|i8|m8n8k8|cpu", {"blocks": {"bm": 8, "bn": 32, "bk": 8}})
    assert c.recovered, "save-time corruption must be flagged"
    corrupt = path.with_name(path.name + ".corrupt")
    assert corrupt.exists(), "corrupt file kept aside for debugging"
    assert corrupt.read_text().startswith("{ trashed"), \
        "quarantine must preserve the corrupt bytes, not our rewrite"
    # and the rewrite itself is clean and complete
    c2 = ScheduleCache(path)
    assert not c2.recovered
    assert c2.lookup("a|f|i8|m8n8k8|cpu") is not None


def test_cache_save_merges_concurrent_writers(tmp_path):
    """Two tuner processes sharing a path must not erase each other's
    buckets: save() re-reads and merges on-disk entries before writing."""
    path = tmp_path / "s.json"
    blocks = {"blocks": {"bm": 8, "bn": 32, "bk": 8}}
    c1, c2 = ScheduleCache(path), ScheduleCache(path)
    c1.lookup("warm")          # both load the (empty) file, like two
    c2.lookup("warm")          # processes starting together
    c1.put("a|f|i8|m8n8k8|cpu", blocks)
    c2.put("b|f|i8|m8n8k8|cpu", blocks)   # later writer, disjoint key
    fresh = ScheduleCache(path)
    assert fresh.lookup("a|f|i8|m8n8k8|cpu") is not None, \
        "first writer's entry lost"
    assert fresh.lookup("b|f|i8|m8n8k8|cpu") is not None


def test_cache_rejects_malformed_entries(tmp_path):
    path = tmp_path / "sched.json"
    path.write_text(json.dumps({"version": 1, "entries": {
        "good|x|y|z|cpu": {"blocks": {"bm": 8, "bn": 32, "bk": 8}},
        "bad1|x|y|z|cpu": {"blocks": "nope"},
        "bad2|x|y|z|cpu": ["not", "a", "dict"],
    }}))
    c = ScheduleCache(path)
    assert c.lookup("good|x|y|z|cpu") is not None
    assert c.lookup("bad1|x|y|z|cpu") is None
    assert c.lookup("bad2|x|y|z|cpu") is None
    assert not c.recovered, "entry-level filtering is not file corruption"


# --- bit-exactness across block choices ------------------------------------

@pytest.mark.parametrize("algo", ["baseline", "fip", "ffip"])
def test_tuned_blocks_bit_identical_int8(algo):
    m, k, n = 48, 40, 36
    a, b = _int_inputs(m, k, n, jnp.int8, lo=-128, hi=128)
    ref = np.asarray(ops.matmul(a, b, algo=algo, interpret=True))
    for bm, bn, bk in space.gemm_candidates(m, n, k, algo)[:4]:
        got = np.asarray(ops.matmul(a, b, algo=algo, interpret=True,
                                    bm=bm, bn=bn, bk=bk))
        np.testing.assert_array_equal(got, ref, err_msg=f"{(bm, bn, bk)}")


@pytest.mark.parametrize("algo", ["baseline", "fip", "ffip"])
def test_tuned_blocks_bit_identical_float(algo):
    m, k, n = 48, 40, 36
    a, b = _int_inputs(m, k, n, jnp.float32)
    ref = np.asarray(ops.matmul(a, b, algo=algo, interpret=True))
    for bm, bn, bk in space.gemm_candidates(m, n, k, algo)[:4]:
        got = np.asarray(ops.matmul(a, b, algo=algo, interpret=True,
                                    bm=bm, bn=bn, bk=bk))
        assert got.tobytes() == ref.tobytes(), \
            f"float bits changed under blocks {(bm, bn, bk)}"


def test_tuned_blocks_bit_identical_int8_ffip_quantized_path(tmp_path):
    """The serving int8-FFIP decode contract survives tuning: a GemmConfig
    with explicit tuned blocks produces bit-identical int32 accumulators."""
    a, b = _int_inputs(24, 32, 40, jnp.int8, lo=-128, hi=128)
    with use_gemm(GemmConfig(algo="ffip", impl="pallas")):
        ref = np.asarray(gemm(a, b))
    with use_gemm(GemmConfig(algo="ffip", impl="pallas", block=(8, 32, 16))):
        got = np.asarray(gemm(a, b))
    np.testing.assert_array_equal(got, ref)


# --- block="auto" resolution -----------------------------------------------

def test_auto_resolves_schedule_from_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "sched.json"))
    m, k, n = 16, 32, 48
    entry = tune.tune_gemm(m, n, k, jnp.int8, algo="ffip", budget=3, iters=1)

    used = {}
    orig = ops.matmul

    def spy(a, b, **kw):
        used.update(kw)
        return orig(a, b, **kw)

    monkeypatch.setattr("repro.kernels.ops.matmul", spy)
    tune.reset_stats()
    a, b = _int_inputs(m, k, n, jnp.int8, lo=-128, hi=128)
    with use_gemm(GemmConfig(algo="ffip", impl="pallas", block="auto")):
        got = np.asarray(gemm(a, b))
    assert tune.stats["hits"] >= 1 and tune.stats["misses"] == 0
    blocks = entry["blocks"]
    assert (used["bm"], used["bn"], used["bk"]) == \
        (blocks["bm"], blocks["bn"], blocks["bk"]), \
        "auto must hand the CACHED schedule to the kernel"
    np.testing.assert_array_equal(
        got, np.asarray(a, np.int64) @ np.asarray(b, np.int64))


def test_auto_miss_falls_back_to_defaults(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "empty.json"))
    tune.reset_stats()
    a, b = _int_inputs(8, 16, 16, jnp.int8, lo=-128, hi=128)
    with use_gemm(GemmConfig(algo="ffip", impl="pallas", block="auto")):
        got = np.asarray(gemm(a, b))
    assert tune.stats["misses"] >= 1, "miss must be counted, never silent"
    np.testing.assert_array_equal(
        got, np.asarray(a, np.int64) @ np.asarray(b, np.int64))


def test_auto_explicit_and_invalid_block_values():
    cfg = GemmConfig(algo="ffip", impl="pallas", block=(16, 32, 8))
    a, b = _int_inputs(16, 16, 16, jnp.int8, lo=-128, hi=128)
    with use_gemm(cfg):
        got = np.asarray(gemm(a, b))
    np.testing.assert_array_equal(
        got, np.asarray(a, np.int64) @ np.asarray(b, np.int64))
    with pytest.raises(ValueError, match="block"):
        with use_gemm(GemmConfig(impl="pallas", block="fastest")):
            gemm(a, b)


def test_flash_auto_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "sched.json"))
    entry = tune.tune_flash(4, 16, 16, 8, budget=2, iters=1)
    got = tune.lookup_flash_blocks(jnp.float32, 4, 16, 16, 8)
    assert got == (entry["blocks"]["bq"], entry["blocks"]["bk"])
    # flash numerics are block-partition invariant up to fp rounding; the
    # attention layer consumes the schedule through _flash_schedule
    from repro.models.attention import _flash_schedule
    with use_gemm(GemmConfig(block="auto")):
        bq, bk, _ = _flash_schedule(jnp.float32, 4, 16, 16, 8)
    assert (bq, bk) == got


def test_tuner_shapes_from_model_config():
    """launch.tune derives a non-empty, bucketable GEMM set from a config."""
    from repro import configs
    from repro.launch.tune import _arch_gemm_shapes
    cfg = configs.smoke_config(configs.get_config("minicpm-2b"))
    shapes = _arch_gemm_shapes(cfg, [2])
    assert shapes, "model config must yield dense GEMM shapes"
    assert all(m == 2 and k > 0 and n > 0 for m, k, n in shapes)
    assert (2, cfg.d_model, cfg.vocab) in shapes, "tied unembed included"
