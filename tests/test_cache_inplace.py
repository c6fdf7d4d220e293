"""The serving cache's writes: a bucketed admission leaves the K/V rows of
the slots decoding beside it bit-identical in every layer, and a write into
the blocked cache lands exactly the rows a plain row write does. (That the
serving programs write the cache in place is checked on the chip's
compiler, in test_tpu_compile.py.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention as A
from repro.models.model import build_model
from repro.serve.batcher import BatchServer, Request


@pytest.mark.parametrize("arch", ["minicpm-2b", "deepseek-v2-lite-16b"],
                         ids=["gqa", "mla"])
def test_admission_keeps_live_slots_kv_rows(arch):
    """Two slots decode; two more requests of one bucket are admitted by one
    bucketed prefill. The live slots' rows are the same bits in every
    layer."""
    cfg = configs.smoke_config(configs.get_config(arch))
    model = build_model(cfg)
    srv = BatchServer(model, batch_slots=4, max_len=64)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompt = lambda n: rng.integers(0, model.cfg.vocab, size=(n,))
    for rid, n in enumerate((7, 12)):
        srv.submit(Request(rid=rid, prompt=prompt(n), max_new_tokens=20))
    for _ in range(3):
        srv.step(params)
    live = {i: s.pos for i, s in enumerate(srv.slots) if s.req is not None}
    assert len(live) == 2
    before = [np.asarray(leaf) for leaf in jax.tree.leaves(srv.cache)]
    for rid, n in enumerate((9, 13), start=2):
        srv.submit(Request(rid=rid, prompt=prompt(n), max_new_tokens=20))
    admitted = srv.stats["prefill_dispatches"]
    srv.step(params)
    assert srv.stats["prefill_dispatches"] == admitted + 1
    assert sum(s.req is not None for s in srv.slots) == 4
    after = [np.asarray(leaf) for leaf in jax.tree.leaves(srv.cache)]
    for b, a in zip(before, after):
        for i, pos in live.items():
            np.testing.assert_array_equal(A.from_blocks(a[:, i])[:, :pos],
                                          A.from_blocks(b[:, i])[:, :pos])


def _reference_write(rows, layer, new, pos, mask):
    """What a write must do to the rows (L, B, S_max, *feat): slot i's new
    rows at clip(pos[i], 0, S_max - s), rows of masked-out slots untouched."""
    out = rows.copy()
    s, s_max = new.shape[1], rows.shape[2]
    for i, p in enumerate(np.clip(pos, 0, s_max - s)):
        if mask is None or mask[i]:
            out[layer, i, p:p + s] = new[i]
    return out


@pytest.mark.parametrize("s_max", [48, 256])
@pytest.mark.parametrize("s", [1, 3, 40])
@pytest.mark.parametrize("shared", [False, True], ids=["per_slot", "shared"])
@pytest.mark.parametrize("masked", [False, True])
def test_cache_write_matches_reference(s_max, s, shared, masked):
    """Per-slot (decode) and shared (prefill) offsets, masked or not, into
    a blocked stack: the rows land where a plain row write puts them and
    nothing else moves."""
    rng = np.random.default_rng(s_max + s)
    b, heads, w, layers = 4, 2, 8, 3
    rows = rng.standard_normal((layers, b, s_max, heads, w), np.float32)
    new = rng.standard_normal((b, s, heads, w), np.float32)
    pos = (np.int32(s_max // 2 + 5) if shared else
           np.array([0, s_max // 2 + 5, s_max - 1, s_max - s], np.int32))
    mask = np.array([True, False, True, True]) if masked else None
    blk = A.row_block(s_max)
    stack = A.to_blocks(rows.reshape((-1,) + rows.shape[2:]), blk)
    stack = stack.reshape((layers, b) + stack.shape[1:])
    got = jax.jit(lambda st, n, p, m: A._cache_write(
        A.LayerSlot(st, jnp.int32(1)), n, p, m).stack)(
            stack, new, pos, mask)
    got = A.from_blocks(np.asarray(got).reshape((-1,) + stack.shape[2:]))
    np.testing.assert_array_equal(
        got.reshape(rows.shape),
        _reference_write(rows, 1, new, np.broadcast_to(pos, (b,)), mask))
