"""``correct`` on the CPU at test size: a sound run passes its cell's limits;
the same run with the timed path broken underneath, and the control one
precision below the configuration's, do not.

Each case but the control drives the whole of ``run.measure``, which is what
``bench/run.py`` does after its look for a chip: weights from the seed, the
server, warm-up, an open-loop or backlog window, the sample, the reference
and the metrics. The cells are cut to test size (``conftest.tiny_cell``) and
hold the limits set for that size.
"""
from __future__ import annotations

import time

import jax
import numpy as np
import pytest

from harness import check, serving, traffic
from run import REFERENCE_BITS, measure

SEED = 2 ** 40 + 11
BACKLOG = [("minicpm-2b.int8", "chat-backlog"),
           ("deepseek-coder-33b-l8.float", "code-backlog")]
CELLS = [("minicpm-2b.float", "chat")] + BACKLOG


def run_cell(cell, seconds=1.5):
    return measure(cell, SEED, seconds, False, jax.devices(),
                   time.perf_counter())


@pytest.mark.parametrize("config,mix", CELLS)
def test_sound_run_is_correct(cell_factory, config, mix):
    cell = cell_factory(config, mix)
    out = run_cell(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out["checks"]) == ["max_gap", "mean_gap", "bad_outputs",
                                   "failed", "window_compiles"]
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_every_prefill_dispatch_is_recorded(cell_factory):
    """Each step's record holds every prefill the server dispatched in it,
    also in steps where a request finishes after the prefill."""
    sess = serving.Session(cell_factory("minicpm-2b.float", "chat"))
    sess.make_weights(SEED)
    sess.build_server()
    sess.warm_up()
    before = sess.server.stats["prefill_dispatches"]
    run = sess.run(SEED, 1.5)
    recorded = [pf for st in run["steps"] for pf in st.prefills]
    assert len(recorded) == sess.server.stats["prefill_dispatches"] - before
    assert sum(pf["requests"] for pf in recorded) == len(run["records"])


@pytest.mark.parametrize("config,mix", BACKLOG)
def test_backlog_window_opens_with_every_slot_filled(cell_factory, config,
                                                     mix):
    """A backlog's first step, before the window opens, admits a request
    into every slot, so the window measures the steady state."""
    sess = serving.Session(cell_factory(config, mix))
    sess.make_weights(SEED)
    sess.build_server()
    sess.warm_up()
    run = sess.run(SEED, 1.0)
    fill = run["steps"][0]
    assert fill.t1 <= run["t0"] and run["fill_s"] > 0
    assert sum(pf["requests"] for pf in fill.prefills) == sess.slots
    assert all(run["t0"] <= st.t0 for st in run["steps"][1:])


def _altered_token(monkeypatch):
    """The sampled token is changed where the device produces it."""
    from repro.models import transformer as T
    orig = T.sample_fn

    def bad(params, hidden, cfg):
        return (orig(params, hidden, cfg) + 1) % cfg.vocab
    monkeypatch.setattr(T, "sample_fn", bad)


def _state_unchanged(monkeypatch):
    """Every cache write returns the cache as it was: a step that leaves
    its state unchanged."""
    from repro.models import attention as A
    monkeypatch.setattr(A, "_cache_write",
                        lambda buf, new, pos, mask=None: buf)


def _half_batch(monkeypatch):
    """The bucketed prefill commits only the first half of the slot rows:
    half of each admitted batch is left out of the cache."""
    import jax.numpy as jnp
    from repro.models import attention as A
    orig = A._cache_write

    def half(buf, new, pos, mask=None):
        if mask is not None and jnp.ndim(pos) == 0:
            mask = mask & (jnp.arange(mask.shape[0]) < mask.shape[0] // 2)
        return orig(buf, new, pos, mask)
    monkeypatch.setattr(A, "_cache_write", half)


@pytest.mark.parametrize("config,mix", CELLS)
@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_batch])
def test_broken_timed_path_is_not_correct(cell_factory, monkeypatch, config,
                                          mix, fault):
    fault(monkeypatch)
    out = run_cell(cell_factory(config, mix))
    assert not out["correct"], out["checks"]


def _frozen_slot(monkeypatch):
    """The request in the first slot never gains another token: its decode
    never advances, and it never finishes."""
    from repro.serve.batcher import BatchServer
    orig = BatchServer.step

    def step(self, params):
        slot = self.slots[0]
        held = slot.remaining if slot.req is not None \
            and slot.req.rid >= 0 else 0          # warm-up rids are negative
        if held:
            slot.remaining = 0                    # sits out the decode
        try:
            return orig(self, params)
        finally:
            if held:
                slot.remaining = held
    monkeypatch.setattr(BatchServer, "step", step)


@pytest.mark.parametrize("config,mix", BACKLOG)
def test_stuck_request_is_not_correct(cell_factory, monkeypatch, config, mix):
    """A backlog request that never advances is counted as failed, not
    withdrawn with the requests the end of the run cut off."""
    _frozen_slot(monkeypatch)
    out = run_cell(cell_factory(config, mix))
    assert not out["correct"] and out["failed"] >= 1, out["checks"]


@pytest.mark.parametrize("config,mix", CELLS)
def test_control_is_not_correct(cell_factory, config, mix):
    """The control: on the float tier the program with its own int8 path
    switched on, on the int8 tier the reference at int4, read at the same
    positions of the same prompts and served tokens as a sound run."""
    from control import control_gaps
    cell = cell_factory(config, mix)
    sess = serving.Session(cell)
    sess.make_weights(SEED)
    sess.build_server()
    sess.warm_up()
    run = sess.run(SEED, 1.5)
    sess.free_server()
    picked = check.sample(run["records"], SEED)
    max_out = int(max(traffic.output_lengths(cell.traffic)))
    sound = check.gaps(cell.family, sess.params, picked,
                       cell.config["model"], sess.max_len, max_out,
                       REFERENCE_BITS[cell.tier])
    ctl = control_gaps(sess, picked, max_out)
    limits = cell.config["limits"]
    assert sound.max() <= limits["max_gap"] \
        and sound.mean() <= limits["mean_gap"]
    assert np.isfinite(ctl).all()
    assert ctl.max() > limits["max_gap"] or ctl.mean() > limits["mean_gap"]
