"""The generator: a seed fixes the stream, every seed offers the same set
of lengths and arrivals in every block, in an order of its own."""
from __future__ import annotations

import itertools
import json

import numpy as np
import pytest

from conftest import BENCH
from harness import traffic


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def take(m, seed, n):
    return list(itertools.islice(traffic.requests(m, seed, 32256), n))


@pytest.mark.parametrize("name", ["chat", "chat-backlog", "code-backlog"])
def test_same_seed_same_stream(name):
    a, b = take(mix(name), 2 ** 40 + 3, 40), take(mix(name), 2 ** 40 + 3, 40)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.gap_s == y.gap_s for x, y in zip(a, b))
    c = take(mix(name), 5, 40)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert [(len(x.prompt), x.max_new) for x in a] != [
        (len(y.prompt), y.max_new) for y in c]


@pytest.mark.parametrize("name", ["chat", "code-backlog"])
def test_every_block_holds_the_same_work(name):
    m = mix(name)
    block = m["block"]
    for seed in (1, 2 ** 33 + 1):
        reqs = take(m, seed, 3 * block)
        for k in range(3):
            blk = reqs[k * block:(k + 1) * block]
            assert sorted(len(r.prompt) for r in blk) == sorted(
                traffic.prompt_lengths(m))
            assert sorted(r.max_new for r in blk) == sorted(
                traffic.output_lengths(m))


@pytest.mark.parametrize("name", ["chat", "code-backlog"])
def test_every_sub_block_holds_one_of_each_quarter(name):
    m = mix(name)
    quarters = [set(q) for q in np.sort(traffic.prompt_lengths(m)).reshape(
        traffic.SUB, -1)]
    reqs = take(m, 2 ** 35 + 9, 2 * m["block"])
    for k in range(0, len(reqs), traffic.SUB):
        sub = [len(r.prompt) for r in reqs[k:k + traffic.SUB]]
        assert all(any(n in q for n in sub) for q in quarters), sub


def test_lengths_stay_in_their_bounds_and_rate_holds():
    m = mix("chat")
    reqs = take(m, 7, 16 * 20)
    assert all(m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
               for r in reqs)
    assert all(m["output"]["min"] <= r.max_new <= m["output"]["max"]
               for r in reqs)
    rate = len(reqs) / sum(r.gap_s for r in reqs)
    assert rate == pytest.approx(m["arrival"]["rate_per_s"], rel=0.05)
