"""How ``correct`` is decided.

Once the window has closed, a sample of the requests the server finished,
drawn from the seed and always holding the longest one, is run through the
plain reference of the configuration's family (``token_gaps`` of its module
under ``bench/harness/families``) over each prompt and its served tokens, on
the weights as the cell holds them: on a mesh, sharded as served. For every served token the gap by which its reference logit lies
below the reference's best is read; the numbers compared are the widest gap
and the mean gap over the sample, each against the limit the configuration
file states. Served tokens are greedy, so a sound program reads gaps of its
rounding only.
"""
from __future__ import annotations

from typing import List

import numpy as np

SAMPLE_TOKENS = 320     # served tokens the sample holds at least ...
SAMPLE_MAX = 16         # ... unless it reaches this many requests first


def sample(records, seed: int) -> List:
    done = [r for r in records if r.out and not r.error]
    if not done:
        return []
    rng = np.random.default_rng([seed, 1])
    longest = max(done, key=lambda r: r.n_prompt + len(r.out))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    picked, tokens = [longest], len(longest.out)
    for r in rest:
        if tokens >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX:
            break
        picked.append(r)
        tokens += len(r.out)
    return picked


def gaps(family, params, picked, model: dict, max_len: int, max_out: int,
         bits: int) -> np.ndarray:
    """Reference gaps of every served token of ``picked``, concatenated, by
    the ``family`` module's reference."""
    import jax.numpy as jnp
    key = family.model_key(model)
    out = []
    for r in picked:
        seq = np.zeros((max_len,), np.int32)
        n = r.n_prompt + len(r.out) - 1
        seq[:n] = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        served = np.zeros((max_out,), np.int32)
        served[:len(r.out)] = r.out
        g, _ = family.token_gaps(params, jnp.asarray(seq),
                                 jnp.asarray(r.n_prompt, jnp.int32),
                                 jnp.asarray(served), model=key,
                                 bits=bits, n_out=max_out)
        out.append(np.asarray(g)[:len(r.out)])
    return np.concatenate(out) if out else np.zeros((0,))


def validity(records, vocab: int) -> int:
    """Finished requests whose answer is malformed: not exactly its token
    budget (the traffic never stops early), or an id outside the vocabulary."""
    bad = 0
    for r in records:
        if r.out is not None and (len(r.out) != r.max_new or any(
                not 0 <= t < vocab for t in r.out)):
            bad += 1
    return bad


def checks(values: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}}, every number beside its limit."""
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def passed(cks: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in cks.values())
