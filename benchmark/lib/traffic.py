"""The one generator of inputs: a traffic file's parameters and a seed in,
token ids and lengths out. Every seed gets the same multiset of lengths (the
quantiles of the file's distributions), so a seed never changes how much work
a run holds; a mix may also fix their order (`order_seed`).
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def _lengths(spec, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles of a clipped distribution."""
    kind = spec["dist"]
    if kind == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    q = (np.arange(n) + 0.5) / n
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def requests(traffic: dict, vocab: int, seed: int):
    """An endless stream of requests {prompt: int32 ids, max_new: int} for a
    serving mix. Lengths cycle through `pool` quantile pairs, permuted anew
    each cycle (prompt and output lengths apart, so they do not correlate).
    The token ids come from the run's seed. The order of the lengths comes
    from the mix's `order_seed` where it has one, and is then the same for
    every run seed: where the system's speed follows which requests overlap,
    the order is part of the mix. Without it the run's seed orders them."""
    pool = int(traffic.get("pool", 256))
    order = rng_for(traffic["order_seed"], 5) if "order_seed" in traffic \
        else rng_for(seed, 1)
    ids = rng_for(seed, 4)
    p_len = _lengths(traffic["prompt_len"], pool)
    o_len = _lengths(traffic["output_len"], pool)
    while True:
        for a, b in zip(order.permutation(p_len), order.permutation(o_len)):
            yield {"prompt": ids.integers(0, vocab, int(a), dtype=np.int32),
                   "max_new": int(b)}


def train_batch(traffic: dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """int32 ids [batch, seq] of step `step`: rows all differ, and step k of
    a seed is the same whoever asks (the runner, the reference)."""
    rng = rng_for(seed, 1000 + step)
    return rng.integers(0, vocab, (int(traffic["batch"]), int(traffic["seq"])),
                        dtype=np.int32)
