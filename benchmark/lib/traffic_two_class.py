"""A closed loop of two classes of clients over one server: each class has its
own distribution of prompt lengths and its own share of the clients (a few
clients that send long documents beside many that chat), and a client's next
request is the next of its class. As `lib.traffic` does, every seed gets the
same multiset of lengths (the mid-quantiles of each class's pool) in the order
the mix's `order_seed` fixes; the seed draws the token ids.
"""
from __future__ import annotations

from benchmark.lib.traffic import _lengths, rng_for


def client_classes(traffic: dict) -> list:
    """The class index of every client, clients of a class side by side."""
    out = []
    for k, cls in enumerate(traffic["classes"]):
        out += [k] * int(cls["clients"])
    if len(out) != int(traffic["clients"]):
        raise ValueError("the classes' clients do not add up to `clients`")
    return out


def class_requests(traffic: dict, k: int, vocab: int, seed: int):
    """An endless stream of class `k`'s requests {prompt, max_new}."""
    cls = traffic["classes"][k]
    pool = int(cls["pool"])
    order = rng_for(traffic["order_seed"], 50 + k) if "order_seed" in traffic \
        else rng_for(seed, 10 + k)
    ids = rng_for(seed, 40 + k)
    p_len = _lengths(cls["prompt_len"], pool)
    o_len = _lengths(cls.get("output_len", traffic["output_len"]), pool)
    import numpy as np
    while True:
        for a, b in zip(order.permutation(p_len), order.permutation(o_len)):
            yield {"prompt": ids.integers(0, vocab, int(a), dtype=np.int32),
                   "max_new": int(b)}


def streams(traffic: dict, vocab: int, seed: int) -> list:
    """One request stream a class."""
    return [class_requests(traffic, k, vocab, seed)
            for k in range(len(traffic["classes"]))]
