"""lib/traffic.py: the same seed gives the same inputs; every seed gets the
same multiset of lengths."""
import itertools
import json
import os

import numpy as np

from benchmark import run as harness
from benchmark.lib import traffic, weights


def _mix(name):
    with open(os.path.join(harness.HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _take(mix, seed, n):
    return list(itertools.islice(traffic.requests(mix, 64000, seed), n))


def test_same_seed_same_requests_and_large_seeds():
    mix = _mix("chat_closed32")
    big = 2**31 + 12345
    a, b = _take(mix, big, 40), _take(mix, big, 40)
    assert all(np.array_equal(x["prompt"], y["prompt"]) and x["max_new"] == y["max_new"]
               for x, y in zip(a, b))
    c = _take(mix, big + 1, 40)
    # this mix fixes the order of its lengths; the seed draws the ids
    assert [len(x["prompt"]) for x in a] == [len(x["prompt"]) for x in c]
    assert not any(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, c))
    free = dict(mix)
    del free["order_seed"]
    d, e = _take(free, 1, 40), _take(free, 2, 40)
    assert [len(x["prompt"]) for x in d] != [len(x["prompt"]) for x in e]
    assert int(weights.seed_u32(big)) == big and int(weights.seed_u32(2**32 + 5)) == 5


def test_every_seed_the_same_multiset_within_the_clips():
    mix = _mix("chat_closed32")
    a, b = _take(mix, 1, mix["pool"]), _take(mix, 2, mix["pool"])
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        assert sorted(map(key, a)) == sorted(map(key, b))
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) >= 16 and max(lens) <= 1536
    assert 230 <= float(np.median(lens)) <= 280
    assert all(16 <= r["max_new"] <= 384 for r in a)
    # the longest request fits a slot
    assert max(lens) + max(r["max_new"] for r in a) < 2048


def test_train_batches_repeat_and_rows_differ():
    mix = _mix("pretrain_b4_s2048")
    a = traffic.train_batch(mix, 32768, 7, 3)
    assert a.shape == (4, 2048) and a.dtype == np.int32
    assert np.array_equal(a, traffic.train_batch(mix, 32768, 7, 3))
    assert not np.array_equal(a, traffic.train_batch(mix, 32768, 7, 4))
    assert len({r.tobytes() for r in a}) == 4
