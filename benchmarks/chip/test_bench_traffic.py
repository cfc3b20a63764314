"""The traffic generator: same seed, same requests; every seed the same
sizes in another order."""
import collections
import json
import os

import numpy as np

from chip.traffic import Traffic, strata

HERE = os.path.dirname(os.path.abspath(__file__))


def mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def take(t, n):
    g = t.requests()
    return [next(g) for _ in range(n)]


def test_same_seed_same_requests():
    p = mix("b1-mixed")
    a = take(Traffic(p, 32128, 2 ** 31 + 3), 20)
    b = take(Traffic(p, 32128, 2 ** 31 + 3), 20)
    assert [(r.task, r.max_new) for r in a] == [(r.task, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = take(Traffic(p, 32128, 2 ** 31 + 4), 20)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_every_seed_serves_the_same_sizes_per_block():
    p = mix("b1-mixed")
    k = p["block"]
    sizes = None
    for seed in (0, 1, 12345, 2 ** 31 + 9):
        block = take(Traffic(p, 32128, seed), k)
        lens = sorted(len(r.prompt) for r in block)
        assert lens == sorted(strata(32, 512, k, "log_uniform"))
        assert collections.Counter(r.task for r in block) == \
            collections.Counter({0: 3, 1: 3, 2: 3})
        if sizes is None:
            sizes = (lens, sorted(r.max_new for r in block))
        assert (lens, sorted(r.max_new for r in block)) == sizes


def test_tokens_stay_in_the_task_slice():
    p = mix("b1-mixed")
    V = 32128
    width = int(V * p["task_vocab_frac"])
    for r in take(Traffic(p, V, 5), 18):
        start = (r.task * (V - width)) // (p["n_tasks"] - 1)
        assert r.prompt.min() >= start and r.prompt.max() < start + width
        assert r.prompt.dtype == np.int32


def test_topic_holds_one_task_warmup_included():
    p = mix("b1-topic")
    for seed in (3, 4, 5):
        t = Traffic(p, 32128, seed)
        tasks = {r.task for r in take(t, 27)} | {r.task for r in t.warmup()}
        assert tasks == {seed % 3}


def test_warmup_reaches_every_power_of_two_prompt():
    t = Traffic(mix("b1-mixed"), 32128, 0)
    assert [len(r.prompt) for r in t.warmup()] == [32, 64, 128, 256, 512]
    assert {r.max_new for r in t.warmup()} == {8}
