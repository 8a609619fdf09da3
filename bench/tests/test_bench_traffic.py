"""The traffic generator: the same work for every seed, in its own order,
drawn from the mix's stated distributions."""
import collections

import bench_tiny  # noqa: F401
import numpy as np
import pytest

from bench import harness
from bench import traffic as gen

MIX = harness.traffic_file("serve.multitenant")
SEEDS = [0, 7, 2**31 + 12345]


def _work(reqs):
    return (sorted(len(r.prompt) for r in reqs),
            sorted(r.max_new for r in reqs),
            collections.Counter(r.tenant for r in reqs))


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_requests_are_deterministic_by_seed(seed):
    a = gen.serve_requests(MIX, seed, 60.0, 1000)
    b = gen.serve_requests(MIX, seed, 60.0, 1000)
    assert a == b
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    assert all(0.0 <= r.due_s < 60.0 for r in a)


def test_every_seed_offers_the_same_work_in_another_order():
    block = MIX["block_s"]
    runs = [gen.serve_requests(MIX, s, block, 1000) for s in SEEDS]
    assert _work(runs[0]) == _work(runs[1]) == _work(runs[2])
    assert [len(r.prompt) for r in runs[0]] != \
        [len(r.prompt) for r in runs[1]]


def test_lengths_follow_the_stated_distributions():
    n = 2000
    p = gen.lengths(MIX["prompt"], n)
    assert p.min() >= MIX["prompt"]["min"] and p.max() <= MIX["prompt"]["max"]
    assert abs(np.median(p) - MIX["prompt"]["median"]) <= 1
    # lognormal, sigma 1: the 84th percentile is e times the median
    assert abs(np.percentile(p, 84.13) / np.median(p) - np.e) < 0.15
    o = gen.lengths(MIX["output"], n)
    lo, hi = MIX["output"]["min"], MIX["output"]["max"]
    assert set(o) == set(range(lo, hi + 1))
    assert abs(o.mean() - (lo + hi) / 2) < 0.1


def test_tenants_follow_zipf_and_ranks_alternate():
    counts = gen.zipf_counts(1000, 8, 1.0)
    assert counts.sum() == 1000
    want = 1000 / np.arange(1, 9) / (1 / np.arange(1, 9)).sum()
    assert np.all(np.abs(counts - want) < 1)
    assert gen.tenant_ranks(MIX) == [16, 64] * 4


def test_arrivals_keep_the_stated_rate():
    reqs = gen.serve_requests(MIX, 3, 10 * MIX["block_s"], 1000)
    rate = len(reqs) / (10 * MIX["block_s"])
    assert abs(rate - MIX["rate_per_s"]) / MIX["rate_per_s"] < 0.02
    gaps = np.diff([r.due_s for r in reqs])
    # exponential gaps: their spread is about their mean
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_train_batches_are_deterministic_and_every_row_differs():
    a = gen.train_batch(5, 0, 2, 16, 100)
    b = gen.train_batch(5, 0, 2, 16, 100)
    c = gen.train_batch(5, 1, 2, 16, 100)
    assert (np.asarray(a["tokens"]) == np.asarray(b["tokens"])).all()
    assert (np.asarray(a["tokens"])[:, 1:] ==
            np.asarray(a["labels"])[:, :-1]).all()
    rows = [tuple(r) for r in np.asarray(a["tokens"])] + \
        [tuple(r) for r in np.asarray(c["tokens"])]
    assert len(set(rows)) == 4
