"""The generator: deterministic per seed, inside the mix's buckets and
lengths, and the same work for every seed."""
import collections

import numpy as np
import traffic


def _sizes(reqs):
    return collections.Counter((len(r.tokens), r.max_new) for r in reqs)


def test_same_seed_same_job():
    mix = traffic.load_mix("longdoc")
    a = traffic.job(mix, 12, 1000, 2**31 + 17, 3)
    b = traffic.job(mix, 12, 1000, 2**31 + 17, 3)
    assert all(np.array_equal(x.tokens, y.tokens) and x.max_new == y.max_new
               for x, y in zip(a, b))


def _order(reqs):
    return [(len(r.tokens), r.max_new) for r in reqs]


def test_seeds_change_ids_not_work():
    mix = traffic.load_mix("longdoc")
    a = traffic.job(mix, 24, 1000, 5, 0)
    b = traffic.job(mix, 24, 1000, 6, 0)
    c = traffic.job(mix, 24, 1000, 5, 1)
    # same sizes in the same order for every seed; jobs differ in order
    assert _order(a) == _order(b)
    assert _sizes(a) == _sizes(c) and _order(a) != _order(c)
    assert not any(np.array_equal(x.tokens, y.tokens)
                   for x, y in zip(a, b))


LOGNORMAL = {"prompt": {"buckets": [1024, 2048, 3072], "median": 1500,
                         "sigma": 0.5},
             "output": {"median": 64, "sigma": 0.6, "min": 32, "max": 192}}


def test_buckets_and_lengths():
    mix = traffic.load_mix("longdoc")
    for n in (12, 24, 48):
        reqs = traffic.job(mix, n, 49152, 1, 0)
        assert {len(r.tokens) for r in reqs} == {3456}
        # answers in the tasks' shares: one task in three caps at 64
        assert sorted(r.max_new for r in reqs) == [64] * (n // 3) + [128] * (
            n - n // 3)
        assert all(len(r.tokens) + r.max_new <= 4096 for r in reqs)
        assert all(r.tokens.dtype == np.int32 and r.tokens.min() >= 0
                   and r.tokens.max() < 49152 for r in reqs)
    # a log-normal mix: lengths snapped up to the buckets, clipped outputs,
    # heavy tail: every bucket is used, the middle one most
    reqs = traffic.job(LOGNORMAL, 48, 100, 1, 0)
    got = collections.Counter(len(r.tokens) for r in reqs)
    assert set(got) == {1024, 2048, 3072} and got[2048] > got[3072]
    assert all(32 <= r.max_new <= 192 for r in reqs)
    assert len({r.max_new for r in reqs}) > 10


def test_warmup_covers_every_bucket():
    assert [len(r.tokens) for r in traffic.warmup_job(
        traffic.load_mix("longdoc"), 100, 1)] == [3456]
    assert sorted(len(r.tokens) for r in traffic.warmup_job(LOGNORMAL, 100, 1)) \
        == [1024, 2048, 3072]
