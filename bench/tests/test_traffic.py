"""The traffic generator: one seed, one stream; seeds differ in order
only."""

import json
from collections import Counter

import numpy as np

import traffic
from conftest import BENCH


def mix(name):
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def test_same_seed_same_traffic():
    for name in ("chat", "rag"):
        a = traffic.make_requests(mix(name), 50, 1000, 2**40 + 7)
        b = traffic.make_requests(mix(name), 50, 1000, 2**40 + 7)
        assert [(r.session, r.prompt, r.max_new) for r in a] == \
            [(r.session, r.prompt, r.max_new) for r in b]
    g1 = traffic.arrival_gaps(mix("rag"), 80, 4.0)
    assert np.array_equal(g1, traffic.arrival_gaps(mix("rag"), 80, 4.0))


def test_seeds_share_the_sizes_and_arrivals():
    a = traffic.make_requests(mix("chat"), 200, 1000, 1)
    b = traffic.make_requests(mix("chat"), 200, 1000, 2)
    shape = lambda rs: Counter((r.session, len(r.prompt), r.max_new)  # noqa
                               for r in rs)
    assert shape(a) == shape(b)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    gaps = traffic.arrival_gaps(mix("chat"), 200, 20.0)
    assert np.isclose(gaps.sum(), 10.0) and (gaps > 0).all()
    bursty = traffic.arrival_gaps(mix("rag"), 2000, 2.0)
    assert 2.5 < bursty.std() / bursty.mean() < 3.5  # the mix's CV of 3


def test_lengths_stay_in_their_ranges():
    for name in ("chat", "rag"):
        m = mix(name)
        for r in traffic.make_requests(m, 300, 1000, 5):
            assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
            assert m["output"]["min"] <= r.max_new <= m["output"]["max"]
            assert all(1 <= t < 1000 for t in r.prompt)
