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


def test_size_groups_keep_each_burst_s_work():
    m = mix("rag")
    assert m["order"]["kind"] == "size_groups"
    k, n = int(m["order"]["group"]), 96
    runs = [traffic.make_requests(m, n, 1000, s) for s in (3, 2**40 + 11)]
    lens = [np.array([len(r.prompt) for r in rs]) for rs in runs]
    assert not np.array_equal(lens[0], lens[1])  # the seed still orders
    assert sorted(lens[0]) == sorted(lens[1])
    # answers and sessions stay with their arrivals
    assert [(r.session, r.max_new) for r in runs[0]] == \
        [(r.session, r.max_new) for r in runs[1]]
    # each slot keeps a length of its own group of k ranks
    ranked = np.sort(lens[0])
    groups = lambda v: {i // k for i in np.flatnonzero(ranked == v)}  # noqa
    for a, b in zip(*lens):
        assert groups(a) & groups(b)
    # so every stretch of arrivals carries the same prompt tokens, to a
    # group's width
    for lo in range(0, n, 24):
        a, b = lens[0][lo:lo + 24].sum(), lens[1][lo:lo + 24].sum()
        assert abs(a - b) < 0.05 * a


def test_an_unknown_order_is_refused():
    m = dict(mix("rag"), order={"kind": "sorted"})
    try:
        traffic.make_requests(m, 10, 1000, 1)
    except ValueError as exc:
        assert "sorted" in str(exc)
    else:
        raise AssertionError("an unknown order was accepted")
