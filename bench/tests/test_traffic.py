"""The generator's shares, lengths and keys on fixed seeds."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench.traffic import (CHECK_SHARE, GET, MULTI_GET, PUT, SCAN,
                           WRITE_TAG_BASE, Calls, Values, Zipfian, check_mix,
                           key_of, splitmix64)

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
SEED = 3_000_000_019          # above 2**31: seeds need not fit 32 bits


def mix(name):
    return check_mix(json.loads((TRAFFIC / f"{name}.json").read_text()),
                     name)


def draw(name, n, records=10_000, key_map="splitmix64", seed=SEED):
    calls = Calls(mix(name), records, key_map, seed)
    return [calls.next() for _ in range(n)]


def test_same_seed_same_calls_other_seed_other_calls():
    assert draw("ycsb-e", 3000) == draw("ycsb-e", 3000)
    assert draw("ycsb-e", 3000) != draw("ycsb-e", 3000, seed=SEED + 1)


def test_get_and_update_shares():
    calls = Calls({"ops": {"read": 0.5, "update": 0.5}, "read_call": "get",
                   "keys": "zipfian", "warmup_calls": 0}, 10_000,
                  "splitmix64", SEED)
    got = Counter(calls.next()[0] for _ in range(20_000))
    assert set(got) == {GET, PUT}
    assert abs(got[GET] / 20_000 - 0.5) < 0.015


@pytest.mark.parametrize("name,want", [
    ("ycsb-e", {SCAN: 0.95, PUT: 0.05}),
    ("ycsb-c-multiget", {MULTI_GET: 1.0}),
    ("dbbench-overwrite", {PUT: 1.0}),
])
def test_shares(name, want):
    n = 20_000
    got = Counter(c[0] for c in draw(name, n))
    assert set(got) == set(want)
    for kind, share in want.items():
        assert abs(got[kind] / n - share) < 0.015, (kind, got[kind] / n)


def test_scan_lengths_are_uniform_1_to_100():
    lengths = np.array([c[2] for c in draw("ycsb-e", 20_000)
                        if c[0] == SCAN])
    assert lengths.min() == 1 and lengths.max() == 100
    assert abs(lengths.mean() - 50.5) < 1.0


def test_inserts_take_new_records_in_order():
    records = 10_000
    puts = [c for c in draw("ycsb-e", 5000, records=records) if c[0] == PUT]
    want = key_of(np.arange(records, records + len(puts)),
                  "splitmix64").tolist()
    assert [c[1] for c in puts] == want
    tags = [c[2] for c in puts]
    assert tags == sorted(set(tags)) and tags[0] > WRITE_TAG_BASE


def test_multiget_batches_and_check_share():
    calls = draw("ycsb-c-multiget", 4000)
    assert {len(c[1]) for c in calls} == {256}
    share = sum(c[3] for c in calls) / len(calls)
    assert abs(share - CHECK_SHARE) < 0.03


def test_uniform_keys_cover_the_records():
    keys = [c[1] for c in draw("dbbench-overwrite", 50_000, records=1000,
                               key_map="identity")]
    assert min(keys) == 0 and max(keys) == 999
    counts = np.bincount(keys, minlength=1000)
    assert counts.max() < 3 * counts.mean()


def test_zipfian_skew():
    z = Zipfian(100_000, np.random.default_rng(1))
    ranks = z.sample(200_000)
    counts = np.bincount(ranks, minlength=100_000)
    # YCSB's zipfian at theta 0.99: rank 0 draws 1/zeta(n) of the requests
    zeta = np.sum(1.0 / np.arange(1, 100_001) ** 0.99)
    assert abs(counts[0] / ranks.size - 1 / zeta) < 0.005
    assert counts[0] > counts[1] > counts[10] > counts[1000]


def test_scramble_is_splitmix64():
    # reference values of splitmix64's finaliser
    assert int(splitmix64(np.array([0], np.uint64))[0]) == 0xE220A8397B1DCDAF
    assert len(set(key_of(np.arange(100_000), "splitmix64").tolist())) \
        == 100_000


def test_values_are_distinct_and_sized():
    v = Values(SEED, 100)
    vals = [v(t) for t in range(1000)] + [v(WRITE_TAG_BASE + 1)]
    assert {len(x) for x in vals} == {100}
    assert len(set(vals)) == len(vals)
    assert Values(SEED, 100)(7) == v(7) != Values(SEED + 1, 100)(7)


def test_bad_mix_is_refused():
    with pytest.raises(ValueError):
        check_mix({"ops": {"read": 0.5}}, "half")
    with pytest.raises(ValueError):
        check_mix({"ops": {"read": 1.0}, "target": 1000}, "open")
