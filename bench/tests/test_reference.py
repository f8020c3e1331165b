"""The plain reference against answers worked out by hand."""
from bench.reference import Reference, read_back, replay
from bench.traffic import GET, MULTI_GET, PUT, SCAN


def value(tag):
    return b"v%d" % tag


def ref():
    return Reference(value, keys=[30, 10, 20], tags=[3, 1, 2])


def test_point_reads_and_scans_by_hand():
    r = ref()
    assert r.get(10) == b"v1" and r.get(20) == b"v2" and r.get(40) is None
    assert r.scan(0, 2) == [(10, b"v1"), (20, b"v2")]
    assert r.scan(15, 10) == [(20, b"v2"), (30, b"v3")]
    assert r.scan(31, 5) == []
    r.put(25, 7)
    r.put(10, 8)
    assert r.scan(11, 2) == [(20, b"v2"), (25, b"v7")]
    assert r.get(10) == b"v8"
    assert r.sorted == [10, 20, 25, 30]


def test_replay_sees_writes_in_call_order():
    calls = [(GET, 10, None, True),
             (PUT, 10, 9, False),
             (GET, 10, None, True),
             (MULTI_GET, [10, 20, 99], None, True),
             (PUT, 15, 5, False),
             (SCAN, 11, 2, True),
             (GET, 30, None, False)]       # not marked: never compared
    right = {0: b"v1", 2: b"v9", 3: [b"v9", b"v2", None],
             5: [(15, b"v5"), (20, b"v2")]}
    assert replay(ref(), calls, right) == dict(
        point_keys=5, wrong_point=0, scans=1, wrong_scans=0)

    stale = {**right, 2: b"v1"}                 # the write not seen
    assert replay(ref(), calls, stale)["wrong_point"] == 1
    short = {**right, 3: [b"v9"]}               # two answers missing
    assert replay(ref(), calls, short)["wrong_point"] == 2
    extra = {**right, 3: right[3] + [b"v2"]}    # one answer too many
    assert replay(ref(), calls, extra)["wrong_point"] == 1
    missed = {**right, 5: [(20, b"v2"), (30, b"v3")]}
    assert replay(ref(), calls, missed)["wrong_scans"] == 1
    longer = {**right, 5: right[5] + [(30, b"v3")]}
    assert replay(ref(), calls, longer)["wrong_scans"] == 1


def test_read_back_counts_every_wrong_key():
    r = ref()
    r.put(40, 4)
    assert read_back(r, [10, 40, 50], [b"v1", b"v4", None]) == 0
    assert read_back(r, [10, 40, 50], [b"v1", None, None]) == 1
    assert read_back(r, [10, 40, 50], [b"v1"]) == 2
    assert read_back(r, [10, 40], [b"v1", b"v4", None]) == 1
    assert read_back(r, [10, 40], [b"v2", b"v1"]) == 2
