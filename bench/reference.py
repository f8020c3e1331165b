"""The plain reference: what a key-value store with these semantics answers.

It imports nothing of the program and takes nothing the program made: it
is built from the harness's own inputs (the loaded records and the client's
calls, in the order the one client made them) and holds each key's value as
the tag :class:`bench.traffic.Values` makes it from.  A ``dict`` gives point
reads; a sorted list of keys gives range reads.  With one client and one
writer, every read must see every write made before it (the configurations'
read-your-writes guarantee), so replaying the calls in order gives the one
right answer to each.
"""
from __future__ import annotations

import bisect
from typing import Iterable, List, Optional, Tuple

from .traffic import GET, MULTI_GET, PUT, SCAN


class Reference:
    def __init__(self, values, keys: Iterable[int], tags: Iterable[int]):
        self.values = values
        self.tag = dict(zip(keys, tags))
        self.sorted = sorted(self.tag)

    def put(self, key: int, tag: int) -> None:
        if key not in self.tag:
            bisect.insort(self.sorted, key)
        self.tag[key] = tag

    def get(self, key: int) -> Optional[bytes]:
        tag = self.tag.get(key)
        return None if tag is None else self.values(tag)

    def scan(self, start: int, count: int) -> List[Tuple[int, bytes]]:
        i = bisect.bisect_left(self.sorted, start)
        return [(k, self.values(self.tag[k]))
                for k in self.sorted[i:i + count]]


def replay(ref: Reference, calls: list, answers: dict) -> dict:
    """Replay ``calls`` on ``ref`` in order and compare every answer kept in
    ``answers`` (call index -> what the store returned).

    Returns counts: point-read keys and scans checked, and how many of each
    differ from the reference.  A ``multi_get`` answer counts each key
    whose value differs, and each answer missing or beyond the batch's
    end.  A scan differs if any entry, key or value, differs or if it is
    longer or shorter.
    """
    out = dict(point_keys=0, wrong_point=0, scans=0, wrong_scans=0)
    for i, (kind, arg, extra, _) in enumerate(calls):
        if kind == PUT:
            ref.put(arg, extra)
            continue
        got = answers.get(i)
        if got is None and i not in answers:
            continue
        if kind == GET:
            out["point_keys"] += 1
            out["wrong_point"] += got != ref.get(arg)
        elif kind == MULTI_GET:
            out["point_keys"] += len(arg)
            out["wrong_point"] += _wrong(ref, arg, got)
        elif kind == SCAN:
            out["scans"] += 1
            want = ref.scan(arg, extra)
            mine = [(int(k), v) for k, v in got]
            out["wrong_scans"] += mine != want
    return out


def read_back(ref: Reference, keys: List[int], got: List[Optional[bytes]]
              ) -> int:
    """Keys whose value read back after the window differs from the
    reference's final state."""
    return _wrong(ref, keys, got)


def _wrong(ref: Reference, keys: List[int], got: List[Optional[bytes]]
           ) -> int:
    """Answers to a batch of keys that differ from the reference's, with
    each answer missing from the batch or beyond its end counted wrong."""
    got = list(got)
    return (sum(g != ref.get(k) for k, g in zip(keys, got))
            + abs(len(got) - len(keys)))
