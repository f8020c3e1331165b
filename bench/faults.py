"""Faults planted under the timed path, for the controls and the fault
tests only: no run of ``bench/run.py`` plants one.

Each is a context manager over one store, active from just after the store
is built until it has been read back.  Those that patch a module restore it
on exit.  ``every`` sets how often a fault strikes (one in a thousand on
the chip; more often in the tests' short CPU runs).

- ``lossy_probe``: the control of the probe cell.  The device bloom probe
  answers 'absent' for one in a thousand keys it should answer 'maybe' for:
  the false negatives a cheaper filter or probe would give, which break the
  configurations' guarantee that an acknowledged write is readable.
- ``lose_writes``: the control of the cells that write.  One put in a
  thousand is acknowledged and dropped.
- ``half_batch``: ``multi_get`` answers the first half of each batch and
  leaves the rest out.
- ``alter_answer``: one read call in a thousand (``get``, ``multi_get`` or
  ``scan``) returns one value with one byte flipped.
- ``alter_written_value``: one put in a thousand stores its value with one
  byte flipped.
- ``extra_answer``: one ``multi_get`` in a thousand returns one answer
  more than it was asked for.
"""
from __future__ import annotations

import contextlib

import numpy as np

EVERY = 1000


@contextlib.contextmanager
def lossy_probe(db, every: int = EVERY):
    from repro.kernels import ops

    orig = ops.bloom_probe_filter
    seen = [0]

    def probe(bf, keys):
        maybe = np.array(orig(bf, keys), dtype=bool)
        idx = np.nonzero(maybe)[0]
        hit = (np.arange(idx.size) + seen[0]) % every == 0
        seen[0] += idx.size
        maybe[idx[hit]] = False
        return maybe

    ops.bloom_probe_filter = probe
    try:
        yield
    finally:
        ops.bloom_probe_filter = orig


@contextlib.contextmanager
def lose_writes(db, every: int = EVERY):
    orig, n = db.put, [0]

    def put(key, value):
        n[0] += 1
        if n[0] % every:
            orig(key, value)

    db.put = put
    yield


@contextlib.contextmanager
def half_batch(db):
    orig = db.multi_get

    def multi_get(keys, *a, **kw):
        keys = list(keys)
        half = len(keys) // 2 or len(keys)
        return orig(keys[:half], *a, **kw)

    db.multi_get = multi_get
    yield


@contextlib.contextmanager
def alter_written_value(db, every: int = EVERY):
    orig, n = db.put, [0]

    def put(key, value):
        n[0] += 1
        if n[0] % every == 0:
            value = bytes([value[0] ^ 1]) + value[1:]
        orig(key, value)

    db.put = put
    yield


@contextlib.contextmanager
def alter_answer(db, every: int = EVERY):
    orig = db.get, db.multi_get, db.scan
    n = [0]

    def flip(value):
        return value if not value else bytes([value[0] ^ 1]) + value[1:]

    def due():
        n[0] += 1
        return n[0] % every == 0

    def get(key, *a, **kw):
        out = orig[0](key, *a, **kw)
        return flip(out) if due() else out

    def multi_get(keys, *a, **kw):
        out = orig[1](keys, *a, **kw)
        if due():
            i = next((j for j, v in enumerate(out) if v), None)
            if i is not None:
                out[i] = flip(out[i])
        return out

    def scan(start, count, *a, **kw):
        out = orig[2](start, count, *a, **kw)
        if due() and out:
            out[0] = (out[0][0], flip(out[0][1]))
        return out

    db.get, db.multi_get, db.scan = get, multi_get, scan
    yield


@contextlib.contextmanager
def extra_answer(db, every: int = EVERY):
    orig, n = db.multi_get, [0]

    def multi_get(keys, *a, **kw):
        out = orig(keys, *a, **kw)
        n[0] += 1
        return out + out[-1:] if n[0] % every == 0 else out

    db.multi_get = multi_get
    yield


PLANTS = {"lossy_probe": lossy_probe, "lose_writes": lose_writes,
          "half_batch": half_batch,
          "alter_written_value": alter_written_value,
          "alter_answer": alter_answer, "extra_answer": extra_answer}
