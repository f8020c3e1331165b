"""One generator for every traffic mix under ``bench/traffic/``.

A mix file gives the shares of the operations, how keys are chosen, how
reads are issued (``get`` or ``multi_get`` batches) and how long scans are.
A configuration file gives the records: how many, their value size and how
a record number maps onto a key.  From those and the seed, :class:`Calls`
yields the same stream of client calls every time, and :class:`Values`
makes every value from a tag, so the reference can rebuild any value
without keeping it.

The Zipfian generator (theta 0.99 over ``[0, n)``) and the splitmix64 key
scramble are YCSB's, as ``benchmarks/common.py`` has them; they are copied
here so that the yardstick does not move with the program.
"""
from __future__ import annotations

import numpy as np

CHUNK_CALLS = 1024
ZIPF_THETA = 0.99             # YCSB's zipfian constant
CHECK_SHARE = 0.25            # share of read calls and scans whose answers
                              # the reference compares
VALUE_POOL_BYTES = 1 << 22
WRITE_TAG_BASE = 1 << 40      # tags of writes made by the client; loaded
                              # record i has tag i

GET, MULTI_GET, PUT, SCAN = "get", "multi_get", "put", "scan"
CLASS_OF = {GET: "read", MULTI_GET: "read", PUT: "write", SCAN: "scan"}
OPS = ("read", "update", "insert", "scan")
MIX_KEYS = {"source", "ops", "read_call", "read_batch", "keys",
            "scan_length", "warmup_calls"}


def seed_words(seed: int, stream: int) -> list:
    """Entropy for ``np.random.default_rng``: any whole number, one stream
    of draws per purpose."""
    return [int(seed) & ((1 << 64) - 1), stream]


def splitmix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser over uint64: YCSB's key scramble, so that hot
    record numbers spread over the key space."""
    z = np.asarray(x, dtype=np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def key_of(numbers: np.ndarray, mapping: str) -> np.ndarray:
    """Record numbers -> uint64 keys, as the configuration maps them."""
    numbers = np.asarray(numbers, dtype=np.uint64)
    if mapping == "splitmix64":
        return splitmix64(numbers)
    if mapping == "identity":
        return numbers
    raise ValueError(f"unknown key mapping {mapping!r}")


class Zipfian:
    """YCSB's zipfian generator (theta 0.99) over ``[0, n)``: rank 0 is the
    most requested record."""

    def __init__(self, n: int, rng: np.random.Generator):
        self.rng = rng
        zeta = np.cumsum(1.0 / np.arange(1, n + 1, dtype=np.float64)
                         ** ZIPF_THETA)
        self.cdf = zeta / zeta[-1]

    def sample(self, size: int) -> np.ndarray:
        return np.searchsorted(self.cdf, self.rng.random(size))


class Values:
    """Every value the benchmark writes, made from a tag: eight bytes of
    the tag, then a slice of a seeded pool of random bytes.  Two tags give
    two different values, so a stale or misplaced value never passes."""

    def __init__(self, seed: int, nbytes: int):
        if nbytes < 8:
            raise ValueError("values carry an 8-byte tag")
        self.nbytes = nbytes
        rng = np.random.default_rng(seed_words(seed, 1))
        self.pool = rng.bytes(VALUE_POOL_BYTES)
        self.span = VALUE_POOL_BYTES - nbytes

    def __call__(self, tag: int) -> bytes:
        off = ((tag * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFF) % self.span
        return tag.to_bytes(8, "little") + self.pool[off:off + self.nbytes - 8]


def check_mix(mix: dict, name: str) -> dict:
    """Refuse a mix file with unknown keys or shares that do not add up.
    Every mix is driven by one closed-loop client; a key asking for more
    clients or an arrival rate is unknown here and refused."""
    extra = set(mix) - MIX_KEYS
    if extra:
        raise ValueError(f"mix {name}: unknown keys {sorted(extra)}")
    ops = mix["ops"]
    if set(ops) - set(OPS) or abs(sum(ops.values()) - 1.0) > 1e-9:
        raise ValueError(f"mix {name}: ops must be shares of {OPS} adding "
                         f"up to 1")
    if mix.get("read_call", GET) not in (GET, MULTI_GET):
        raise ValueError(f"mix {name}: read_call is get or multi_get")
    if mix.get("keys", "zipfian") not in ("zipfian", "uniform"):
        raise ValueError(f"mix {name}: keys are zipfian or uniform")
    return mix


class Calls:
    """The client's calls for one mix, drawn from the seed in chunks.

    Each call is a tuple ``(kind, arg, extra, check)``:

    - ``(GET, key, None, check)``, ``(MULTI_GET, [keys], None, check)``;
    - ``(PUT, key, tag, False)``: the value is ``Values(tag)``;
    - ``(SCAN, start_key, length, check)``.

    ``check`` marks the reads whose answers the reference compares,
    ``CHECK_SHARE`` of them drawn from the seed.  Shares of ``ops`` are
    shares of calls.  Reads, updates and scan starts pick loaded records, by YCSB's
    zipfian or uniformly; an insert writes the next record number past the
    loaded ones.  The warm-up takes the stream's first calls and the
    window the rest.
    """

    def __init__(self, mix: dict, records: int, key_map: str, seed: int):
        self.records = records
        self.key_map = key_map
        self.rng = np.random.default_rng(seed_words(seed, 2))
        self.kinds = [op for op in OPS if mix["ops"].get(op, 0) > 0]
        self.shares = np.array([mix["ops"][op] for op in self.kinds])
        self.read_call = mix.get("read_call", GET)
        self.batch = int(mix.get("read_batch", 1))
        lo, hi = mix.get("scan_length", [1, 1])
        self.scan_lo, self.scan_hi = int(lo), int(hi)
        self.zipf = (Zipfian(records, self.rng)
                     if mix.get("keys", "zipfian") == "zipfian" else None)
        self.next_insert = records
        self.tag = WRITE_TAG_BASE
        self._buf: list = []
        self._pos = 0

    def _records(self, n: int) -> np.ndarray:
        if self.zipf is not None:
            return self.zipf.sample(n)
        return self.rng.integers(0, self.records, n)

    def _chunk(self) -> list:
        rng, n = self.rng, CHUNK_CALLS
        kinds = rng.choice(len(self.kinds), n, p=self.shares)
        check = (rng.random(n) < CHECK_SHARE).tolist()
        per_call = self.batch if self.read_call == MULTI_GET else 1
        keys = key_of(self._records(n * per_call), self.key_map).tolist()
        lengths = rng.integers(self.scan_lo, self.scan_hi + 1, n).tolist()
        out = []
        for i, k in enumerate(kinds.tolist()):
            op = self.kinds[k]
            if op == "read":
                if self.read_call == MULTI_GET:
                    out.append((MULTI_GET, keys[i * per_call:(i + 1) * per_call],
                                None, check[i]))
                else:
                    out.append((GET, keys[i * per_call], None, check[i]))
            elif op == "scan":
                out.append((SCAN, keys[i * per_call], lengths[i], check[i]))
            else:
                if op == "insert":
                    key = int(key_of([self.next_insert], self.key_map)[0])
                    self.next_insert += 1
                else:
                    key = keys[i * per_call]
                self.tag += 1
                out.append((PUT, key, self.tag, False))
        return out

    def next(self) -> tuple:
        if self._pos == len(self._buf):
            self._buf, self._pos = self._chunk(), 0
        call = self._buf[self._pos]
        self._pos += 1
        return call


def ops_in(call: tuple) -> int:
    """Operations in one call: a key read, a record written or a scan."""
    return len(call[1]) if call[0] == MULTI_GET else 1
