"""Bloom filters + the Monkey/Autumn optimal FPR allocation (paper Eq. 2, 7-10).

``BloomFilter`` is a vectorized double-hashing bloom filter over uint64 keys.
Bit positions are computed with the *same* 32-bit murmur-style hash family as
the device batched probe (``repro.kernels.bloom_probe.hash_pair``) and the
bitset is stored as uint32 words, so the engine's batched read path can
probe the identical filter either in numpy (``may_contain``) or on the
device (``repro.kernels.ops.bloom_probe_filter``) and get bit-identical
answers (DESIGN.md §3).

``allocate_fprs`` solves the Monkey optimization adapted to Garnering: minimize
the zero-result point-read cost R = sum_i p_i subject to the total filter
memory budget (Eq. 8).  The Lagrangian solution is p_i proportional to N_i
(capped at 1), which for Garnering capacities reproduces Eq. 9:
p_{L-i} = p_L * c^{i(i-1)/2} / T^i.
"""
from __future__ import annotations

import math
import sys
from typing import Optional, Sequence

import numpy as np

LN2 = math.log(2.0)
LN2_SQ = LN2 * LN2


def _mix32(x: np.ndarray, c1: int, c2: int) -> np.ndarray:
    """numpy twin of kernels.bloom_probe._mix32 (must stay in lockstep)."""
    x = x.astype(np.uint32, copy=True)
    x ^= x >> np.uint32(16)
    x *= np.uint32(c1)
    x ^= x >> np.uint32(13)
    x *= np.uint32(c2)
    x ^= x >> np.uint32(16)
    return x


def hash_pair(keys: np.ndarray):
    """Two independent uint32 hashes of u64 keys — identical positions to the
    device probe's ``hash_pair`` on the (lo, hi) halves."""
    keys = np.asarray(keys, dtype=np.uint64)
    lo = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (keys >> np.uint64(32)).astype(np.uint32)
    h1 = _mix32(lo ^ _mix32(hi, 0x85EBCA6B, 0xC2B2AE35),
                0xCC9E2D51, 0x1B873593)
    h2 = _mix32(hi ^ _mix32(lo, 0x27D4EB2F, 0x165667B1),
                0x9E3779B9, 0x85EBCA77) | np.uint32(1)
    return h1, h2


def build_bits(h1: np.ndarray, h2: np.ndarray, k: int, m_bits: int
               ) -> np.ndarray:
    """Construct the uint32-word bitset from hashes in one vectorized pass.

    All ``k * n`` double-hash positions are computed at once, scattered into
    a boolean bit map (duplicate positions collapse for free), and packed
    little-endian — the exact word/bit layout ``may_contain`` and the device
    probe index.  Replaces the k-iteration ``np.bitwise_or.at`` loop,
    which is unbuffered and dominates compaction's filter-rebuild cost.
    """
    ks = np.arange(k, dtype=np.uint32)[:, None]
    pos = (h1[None, :] + ks * h2[None, :]) % np.uint32(m_bits)
    bitmap = np.zeros(m_bits, dtype=bool)
    bitmap[pos.ravel()] = True
    words = np.packbits(bitmap, bitorder="little").view(np.uint32)
    if sys.byteorder == "big":   # packed bytes are little-endian words
        words = words.byteswap()
    return words


class BloomFilter:
    """Standard bloom filter with k = round(bits_per_key * ln2) double hashes.

    ``bits`` is a uint32 word array with m_bits == 32 * len(bits), the exact
    layout the device probe (``kernels.ops.bloom_probe_filter``) consumes.
    A filter never changes after it is built, so the device probe keeps its
    padded copy of ``bits`` in ``device_bits`` (None until the first device
    probe); it is freed with the filter.
    """

    __slots__ = ("m_bits", "k", "bits", "n_keys", "device_bits")

    def __init__(self, keys: np.ndarray, bits_per_key: float, hash_fn=None):
        """``hash_fn(keys) -> (h1, h2)`` optionally reroutes the hash pass
        (e.g. ``kernels.ops.bloom_build_hashes``, the engine's
        ``use_pallas_bloom`` build route); it must stay in bit-lockstep with
        :func:`hash_pair` so numpy and VPU probes agree on the bitset."""
        n = int(keys.size)
        self.n_keys = n
        self.device_bits = None
        if n == 0 or bits_per_key <= 0:
            # Degenerate filter: answers "maybe" for everything (FPR = 1).
            self.m_bits = 0
            self.k = 0
            self.bits = np.zeros(0, dtype=np.uint32)
            return
        # Round up to whole uint32 words, the unit both probes index.
        m = -(-max(64, int(round(bits_per_key * n))) // 32) * 32
        self.m_bits = m
        self.k = max(1, int(round(bits_per_key * LN2)))
        h1, h2 = (hash_fn or hash_pair)(np.asarray(keys, dtype=np.uint64))
        self.bits = build_bits(np.asarray(h1, dtype=np.uint32),
                               np.asarray(h2, dtype=np.uint32), self.k, m)

    def may_contain(self, keys: np.ndarray) -> np.ndarray:
        """Vectorized membership test. True = maybe present, False = absent."""
        keys = np.asarray(keys, dtype=np.uint64)
        if self.m_bits == 0:
            return np.ones(keys.shape, dtype=bool)
        h1, h2 = hash_pair(keys)
        out = np.ones(keys.shape, dtype=bool)
        m = np.uint32(self.m_bits)
        for i in range(self.k):
            pos = (h1 + np.uint32(i) * h2) % m
            word = self.bits[(pos >> np.uint32(5)).astype(np.int64)]
            out &= (word >> (pos & np.uint32(31))) & np.uint32(1) != 0
        return out

    @property
    def memory_bits(self) -> int:
        return self.m_bits

    def expected_fpr(self) -> float:
        if self.m_bits == 0:
            return 1.0
        return theoretical_fpr(self.m_bits / max(self.n_keys, 1))


def theoretical_fpr(bits_per_key: float) -> float:
    """Eq. 2: FPR = e^{-ln(2)^2 * M/N}."""
    if bits_per_key <= 0:
        return 1.0
    return math.exp(-LN2_SQ * bits_per_key)


def bits_for_fpr(p: float) -> float:
    """Invert Eq. 2: bits/key needed for target FPR p (p in (0, 1])."""
    if p >= 1.0:
        return 0.0
    return -math.log(p) / LN2_SQ


def allocate_fprs(level_sizes: Sequence[int], total_bits: float) -> np.ndarray:
    """Monkey/Autumn water-filling (Eq. 7-10 generalized to measured N_i).

    Minimize sum_i p_i  s.t.  sum_i (-N_i ln p_i / ln2^2) = total_bits,
    0 < p_i <= 1.  KKT => p_i = lam * N_i on the interior, p_i = 1 where the
    budget runs out (largest levels saturate first, exactly as the paper sets
    p_L = 1 in the "Filter Memory Budget" analysis).
    Returns the optimal per-level FPRs.
    """
    sizes = np.asarray([max(int(s), 0) for s in level_sizes], dtype=np.float64)
    L = sizes.size
    fprs = np.ones(L)
    if total_bits <= 0 or L == 0:
        return fprs
    active = sizes > 0
    # Saturate levels (p_i = 1) from the largest down until the remaining
    # budget supports an interior solution with p_i <= 1 for all active i.
    order = np.argsort(-sizes)  # largest first
    saturated = np.zeros(L, dtype=bool)
    for cut in range(L + 1):
        interior = active & ~saturated
        if not interior.any():
            break
        n_int = sizes[interior]
        # Interior solution: p_i = lam*N_i; budget constraint gives
        # sum(-N_i ln(lam N_i)) / ln2^2 = total_bits  =>  solve for ln lam.
        s = n_int.sum()
        ln_lam = -(total_bits * LN2_SQ + (n_int * np.log(n_int)).sum()) / s
        p = np.exp(ln_lam) * n_int
        if (p <= 1.0 + 1e-12).all():
            fprs[interior] = np.minimum(p, 1.0)
            return fprs
        # Saturate the largest not-yet-saturated level and retry.
        for idx in order:
            if active[idx] and not saturated[idx]:
                saturated[idx] = True
                break
    return fprs


def fprs_to_bits_per_key(fprs: Sequence[float]) -> np.ndarray:
    return np.asarray([bits_for_fpr(p) for p in fprs])


def garnering_theoretical_fprs(L: int, T: float, c: float, p_last: float = 1.0
                               ) -> np.ndarray:
    """Closed-form Eq. 9: p_{L-i} = p_L * c^{i(i-1)/2} / T^i (1-indexed levels)."""
    out = np.empty(L)
    for i in range(L):  # i = distance from last level
        out[L - 1 - i] = p_last * (c ** (i * (i - 1) / 2)) / (T ** i)
    return np.minimum(out, 1.0)


def zero_result_read_cost(fprs: Sequence[float]) -> float:
    """Eq. 7: expected blocks read by a point query for an absent key."""
    return float(np.sum(fprs))
