"""jit'd entry points for the store's device path and the serving kernels.

The store calls three of them: :func:`bloom_probe_filter` (batched point
reads), :func:`bloom_build_hashes` (filter builds) and
:func:`merge_runs_tiled` (compaction).  Each pads its inputs to a bucketed
shape (powers of two above a floor; the probe takes its keys in fixed
chunks), so each entry compiles once per bucket of one size axis: the
count grows with the log of the data size, not with the number of runs or
batches.

Pallas kernels run compiled on a TPU and in interpret mode on the CPU;
:func:`interpret_mode` decides which from the default backend, and every
wrapper asks it — no caller passes a flag.

The three store entries open ``kernel.*`` profiler spans around their host
phases (``repro.core.telemetry.span``: no-ops unless a ``jax.profiler``
session records), so a trace shows which host work sits between device
programs.  The jitted programs keep the names the benchmark's trace
reduction reads: ``jit_bloom_probe``, ``jit_hash_pair`` and
``jit_bitonic_merge_pallas``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import numpy as np

from repro.core.telemetry import span

from .bloom_probe import bloom_probe as _bloom_probe
from .bloom_probe import hash_pair as _kernel_hash_pair
from .flash_attention import flash_attention_pallas
from .merge_path import ROWS, bitonic_merge_pallas, merge_path_partition
from .paged_attention import paged_attention_pallas

PROBE_BATCH = 1 << 16       # keys per probe launch (the last chunk padded)
PROBE_MIN_WORDS = 1 << 12   # smallest padded bitset (16 KiB)
HASH_MIN_BATCH = 1024       # smallest padded hash batch
_SIGN = np.uint32(1 << 31)


def interpret_mode() -> bool:
    """True when Pallas kernels must be interpreted (CPU backend), False when
    they compile (TPU).  Any other backend has no path and is an error."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"no device path for platform {platform!r}: the "
                       f"kernels run compiled on tpu or interpreted on cpu")


def _bucket(n: int, floor: int) -> int:
    """Smallest power of two >= max(n, floor)."""
    return max(floor, 1 << max(0, int(n) - 1).bit_length())


def split_u64(keys) -> Tuple[np.ndarray, np.ndarray]:
    """u64 -> (lo32, hi32) host arrays.  Done in numpy: jax's default x32
    mode would silently truncate uint64."""
    keys = np.asarray(keys, dtype=np.uint64)
    lo = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (keys >> np.uint64(32)).astype(np.uint32)
    return lo, hi


def _pad(x: np.ndarray, n: int) -> np.ndarray:
    return x if x.size == n else np.concatenate(
        [x, np.zeros(n - x.size, x.dtype)])


_probe_jit = jax.jit(_bloom_probe)


def bloom_probe_filter(bf, keys) -> np.ndarray:
    """Probe a ``repro.core.bloom.BloomFilter`` on the device.

    The filter builds its bitset with the device's own 32-bit hash family,
    so this returns bit-identical answers to ``bf.may_contain`` — it is the
    engine's device route for batched point reads (DESIGN.md §3).  The
    bitset is padded to a bucketed length (the pad words are never indexed:
    positions stay below ``bf.m_bits``) and uploaded once; the keys go in
    chunks of ``PROBE_BATCH``, the last one padded, so the only shape that
    varies between compiles is the bitset's bucket.  All chunks are launched
    before any result is read back.
    """
    keys = np.asarray(keys, dtype=np.uint64)
    n = keys.size
    if bf.k == 0 or n == 0:
        return np.ones(n, dtype=bool)
    words = _bucket(bf.bits.size, PROBE_MIN_WORDS)
    with span("kernel.probe_upload", words=words):
        bits = jax.device_put(_pad(bf.bits, words))
    m_bits, k = np.uint32(bf.m_bits), np.int32(bf.k)
    with span("kernel.probe_launch", keys=n, words=words):
        outs = [_probe_jit(*split_u64(_pad(keys[i:i + PROBE_BATCH],
                                           PROBE_BATCH)),
                           bits, m_bits, k)
                for i in range(0, n, PROBE_BATCH)]
    with span("kernel.probe_readback", keys=n):
        return np.concatenate([np.asarray(o) for o in outs])[:n]


_hash_jit = jax.jit(_kernel_hash_pair)


def bloom_build_hashes(keys) -> Tuple[np.ndarray, np.ndarray]:
    """Device-side hash pass for filter *construction* (DESIGN.md §10).

    The ``use_pallas_bloom`` build route: compaction's output-filter rebuild
    hashes every surviving key through the device's u32 hash family, and
    ``core.bloom.build_bits`` packs the bitset from the returned pair —
    bit-identical to ``core.bloom.hash_pair`` (the numpy twin), so probes
    from either backend agree on the result.
    """
    keys = np.asarray(keys, dtype=np.uint64)
    n = keys.size
    with span("kernel.hash_pass", entries=n):
        lo, hi = split_u64(_pad(keys, _bucket(n, HASH_MIN_BATCH)))
        h1, h2 = _hash_jit(lo, hi)
        return np.asarray(h1)[:n], np.asarray(h2)[:n]


def _to_u64_order(keys: np.ndarray) -> np.ndarray:
    """Order-preserving map of any integer dtype onto uint64.

    Unsigned dtypes widen directly; signed dtypes flip the sign bit after
    widening to int64 (the classic radix trick), so lexicographic (hi, lo)
    u32-lane comparison reproduces the native ordering exactly.  Float keys
    are rejected — the two-lane kernel compares integer lanes only.
    """
    if keys.dtype == np.uint64:
        return keys
    if np.issubdtype(keys.dtype, np.unsignedinteger):
        return keys.astype(np.uint64)
    if np.issubdtype(keys.dtype, np.signedinteger):
        return keys.astype(np.int64).view(np.uint64) ^ np.uint64(1 << 63)
    raise TypeError(f"merge_runs_tiled requires integer keys, "
                    f"got {keys.dtype}")


def _from_u64_order(merged: np.ndarray, dtype: np.dtype) -> np.ndarray:
    """Invert :func:`_to_u64_order` back to the caller's key dtype."""
    if dtype == np.uint64:
        return merged
    if np.issubdtype(dtype, np.unsignedinteger):
        return merged.astype(dtype)
    return (merged ^ np.uint64(1 << 63)).view(np.int64).astype(dtype)


def _signed_lane(x: np.ndarray) -> np.ndarray:
    """u32 -> int32 with the sign bit flipped: signed order = unsigned order."""
    return (x ^ _SIGN).view(np.int32)


def _unsigned_lane(x: np.ndarray) -> np.ndarray:
    return x.view(np.uint32) ^ _SIGN


_merge_jit = jax.jit(bitonic_merge_pallas, static_argnames=("interpret",))


def merge_runs_tiled(keys_a: np.ndarray, keys_b: np.ndarray,
                     tile: int = 256) -> Tuple[np.ndarray, np.ndarray]:
    """Full two-run merge: host-side merge-path partition + one bitonic
    kernel launch over every tile pair (the engine's ``use_pallas_merge``
    lane).

    The partition and the tile packing are fully vectorized
    (``merge_path_partition`` + two scatter passes — no per-tile Python
    loop).  Each tile pair is packed as one row concat(A, reverse(B)), and
    the row count is padded to a power of two (at least ``ROWS``).  Keys are
    carried as (hi, lo) u32 lanes so uint64 engine keys merge exactly.
    Returns (merged_keys, source_index) where source_index is uint32 with
    bit 31 flagging entries from ``keys_b`` and the low bits giving the
    source row, so the engine can permute value rows.  Tile pads carry the
    lane maxima plus payload 0xFFFFFFFF, which the kernel's payload
    tie-break orders after any real entry — keys equal to the dtype maximum
    therefore merge correctly (runs longer than 2^31 - 1 entries would
    collide with the pad payload, far beyond this engine's scale).
    ``tile`` must be a power of two of at least 64.
    """
    out_dtype = keys_a.dtype
    entries = len(keys_a) + len(keys_b)
    with span("kernel.merge_pack", entries=entries):
        keys_a = _to_u64_order(np.ascontiguousarray(keys_a))
        keys_b = _to_u64_order(np.ascontiguousarray(keys_b))
        # Diagonal spacing = tile: merge-path guarantees each cell consumes
        # at most `tile` from either input; pads sort to the back (lane
        # maxima), so each cell's first `consumed` outputs are exact.
        bounds_a, bounds_b = merge_path_partition(keys_a, keys_b, tile)
        n_tiles = len(bounds_a) - 1
        n_rows = _bucket(n_tiles, ROWS)
        width = 2 * tile
        # pad payload 0xFFFFFFFF: sorts after every real source index, so
        # the payload tie-break keeps pads strictly behind real entries even
        # when a real key equals the dtype maximum
        rows = [np.full((n_rows, width), 0xFFFFFFFF, dtype=np.uint32)
                for _ in range(3)]
        for keys, bounds, flag in ((keys_a, bounds_a, 0),
                                   (keys_b, bounds_b, _SIGN)):
            n = len(keys)
            if n == 0:
                continue
            idx = np.arange(n, dtype=np.int64)
            t_of = np.searchsorted(bounds, idx, side="right") - 1
            off = idx - bounds[t_of]
            if flag:                  # B fills its half of the row reversed
                off = width - 1 - off
            rows[0][t_of, off] = (keys >> np.uint64(32)).astype(np.uint32)
            rows[1][t_of, off] = (keys & np.uint64(0xFFFFFFFF)).astype(
                np.uint32)
            rows[2][t_of, off] = idx.astype(np.uint32) | flag
    with span("kernel.merge_launch", entries=entries, rows=n_rows):
        out = _merge_jit(*(_signed_lane(r) for r in rows),
                         interpret=interpret_mode())
    with span("kernel.merge_unpack", entries=entries):
        ohi, olo, op = (np.asarray(r)[:n_tiles] for r in out)
        # strip padding: valid entries per cell sit at the front
        cnt = np.diff(bounds_a) + np.diff(bounds_b)
        keep = np.arange(width)[None, :] < cnt[:, None]
        merged = (_unsigned_lane(ohi[keep]).astype(np.uint64)
                  << np.uint64(32)) | _unsigned_lane(olo[keep])
        return _from_u64_order(merged, out_dtype), _unsigned_lane(op[keep])


_paged_attention_jit = jax.jit(paged_attention_pallas,
                               static_argnames=("interpret",))


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array) -> jax.Array:
    return _paged_attention_jit(q, k_pages, v_pages, block_tables, lengths,
                                interpret=interpret_mode())


_flash_attention_jit = jax.jit(
    flash_attention_pallas,
    static_argnames=("causal", "window", "bq", "bk", "interpret"))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, bq: int = 128,
                    bk: int = 128) -> jax.Array:
    return _flash_attention_jit(q, k, v, causal=causal, window=window,
                                bq=bq, bk=bk, interpret=interpret_mode())
