"""Peaks by device kind, and the bytes each device entry of the store
needs for the real work of one call: counted from the keys, filters and
entries the call was given, never from the padded shapes it runs at."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of one device kind; a kind not in the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table[device_kind]


def probe_bytes(n_keys: int, k_hashes: int, m_bits: int) -> int:
    """Bloom probe of ``n_keys`` keys against one filter of ``m_bits`` bits
    with ``k_hashes`` probes: each key is read (8 B) and answered (1 B), and
    each probe reads one 32-bit word of the bitset, but no probe batch needs
    more of the bitset than all of it."""
    words = -(-int(m_bits) // 32)
    gathered = min(4 * int(k_hashes) * int(n_keys), 4 * words)
    return 9 * int(n_keys) + gathered


def merge_bytes(n_entries: int) -> int:
    """Merge of two sorted runs holding ``n_entries`` entries between them:
    each entry is read and written once as three 32-bit lanes (key high,
    key low, source index)."""
    return 24 * int(n_entries)


def roofline_pct(nbytes: float, device_s: float, bytes_per_s: float):
    """Share of the bandwidth roofline: the least time the bytes need at the
    peak, over the device time they took; None where nothing ran."""
    if device_s <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / bytes_per_s / device_s
