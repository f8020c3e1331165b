"""Pallas TPU kernel: bitonic two-way sorted merge (the compaction hotspot).

Hardware adaptation (DESIGN.md §2): a CPU/GPU merge walks two cursors
(branchy, serial) or binary-searches a merge path (dynamic control flow).
Neither maps to the TPU VPU.  Instead we use the classic bitonic-merge
network: concat(A, reverse(B)) of two sorted tiles is a bitonic sequence,
and log2(2T) static compare-exchange stages sort it.  Payloads (value
indices) ride along through the same selects, so the engine can permute
value rows after the kernel returns.

Layout, chosen so Mosaic lowers it:

* each tile pair is one row of 2T lanes, already concat(A, reverse(B)) —
  the host packs the tiles (``ops.merge_runs_tiled``) and reverses B there;
* a grid step takes ``ROWS`` rows, so every block is (8, 2T): the last two
  dimensions are multiples of (8, 128) whenever T is a multiple of 64;
* a stage at stride s finds each lane's partner (lane XOR s) with two lane
  rotations (``pltpu.roll``) and picks between them with lane-iota masks, so
  no reshape or reversal runs on the device;
* keys travel as two lanes (hi, lo) and the payload as a third, all int32
  with the sign bit flipped on the host, so signed compares give unsigned
  order (Mosaic has no unsigned min/max); the lexicographic
  (hi, lo, payload) compare makes the network deterministic.

ops.py composes multi-tile runs: tile boundaries are partitioned with the
host-side :func:`merge_path_partition` (one vectorized ``np.searchsorted``
pass instead of a per-diagonal binary-search loop), and each pair of
partitions is merged by one row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8   # tile pairs per grid step: the sublane count of one vreg


def _greater(a, b):
    """Lexicographic (hi, lo, payload) a > b over int32 lanes."""
    (ah, al, ap), (bh, bl, bp) = a, b
    return (ah > bh) | ((ah == bh) & ((al > bl) | ((al == bl) & (ap > bp))))


def bitonic_merge_kernel(hi_ref, lo_ref, p_ref, ohi_ref, olo_ref, op_ref):
    """Sort every row of a (ROWS, 2T) block; each row is bitonic on entry."""
    x = (hi_ref[...], lo_ref[...], p_ref[...])
    width = x[0].shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x[0].shape, 1)
    stride = width // 2
    while stride >= 1:
        # Which rotation brings lane XOR stride to each lane is read off a
        # rotated iota, so the network holds whatever direction roll uses.
        from_roll = pltpu.roll(lane, stride, 1) == (lane ^ stride)
        partner = tuple(jnp.where(from_roll, pltpu.roll(v, stride, 1),
                                  pltpu.roll(v, width - stride, 1))
                        for v in x)
        is_low = (lane & stride) == 0
        # the low lane of a pair keeps the smaller entry, the high the larger
        swap = (is_low & _greater(x, partner)) \
            | (~is_low & _greater(partner, x))
        x = tuple(jnp.where(swap, p, v) for v, p in zip(x, partner))
        stride //= 2
    ohi_ref[...], olo_ref[...], op_ref[...] = x


def bitonic_merge_pallas(hi: jax.Array, lo: jax.Array, payload: jax.Array, *,
                         interpret: bool):
    """hi, lo, payload: (n, 2T) int32 rows, each concat(A, reverse(B)) of two
    sorted tiles, n a multiple of ``ROWS``.  Returns the three lanes with
    every row sorted — one grid step per ``ROWS`` tile pairs."""
    n, width = hi.shape
    spec = pl.BlockSpec((ROWS, width), lambda i: (i, 0))
    return pl.pallas_call(
        bitonic_merge_kernel,
        grid=(n // ROWS,),
        in_specs=[spec] * 3,
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((n, width), jnp.int32)] * 3,
        name="bitonic_merge",
        interpret=interpret,
    )(hi, lo, payload)


def merge_path_partition(keys_a: np.ndarray, keys_b: np.ndarray, tile: int):
    """Host-side merge-path split at every ``tile``-th output diagonal.

    One vectorized pass: each element's final slot in the merged output is
    its own index plus its rank in the other input (ties break a-first), so
    the count of A-elements before diagonal ``d`` is one ``searchsorted``
    into those slots.  Replaces the per-diagonal binary-search loop; each
    cell consumes at most ``tile`` from either input by construction.

    Returns ``(bounds_a, bounds_b)``, int64 arrays of length n_tiles + 1.
    """
    na, nb = len(keys_a), len(keys_b)
    n_out = na + nb
    n_tiles = max(1, -(-n_out // tile))
    pos_a = np.arange(na, dtype=np.int64) + np.searchsorted(keys_b, keys_a,
                                                            side="left")
    diag = np.minimum(np.arange(n_tiles + 1, dtype=np.int64) * tile, n_out)
    bounds_a = np.searchsorted(pos_a, diag, side="left")
    return bounds_a, diag - bounds_a
