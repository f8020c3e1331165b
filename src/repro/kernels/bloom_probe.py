"""Device bloom-filter probe (the point-read filter pass, paper §3.1).

The paper argues filter probing is the emerging point-read bottleneck;
Autumn reduces probe count via fewer levels, and this pass makes each batch
of probes one device program: the k double-hashes are computed vectorially
(two 32-bit murmur-style mixes on the (lo, hi) halves of each u64 key — the
TPU vector unit has no u64 lanes) and each probe gathers one u32 word from
the bitset in HBM.

It is a plain jitted XLA function, not a Pallas kernel: Mosaic lowers only
2-D gathers within a vreg, so a random word gather over a whole filter
cannot be written there, while XLA's gather reads it straight from HBM with
no cap on filter size.  ``m_bits`` and the hash count are operands, so the
bitset can be padded to a bucketed length without changing any bit
position, and one compile serves every level's hash count.  The caller
(``ops.bloom_probe_filter``) pads the keys to a power-of-two bucket sized
to the batch and passes a bitset that stays on the device for its filter's
lifetime, so a launch carries the batch's keys and no bitset copy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _mix32(x: jnp.ndarray, c1: int, c2: int) -> jnp.ndarray:
    """32-bit finalizer (murmur3-style), vectorizable on the VPU."""
    x = x.astype(jnp.uint32)
    x ^= x >> jnp.uint32(16)
    x *= jnp.uint32(c1)
    x ^= x >> jnp.uint32(13)
    x *= jnp.uint32(c2)
    x ^= x >> jnp.uint32(16)
    return x


def hash_pair(keys_lo: jnp.ndarray, keys_hi: jnp.ndarray):
    """Two independent 32-bit hashes from the (lo, hi) halves of u64 keys."""
    h1 = _mix32(keys_lo ^ _mix32(keys_hi, 0x85EBCA6B, 0xC2B2AE35),
                0xCC9E2D51, 0x1B873593)
    h2 = _mix32(keys_hi ^ _mix32(keys_lo, 0x27D4EB2F, 0x165667B1),
                0x9E3779B9, 0x85EBCA77) | jnp.uint32(1)
    return h1, h2


def bloom_probe(keys_lo: jax.Array, keys_hi: jax.Array, bits: jax.Array,
                m_bits: jax.Array, k_hashes: jax.Array) -> jax.Array:
    """keys_lo/hi: (N,) uint32; bits: (W,) uint32 words with 32 * W >= m_bits;
    m_bits: () uint32; k_hashes: () int32.  Returns (N,) bool 'maybe
    present'.  Both scalars are operands, so filters of one padded length
    share a compile whatever their hash count."""
    h1, h2 = hash_pair(keys_lo, keys_hi)

    def probe(i, maybe):
        pos = (h1 + i.astype(jnp.uint32) * h2) % m_bits
        word = bits[(pos >> jnp.uint32(5)).astype(jnp.int32)]
        return maybe & (((word >> (pos & jnp.uint32(31))) & jnp.uint32(1))
                        != 0)

    return jax.lax.fori_loop(0, k_hashes, probe,
                             jnp.ones(keys_lo.shape, jnp.bool_))
