"""Pure-jnp oracles for the attention kernels (the allclose reference).

The store's device path has numpy references in ``repro.core``:
``BloomFilter.may_contain`` for the probe and ``merge_runs_scalar`` for the
merge."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_tables: jax.Array, lengths: jax.Array
                        ) -> jax.Array:
    B, H, dh = q.shape
    n_phys, page, KH, _ = k_pages.shape
    G = H // KH
    P = block_tables.shape[1]
    k = k_pages[block_tables]            # (B, P, page, KH, dh)
    v = v_pages[block_tables]
    k = k.reshape(B, P * page, KH, dh)
    v = v.reshape(B, P * page, KH, dh)
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dh ** -0.5)
    mask = jnp.arange(P * page)[None] < lengths[:, None]
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0) -> jax.Array:
    B, Sq, H, dh = q.shape
    KH = k.shape[2]
    G = H // KH
    kr = jnp.repeat(k, G, axis=2)
    vr = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * (dh ** -0.5)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    ok = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)
