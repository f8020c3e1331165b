"""Pallas TPU kernel: paged decode attention — AutumnKV's on-TPU read path.

The block table plays the role of the paper's fence pointers: it maps each
sequence's logical page index to a physical page in the HBM page pool, so a
decode step reads exactly the pages it needs (no contiguous KV buffer, no
copy at prefix-cache hits).  Grid is (batch, pages); the block table and
sequence lengths ride in scalar-prefetch so the BlockSpec index_map can
DMA-schedule the right page while the previous one computes — the
overlap-compute-and-memory trick that makes decode HBM-bandwidth-bound
instead of latency-bound.

Flash-decoding accumulation: running (m, l, acc) in VMEM scratch across the
page axis; output written on the last page.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def paged_attention_kernel(block_tables_ref, lengths_ref,   # scalar prefetch
                           q_ref, k_ref, v_ref, out_ref,
                           m_ref, l_ref, acc_ref,
                           *, page: int, n_pages: int):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                       # (H, dh)
    k = k_ref[...]                       # (page, KH, dh)
    v = v_ref[...]
    H, dh = q.shape
    KH = k.shape[1]
    G = H // KH
    qg = q.reshape(KH, G, dh)
    s = jnp.einsum("kgd,pkd->kgp", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dh ** -0.5)
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    s = jnp.where(pos < lengths_ref[b], s, -1e30)

    m_prev = m_ref[...]                  # (KH, G)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new[..., None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + \
        jnp.einsum("kgp,pkd->kgd", pexp, v.astype(jnp.float32))
    m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        out_ref[...] = out.reshape(H, dh).astype(out_ref.dtype)


def paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           interpret: bool) -> jax.Array:
    """q: (B,H,dh); k/v_pages: (n_phys_pages, page, KH, dh);
    block_tables: (B, pages_per_seq) int32; lengths: (B,) int32.
    Returns (B,H,dh)."""
    B, H, dh = q.shape
    n_phys, page, KH, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    kern = functools.partial(paged_attention_kernel, page=page,
                             n_pages=pages_per_seq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pages_per_seq),
        in_specs=[
            pl.BlockSpec((None, H, dh), lambda b, p, bt, ln: (b, 0, 0)),
            pl.BlockSpec((None, page, KH, dh),
                         lambda b, p, bt, ln: (bt[b, p], 0, 0, 0)),
            pl.BlockSpec((None, page, KH, dh),
                         lambda b, p, bt, ln: (bt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, H, dh), lambda b, p, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KH, H // KH), jnp.float32),
            pltpu.VMEM((KH, H // KH), jnp.float32),
            pltpu.VMEM((KH, H // KH, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dh), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, q, k_pages, v_pages)
