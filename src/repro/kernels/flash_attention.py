"""Pallas TPU kernel: flash attention (prefill/train hotspot).

The XLA fallback materializes (q_chunk, S) fp32 score buffers through a
multi-fusion softmax chain — the dominant HBM term in the dry-run roofline
for every attention arch (EXPERIMENTS.md §Perf).  This kernel streams KV
blocks through VMEM with running-softmax scratch, so score traffic never
touches HBM: per-(q-block) HBM traffic drops from O(S) score rows to the
q/k/v/o tiles themselves.

Supports causal masking, sliding windows, and GQA (KV heads repeated on the
fly inside the kernel).  Block sizes default to MXU-aligned (128, 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def flash_attention_kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref,
                           acc_ref, *, bq: int, bk: int, causal: bool,
                           window: int, n_kv_blocks: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                                   # (bq, dh)
    k = k_ref[...]                                   # (bk, dh)
    v = v_ref[...]
    s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T,
                preferred_element_type=jnp.float32) * scale
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, -1e30)

    m_prev = m_ref[...]                              # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + \
        jnp.dot(p, v.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        out_ref[...] = (acc_ref[...] /
                        jnp.maximum(l_ref[...], 1e-30)[:, None]
                        ).astype(out_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           bq: int = 128, bk: int = 128,
                           interpret: bool) -> jax.Array:
    """q: (B,Sq,H,dh); k/v: (B,Sk,KH,dh). Returns (B,Sq,H,dh)."""
    B, Sq, H, dh = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    G = H // KH
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    # layout: (B, H, S, dh) with KV heads repeated via the index map (free)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    kern = functools.partial(
        flash_attention_kernel, bq=bq, bk=bk, causal=causal, window=window,
        n_kv_blocks=Sk // bk, scale=dh ** -0.5)
    out = pl.pallas_call(
        kern,
        grid=(B, H, Sq // bq, Sk // bk),
        in_specs=[
            pl.BlockSpec((None, None, bq, dh),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, bk, dh),
                         lambda b, h, i, j, _G=G: (b, h // _G, j, 0)),
            pl.BlockSpec((None, None, bk, dh),
                         lambda b, h, i, j, _G=G: (b, h // _G, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, dh),
                               lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, dh), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
