"""Device kernels vs their references: shape/dtype sweeps (interpret mode
on the CPU).  The probe is checked against ``BloomFilter.may_contain``, the
merge against sorting and the engine's own merge."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.bloom import BloomFilter, build_bits, hash_pair
from repro.kernels import (bloom_probe_filter, flash_attention,
                           merge_runs_tiled, paged_attention)
from repro.kernels import ops, ref


def _filter(keys, m_words, k):
    """A BloomFilter over ``keys`` with an explicit word and hash count."""
    bf = BloomFilter(np.zeros(0, np.uint64), 0)
    bf.m_bits, bf.k, bf.n_keys = m_words * 32, k, keys.size
    bf.bits = build_bits(*hash_pair(keys), k, m_words * 32)
    return bf


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("tpu", False),
                                                ("gpu", None)])
def test_interpret_mode_follows_backend(monkeypatch, platform, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match="no device path"):
            ops.interpret_mode()
    else:
        assert ops.interpret_mode() is interpret


# (members, bitset words, hashes, batch): the batch probes the members
# first, then random keys; None probes the members and as many random keys
@pytest.mark.parametrize("n,m_words,k,batch", [
    pytest.param(512, 128, 5, None, id="512-128-5"),
    pytest.param(2048, 1024, 7, None, id="2048-1024-7"),
    pytest.param(4096, 64, 3, None, id="4096-64-3"),
    # queries span two probe chunks
    pytest.param(40000, 16384, 7, None, id="40000-16384-7"),
    # batches at the edges of the key buckets and of the 64k cap
    *(pytest.param(2048, 1024, 7, b, id=f"batch{b}")
      for b in (1, 1023, 1024, 1025, 65535, 65537))])
def test_bloom_probe_sweep(n, m_words, k, batch):
    rng = np.random.default_rng(n + k)
    keys = rng.integers(0, 2**63, n, dtype=np.uint64)
    bf = _filter(keys, m_words, k)
    batch = batch or 2 * n
    members = min(n, batch)
    queries = np.concatenate([keys[:members],
                              rng.integers(0, 2**63, batch - members,
                                           np.uint64)])
    got = bloom_probe_filter(bf, queries)
    assert got.shape == (batch,)
    assert (got == bf.may_contain(queries)).all()
    assert got[:members].all()  # no false negatives on members


def test_bloom_fpr_reasonable():
    rng = np.random.default_rng(9)
    keys = rng.integers(0, 2**62, 4096, dtype=np.uint64)
    bf = _filter(keys, 2048, 7)
    absent = rng.integers(2**62, 2**63, 8192, dtype=np.uint64)
    fpr = float(np.mean(bloom_probe_filter(bf, absent)))
    assert fpr < 0.05


def test_device_path_compiles_once_per_bucket():
    """Probe keys (1,024 at least), bitset, hash batch and tile counts are
    padded to power-of-two buckets, so sizes that share a bucket share one
    compile and answers stay exact."""
    rng = np.random.default_rng(4)
    keys = rng.integers(0, 2**63, 3000, dtype=np.uint64)
    before = ops._probe_jit._cache_size()
    for n_keys, n_q in ((3000, 1), (2900, 700), (2500, 1024)):
        bf = BloomFilter(keys[:n_keys], 10)
        q = rng.integers(0, 2**63, n_q, dtype=np.uint64)
        assert (bloom_probe_filter(bf, q) == bf.may_contain(q)).all()
    assert ops._probe_jit._cache_size() - before <= 1
    h1, h2 = ops.bloom_build_hashes(keys[:5])
    e1, e2 = hash_pair(keys[:5])
    assert (h1 == e1).all() and (h2 == e2).all()
    before = ops._merge_jit._cache_size()
    for na, nb in ((100, 200), (300, 400), (5, 1000)):
        a = np.sort(rng.integers(0, 2**63, na, dtype=np.uint64))
        b = np.sort(rng.integers(0, 2**63, nb, dtype=np.uint64))
        mk, _ = merge_runs_tiled(a, b)
        assert (mk == np.sort(np.concatenate([a, b]))).all()
    assert ops._merge_jit._cache_size() - before <= 1


@pytest.mark.parametrize("na,nb,tile", [(777, 1333, 256), (1, 5000, 128),
                                        (256, 256, 256), (0, 100, 64),
                                        (4096, 4096, 512)])
def test_merge_sweep(na, nb, tile):
    rng = np.random.default_rng(na + nb)
    a = np.sort(rng.integers(0, 1 << 31, na, dtype=np.uint32))
    b = np.sort(rng.integers(0, 1 << 31, nb, dtype=np.uint32))
    mk, mp = merge_runs_tiled(a, b, tile=tile)
    assert (mk == np.sort(np.concatenate([a, b]))).all()
    # payload integrity: every source index appears exactly once
    src_a = (mp >> 31) == 0
    assert (np.sort(mp[src_a] & 0x7FFFFFFF) == np.arange(na)).all()
    assert (np.sort(mp[~src_a] & 0x7FFFFFFF) == np.arange(nb)).all()
    # payload/key pairing: key at output equals source key
    back_a = mk[src_a]
    assert (back_a == a[(mp[src_a] & 0x7FFFFFFF)]).all()


@pytest.mark.parametrize("dt,lo,hi", [(np.int64, -2**60, 2**60),
                                      (np.int32, -2**31, 2**31 - 1),
                                      (np.uint64, 0, 2**63)])
def test_merge_signed_and_wide_dtypes(dt, lo, hi):
    """Regression: keys wider than 32 bits (and signed keys) must merge via
    the order-preserving u64 lane map, not a truncating u32 cast."""
    rng = np.random.default_rng(11)
    a = np.sort(rng.integers(lo, hi, 700).astype(dt))
    b = np.sort(rng.integers(lo, hi, 900).astype(dt))
    mk, mp = merge_runs_tiled(a, b, tile=128)
    assert mk.dtype == dt
    assert (mk == np.sort(np.concatenate([a, b]))).all()
    src_a = (mp >> 31) == 0
    assert (mk[src_a] == a[mp[src_a] & 0x7FFFFFFF]).all()
    assert (mk[~src_a] == b[mp[~src_a] & 0x7FFFFFFF]).all()


def test_merge_matches_engine_merge():
    """Ties the TPU kernel to the engine's compaction semantics."""
    from repro.core import IOStats, build_run, merge_runs
    rng = np.random.default_rng(3)
    ka = np.sort(rng.choice(1 << 20, 900, replace=False)).astype(np.uint64)
    kb = np.sort(rng.choice(1 << 20, 500, replace=False)).astype(np.uint64)
    mk, _ = merge_runs_tiled(ka.astype(np.uint32), kb.astype(np.uint32))
    ra = build_run(ka, np.arange(900, dtype=np.uint64),
                   np.zeros(900, np.int32), np.zeros((900, 0), np.uint8))
    rb = build_run(kb, np.arange(1000, 1500, dtype=np.uint64),
                   np.zeros(500, np.int32), np.zeros((500, 0), np.uint8))
    merged = merge_runs([ra, rb], 0.0, IOStats())
    # engine dedups duplicate keys; kernel keeps both — compare on uniques
    assert (np.unique(mk) == merged.keys.astype(np.uint32)).all()


@pytest.mark.parametrize("B,H,KH,dh,page,P", [
    (2, 4, 4, 16, 8, 3),     # MHA
    (3, 8, 2, 32, 16, 4),    # GQA
    (1, 16, 1, 64, 32, 2),   # MQA
])
def test_paged_attention_sweep(B, H, KH, dh, page, P):
    rng = np.random.default_rng(B * H)
    nphys = P * B + 2
    q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((nphys, page, KH, dh)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nphys, page, KH, dh)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, nphys, (B, P)), jnp.int32)
    ln = jnp.asarray(rng.integers(1, P * page + 1, B), jnp.int32)
    got = paged_attention(q, kp, vp, bt, ln)
    exp = ref.paged_attention_ref(q, kp, vp, bt, ln)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(dtype, causal, window):
    rng = np.random.default_rng(42)
    q = jnp.asarray(rng.standard_normal((2, 256, 4, 32)), dtype)
    k = jnp.asarray(rng.standard_normal((2, 256, 2, 32)), dtype)
    v = jnp.asarray(rng.standard_normal((2, 256, 2, 32)), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          bq=64, bk=64)
    exp = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_model_attention():
    """Kernel vs the model's XLA-fallback gqa_attention."""
    from repro.models.layers import gqa_attention
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((2, 128, 8, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 128, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 128, 2, 16)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    xla = gqa_attention(q, k, v, q_positions=pos, k_positions=pos,
                        causal=True, window=None)
    pallas = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(pallas),
                               rtol=2e-5, atol=2e-5)
