"""Device kernel demo: the paper's hot paths on TPU-shaped kernels.  They
run compiled on a TPU and in interpret mode on the CPU, picked from the
default backend.

    PYTHONPATH=src python examples/kernels_demo.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import BloomFilter
from repro.kernels import (bloom_probe_filter, flash_attention,
                           merge_runs_tiled, paged_attention)
from repro.kernels import ref

rng = np.random.default_rng(0)

# 1. bloom probe: the point-read filter pass (paper §3.1 CPU optimization)
members = rng.integers(0, 2**62, 4096, dtype=np.uint64)
bf = BloomFilter(members, bits_per_key=16)
absent = rng.integers(2**62, 2**63, 4096, dtype=np.uint64)
assert bloom_probe_filter(bf, members).all()
fpr = float(np.mean(bloom_probe_filter(bf, absent)))
print(f"bloom_probe      : members all hit, absent FPR={fpr:.4f}")

# 2. merge_path: bitonic compaction merge (two sorted runs -> one)
a = np.sort(rng.integers(0, 1 << 30, 3000, dtype=np.uint32))
b = np.sort(rng.integers(0, 1 << 30, 5000, dtype=np.uint32))
merged, src = merge_runs_tiled(a, b, tile=256)
print(f"merge_path       : {len(a)}+{len(b)} -> {len(merged)} sorted "
      f"({int((src >> 31).sum())} from run B)")

# 3. paged_attention: AutumnKV's decode read path (block table = fence ptrs)
B, H, KH, dh, page, P = 4, 8, 2, 64, 16, 8
q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
kp = jnp.asarray(rng.standard_normal((64, page, KH, dh)), jnp.float32)
vp = jnp.asarray(rng.standard_normal((64, page, KH, dh)), jnp.float32)
bt = jnp.asarray(rng.integers(0, 64, (B, P)), jnp.int32)
ln = jnp.asarray(rng.integers(page, P * page, B), jnp.int32)
out = paged_attention(q, kp, vp, bt, ln)
err = float(jnp.max(jnp.abs(out - ref.paged_attention_ref(q, kp, vp, bt, ln))))
print(f"paged_attention  : out {out.shape}, max err vs oracle {err:.2e}")

# 4. flash_attention: prefill hotspot (kills XLA softmax-chain HBM traffic)
q = jnp.asarray(rng.standard_normal((2, 512, 8, 64)), jnp.bfloat16)
k = jnp.asarray(rng.standard_normal((2, 512, 2, 64)), jnp.bfloat16)
v = jnp.asarray(rng.standard_normal((2, 512, 2, 64)), jnp.bfloat16)
o = flash_attention(q, k, v, causal=True, window=128)
e = ref.flash_attention_ref(q, k, v, causal=True, window=128)
err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - e.astype(jnp.float32))))
print(f"flash_attention  : out {o.shape}, max err vs oracle {err:.2e}")
