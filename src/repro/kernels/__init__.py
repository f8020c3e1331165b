"""Device kernels for the paper's compute hot-spots.  Pallas kernels compile
for the TPU there and run in interpret mode on the CPU (``ops.interpret_mode``
picks from the default backend):
  bloom_probe      — batched point-read filter probes (paper §3.1 CPU cost);
                     a jitted XLA gather, not Pallas
  merge_path       — bitonic two-way sorted merge (compaction)
  paged_attention  — AutumnKV decode read path (block table = fence pointers)
  flash_attention  — prefill/train attention (kills the XLA softmax-chain HBM
                     traffic that dominates the dry-run roofline)
"""
from .ops import (bloom_build_hashes, bloom_probe_filter, flash_attention,
                  interpret_mode, merge_runs_tiled, paged_attention)
