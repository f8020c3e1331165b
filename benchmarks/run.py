"""Benchmark entry point — one function per paper table/figure.

  fig2_micro        db_bench six ops x value sizes (Autumn vs RocksDB)
  fig3_sensitivity  c/T sweep on writes + small range reads
  fig4_ycsb         YCSB A-F + load + tail latencies (Table 3)
  fig5_bloom        Monkey bloom optimization vs DB size
  table2_complexity levels/runs/WA/zero-read vs N for all five policies
  roofline          dry-run roofline table (from artifacts, if present)

Usage: PYTHONPATH=src python -m benchmarks.run [--quick|--full] [names...]
"""
from __future__ import annotations

import sys
import time

from repro.compile_cache import enable_compile_cache

from . import (bloom_opt, complexity_check, micro_dbbench, roofline,
               sensitivity_ct, ycsb)


def main() -> None:
    enable_compile_cache()
    args = [a for a in sys.argv[1:]]
    scale = 1.0
    for flag, s in (("--quick", 0.25), ("--full", 10.0)):
        if flag in args:
            scale = s
            args.remove(flag)
    names = args or ["fig2_micro", "fig3_sensitivity", "fig4_ycsb",
                     "fig5_bloom", "table2_complexity", "roofline"]
    for name in names:
        t0 = time.perf_counter()
        print(f"\n=== {name} ===")
        if name == "fig2_micro":
            micro_dbbench.main(n=int(100_000 * scale))
        elif name == "fig3_sensitivity":
            sensitivity_ct.main(n=int(80_000 * scale))
        elif name == "fig4_ycsb":
            ycsb.main(n=int(50_000 * scale), n_ops=int(6_000 * scale))
        elif name == "fig5_bloom":
            bloom_opt.main()
        elif name == "table2_complexity":
            complexity_check.main()
        elif name == "roofline":
            try:
                roofline.main()
            except Exception as e:
                print(f"(roofline artifacts unavailable: {e})")
        else:
            print(f"unknown benchmark {name!r}")
        print(f"# {name} took {time.perf_counter() - t0:.1f}s")


if __name__ == "__main__":
    main()
