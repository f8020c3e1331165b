"""Hillclimb drivers: offline greedy search over both tuning surfaces.

Two climbs share one scoring contract:

  * ``--lsm`` — offline LSM-knob hill-climb: candidate (c, T, pin_frac)
    sets are each measured on a fresh store under a short mixed workload
    and scored with ``repro.core.tuning_objective`` — the *same*
    p99-weighted foreground cost the online ``OnlineTuner`` optimises
    (DESIGN.md §17), so offline and online scoring cannot drift apart.
    The online counterpart (convergence from a mis-tuned start, YCSB A-F,
    phase-change re-convergence) is ``benchmarks/tuner_bench.py``.
  * default — tagged dry-run variants for three model cells (roofline
    table follow-ups): minicpm_2b/prefill_32k (memory-dominated),
    recurrentgemma_2b/train_4k (collective-bound), qwen3_4b/decode_32k
    (AutumnKV serving read path).

Run AFTER the main dry-run sweep:  PYTHONPATH=src python -m benchmarks.hillclimb
"""
import dataclasses
import json
import os


def lsm_score(c: float, T: float, pin_frac: float, n: int = 20_000,
              n_ops: int = 4_000, total_mem_kb: int = 512) -> float:
    """Measure one LSM knob set and score it with the online tuner's own
    objective (ns; lower is better).  Import is local so the default
    model-cell path stays importable without the core package on path."""
    from repro.core import Telemetry, tuning_objective

    from .common import make_db
    from .ycsb import _load, _mix

    tel = Telemetry()
    pin_kb = int(total_mem_kb * pin_frac)
    db = make_db(c=c, T=T, bits_per_key=10, bloom_allocation="monkey",
                 cache_kb=total_mem_kb - pin_kb, pin_l0_kb=pin_kb,
                 telemetry=tel)
    _load(db, n)
    prev = tel.snapshot()
    _mix(db, n, n_ops, read_frac=0.5, seed=13)
    score = tuning_objective(tel.delta(prev).hists)
    db.close()
    return score


def lsm_main(n: int = 20_000, n_ops: int = 4_000):
    """Greedy coordinate climb over (c, T, pin_frac) on measured stores —
    the offline twin of OnlineTuner's bounded hill-climb, one store per
    candidate instead of one live store retuned at boundaries."""
    from repro.core.tuner import KNOB_BOUNDS

    cur = dict(c=1.0, T=2.0, pin_frac=0.5)
    steps = {"c": 0.2, "T": 1.0, "pin_frac": 0.25}
    best = lsm_score(n=n, n_ops=n_ops, **cur)
    print(f"start {cur} objective={best/1e3:.1f}us")
    improved = True
    while improved:
        improved = False
        for k in cur:
            lo, hi = KNOB_BOUNDS[k]
            for d in (+1, -1):
                cand = dict(cur)
                cand[k] = min(hi, max(lo, round(cur[k] + d * steps[k], 4)))
                if cand[k] == cur[k]:
                    continue
                s = lsm_score(n=n, n_ops=n_ops, **cand)
                print(f"  try {k}={cand[k]}: {s/1e3:.1f}us "
                      f"({'accept' if s < best else 'reject'})")
                if s < best:
                    best, cur, improved = s, cand, True
                    break
    print(f"settled {cur} objective={best/1e3:.1f}us")
    return cur, best


def show(tag, r):
    if r["status"] != "ok":
        print(f"  {tag}: {r['status']} {r.get('error','')[:120]}")
        return
    h = r["hlo_cost"]
    rf = r.get("roofline", {})
    print(f"  {tag:28s} mem={h['hbm_bytes_per_device']/819e9:8.3f}s "
          f"coll={h['collective_bytes_per_device']/50e9:8.3f}s "
          f"comp={h['flops_per_device']/197e12:8.3f}s "
          f"peak={r['memory_analysis']['peak_estimate_bytes']/2**30:6.2f}GiB "
          f"frac={rf.get('roofline_fraction', 0):.4f}")


def main():
    from repro.configs import get_config
    from repro.launch.dryrun import HOST_DEVICES_FLAG, run_cell

    os.environ["XLA_FLAGS"] = HOST_DEVICES_FLAG   # before any device query

    # ---- Cell A: minicpm prefill ------------------------------------------
    print("[A] minicpm_2b / prefill_32k")
    base = get_config("minicpm_2b")
    show("baseline(q_chunk=512)",
         run_cell("minicpm_2b", "prefill_32k", False, force=True))
    it1 = dataclasses.replace(base, scores_dtype="bfloat16")
    show("it1: scores bf16",
         run_cell("minicpm_2b", "prefill_32k", False, force=True,
                  tag="_it1", cfg_override=it1))
    it2 = dataclasses.replace(base, scores_dtype="bfloat16", q_chunk=256)
    show("it2: + q_chunk 256",
         run_cell("minicpm_2b", "prefill_32k", False, force=True,
                  tag="_it2", cfg_override=it2))

    # ---- Cell B: recurrentgemma train -------------------------------------
    print("[B] recurrentgemma_2b / train_4k")
    base = get_config("recurrentgemma_2b")
    show("baseline(dense gates)",
         run_cell("recurrentgemma_2b", "train_4k", False, force=True))
    it1 = dataclasses.replace(
        base, rglru=dataclasses.replace(base.rglru, gate_blocks=16))
    show("it1: block-diag gates",
         run_cell("recurrentgemma_2b", "train_4k", False, force=True,
                  tag="_it1", cfg_override=it1))
    it2 = dataclasses.replace(it1, scores_dtype="bfloat16")
    show("it2: + scores bf16",
         run_cell("recurrentgemma_2b", "train_4k", False, force=True,
                  tag="_it2", cfg_override=it2))

    # ---- Cell C: qwen3 decode ---------------------------------------------
    print("[C] qwen3_4b / decode_32k")
    base = get_config("qwen3_4b")
    show("current(grouped+in-place)",
         run_cell("qwen3_4b", "decode_32k", False, force=True))
    it1 = dataclasses.replace(base, scores_dtype="bfloat16")
    show("it1: scores bf16",
         run_cell("qwen3_4b", "decode_32k", False, force=True,
                  tag="_it1", cfg_override=it1))
    it2 = dataclasses.replace(it1, param_dtype="bfloat16")
    show("it2: + params bf16",
         run_cell("qwen3_4b", "decode_32k", False, force=True,
                  tag="_it2", cfg_override=it2))


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--lsm", action="store_true",
                    help="offline LSM knob climb scored by tuning_objective")
    ap.add_argument("-n", type=int, default=20_000,
                    help="--lsm: loaded keys per candidate store")
    ap.add_argument("--ops", type=int, default=4_000,
                    help="--lsm: mixed ops per candidate store")
    args = ap.parse_args()
    if args.lsm:
        lsm_main(n=args.n, n_ops=args.ops)
    else:
        main()
