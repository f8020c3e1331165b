"""Runs of one cell at its full size, several seeds in one process: sound
runs for the readings of the checks, or a fault planted for a control.

    python3 bench/tests/control.py --workload ycsb-c-multiget \
        --seconds 20 --seeds 11 12 13 [--plant lossy_probe]

Each run prints one JSON line: seed, plant, the checks with their limits,
whether every answer agreed with the reference, and the end-to-end
metrics.  The benchmark's own runs (``bench/run.py``) never plant a fault.
On a CPU it needs ``--cpu-rehearsal`` and a small ``--records``.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--plant", default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--records", type=int, default=None)
    args = ap.parse_args()

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[0:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import faults
    from bench.cell import run_cell
    from repro.compile_cache import enable_compile_cache

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        print("no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    plant = faults.PLANTS[args.plant] if args.plant else None
    for seed in args.seeds:
        out = run_cell(args.workload, seed, args.seconds, bool(args.trace),
                       t_start=time.perf_counter(), on_tpu=on_tpu,
                       records=args.records, plant=plant)
        line, info = out["line"], out["info"]
        print(json.dumps({
            "workload": args.workload, "seed": seed, "plant": args.plant,
            "answers_ok": out["answers_ok"], "correct": line["correct"],
            "checks": out["checks"], "checked": info["checked"],
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
            "window_compiles": info["window_compiles"],
            "reference_s": info["reference_s"], "tails": info["tails"],
            "device": line["device"], "breakdown": line.get("breakdown"),
            "store": info["store"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
