"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with the reference beside its limit.  The same checks are
the last lines of standard error.  An earlier line of standard output gives
the tails' sample counts and medians, the compiles inside the window and
what the store did in it.

The run refuses to start unless JAX's first device is a TPU with as many
chips as the cell asks for.  ``--cpu-rehearsal`` lets it run on the CPU at
a small ``--records``, to rehearse the code paths; such a run never reports
``correct: true``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
REHEARSAL_MAX_RECORDS = 20_000


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU (never reports correct: true)")
    ap.add_argument("--records", type=int, default=None,
                    help=f"record count of a CPU rehearsal "
                         f"(at most {REHEARSAL_MAX_RECORDS})")
    args = ap.parse_args(argv)

    # The benchmark keeps JAX's persistent compilation cache at a fixed path
    # inside its checkout, and gives the program that directory.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    try:
        from repro import compile_cache
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"the program (src/repro) is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    if not Path(compile_cache.__file__).resolve().is_relative_to(ROOT / "src"):
        print(f"repro imported from {compile_cache.__file__}, outside this "
              f"checkout",
              file=sys.stderr)
        return 2

    from bench.spec import Spec
    spec = Spec()
    cell = spec.cell(args.workload)

    import jax
    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        print(f"no TPU: JAX's first device is {devs[0].platform!r}",
              file=sys.stderr)
        return 3
    if on_tpu and len(devs) < cell["chips"]:
        print(f"the cell needs {cell['chips']} chips, JAX finds {len(devs)}",
              file=sys.stderr)
        return 3
    if args.records is not None and (on_tpu or not 0 < args.records
                                     <= REHEARSAL_MAX_RECORDS):
        print(f"--records is for CPU rehearsals of at most "
              f"{REHEARSAL_MAX_RECORDS} records", file=sys.stderr)
        return 2
    enable_compile_cache()

    from bench.cell import run_cell
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, on_tpu=on_tpu, spec=spec,
                   records=args.records)
    print(json.dumps(out["info"]), flush=True)
    if not on_tpu:
        print("CPU rehearsal: not a chip run, never correct",
              file=sys.stderr)
    if "first_error" in out["info"]:
        print(out["info"]["first_error"], file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["line"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
