"""Run one benchmark cell on the chip this process finds.

    python3 chipbench/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is
the result as one JSON object; the numbers the verdict compared are the
last lines of standard error.  Exits non-zero, printing no result,
without a TPU listed in ``chipbench/peaks.json`` or without the program
(``src/``) beside the benchmark.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
#: The persistent compile cache: fixed, inside the checkout, so only a
#: checkout's first run of a cell compiles and two checkouts share none.
CACHE = ROOT / ".jax_cache"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path.insert(0, str(ROOT / "src"))
    import bench

    try:
        from repro.utils.jax_cache import enable_compile_cache
    except ImportError as e:
        print(f"chipbench: the program is missing: {e}", file=sys.stderr)
        return 2
    import jax

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        result = bench.run(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    except bench.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(bench.json_line(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
