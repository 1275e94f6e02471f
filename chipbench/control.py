"""The control of the verdict: the reference in float32, put in the
program's place, must come out not correct.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3 \
        [--matrices 2]

For each seed it builds the matrices a run of that seed submits first,
answers them with the numpy fluid model in float32 (the precision below
the float64 the configurations state) and judges those answers against
the float64 reference exactly as a run's answers are judged.  It prints
one JSON line per seed with the numbers compared.  It needs no chip and
imports nothing of the program.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

import fb_reference
import judge
import traffic

ROOT = Path(__file__).resolve().parents[1]


def control_verdict(config: dict, mix: dict, limits: dict, seed: int,
                    matrices: int = 1) -> dict:
    expected, records, captured = [], {}, []
    keys = fb_reference.ROW_KEYS + fb_reference.SCALAR_KEYS
    for index in range(matrices):
        cells = traffic.matrix_cells(mix, seed, traffic.WINDOW, index)
        ref = judge.reference_cells(config, cells)
        low = judge.reference_cells(config, cells, dtype=np.float32)
        params = {k: np.stack([np.asarray(r["inputs"][k]) for r in low])
                  for k in keys}
        params["present"] = np.ones_like(params["arrival"])
        captured.append((params, {"comp": np.stack([r["comp"] for r in low])}))
        for i, (r, lo) in enumerate(zip(ref, low)):
            cid = f"{index}.{i}"
            expected.append((cid, r))
            records[cid] = {"compute": "accel", "jobs_completed": lo["jobs"],
                            "jobs_lost": 0, "makespan_s": lo["makespan_s"],
                            "mean_sojourn_s": lo["mean_sojourn_s"]}
    return judge.judge(expected, records, captured, limits)


def main(argv=None) -> int:
    import bench

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--matrices", type=int, default=1)
    args = p.parse_args(argv)
    cat = bench.Catalog(ROOT, args.workload)
    for seed in args.seeds:
        v = control_verdict(cat.config, cat.mix, cat.limits, seed,
                            args.matrices)
        print(json.dumps({"seed": seed, "correct": v["correct"],
                          "failed": v["failed"],
                          "checks": judge.check_json(v["numbers"],
                                                     cat.limits)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
