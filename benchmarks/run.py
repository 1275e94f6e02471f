"""Benchmark driver: one benchmark per paper table/figure + the
beyond-paper ML-workload, kernel/roofline, and scheduler-overhead benches.
Emits CSV blocks.

  PYTHONPATH=src python -m benchmarks.run [--only fig3,fig7] [--fast]
  PYTHONPATH=src python -m benchmarks.run --quick --json BENCH_sched.json

``--quick`` runs only the scheduler wall-clock smoke bench (one FB run per
scheduler) — the one-command perf gate used by scripts/check.sh.  With
``--json PATH`` the per-scheduler wall-clock (and result fingerprints) are
also written to ``PATH`` so successive PRs accumulate a perf trajectory.
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import traceback

from benchmarks import (
    bench_cluster_size,
    bench_estimation_error,
    bench_kernels,
    bench_locality,
    bench_ml_workload,
    bench_per_job_delta,
    bench_preemption,
    bench_roofline,
    bench_sched_overhead,
    bench_sojourn,
)

BENCHES = {
    "fig3": bench_sojourn.main,
    "fig4": bench_per_job_delta.main,
    "fig5": bench_cluster_size.main,
    "fig6": bench_estimation_error.main,
    "fig7": bench_preemption.main,
    "locality": bench_locality.main,
    "ml": bench_ml_workload.main,
    "kernels": bench_kernels.main,
    "roofline": bench_roofline.main,
    "sched_overhead": bench_sched_overhead.main,
}

FAST_SKIP = {"fig5", "fig6", "ml", "sched_overhead"}  # the long ones

QUICK_SCHEDULERS = ("fifo", "fair", "hfsp")


def quick_sched_wall(json_path: str | None = None, seed: int = 0) -> dict:
    """Wall-clock one FB run per scheduler; optionally dump JSON.

    The JSON records, per scheduler: wall-clock seconds, mean sojourn, and
    a completion fingerprint (so a perf regression AND a behaviour change
    are both visible in the trajectory file), plus the water-fill kernel
    microbenchmark at the 5000-job cell (numpy loop vs jitted jax backend,
    see benchmarks/bench_sched_overhead.py), plus the reduced-scale
    ``paper-fb`` scenario smoke sweep (all three schedulers on one small
    FB trace) whose per-scenario mean sojourns let scripts/bench_gate.py
    track *policy-level* regressions across PRs, not just wall-clock.
    """
    from benchmarks.common import CsvOut, run_fb

    out = CsvOut("sched_wall", ["scheduler", "wall_s", "mean_sojourn_s",
                                "completion_fingerprint"])
    record: dict = {
        "bench": "sched_wall",
        "seed": seed,
        "python": platform.python_version(),
        "schedulers": {},
    }
    from repro.scenarios.report import completion_fingerprint

    for name in QUICK_SCHEDULERS:
        res, _, _, wall = run_fb(name, seed=seed)
        fingerprint = completion_fingerprint(res)
        out.add(name, round(wall, 3), round(res.mean_sojourn(), 2), fingerprint)
        record["schedulers"][name] = {
            "wall_s": round(wall, 3),
            "mean_sojourn_s": round(res.mean_sojourn(), 2),
            "completion_fingerprint": fingerprint,
        }
        print(f"# {name}: {wall:.2f}s wall", flush=True)
    out.emit()
    cell = bench_sched_overhead.waterfill_cell(5000, seed=seed)
    record["waterfill_5000"] = {
        k: (round(v, 3) if isinstance(v, float) else v)
        for k, v in cell.items()
    }
    speed = cell["waterfill_speedup"]
    print(
        "# waterfill@5000: "
        + (f"{speed:.1f}x jax speedup" if speed is not None
           else "jax unavailable"),
        flush=True,
    )
    # Demand-indexed decision latency at the trace-scale sparse-demand
    # cell (5000 jobs x 1000 machines): the PR-4 tentpole gate cell —
    # bench_gate.py fails check.sh on a >25% regression of
    # decision_latency_ms, same policy as the hfsp wall gate.
    sparse = bench_sched_overhead.run_sparse_demand(cells=((5000, 1000),))[0]
    record["sched_sparse_5000x1000"] = {
        k: (round(v, 4) if isinstance(v, float) else v)
        for k, v in sparse.items()
    }
    # Per-discipline decision latency at the same cell (Discipline API
    # sanity bound: bench_gate.py fails any recorded discipline >2x the
    # hfsp latency above).  hfsp itself is covered by the sparse block,
    # so only the new registry disciplines re-measure here.
    disc_rows = bench_sched_overhead.run_discipline_latency(
        cells=((5000, 1000),), disciplines=("srpt", "las", "psbs"),
    )
    record["sched_disciplines_5000x1000"] = {
        r["discipline"]: {
            "decision_latency_ms": round(r["decision_latency_ms"], 4),
            "p99_pass_ms": round(r["p99_pass_ms"], 4),
        }
        for r in disc_rows
    }
    # Epsilon-window coalescing sweep: pass-count delta at equal event
    # progress (check.sh prints the delta from this block).
    eps_rows = bench_sched_overhead.run_eps_sweep(seed=seed)
    record["eps_sweep"] = {
        str(r["eps"]): {
            "passes": r["passes"],
            "events": r["events"],
            "passes_per_event": round(r["passes_per_event"], 4),
        }
        for r in eps_rows
    }
    # Compare events-normalized pass rates: a row that hit the sweep's
    # wall-clock safety cap processed fewer events, so raw pass counts
    # across rows would not be comparable.
    base = eps_rows[0]
    for r in eps_rows[1:]:
        ratio = r["passes_per_event"] / max(base["passes_per_event"], 1e-12)
        extra = (
            "" if r["events"] == base["events"]
            else f" [events {r['events']} vs {base['events']}]"
        )
        print(
            f"# eps sweep: eps={r['eps']} cuts passes/event "
            f"{base['passes_per_event']:.4f} -> {r['passes_per_event']:.4f} "
            f"({ratio:.1%} of eps=0){extra}",
            flush=True,
        )
    # Whole-sweep-on-accelerator throughput at the 64-cell matrix
    # (vmapped device program vs process-pool runner, cells/sec) —
    # bench_gate.py enforces a same-record speedup floor on it.  Only a
    # missing jax skips it; an accel error fails the bench.
    accel = bench_sched_overhead.run_accel_sweep()
    if accel is not None:
        record["accel_sweep"] = {
            k: (round(v, 3) if isinstance(v, float) else v)
            for k, v in accel.items()
        }
    else:
        print("# accel sweep: skipped (jax not importable)", flush=True)
    record["scenarios"] = scenario_smoke()
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
        print(f"# wrote {json_path}")
    return record


def scenario_smoke() -> dict:
    """The fast scenario smoke sweep: ``paper-fb`` at reduced scale, all
    three schedulers on one trace.  Returns per-scenario mean sojourn +
    completion fingerprint, keyed ``paper-fb@quick/<policy>`` — the
    policy-level trajectory scripts/bench_gate.py gates on.
    """
    from repro.scenarios import get_preset, quick_sweep, run_sweep

    sweep = quick_sweep(get_preset("paper-fb"))
    results = run_sweep(sweep)
    out: dict = {}
    means: dict = {}
    for cid, rep in sorted(results.items()):
        policy = cid.split("=", 1)[1]
        means[policy] = rep["mean_sojourn_s"]
        out[f"{sweep.name}/{policy}"] = {
            "mean_sojourn_s": round(rep["mean_sojourn_s"], 2),
            "completion_fingerprint": rep["completion_fingerprint"],
            # Tail/fairness trajectory (bench_gate.py gates these the
            # same way as the mean: only when the baseline carries them).
            "p99_sojourn_s": round(rep["tails"]["sojourn"]["p99"], 2),
            "p999_sojourn_s": round(rep["tails"]["sojourn"]["p999"], 2),
            "jain_slowdown": round(rep["fairness"]["jain_slowdown"], 4),
        }
    hfsp_lowest = means["hfsp"] < min(means["fair"], means["fifo"])
    print(
        "# scenario smoke (paper-fb@quick): "
        + " ".join(f"{p}={means[p]:.0f}s" for p in ("fifo", "fair", "hfsp"))
        + f"; hfsp strictly lowest: {hfsp_lowest}",
        flush=True,
    )
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="scheduler wall-clock smoke bench only")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="with --quick: dump per-scheduler wall-clock JSON")
    args = ap.parse_args()
    from repro.utils.jax_cache import enable_compile_cache

    enable_compile_cache()

    if args.quick:
        quick_sched_wall(json_path=args.json)
        return

    names = list(BENCHES)
    if args.only:
        names = [n for n in args.only.split(",") if n in BENCHES]
    elif args.fast:
        names = [n for n in names if n not in FAST_SKIP]

    failed = []
    for name in names:
        print(f"\n==== {name} " + "=" * (60 - len(name)), flush=True)
        t0 = time.time()
        try:
            BENCHES[name]()
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"\nFAILED: {failed}")
        sys.exit(1)
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
