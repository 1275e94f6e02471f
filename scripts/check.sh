#!/usr/bin/env bash
# One-command gate: tier-1 tests (including the fault-injection
# determinism/robustness suite, tests/test_faults.py) + the quick
# scheduler benchmark (which includes the paper-fb@quick scenario smoke
# sweep, the sparse-demand 5000x1000 decision-latency cell, and the
# epsilon-window coalescing sweep) + the perf-trajectory gate (appends
# BENCH_sched.json to BENCH_history.jsonl and fails on a >25% hfsp
# wall-clock regression OR a >25% sparse-demand decision-latency
# regression (0.3ms noise floor) OR a >10% per-scenario mean-sojourn
# regression — policy-level quality, not just speed — vs the previous
# entry) + a paper-faults@quick goodput/sojourn summary (scheduling
# under machine/task failures; informational, the properties themselves
# are pinned by tests/test_faults.py) + the live-service smoke + the
# distributed-sweep smoke (2 workers, 1 SIGKILLed; exactly-once
# convergence with a reclaimed lease) + the accel smoke (vmapped
# executor + Pallas water-fill; skips cleanly without jax).  The quick
# bench also records the 64-cell accel_sweep block, gated by
# bench_gate.py at >= 3x pool cells/sec.
#
#   scripts/check.sh            # tests + quick bench + trajectory gate
#   scripts/check.sh --no-bench # tests only
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 tests =="
python -m pytest -x -q

if [[ "${1:-}" != "--no-bench" ]]; then
  echo
  echo "== quick scheduler benchmark =="
  python -m benchmarks.run --quick --json BENCH_sched.json
  echo
  echo "== perf trajectory gate =="
  python scripts/bench_gate.py --json BENCH_sched.json \
    --history BENCH_history.jsonl --threshold 0.25
  echo
  echo "== epsilon-window pass-count delta =="
  python - <<'PY'
import json
rec = json.load(open("BENCH_sched.json"))
sweep = rec.get("eps_sweep", {})
# Ratios use passes_per_event: rows that hit the sweep's wall-clock
# safety cap processed fewer events, so raw pass counts don't compare.
base = sweep.get("0.0", {}).get("passes_per_event")
for eps in sorted(sweep, key=float):
    row = sweep[eps]
    delta = (
        f" ({row['passes_per_event'] / base:.1%} of eps=0 passes/event)"
        if base and float(eps) > 0 else ""
    )
    print(
        f"eps={eps}: {row['passes']} passes / {row['events']} events"
        f"{delta}"
    )
PY
  echo
  echo "== paper-faults@quick goodput/sojourn =="
  python scripts/faults_summary.py --workers 4

  echo
  echo "== live service smoke (twin fingerprint + p99 decision latency) =="
  # Master + 2 in-process workers, 50-job burst, one worker killed
  # mid-workload; fails if the journal's Simulator replay diverges from
  # the live run or p99 decision latency blows past the bound.
  python scripts/service_smoke.py --jobs 50 --p99-ms 250

  echo
  echo "== distributed sweep smoke (2 workers, 1 SIGKILLed mid-cell) =="
  # Two CLI workers share a store on paper-fb@quick; the one holding a
  # lease is SIGKILLed mid-cell.  Fails unless the survivor reclaims
  # the lease (reissues >= 1) and the sweep converges exactly-once with
  # zero quarantines.
  python scripts/dist_sweep_smoke.py

  echo
  echo "== accel sweep smoke (vmapped executor, Pallas water-fill) =="
  # A small accel batch end-to-end: eligible cells run as one vmapped
  # device program, the Pallas water-fill in interpret mode must be
  # bit-identical to the f32 lax reference on a random row, and an
  # ineligible spec must report a fallback reason.  Skips cleanly when
  # jax is absent (the conformance pins live in tests/test_accel.py).
  python - <<'PY'
from repro.accel.cell_step import have_jax

if not have_jax():
    print("accel smoke: SKIPPED (jax unavailable)")
    raise SystemExit(0)

import numpy as np
from repro.accel import accel_eligible, run_cells_accel
from repro.accel.waterfill_pallas import water_fill_host
from repro.scenarios.presets import paper_fb_base

specs = [
    paper_fb_base(seed=0).quick().override(**{"scheduler.policy": p})
    for p in ("fifo", "hfsp")
]
for r in run_cells_accel(specs):
    assert r["compute"] == "accel" and r["jobs_completed"] == 30, r
    print(f"accel smoke: {r['spec']['scheduler']['policy']:4s} "
          f"mean_sojourn {r['mean_sojourn_s']:.1f}s "
          f"({r['accel_steps']} device steps)")
rng = np.random.default_rng(0)
caps = rng.integers(1, 30, 32).astype(float)
ws = np.ones(32)
live = rng.random(32) < 0.8
pallas = water_fill_host(caps, ws, 40.0, live, impl="pallas_interpret")
lax = water_fill_host(caps, ws, 40.0, live, impl="lax")
assert pallas.tobytes() == lax.tobytes(), "pallas != f32 lax bitwise"
print("accel smoke: pallas water-fill (interpret) bit-identical to the "
      "f32 lax reference")
ok, reason = accel_eligible(
    paper_fb_base(seed=0).quick().override(**{"scheduler.policy": "fair"})
)
assert not ok and reason
print(f"accel smoke: fallback reason plumbed ({reason!r})")
PY
fi
