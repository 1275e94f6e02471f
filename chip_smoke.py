"""Chip smoke: the device path on one TPU at the paper's Sect. 4.1 size.

    python3 chip_smoke.py

Runs everything in one process (a chip belongs to one process at a time;
the one Python-path attempt the worker phase spawns is pinned to the
CPU) and exits non-zero on any failure.  Phases:

1. **device** — JAX's first device must be a TPU; there is no CPU
   fallback.
2. **accel matrix** — the 64 cells of ``accel_matrix(quick=False)``
   (``paper_fb_base()``: 100 FB jobs on 100 machines with 4 map + 2
   reduce slots; seeds 0-3 x fifo/hfsp/srpt/las x error_alpha 0, 0.1,
   0.3, 0.5) through ``run_cells_accel(impl="lax")``, then the 16 hfsp
   cells with ``impl="pallas"``.  Every cell is compared with the same
   program on the host CPU device of this process (``pallas`` against
   ``pallas_interpret``): equal job counts, makespan and mean sojourn
   within 1e-6 relative.  The pallas cells stay within the docs/accel.md
   fidelity bands of the lax cells, and the compiled kernel within 8 f32
   ulps of the f32 ``lax`` loop on random rows.  The warm matrix is
   also timed by layer (synthesis, packing, device program).  The
   deviation from the Python simulator (seeds 0-1, alpha 0) is printed,
   not bounded.
3. **worker** — ``run_worker(compute="accel")`` over the full-size
   ``paper-fb`` preset: fifo and hfsp run on the chip, fair falls back
   to a spawned Python attempt with its eligibility reason.
4. **jax virtual cluster** — paper-fb hfsp with ``vc_backend="jax"``
   must schedule bit-identically to the numpy backend (same completion
   fingerprint); ``water_fill_batch`` / ``project_finish_times`` at
   5000 live jobs must meet the kernels' contract against numpy (1e-9
   relative), and any departure from bit-identity is printed.

Wall times are the host's clock around each phase on this device: cold
includes compilation, warm reruns the compiled program.  The last line
of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: Chip-vs-host agreement of the same cell program (relative).
REL_TOL = 1e-6
SEEDS = (0, 1, 2, 3)
SIZE_BASED = ("hfsp", "srpt")
WATERFILL_JOBS = 5000
#: Compiled Pallas kernel vs the f32 lax loop on the chip, in f32 ulps.
KERNEL_ULPS = 8


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def timed(label: str, fn):
    t0 = time.perf_counter()
    out = fn()
    print(f"# {label}: {time.perf_counter() - t0:.3f} s host wall",
          flush=True)
    return out


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-300)


def require_tpu():
    import jax

    devices = jax.devices()
    dev = devices[0]
    check(
        dev.platform == "tpu",
        f"no TPU: JAX's first device is {dev.platform!r} "
        f"({len(devices)} device(s)); this smoke runs only on a TPU",
    )
    print(f"# device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    return dev, devices


def compare_cells(label, specs, got, ref) -> float:
    """Chip vs host results of the same cells; returns the worst
    relative deviation and fails past :data:`REL_TOL`."""
    worst, bad = 0.0, []
    for spec, g, r in zip(specs, got, ref):
        check(g["jobs_completed"] == r["jobs_completed"],
              f"{label} {spec.name}: jobs {g['jobs_completed']} "
              f"vs host {r['jobs_completed']}")
        for key in ("makespan_s", "mean_sojourn_s"):
            d = rel(g[key], r[key])
            worst = max(worst, d)
            if d > REL_TOL:
                s = spec.scheduler
                bad.append(
                    f"seed={spec.workload.seed} {s.policy} "
                    f"alpha={s.error_alpha} {key}: chip {g[key]!r} "
                    f"host {r[key]!r} (rel {d:.3e})"
                )
    print(f"# {label}: {len(specs)} cells, worst chip-vs-host relative "
          f"deviation {worst:.3e}", flush=True)
    check(not bad, f"{label}: {len(bad)} values past {REL_TOL:g}: "
          + "; ".join(bad[:5]))
    return worst


def kernel_ulps() -> None:
    """The compiled kernel against the f32 lax reference on the chip:
    the largest distance in f32 ulps per width, bounded by
    :data:`KERNEL_ULPS` (interpret mode is bit-equal, pinned by
    tests/test_accel.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.accel.waterfill_pallas import water_fill_lax, water_fill_pallas

    rng = np.random.default_rng(0)
    for width in (32, 128, 1024):
        caps = np.floor(rng.uniform(0.0, 30.0, (64, width))).astype(np.float32)
        ws = rng.uniform(0.5, 2.0, (64, width)).astype(np.float32)
        live = rng.random((64, width)) < 0.7
        slots = rng.uniform(1.0, caps.sum(-1) * 1.2).astype(np.float32)
        args = tuple(map(jnp.asarray, (caps, ws, slots, live)))
        got = np.asarray(jax.jit(jax.vmap(water_fill_pallas))(*args))
        ref = np.asarray(jax.jit(jax.vmap(water_fill_lax))(*args))
        ulps = np.abs(got.view(np.int32).astype(np.int64)
                      - ref.view(np.int32).astype(np.int64)).max()
        print(f"# pallas vs f32 lax on chip, W={width}: max {ulps} ulp",
              flush=True)
        check(ulps <= KERNEL_ULPS, f"pallas vs f32 lax at W={width}: "
              f"{ulps} ulp, bound {KERNEL_ULPS}")


def layer_split(specs) -> None:
    """Host wall of the warm accel matrix by layer on this machine: cell
    synthesis and packing on the host, then ``run_packed`` (the device
    program plus its per-chunk host syncs), grouped as
    ``run_cells_accel`` groups them."""
    from repro.accel.cell_step import pack_cells, run_packed
    from repro.accel.sweep_exec import synthesize_cell

    for klass, impl in (("plain", "none"), ("hfsp", "lax")):
        group = [s for s in specs
                 if (s.scheduler.policy == "hfsp") == (klass == "hfsp")]
        t0 = time.perf_counter()
        cells = [synthesize_cell(s) for s in group]
        t1 = time.perf_counter()
        packed = pack_cells(cells)
        t2 = time.perf_counter()
        run_packed(packed, impl=impl)
        t3 = time.perf_counter()
        print(f"# accel matrix {klass} ({len(group)} cells) host wall: "
              f"synthesize_cell {t1 - t0:.3f} s, pack_cells "
              f"{t2 - t1:.3f} s, run_packed {t3 - t2:.3f} s", flush=True)


def phase_accel_matrix(cpu) -> None:
    import jax

    from benchmarks.bench_sched_overhead import accel_matrix
    from repro.accel import run_cells_accel
    from repro.scenarios.runner import run_scenario

    specs = [s for _, s in accel_matrix(quick=False).expand()]
    check(len(specs) == 64, f"accel matrix has {len(specs)} cells")
    check(all(s.workload.num_jobs == 100 and s.cluster.num_machines == 100
              and s.cluster.map_slots == 4 and s.cluster.reduce_slots == 2
              for s in specs), "accel matrix is not the Sect. 4.1 cell")
    chip = timed("accel matrix lax, 64 cells, chip cold",
                 lambda: run_cells_accel(specs, impl="lax"))
    timed("accel matrix lax, 64 cells, chip warm",
          lambda: run_cells_accel(specs, impl="lax"))
    layer_split(specs)
    with jax.default_device(cpu):
        host = timed("accel matrix lax, 64 cells, host CPU",
                     lambda: run_cells_accel(specs, impl="lax"))
    compare_cells("accel lax", specs, chip, host)

    kernel_ulps()
    hfsp = [i for i, s in enumerate(specs) if s.scheduler.policy == "hfsp"]
    hspecs = [specs[i] for i in hfsp]
    pchip = timed("accel matrix pallas, 16 hfsp cells, chip cold",
                  lambda: run_cells_accel(hspecs, impl="pallas"))
    timed("accel matrix pallas, 16 hfsp cells, chip warm",
          lambda: run_cells_accel(hspecs, impl="pallas"))
    with jax.default_device(cpu):
        phost = timed(
            "accel matrix pallas_interpret, 16 hfsp cells, host CPU",
            lambda: run_cells_accel(hspecs, impl="pallas_interpret"),
        )
    compare_cells("accel pallas", hspecs, pchip, phost)
    # The kernel is f32, the lax cell f64: the same fidelity bands as
    # the fluid cell against the simulator (docs/accel.md#fidelity).
    for spec, p, i in zip(hspecs, pchip, hfsp):
        ls = chip[i]
        check(p["jobs_completed"] == ls["jobs_completed"],
              f"pallas vs lax jobs on {spec.name}")
        check(rel(p["mean_sojourn_s"], ls["mean_sojourn_s"]) <= 0.10
              and rel(p["makespan_s"], ls["makespan_s"]) <= 0.02,
              f"pallas vs lax outside the hfsp band: {p} vs {ls}")
    worst = max(max(rel(p["mean_sojourn_s"], chip[i]["mean_sojourn_s"]),
                    rel(p["makespan_s"], chip[i]["makespan_s"]))
                for p, i in zip(pchip, hfsp))
    print(f"# accel pallas vs lax on chip: worst relative deviation "
          f"{worst:.3e}", flush=True)

    by_cell = {(s.workload.seed, s.scheduler.policy, s.scheduler.error_alpha):
               r for s, r in zip(specs, chip)}
    for seed in SEEDS:
        fifo = by_cell[(seed, "fifo", 0.0)]["mean_sojourn_s"]
        for policy in SIZE_BASED:
            got = by_cell[(seed, policy, 0.0)]["mean_sojourn_s"]
            check(got < fifo, f"seed {seed}: {policy} mean sojourn {got} "
                  f"does not beat fifo {fifo}")
    for seed in (0, 1):
        for s, r in zip(specs, chip):
            if s.workload.seed != seed or s.scheduler.error_alpha != 0.0:
                continue
            py = run_scenario(s)
            check(r["jobs_completed"] == py["jobs_completed"],
                  f"seed {seed} {s.scheduler.policy}: accel jobs "
                  f"{r['jobs_completed']} vs simulator "
                  f"{py['jobs_completed']}")
            print(
                f"# fluid vs simulator seed={seed} "
                f"{s.scheduler.policy:4s}: mean sojourn "
                f"{r['mean_sojourn_s'] / py['mean_sojourn_s'] - 1:+.2%}, "
                f"makespan {r['makespan_s'] / py['makespan_s'] - 1:+.2%}",
                flush=True,
            )


def phase_worker() -> None:
    from repro.scenarios.presets import get_preset
    from repro.scenarios.store import open_store
    from repro.scenarios.worker import run_worker

    sweep = get_preset("paper-fb")
    cells = [cid for cid, _ in sweep.expand()]
    with tempfile.TemporaryDirectory() as tmp:
        store = Path(tmp) / "paper-fb.sweep.jsonl"
        summary = timed(
            "worker paper-fb compute=accel",
            lambda: run_worker(sweep, store, compute="accel",
                               deadline=600.0),
        )
        results = open_store(store).load()
    fallbacks = summary["accel_fallbacks"]
    print(f"# worker: accel cells {summary['accel_cells']} in "
          f"{summary['accel_batches']} batch(es); fallbacks {fallbacks}",
          flush=True)
    check(not summary["stalled"], "worker stalled")
    check(sorted(summary["computed"]) == sorted(cells),
          f"worker computed {summary['computed']}")
    check(summary["accel_cells"] == 2 and summary["accel_batches"] == 1,
          f"worker ran {summary['accel_cells']} accel cells in "
          f"{summary['accel_batches']} batches, expected 2 in 1")
    check(list(fallbacks) == ["scheduler.policy=fair"],
          f"worker fallbacks {fallbacks}")
    reason = fallbacks["scheduler.policy=fair"]
    check("policy 'fair'" in reason and "accel batch failed" not in reason,
          f"fair fell back for {reason!r}")
    by_cid = {cid: res for (cid, _h), res in results.items()}
    for cid in cells:
        res = by_cid[cid]
        check(res.get("jobs_completed") == 100, f"{cid}: {res}")
        want = None if cid.endswith("fair") else "accel"
        check(res.get("compute") == want, f"{cid} ran as {res.get('compute')}")


def first_divergence(a, b) -> str:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    diff = np.flatnonzero(a.ravel() != b.ravel())
    if not len(diff):
        return "none"
    i = int(diff[0])
    return (f"{len(diff)} of {a.size} entries differ; first at flat index "
            f"{i}: chip {a.ravel()[i]!r} numpy {b.ravel()[i]!r}")


def phase_vcluster_jax() -> None:
    import numpy as np

    from repro.core import vcluster_jax
    from repro.core.vcluster import _project_array, _water_fill
    from repro.scenarios.presets import paper_fb_base
    from repro.scenarios.report import completion_fingerprint
    from repro.scenarios.runner import simulate
    from repro.workload import fb_scaled_dataset

    base = paper_fb_base(seed=0).override(**{"scheduler.policy": "hfsp"})
    ref, _, _ = timed("hfsp paper-fb vc_backend=numpy", lambda: simulate(
        base.override(**{"scheduler.vc_backend": "numpy"})))
    got, _, sch = timed("hfsp paper-fb vc_backend=jax, chip", lambda: simulate(
        base.override(**{"scheduler.vc_backend": "jax"})))
    check(all(vc.backend == "jax" for vc in sch.vc.values()),
          "jax virtual cluster did not run on the jax backend")
    fp_ref, fp_got = completion_fingerprint(ref), completion_fingerprint(got)
    print(f"# vcluster fingerprint numpy {fp_ref} jax {fp_got}", flush=True)
    if fp_got != fp_ref:
        order = sorted(ref.completion, key=lambda j: (ref.completion[j], j))
        first = next(j for j in order
                     if got.completion.get(j) != ref.completion[j])
        raise SmokeFailure(
            f"jax virtual cluster schedule differs from numpy; first "
            f"divergent job {first}: chip {got.completion.get(first)!r} "
            f"numpy {ref.completion[first]!r}")

    jobs, _ = fb_scaled_dataset(seed=0, num_jobs=WATERFILL_JOBS,
                                num_machines=1000)
    caps = np.array([len(j.map_tasks) for j in jobs], np.float64)
    rng = np.random.default_rng(0)
    rem = caps * rng.uniform(5.0, 50.0, len(caps))
    ws = np.ones(len(caps))
    slots = 4000.0
    caps_b = np.stack([caps, np.maximum(caps - 1.0, 0.0), caps * 2.0,
                       np.ceil(caps / 2.0)])
    slots_b = np.array([slots, slots, slots / 2.0, slots * 2.0])
    fill = timed(f"water_fill_batch 4x{len(caps)}, chip",
                 lambda: vcluster_jax.water_fill_batch(
                     caps_b, np.ones_like(caps_b), slots_b))
    fill_ref = np.stack([_water_fill(c, np.ones_like(c), s)
                         for c, s in zip(caps_b, slots_b)])
    proj = timed(f"project_finish_times {len(caps)}, chip",
                 lambda: vcluster_jax.project_finish_times(
                     rem, caps, ws, slots, 0.0))
    proj_ref = _project_array(rem.copy(), caps, ws, slots, 0.0)
    print(f"# waterfill_{WATERFILL_JOBS} bit-identity to numpy: fill "
          f"{first_divergence(fill, fill_ref)}; projection "
          f"{first_divergence(proj, proj_ref)}", flush=True)
    # The kernels' numerical contract (docs/vcluster.md, pinned by
    # tests/test_vcluster_jax.py): allocations and finish times within
    # 1e-9 relative of numpy; bit-identity is owed by the schedules.
    try:
        np.testing.assert_allclose(fill, fill_ref, rtol=1e-9, atol=1e-8)
        finite = np.isfinite(proj_ref)
        check(bool((finite == np.isfinite(proj)).all()),
              "projection finiteness differs from numpy")
        np.testing.assert_allclose(proj[finite], proj_ref[finite],
                                   rtol=1e-9, atol=1e-9)
    except AssertionError as e:
        raise SmokeFailure(f"waterfill_{WATERFILL_JOBS} vs numpy: {e}")


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: no src/repro beside this script; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.utils.jax_cache import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    try:
        dev, devices = require_tpu()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    import jax

    cpu = jax.devices("cpu")[0]
    phases = {
        "accel matrix": lambda: phase_accel_matrix(cpu),
        "worker": phase_worker,
        "jax virtual cluster": phase_vcluster_jax,
    }
    failed = []
    for name, phase in phases.items():
        # Every phase runs even after one fails, so one chip run reports
        # all of them; any failure still fails the smoke.
        try:
            timed(f"phase {name}", phase)
        except Exception as e:  # noqa: BLE001 - reported, then fails the run
            if not isinstance(e, SmokeFailure):
                traceback.print_exc()
            print(f"chip_smoke: phase {name} FAILED: {e}", file=sys.stderr,
                  flush=True)
            failed.append(name)
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
