"""Whole-sweep-on-accelerator subsystem (repro.accel): conformance pins.

Correctness is anchored the way every backend so far has been — against
the Python simulator on the golden traces:

* **summary conformance** — accel cell summaries vs ``run_scenario``
  across GOLDEN_SEEDS x {fifo, hfsp, srpt, las} on the quick-scale
  paper-FB workload.  The accel cell is a *fluid* model (fractional
  slots, no task-grain quantization), so the pin is a per-policy
  tolerance band, not bit-equality: mean sojourn within a few percent
  (widest for las, whose attained-service ranking is the most sensitive
  to task granularity), makespan within 2%, completed-job counts exact.
  The bands were set from measured deltas with ~1.5-2x headroom — tight
  enough that a scheduling-logic bug (a wrong key, a starved phase)
  blows through them (docs/accel.md#fidelity);
* **Pallas vs lax bit-equality** — the f32 Pallas water-fill kernel
  (interpret mode on CPU) must match the ``lax.while_loop`` reference
  evaluated in f32 bit for bit, standalone and through a whole cell
  run; the f32-kernel cell stays inside the hfsp fidelity band of the
  f64 lax cell;
* **padding neutrality** — widening the fixed-shape window must not
  change any output bit (padding rows are inert by construction);
* **determinism** — repeated batched runs are bitwise identical;
* **eligibility / fallback** — ineligible specs are refused with a
  reason and routed through the Python runner by the sweep/worker
  entry points.
"""

import os

import numpy as np
import pytest

from conformance import GOLDEN_SEEDS

pytest.importorskip("jax")

from repro.accel import (  # noqa: E402
    accel_eligible,
    run_cells_accel,
    run_sweep_accel,
    synthesize_cell,
)
from repro.accel.cell_step import pack_cells, run_packed, summarize  # noqa: E402
from repro.accel.waterfill_pallas import water_fill_host  # noqa: E402
from repro.scenarios.presets import paper_fb_base  # noqa: E402
from repro.scenarios.runner import run_scenario  # noqa: E402
from repro.scenarios.spec import ScenarioSpec, SweepSpec  # noqa: E402

ACCEL_POLICIES = ("fifo", "hfsp", "srpt", "las")

#: Per-policy mean-sojourn tolerance (relative).  Measured worst-case
#: deltas across GOLDEN_SEEDS: fifo 1.1%, srpt 4.2%, hfsp 5.3%, las
#: 12.4% — the fluid model consistently runs slightly *fast* (it never
#: idles fractional slots), and the error grows with how much the
#: policy's ranking depends on task-grain attained service.
SOJOURN_TOL = {"fifo": 0.03, "srpt": 0.08, "hfsp": 0.10, "las": 0.18}
MAKESPAN_TOL = 0.02


def _golden_specs() -> list[ScenarioSpec]:
    return [
        paper_fb_base(seed=seed).quick().override(
            **{"scheduler.policy": policy}
        )
        for policy in ACCEL_POLICIES
        for seed in GOLDEN_SEEDS
    ]


@pytest.fixture(scope="module")
def golden_runs():
    """All GOLDEN_SEEDS x policies cells, both computes: one vmapped
    accel batch (shared jit cache) + the Python runner per cell."""
    specs = _golden_specs()
    accel = run_cells_accel(specs)
    python = [run_scenario(spec) for spec in specs]
    return {
        (s.scheduler.policy, s.workload.seed): (a, p)
        for s, a, p in zip(specs, accel, python)
    }


@pytest.mark.parametrize("seed", GOLDEN_SEEDS)
@pytest.mark.parametrize("policy", ACCEL_POLICIES)
def test_accel_summary_conformance(golden_runs, policy, seed):
    accel, python = golden_runs[(policy, seed)]
    assert accel["compute"] == "accel"
    assert accel["jobs_completed"] == python["jobs_completed"]
    assert accel["jobs_lost"] == 0
    soj = python["mean_sojourn_s"]
    assert accel["mean_sojourn_s"] == pytest.approx(
        soj, rel=SOJOURN_TOL[policy]
    ), f"{policy}/seed={seed}: accel {accel['mean_sojourn_s']} vs py {soj}"
    assert accel["makespan_s"] == pytest.approx(
        python["makespan_s"], rel=MAKESPAN_TOL
    )


def test_accel_preserves_policy_ordering(golden_runs):
    """The qualitative Sect. 4 result survives the fluid approximation:
    on every golden trace the size-based policies beat FIFO on mean
    sojourn, on the accel path just as on the Python path."""
    for seed in GOLDEN_SEEDS:
        fifo = golden_runs[("fifo", seed)][0]["mean_sojourn_s"]
        for policy in ("hfsp", "srpt"):
            assert golden_runs[(policy, seed)][0]["mean_sojourn_s"] < fifo


# ---------------------------------------------------------------------------
# Pallas water-fill: bit-equality with the lax.while_loop reference at f32
# ---------------------------------------------------------------------------
def test_pallas_waterfill_bit_identical_random_rows():
    """The kernel computes in f32 (Mosaic lowers no f64), so its
    reference is the lax loop evaluated in f32 on the same rows."""
    rng = np.random.default_rng(7)
    for trial in range(25):
        width = int(rng.integers(1, 65))
        caps = rng.uniform(0.0, 30.0, width)
        caps[rng.random(width) < 0.2] = 0.0
        ws = rng.uniform(0.5, 2.0, width)
        live = rng.random(width) < 0.7
        slots = float(rng.uniform(0.0, caps.sum() * 1.2 + 1.0))
        a = water_fill_host(caps, ws, slots, live, impl="pallas_interpret")
        b = water_fill_host(caps, ws, slots, live, impl="lax")
        assert a.dtype == b.dtype == np.float32
        assert a.tobytes() == b.tobytes(), f"trial {trial}"


def test_pallas_waterfill_degenerate_rows():
    ws = np.ones(8)
    # No live entries / no free slots / slots exceeding total caps.
    for slots, live in ((10.0, np.zeros(8, bool)),
                        (0.0, np.ones(8, bool)),
                        (1e6, np.ones(8, bool))):
        a = water_fill_host(np.arange(8.0), ws, slots, live,
                            impl="pallas_interpret")
        b = water_fill_host(np.arange(8.0), ws, slots, live, impl="lax")
        assert a.tobytes() == b.tobytes()


def test_cell_run_pallas_interpret_matches_lax_bitwise(monkeypatch):
    """A whole hfsp cell driven by the Pallas kernel (interpret mode)
    reproduces, bit for bit, the cell driven by the lax reference at
    the kernel's f32 precision (rounded and widened at the same seam);
    against the f64 lax cell it stays inside the hfsp fidelity band."""
    import jax.numpy as jnp

    from repro.accel import cell_step
    from repro.accel.waterfill_pallas import water_fill_lax

    def lax_f32(caps, ws, slots, live):
        f32 = jnp.float32
        return water_fill_lax(
            caps.astype(f32), ws.astype(f32), jnp.asarray(slots, f32), live
        ).astype(caps.dtype)

    make = cell_step._make_water_fill
    monkeypatch.setattr(
        cell_step, "_make_water_fill",
        lambda impl: lax_f32 if impl == "lax_f32" else make(impl),
    )
    spec = paper_fb_base(seed=0).quick().override(
        **{"scheduler.policy": "hfsp"}
    )
    packed = pack_cells([synthesize_cell(spec)])
    try:
        ref32 = summarize(packed, run_packed(packed, impl="lax_f32"))[0]
    finally:
        cell_step._chunk_runner.cache_clear()
    ref64 = summarize(packed, run_packed(packed, impl="lax"))[0]
    got = summarize(packed, run_packed(packed, impl="pallas_interpret"))[0]
    assert got == ref32
    assert got["jobs_completed"] == ref64["jobs_completed"]
    assert got["mean_sojourn_s"] == pytest.approx(
        ref64["mean_sojourn_s"], rel=SOJOURN_TOL["hfsp"]
    )
    assert got["makespan_s"] == pytest.approx(
        ref64["makespan_s"], rel=MAKESPAN_TOL
    )


# ---------------------------------------------------------------------------
# Fixed-shape contract: padding neutrality + determinism
# ---------------------------------------------------------------------------
def test_padding_neutrality_bitwise():
    """Re-packing the same cells at a wider padded width must not move
    one bit of any summary — padded rows are inert (present=0, zero
    caps, infinite arrival)."""
    cells = [
        synthesize_cell(
            paper_fb_base(seed=0).quick().override(
                **{"scheduler.policy": p}
            )
        )
        for p in ("hfsp", "srpt")
    ]
    narrow = pack_cells(cells)
    wide = pack_cells(cells, width=128)
    assert narrow["arrival"].shape[-1] < 128
    a = summarize(narrow, run_packed(narrow, impl="lax"))
    b = summarize(wide, run_packed(wide, impl="lax"))
    assert a == b


def _drop_wall(result):
    """Results are bitwise deterministic except the measured wall time."""
    return {k: v for k, v in result.items() if k != "wall_s"}


def test_accel_batch_deterministic():
    specs = [
        paper_fb_base(seed=1).quick().override(**{"scheduler.policy": p})
        for p in ("hfsp", "las")
    ]
    a = run_cells_accel(specs)
    b = run_cells_accel(specs)
    for ra, rb in zip(a, b):
        assert _drop_wall(ra) == _drop_wall(rb)


def test_error_alpha_cells_run_and_stay_deterministic():
    """Fig. 6-style error cells (alpha > 0) batch alongside exact ones;
    the estimate perturbation is seeded, so repeated runs are bitwise
    equal and differ from the alpha=0 schedule."""
    base = paper_fb_base(seed=0).quick().override(
        **{"scheduler.policy": "hfsp"}
    )
    noisy = base.override(**{"scheduler.error_alpha": 0.5})
    (r0a, rna), (r0b, rnb) = (
        run_cells_accel([base, noisy]), run_cells_accel([base, noisy])
    )
    assert _drop_wall(r0a) == _drop_wall(r0b)
    assert _drop_wall(rna) == _drop_wall(rnb)
    assert r0a["jobs_completed"] == rna["jobs_completed"] == 30
    assert rna["mean_sojourn_s"] != r0a["mean_sojourn_s"]


# ---------------------------------------------------------------------------
# Eligibility + fallback seams
# ---------------------------------------------------------------------------
def test_eligibility_refusals_carry_reasons():
    base = paper_fb_base(seed=0).quick()
    cases = {
        "scheduler.policy": "fair",
        "workload.task_jitter": 0.3,
        "scheduler.preemption": "kill",
        "faults.task_fail_rate": 0.05,
    }
    assert accel_eligible(base.override(**{"scheduler.policy": "hfsp"}))[0]
    for axis, value in cases.items():
        ok, reason = accel_eligible(base.override(**{axis: value}))
        assert not ok and reason, f"{axis}={value}"


def test_run_sweep_accel_mixed_matrix_falls_back_per_cell():
    """A sweep mixing accel-eligible and ineligible policies converges
    with every cell answered: eligible ones carry compute="accel",
    ineligible ones are Python scenario reports (no compute tag)."""
    sweep = SweepSpec(
        name="mixed",
        base=paper_fb_base(seed=0).quick(),
        grids=(SweepSpec.grid(**{
            "scheduler.policy": ("fifo", "fair", "hfsp"),
        }),),
    )
    results = run_sweep_accel(sweep)
    assert set(results) == {
        "scheduler.policy=fifo", "scheduler.policy=fair",
        "scheduler.policy=hfsp",
    }
    assert results["scheduler.policy=fifo"]["compute"] == "accel"
    assert results["scheduler.policy=hfsp"]["compute"] == "accel"
    fair = results["scheduler.policy=fair"]
    assert "compute" not in fair
    assert fair["jobs_completed"] == 30


def test_worker_compute_accel_converges_mixed_sweep(tmp_path):
    """``run_worker(compute="accel")`` drains eligible cells through the
    vmapped batch (in-process, leases renewed between chunks) and routes
    the rest through the spawned Python path — the store converges
    exactly-once either way, and the summary names each fallback."""
    from repro.scenarios.worker import run_worker

    sweep = SweepSpec(
        name="mixed",
        base=paper_fb_base(seed=0).quick(),
        grids=(SweepSpec.grid(**{
            "scheduler.policy": ("fifo", "fair", "hfsp"),
        }),),
    )
    store = tmp_path / "accel_store.jsonl"
    summary = run_worker(sweep, store, compute="accel", deadline=300.0)
    assert not summary["stalled"]
    assert sorted(summary["computed"]) == [
        "scheduler.policy=fair", "scheduler.policy=fifo",
        "scheduler.policy=hfsp",
    ]
    assert summary["accel_cells"] == 2
    assert summary["accel_batches"] == 1
    assert list(summary["accel_fallbacks"]) == ["scheduler.policy=fair"]


def _fifo_sweep() -> SweepSpec:
    return SweepSpec(
        name="one",
        base=paper_fb_base(seed=0).quick(),
        grids=(SweepSpec.grid(**{"scheduler.policy": ("fifo",)}),),
    )


def test_worker_accel_device_error_fails_worker(tmp_path, monkeypatch):
    """Only a batch that runs out of chunks is demoted to the Python
    path; any other accel error (a device or compile fault) fails the
    worker instead of finishing the sweep on the host."""
    from repro.accel import sweep_exec
    from repro.scenarios.worker import run_worker

    def device_fault(*_a, **_k):
        raise RuntimeError("device fault")

    monkeypatch.setattr(sweep_exec, "run_cells_accel", device_fault)
    with pytest.raises(RuntimeError, match="device fault"):
        run_worker(_fifo_sweep(), tmp_path / "s.jsonl", compute="accel",
                   deadline=60.0)


def test_worker_accel_nonconvergence_demotes_to_python(tmp_path, monkeypatch):
    from repro.accel import sweep_exec
    from repro.accel.cell_step import CellBatchNotConverged
    from repro.scenarios.worker import run_worker

    def stuck(*_a, **_k):
        raise CellBatchNotConverged("did not converge")

    monkeypatch.setattr(sweep_exec, "run_cells_accel", stuck)
    summary = run_worker(_fifo_sweep(), tmp_path / "s.jsonl",
                         compute="accel", deadline=120.0)
    assert summary["computed"] == ["scheduler.policy=fifo"]
    assert summary["accel_cells"] == 0
    reason = summary["accel_fallbacks"]["scheduler.policy=fifo"]
    assert reason.startswith("accel batch failed: CellBatchNotConverged")


def test_attempt_process_pins_cpu_backend(monkeypatch):
    """The spawned attempt entry point pins JAX to the CPU before it
    computes anything, so a parent that owns the chip keeps it."""
    import multiprocessing

    import jax

    from repro.scenarios.worker import _cell_worker

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    before = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)
    recv, send = multiprocessing.Pipe(duplex=False)
    try:
        spec = _fifo_sweep().expand()[0][1]
        _cell_worker(send, "c", spec.to_dict())
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert jax.config.jax_platforms == "cpu"
        kind, report = recv.recv()
        assert kind == "ok" and report["jobs_completed"] == 30
    finally:
        jax.config.update("jax_platforms", before)
        recv.close()


def test_compile_cache_location(monkeypatch, tmp_path):
    """The entry points' compile cache: ``JAX_COMPILATION_CACHE_DIR``
    when set (and nothing else touched), else the checkout's fixed,
    git-ignored ``.jax_cache``."""
    import jax

    from repro.utils.jax_cache import CHECKOUT_CACHE, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = CHECKOUT_CACHE.parent
    assert (root / "src" / "repro").is_dir()
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_compile_cache_without_jax(monkeypatch):
    """jax is optional: without it the helper does nothing and returns
    None, so the entry points that call it still run."""
    import sys

    from repro.utils.jax_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setitem(sys.modules, "jax", None)
    assert enable_compile_cache() is None
