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


# ---------------------------------------------------------------------------
# Workload memo: each distinct workload built once per claim
# ---------------------------------------------------------------------------
def _cell_bytes(cell: dict) -> dict:
    return {k: (np.asarray(v).dtype, np.asarray(v).tobytes())
            for k, v in cell.items()}


@pytest.mark.parametrize("policy", ACCEL_POLICIES)
@pytest.mark.parametrize("kind", ("fb", "fb_scaled"))
def test_memoized_synthesis_is_byte_identical(kind, policy):
    """Cells answered from the memo carry every key's exact bytes; the
    per-cell part (error model, heartbeat cap, event window) is computed
    afresh for each cell on the shared workload."""
    from repro.accel.sweep_exec import workload_memo

    base = paper_fb_base(seed=3).quick().override(**{
        "workload.kind": kind, "scheduler.policy": policy,
        "scheduler.error_seed": 17,
    })
    specs = [
        base.override(**{"scheduler.error_alpha": alpha,
                         "heartbeat": heartbeat, "event_epsilon": eps})
        for alpha in (0.0, 0.3)
        for heartbeat in (3.0, 0.5)
        for eps in ("auto", 1.5)
    ]
    plain = [_cell_bytes(synthesize_cell(s)) for s in specs]
    with workload_memo() as memo:
        memoized = [_cell_bytes(synthesize_cell(s)) for s in specs]
    assert memoized == plain
    assert (memo.built, memo.hits) == (1, len(specs) - 1)


@pytest.mark.parametrize(
    "seeds, policies, built, hits",
    [((0, 1), ACCEL_POLICIES, 2, 14), (tuple(range(8)), ("hfsp",), 8, 8)],
    ids=["2-seeds-x-4-policies", "8-distinct-seeds"],
)
def test_worker_builds_each_workload_once_per_claim(
        tmp_path, seeds, policies, built, hits):
    """The dry run and the batch of one claim share the memo: one build
    per distinct workload, every other synthesis a hit."""
    from repro.scenarios.worker import run_worker

    sweep = SweepSpec(
        name="memo",
        base=paper_fb_base().quick(),
        grids=(SweepSpec.grid(**{"workload.seed": seeds,
                                 "scheduler.policy": policies}),),
    )
    summary = run_worker(sweep, tmp_path / "s.jsonl", compute="accel",
                         deadline=300.0)
    assert summary["accel_cells"] == len(seeds) * len(policies)
    assert summary["accel_batches"] == 1
    assert summary["accel_workloads_built"] == built
    assert summary["accel_synth_hits"] == hits


@pytest.mark.parametrize("kind", ("fb", "fb_scaled"))
def test_unfit_workload_rejects_every_cell_from_one_build(kind):
    from repro.accel.sweep_exec import workload_memo

    base = paper_fb_base(seed=0).quick().override(**{
        "workload.kind": kind, "workload.task_jitter": 0.3,
    })
    with workload_memo() as memo:
        for policy in ACCEL_POLICIES:
            spec = base.override(**{"scheduler.policy": policy})
            with pytest.raises(ValueError, match="unequal per-phase"):
                synthesize_cell(spec)
    assert (memo.built, memo.hits) == (1, len(ACCEL_POLICIES) - 1)


@pytest.mark.parametrize("scoped", (False, True), ids=["no-scope", "scope"])
def test_workload_arrays_are_read_only(scoped):
    """Cells share the workload's arrays, so a write into one raises
    instead of reaching the others."""
    import contextlib

    from repro.accel.sweep_exec import workload_memo

    spec = paper_fb_base(seed=0).quick()
    with workload_memo() if scoped else contextlib.nullcontext():
        cell = synthesize_cell(spec)
    for key in ("arrival", "m_work", "m_dur", "m_tasks", "r_work", "r_dur",
                "r_tasks", "weight", "present", "est_m", "est_r"):
        assert not cell[key].flags.writeable, key
        with pytest.raises(ValueError, match="read-only"):
            cell[key][0] = 1.0


@pytest.mark.parametrize("nested", (False, True),
                         ids=["second-scope", "nested-scope"])
def test_memo_lives_with_its_scope(nested):
    """A second scope builds again; a scope opened inside an open one
    joins it."""
    from repro.accel.sweep_exec import workload_memo

    spec = paper_fb_base(seed=0).quick()
    with workload_memo() as first:
        a = synthesize_cell(spec)
        if nested:
            with workload_memo() as inner:
                b = synthesize_cell(spec)
            assert inner is first
    if not nested:
        with workload_memo() as second:
            b = synthesize_cell(spec)
        assert (second.built, second.hits) == (1, 0)
        assert b["arrival"] is not a["arrival"]
    else:
        assert (first.built, first.hits) == (1, 1)
        assert b["arrival"] is a["arrival"]


# ---------------------------------------------------------------------------
# Arrival-window closes: a step that closes a window lands on the close
# ---------------------------------------------------------------------------
def _fluid_reference():
    """The benchmark's plain numpy fluid model, imported from its file."""
    import importlib.util
    from pathlib import Path

    path = (Path(__file__).resolve().parents[1] / "chipbench"
            / "fluid_reference.py")
    spec = importlib.util.spec_from_file_location("fluid_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tie_cell(t_entry: float, close: float, eps: float) -> tuple:
    """A fifo cell whose pending job 1 opens a window of ``eps`` that
    closes exactly on job 2's arrival; job 0 runs long.  Returns the
    packed batch and a state at ``t_entry`` with job 0 live."""
    from repro.accel.cell_step import init_state

    arrival = np.array([0.0, close - eps, close])
    assert arrival[1] + eps == close and arrival[1] > t_entry
    ones = np.ones(3)
    cell = {
        "arrival": arrival, "m_work": np.array([500.0, 5.0, 5.0]),
        "m_dur": np.array([500.0, 5.0, 5.0]), "m_tasks": ones,
        "r_work": np.zeros(3), "r_dur": ones, "r_tasks": np.zeros(3),
        "weight": ones, "present": ones,
        "est_m": np.array([500.0, 5.0, 5.0]), "est_r": np.zeros(3),
        "policy": np.int32(0), "map_slots": np.float64(10.0),
        "red_slots": np.float64(10.0), "dt_cap": np.float64(np.inf),
        "eps": np.float64(eps),
    }
    packed = pack_cells([cell])
    state = init_state(packed)
    state["t"][:] = t_entry
    return packed, state


@pytest.mark.parametrize(
    "t_entry, close, eps",
    [
        # Entry 1e-12 (relative) short of the close, job 1 inside it.
        (1000.0 * (1 - 1e-12), 1000.0, 1000.0 * 0.5e-12),
        # Entry far before the close: IEEE's t + (close - t) rounds an
        # ulp short here, as a chip's float64 can anywhere.
        (8.141953458148652, 40.30694090928882, 1.5),
    ],
    ids=["entry-1e-12-short", "t-plus-dt-rounds-short"],
)
def test_cell_step_lands_on_the_window_close(t_entry, close, eps):
    """The step that closes job 1's window ends on the packed close
    itself, so job 2, whose arrival ties the close, arrives with job 1
    in the next step and no further window later."""
    import jax
    import jax.numpy as jnp

    from repro.accel import cell_step

    packed, state = _tie_cell(t_entry, close, eps)
    fill = cell_step._make_water_fill("none")
    with jax.enable_x64(True):
        params = {k: jnp.asarray(v[0]) for k, v in packed.items()}
        st = {k: jnp.asarray(v[0]) for k, v in state.items()}
        one = cell_step._cell_step(params, st, fill)
        two = cell_step._cell_step(params, one, fill)
    assert float(one["t"]) == close
    assert int(one["closes"]) == 1 and int(one["steps"]) == 1
    # Both jobs of the window hold slots in the second step.
    m_rem = np.asarray(two["m_rem"])
    assert m_rem[1] < 5.0 and m_rem[2] < 5.0


def _tie_specs(policy: str) -> list[ScenarioSpec]:
    """fb_scaled at 48 jobs (width 64); a 30 s heartbeat lets the auto
    window be the median gap, which then ties one arrival per workload."""
    return [
        paper_fb_base(seed=seed).override(**{
            "workload.kind": "fb_scaled", "workload.num_jobs": 48,
            "cluster.num_machines": 48, "heartbeat": 30.0,
            "scheduler.policy": policy, "scheduler.error_alpha": alpha,
            "scheduler.error_seed": 9,
        })
        for seed in (2, 5, 8) for alpha in (0.0, 0.3)
    ]


@pytest.mark.parametrize("policy", ACCEL_POLICIES)
def test_window_ties_match_the_fluid_reference(policy):
    """Where the auto window closes exactly on an arrival, the program
    agrees with the float64 reference within 1e-12 of the makespan."""
    ref = _fluid_reference()
    cells = [synthesize_cell(s) for s in _tie_specs(policy)]
    for c in cells:
        assert c["eps"] > 0
        assert np.isin(c["arrival"], c["arrival"] + c["eps"]).any()
    packed = pack_cells(cells)
    assert packed["arrival"].shape[-1] == 64
    final = run_packed(packed, impl="lax")
    rows = {k: np.stack([np.asarray(c[k]) for c in cells]) for k in (
        "arrival", "m_work", "m_dur", "m_tasks", "r_work", "r_dur",
        "r_tasks", "est_m", "est_r", "weight", "policy", "map_slots",
        "red_slots", "dt_cap", "eps")}
    want = ref.simulate(rows)["comp"]
    got = final["comp"][:, :48]
    makespan = want.max(axis=1, keepdims=True)
    assert np.all(np.abs(got - want) <= 1e-12 * makespan)
    assert np.all(final["closes"] > 0)


# ---------------------------------------------------------------------------
# run_packed's counters and the worker's sums of them
# ---------------------------------------------------------------------------
def test_worker_sums_the_run_counters(tmp_path):
    """One claim's summary: event steps are its cells' ``accel_steps``;
    host syncs and scanned row-steps follow each group's chunks."""
    from repro.accel.cell_step import RUN_LOG
    from repro.scenarios.store import open_store
    from repro.scenarios.worker import run_worker

    sweep = SweepSpec(
        name="counters",
        base=paper_fb_base().quick(),
        grids=(SweepSpec.grid(**{"workload.seed": (0, 1),
                                 "scheduler.policy": ("fifo", "hfsp",
                                                      "las")}),),
    )
    before = RUN_LOG.count
    store = tmp_path / "s.jsonl"
    summary = run_worker(sweep, store, compute="accel", deadline=300.0)
    assert summary["accel_batches"] == 1
    records = RUN_LOG.since(before)
    assert len(records) == 2  # the plain group, then the hfsp group
    results = list(open_store(store).load().values())
    steps = {"hfsp": [], "plain": []}
    for r in results:
        klass = "hfsp" if r["spec"]["scheduler"]["policy"] == "hfsp" \
            else "plain"
        steps[klass].append(r["accel_steps"])
    chunks = {k: -(-max(v) // 512) for k, v in steps.items()}
    assert summary["accel_event_steps"] == sum(
        r["accel_steps"] for r in results)
    assert summary["accel_host_syncs"] == sum(chunks.values())
    assert summary["accel_scanned_row_steps"] == sum(
        len(steps[k]) * 512 * chunks[k] for k in steps)
    assert 0 < summary["accel_window_closes"] <= summary["accel_event_steps"]
    assert [r["host_syncs"] for r in records] == [chunks["plain"],
                                                  chunks["hfsp"]]


def test_run_log_records_a_batch_that_does_not_drain():
    from repro.accel.cell_step import RUN_LOG, CellBatchNotConverged

    cell = synthesize_cell(paper_fb_base(seed=0).quick())
    packed = pack_cells([cell, cell])
    before = RUN_LOG.count
    with pytest.raises(CellBatchNotConverged):
        run_packed(packed, t_chunk=4, max_chunks=2, impl="none")
    (record,) = RUN_LOG.since(before)
    assert record["host_syncs"] == 2
    assert record["scanned_row_steps"] == 2 * 4 * 2
    assert record["event_steps"] == 2 * 4 * 2
