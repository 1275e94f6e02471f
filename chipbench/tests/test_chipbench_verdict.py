"""The references against the program, the control, and whole runs with
the timed path broken underneath: each fault must read ``correct``
false.  All on the CPU at the program's ``.quick()`` size."""

from __future__ import annotations

import numpy as np
import pytest

import bench
import control
import fb_reference
import fluid_reference
import judge
import traffic
from bench_root import TINY_CELL, make_root

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def _quick_config(root, generator):
    cat = bench.Catalog(root, TINY_CELL)
    return dict(cat.config, generator=generator)


@pytest.mark.parametrize("generator", ["fb", "fb_scaled"])
def test_frozen_synthesis_matches_the_program(root, generator):
    from repro.accel.sweep_exec import synthesize_cell
    from repro.scenarios.spec import ScenarioSpec

    config = _quick_config(root, generator)
    base = ScenarioSpec().override(**traffic.base_overrides(config))
    for seed, policy, alpha in ((3, "hfsp", 0.0), (2**32 - 5, "las", 0.3)):
        spec = base.override(**{"workload.seed": seed,
                                "scheduler.policy": policy,
                                "scheduler.error_alpha": alpha,
                                "scheduler.error_seed": 11})
        got = dict(synthesize_cell(spec))
        wl = fb_reference.workload_rows(config, seed)
        want = fb_reference.cell_inputs(config, wl, policy, alpha, 11)
        assert fb_reference.row_key(got) == fb_reference.row_key(want)


def test_fluid_reference_matches_run_packed(root):
    """The plain fluid reference against the device program (on the CPU)
    over every policy, at the program's .quick() size."""
    from repro.accel.cell_step import pack_cells, run_packed

    config = _quick_config(root, "fb")
    cells = [{"workload.seed": s, "scheduler.policy": p,
              "scheduler.error_alpha": a, "scheduler.error_seed": 5}
             for s in (1, 2) for p in ("fifo", "hfsp", "srpt", "las")
             for a in (0.0, 0.5)]
    refs = judge.reference_cells(config, cells)
    for pair in range(0, len(refs), 2):  # the harness runs' batch shape
        batch = refs[pair:pair + 2]
        packed = pack_cells([dict(r["inputs"], present=np.ones(r["jobs"]))
                             for r in batch])
        rows = judge.captured_rows([(packed, run_packed(packed, impl="lax"))])
        for r in batch:
            got = rows[r["key"]]
            assert np.max(np.abs(got - r["comp"])) <= 1e-12 * r["makespan_s"]


def test_float32_control_is_not_correct(root):
    cat = bench.Catalog(root, TINY_CELL)
    for seed in (SEED, 5, 6):
        verdict = control.control_verdict(cat.config, cat.mix, cat.limits,
                                          seed)
        assert not verdict["correct"]
        assert verdict["numbers"]["completion_gap"] > \
            cat.limits["completion_gap"]


def _init_state(params):
    from repro.accel.cell_step import init_state

    return init_state(params)


def _unchanged(orig):
    def run_packed(params, **kw):
        return _init_state(params)
    return run_packed


def _half_batch(orig):
    def run_packed(params, **kw):
        out = {k: np.array(v) for k, v in orig(params, **kw).items()}
        rest = _init_state(params)
        half = (np.asarray(params["arrival"]).shape[0] + 1) // 2
        for k in out:
            out[k][half:] = rest[k][half:]
        return out
    return run_packed


def _altered_answer(orig):
    def run_packed(params, **kw):
        final = orig(params, **kw)
        final["comp"] = np.array(final["comp"])
        j = int(np.argmax(final["comp"][0]))
        final["comp"][0, j] *= 1 + 1e-6
        return final
    return run_packed


FAULTS = {"state_unchanged": ("run_packed", _unchanged),
          "half_batch": ("run_packed", _half_batch),
          "answer_altered": ("run_packed", _altered_answer)}


def _altered_synthesis(orig):
    def synthesize_cell(spec):
        cell = dict(orig(spec))
        cell["arrival"] = cell["arrival"].copy()
        cell["arrival"][-1] += 1e-6
        return cell
    return synthesize_cell


FAULTS["synthesis_altered"] = ("synthesize_cell", _altered_synthesis)


def test_sound_run_is_correct(root):
    result = bench.run(root, TINY_CELL, SEED, 0.0, False, require_chip=False)
    assert result["correct"], result["checks"]
    assert result["attempted"] == 4 and result["failed"] == 0
    assert set(result["metrics"]) == {"cells_per_s", "setup_s"}
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(root, fault, monkeypatch):
    from repro.accel import sweep_exec

    name, make = FAULTS[fault]
    monkeypatch.setattr(sweep_exec, name, make(getattr(sweep_exec, name)))
    result = bench.run(root, TINY_CELL, SEED, 0.0, False, require_chip=False)
    assert not result["correct"]
    assert result["failed"] > 0


def test_no_tpu_no_run(root):
    cat = bench.Catalog(root, TINY_CELL)
    with pytest.raises(bench.BenchError):
        bench.device_info(cat, require_chip=True)


def test_model_stated_in_the_reference_drains():
    """A lone job: MAP's two 10 s tasks run side by side on 4 slots and
    end at 10 s, then REDUCE's one 30 s task ends at 40 s."""
    one = {k: np.array([[v]]) for k, v in dict(
        arrival=0.0, m_work=20.0, m_dur=10.0, m_tasks=2.0, r_work=30.0,
        r_dur=30.0, r_tasks=1.0, est_m=20.0, est_r=30.0, weight=1.0).items()}
    one.update({k: np.array([v]) for k, v in dict(
        policy=0, map_slots=4.0, red_slots=2.0, dt_cap=np.inf,
        eps=0.0).items()})
    out = fluid_reference.simulate(one)
    assert out["comp"][0, 0] == pytest.approx(40.0)
