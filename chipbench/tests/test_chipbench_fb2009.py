"""FB-2009 at 600 machines: its cell resolves its files, and the readers
of the cell program's counters (``repro.accel.cell_step.RUN_LOG``) take
the window's ``run_packed`` calls only."""

from __future__ import annotations

import pytest

import bench
import traffic
from bench_root import REPO


def test_fb2009_cell_resolves_its_files():
    """FB-2009 at 600 machines: its configuration, the hfsp mix, the
    paper cell's limits and the readers of the cell program's counters;
    one matrix is 16 cells of 600 jobs on 600 machines."""
    cat = bench.Catalog(REPO, "fb2009-600.hfsp")
    assert cat.config["name"] == "fb2009-600"
    assert cat.config["generator"] == "fb_scaled"
    assert cat.mix["grid"] == {"scheduler.policy": ["hfsp"],
                               "scheduler.error_alpha": [0.0, 0.1, 0.3, 0.5]}
    assert cat.limits == bench.Catalog(REPO, "paper-fb.mixed").limits
    names = {m["name"] for m in cat.per_layer}
    assert {"event_steps_per_cell", "scan_fill_share"} <= names
    for name in ("event_steps_per_cell", "scan_fill_share"):
        assert callable(cat.reader(name))
    assert int(cat.cell["chips"]) == 1
    sweep = traffic.sweep_for(cat.config, cat.mix, 2**31 + 9, traffic.WINDOW,
                              0, name="t")
    specs = [s for _, s in sweep.expand()]
    assert len(specs) == 16
    assert {(s.workload.num_jobs, s.cluster.num_machines) for s in specs} == \
        {(600, 600)}


def _counter_readers():
    cat = bench.Catalog(REPO, "fb2009-600.hfsp")
    return {name: cat.reader(name)
            for name in ("event_steps_per_cell", "scan_fill_share")}


def test_counter_readers_take_the_window_calls_only():
    """A warm-up call, then two window calls of the device program on
    small cells: the readers see the window's two calls and nothing of
    the warm-up's."""
    import numpy as np

    from repro.accel.cell_step import pack_cells, run_packed
    from repro.accel.sweep_exec import synthesize_cell
    from repro.scenarios.presets import paper_fb_base

    def batch(policies, seed):
        return pack_cells([synthesize_cell(paper_fb_base(seed=seed).quick()
                                           .override(**{"scheduler.policy": p}))
                           for p in policies])

    run_packed(batch(("las",) * 5, 9), t_chunk=64, impl="none")  # warm-up
    window = [batch(("fifo", "srpt"), 1), batch(("hfsp",), 2)]
    finals = [run_packed(p, t_chunk=64, impl="lax") for p in window]
    steps = sum(int(f["steps"].sum()) for f in finals)
    scanned = sum(len(f["steps"]) * 64 * -(-int(f["steps"].max()) // 64)
                  for f in finals)
    obs = {"cells": 3, "window_s": 1.0, "rows_steps": scanned, "trace": None,
           "host": {"synthesize_cell": {"calls": 3, "seconds": 0.1},
                    "run_packed": {"calls": 2, "seconds": 0.1}}}
    got = {name: read(obs) for name, read in _counter_readers().items()}
    assert got["event_steps_per_cell"] == pytest.approx(steps / 3)
    assert got["scan_fill_share"] == pytest.approx(100.0 * steps / scanned)
    assert 0.0 < got["scan_fill_share"] <= 100.0
    assert np.all([f["closes"].sum() > 0 for f in finals])


def test_counter_readers_find_nothing_without_the_log(monkeypatch):
    """A program whose device path keeps no log (the parent of the
    counters) gives the readers nothing to read; they do not raise."""
    from repro.accel import cell_step

    monkeypatch.delattr(cell_step, "RUN_LOG")
    obs = {"cells": 16, "window_s": 1.0, "rows_steps": 1, "trace": None,
           "host": {"synthesize_cell": {"calls": 16, "seconds": 0.1},
                    "run_packed": {"calls": 1, "seconds": 0.1}}}
    assert all(read(obs) is None for read in _counter_readers().values())
