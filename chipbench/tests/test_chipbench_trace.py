"""The trace reduction and the per-layer readers on a small trace
recorded on one TPU v5e: three driver spans, each with a synthesis span
(a host sleep) and a run_packed span that runs five small programs."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

import bench
import trace_reduce
from bench_root import REPO

TRACE = Path(__file__).parent / "data" / "tiny_trace.json"


@pytest.fixture(scope="module")
def reduced():
    events = [tuple(e) for e in json.loads(TRACE.read_text())]
    return trace_reduce.reduce_events(events)


def test_window_and_busy_time(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.087456131)
    assert 0.0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["busy_s"] == pytest.approx(0.000632745)


def test_idle_gaps_are_labelled_by_host_span(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) == 10
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert gaps[0][0] == "synthesize_cell"
    assert {g[0] for g in gaps} <= {"synthesize_cell", "run_packed", "driver",
                                    "harness"}
    assert sum(g[1] for g in gaps) <= reduced["window_s"] - reduced["busy_s"]


def test_device_ops_name_program_and_op(reduced):
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= 10
    assert all(name.startswith("jit_") and "/%" in name for name, _ in ops)
    assert sum(t for _, t in ops) <= reduced["busy_s"] * 1.000001


def test_union_and_clip():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == \
        [(0, 3), (5, 6)]
    assert trace_reduce.clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]


def test_readers_on_the_recorded_trace(reduced):
    cat = bench.Catalog(REPO, "paper-fb.mixed")
    obs = {"cells": 32, "window_s": 0.1, "rows_steps": 32 * 512 * 2,
           "host": {"synthesize_cell": {"calls": 64, "seconds": 0.06},
                    "run_packed": {"calls": 2, "seconds": 0.01}},
           "trace": reduced}
    got = {m["name"]: cat.reader(m["name"])(obs) for m in cat.per_layer}
    assert got["synth_calls_per_cell"] == 2.0
    assert got["synth_ms_per_cell"] == pytest.approx(1e3 * 0.06 / 32)
    assert got["driver_ms_per_cell"] == pytest.approx(1e3 * 0.03 / 32)
    assert got["cell_step_device_us"] == pytest.approx(
        1e6 * 0.000632745 / (32 * 512 * 2))
    assert got["device_idle_share"] == pytest.approx(
        100 * (1 - 0.000632745 / 0.087456131))
    assert 0.0 < got["device_idle_share"] < 100.0


def test_readers_find_nothing_without_a_trace():
    cat = bench.Catalog(REPO, "paper-fb.mixed")
    obs = {"cells": 0, "window_s": 1.0, "rows_steps": 0, "trace": None,
           "host": {"synthesize_cell": {"calls": 0, "seconds": 0.0},
                    "run_packed": {"calls": 0, "seconds": 0.0}}}
    assert all(cat.reader(m["name"])(obs) is None for m in cat.per_layer)


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events(
            [("/device:TPU:0", "XLA Ops", "%x = f32[] add()", 0.0, 1.0)])
