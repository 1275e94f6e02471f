"""BENCHMARK.json, its data files and the traffic arithmetic (no JAX)."""

from __future__ import annotations

import json
import re
import subprocess
import sys

import pytest

import bench
import traffic
from bench_root import BENCH_DIR, REPO, TINY_CELL, TINY_READER, make_root

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_benchmark_json_keys_and_names(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["chipbench"]
    assert 1 <= spec["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in spec[k]]
    assert all(NAME.match(n) for n in names)
    for key in ("configs", "workloads"):
        assert len({e["name"] for e in spec[key]}) == len(spec[key])
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert e2e == {"cells_per_s", "setup_s"}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
    pairs = {(w["config"], w["traffic"]) for w in spec["workloads"]}
    assert len(pairs) == len(spec["workloads"])
    assert all(w["chips"] == 1 for w in spec["workloads"])


def test_free_text_fits_its_limits(spec):
    texts = [e["why"] for k in ("configs", "workloads") for e in spec[k]]
    texts += [c["source"] for c in spec["configs"]]
    texts += [m["layer"] for m in spec["per_layer"]] + spec["command"]
    assert all(1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_run_seconds_fits_a_full_check(spec):
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (spec["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


CELLS = [w["name"] for w in
         json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files(cell, spec):
    cat = bench.Catalog(REPO, cell)
    assert cat.config["name"] == cat.cell["config"]
    assert cat.config["precision"] == "float64"
    assert set(cat.limits) >= {"completion_gap", "summary_gap"}
    assert {m["name"] for m in cat.end_to_end} == {"cells_per_s", "setup_s"}
    for m in cat.per_layer:
        assert callable(cat.reader(m["name"]))
    entry = next(c for c in spec["configs"] if c["name"] == cat.cell["config"])
    assert sorted(entry["reduced"]) == sorted(cat.config["reduced"])


def test_an_added_cell_is_found_from_files_only(tmp_path):
    root = make_root(tmp_path)
    cat = bench.Catalog(root, TINY_CELL)
    assert cat.config["num_jobs"] == 30
    assert cat.mix["seeds_per_matrix"] == 1
    names = [m["name"] for m in cat.per_layer]
    assert TINY_READER in names
    assert cat.reader(TINY_READER)({"cells": 8}) == 2
    # The repository's own cells do not see the test's reader.
    assert TINY_READER not in [
        m["name"] for m in bench.Catalog(root, "paper-fb.mixed").per_layer]


def test_window_arithmetic():
    assert not bench.window_done(29.9, 30)
    assert bench.window_done(30.0, 30)
    assert bench.window_done(0.1, 0)
    assert bench.window_rate(64, 53.3) == pytest.approx(64 / 53.3)


def test_matrix_seeds_are_fresh_and_repeatable():
    big = 2**31 + 12345
    a = traffic.matrix_seeds(big, traffic.WINDOW, 0, 2)
    assert a == traffic.matrix_seeds(big, traffic.WINDOW, 0, 2)
    others = [traffic.matrix_seeds(big, traffic.WINDOW, 1, 2),
              traffic.matrix_seeds(big, traffic.WARMUP, 0, 2),
              traffic.matrix_seeds(big + 1, traffic.WINDOW, 0, 2)]
    for o in others:
        assert not set(a[0]) & set(o[0])
    assert all(0 <= s < 2**32 for s in a[0] + [a[1]])


def test_matrix_cells_match_the_program_sweep():
    cat = bench.Catalog(REPO, "paper-fb.mixed")
    cells = traffic.matrix_cells(cat.mix, 7, traffic.WINDOW, 3)
    assert len(cells) == 32
    sweep = traffic.sweep_for(cat.config, cat.mix, 7, traffic.WINDOW, 3,
                              name="t")
    got = [traffic.cell_of_spec(s) for _, s in sweep.expand()]
    assert got == [traffic.cell_identity(c) for c in cells]
    spec = sweep.expand()[0][1]
    assert spec.cluster.num_machines == 100 and spec.workload.num_jobs == 100
    assert spec.scheduler.error_seed == cells[0]["scheduler.error_seed"]


def test_mix_axes_need_a_reference():
    with pytest.raises(ValueError):
        traffic.check_mix({"seeds_per_matrix": 1,
                           "grid": {"scheduler.policy": ["fifo"],
                                    "cluster.num_machines": [10]}})


def test_no_program_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    exits non-zero and prints nothing on standard output."""
    import shutil

    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "chipbench/run_cell.py", "--workload",
         "paper-fb.mixed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout == ""
