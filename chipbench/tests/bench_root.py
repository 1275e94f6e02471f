"""A temporary benchmark root for the tests: the repository's benchmark
files plus a configuration, mix, limits and reader added as files only."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parent

TINY_CONFIG = "paper-fb-quick"
TINY_CELL = "paper-fb-quick.tiny"
TINY_READER = "cells_per_matrix"


def make_root(tmp: Path) -> Path:
    """Copy BENCHMARK.json and the data the harness reads, then add a
    test configuration (the paper's cell at the program's ``.quick()``
    size), a mix, its limits and a per-layer reader."""
    home = tmp / "chipbench"
    for sub in ("configs", "mixes", "limits", "readers"):
        shutil.copytree(BENCH_DIR / sub, home / sub)
    shutil.copy(BENCH_DIR / "peaks.json", home / "peaks.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())

    config = json.loads((home / "configs" / "paper-fb.json").read_text())
    config.update(name=TINY_CONFIG, num_jobs=30, num_machines=20)
    (home / "configs" / f"{TINY_CONFIG}.json").write_text(json.dumps(config))
    (home / "mixes" / "tiny.json").write_text(json.dumps({
        "seeds_per_matrix": 1,
        "grid": {"scheduler.policy": ["fifo", "hfsp"],
                 "scheduler.error_alpha": [0.0, 0.3]},
    }))
    limits = json.loads(
        (home / "limits" / "paper-fb.mixed.json").read_text())
    (home / "limits" / f"{TINY_CELL}.json").write_text(json.dumps(limits))
    (home / "readers" / f"{TINY_READER}.py").write_text(
        "def read(obs):\n    return obs['cells'] / 4\n")

    bench["configs"].append({
        "name": TINY_CONFIG, "source": "test", "reduced": [], "why": "test",
        "file": f"chipbench/configs/{TINY_CONFIG}.json"})
    bench["workloads"].append({
        "name": TINY_CELL, "config": TINY_CONFIG, "traffic": "tiny",
        "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": TINY_READER, "unit": "cells", "better": "higher",
        "source": "host_clock", "layer": "sweep driver",
        "moves": "cells_per_s", "workloads": [TINY_CELL]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
