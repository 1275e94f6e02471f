"""The one traffic generator: a closed loop of sweep matrices.

A traffic mix is a data file, ``mixes/<name>.json``:

* ``seeds_per_matrix`` — workload seeds in each matrix;
* ``grid`` — ``{dotted scenario path: [values]}`` crossed with the seeds
  (the paths the reference knows: ``scheduler.policy`` and
  ``scheduler.error_alpha``);
* ``accel_batch`` — the worker's batch claim (the CLI's default is 32).

One client submits a matrix, waits for every result and submits the
next.  Matrix ``k`` of a run with ``--seed s`` draws its workload seeds
and its error seed from ``SeedSequence([s, 0, k])``; the warm-up matrix
from ``SeedSequence([s, 1])``.  So every matrix is fresh (nothing can be
reused across matrices) while the same seed repeats the same inputs.
"""

from __future__ import annotations

import itertools

import numpy as np

REFERENCE_AXES = ("scheduler.policy", "scheduler.error_alpha")
WINDOW, WARMUP = 0, 1


def check_mix(mix: dict) -> None:
    unknown = set(mix["grid"]) - set(REFERENCE_AXES)
    if unknown:
        raise ValueError(f"mix grid axes without a reference: {sorted(unknown)}")
    if "scheduler.policy" not in mix["grid"]:
        raise ValueError("a mix grid names its policies")
    if int(mix["seeds_per_matrix"]) < 1:
        raise ValueError("seeds_per_matrix must be at least 1")


def matrix_seeds(seed: int, phase: int, index: int, n: int) -> tuple[list, int]:
    """(workload seeds, error seed) of one matrix; all below 2**32."""
    words = np.random.SeedSequence(
        [seed % 2**64, phase, index]).generate_state(n + 1)
    return [int(w) for w in words[:n]], int(words[n])


def matrix_cells(mix: dict, seed: int, phase: int, index: int) -> list[dict]:
    """The cells of one matrix as plain dicts (seed-major order)."""
    seeds, error_seed = matrix_seeds(seed, phase, index,
                                     int(mix["seeds_per_matrix"]))
    axes = list(mix["grid"])
    cells = []
    for ws, combo in itertools.product(
            seeds, itertools.product(*(mix["grid"][a] for a in axes))):
        cell = {"workload.seed": ws, "scheduler.error_seed": error_seed}
        cell.update(zip(axes, combo))
        cells.append(cell)
    return cells


def base_overrides(config: dict) -> dict:
    """Scenario fields a configuration file fixes."""
    return {
        "workload.kind": config["generator"],
        "workload.num_jobs": config["num_jobs"],
        "workload.task_jitter": config["task_jitter"],
        "cluster.num_machines": config["num_machines"],
        "cluster.map_slots": config["map_slots_per_machine"],
        "cluster.reduce_slots": config["reduce_slots_per_machine"],
        "scheduler.preemption": config["preemption"],
        "heartbeat": config["heartbeat_s"],
        "event_epsilon": config["event_epsilon"],
    }


def cell_of_spec(spec) -> tuple:
    """The identity of a program scenario in the terms of a plain cell."""
    return (spec.workload.seed, spec.scheduler.policy,
            float(spec.scheduler.error_alpha))


def cell_identity(cell: dict) -> tuple:
    return (cell["workload.seed"], cell["scheduler.policy"],
            float(cell.get("scheduler.error_alpha", 0.0)))


def sweep_for(config: dict, mix: dict, seed: int, phase: int, index: int,
              name: str):
    """The program's ``SweepSpec`` of one matrix: one grid of the
    matrix's workload seeds x the mix's axes over the configuration's
    base scenario, as a user would submit it."""
    from repro.scenarios.spec import ScenarioSpec, SweepSpec

    seeds, error_seed = matrix_seeds(seed, phase, index,
                                     int(mix["seeds_per_matrix"]))
    base = ScenarioSpec(name=name).override(
        **base_overrides(config), **{"scheduler.error_seed": error_seed})
    grid = SweepSpec.grid(**{"workload.seed": seeds}, **mix["grid"])
    return SweepSpec(name=name, base=base, grids=(grid,))
