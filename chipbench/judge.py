"""How ``correct`` is decided: the timed path's answers against the
reference's.

For every cell of the window the reference builds the cell's inputs
from the frozen generators (``fb_reference``) and runs them through the
numpy fluid model (``fluid_reference``).  Five numbers are compared,
each with a limit of its own (``limits/<cell>.json``):

* ``cells_missing`` — cells with no result in the store, a quarantined
  one, or one computed off the device path;
* ``rows_unmatched`` — cells whose exact inputs (float64 bytes of every
  row array and scalar) no row handed to the device program carries:
  the synthesis layer made a different job stream;
* ``job_count_errors`` — stored results whose job count is not the
  reference's or that lost a job;
* ``completion_gap`` — the widest gap of a job's completion time, from
  the device program's final state, to the reference's, over the
  cell's reference makespan;
* ``summary_gap`` — the widest relative gap of a stored makespan or mean
  sojourn to the reference's.
"""

from __future__ import annotations

import math

import numpy as np

import fb_reference
import fluid_reference

NUMBERS = ("cells_missing", "rows_unmatched", "job_count_errors",
           "completion_gap", "summary_gap")


def reference_cells(config: dict, cells: list[dict], dtype=np.float64):
    """Reference answers of one matrix's plain cells: a list of dicts
    with the inputs' key, completion times, makespan and mean sojourn."""
    workloads = {}
    inputs = []
    for c in cells:
        seed = c["workload.seed"]
        if seed not in workloads:
            workloads[seed] = fb_reference.workload_rows(config, seed)
        inputs.append(fb_reference.cell_inputs(
            config, workloads[seed], c["scheduler.policy"],
            float(c.get("scheduler.error_alpha", 0.0)),
            c["scheduler.error_seed"]))
    keys = fb_reference.ROW_KEYS + fb_reference.SCALAR_KEYS
    batch = {k: np.stack([np.asarray(i[k]) for i in inputs]) for k in keys}
    comp = fluid_reference.simulate(batch, dtype=dtype)["comp"]
    out = []
    for c, inp, row in zip(cells, inputs, comp.astype(np.float64)):
        soj = row - inp["arrival"]
        out.append({
            "cell": c,
            "inputs": inp,
            "key": fb_reference.row_key(inp),
            "comp": row,
            "jobs": len(row),
            "makespan_s": float(row.max()),
            "mean_sojourn_s": float(soj.mean()),
        })
    return out


def captured_rows(captured: list[tuple[dict, dict]]) -> dict[bytes, np.ndarray]:
    """{input key: completion times} of every row the device ran."""
    out = {}
    for params, final in captured:
        present = np.asarray(params["present"]) > 0.5
        comp = np.asarray(final["comp"], np.float64)
        for b in range(present.shape[0]):
            m = present[b]
            if not m.any():
                continue
            rows = {k: np.asarray(params[k][b])[m]
                    for k in fb_reference.ROW_KEYS}
            rows.update({k: np.asarray(params[k][b])
                         for k in fb_reference.SCALAR_KEYS})
            out[fb_reference.row_key(rows)] = comp[b][m]
    return out


def _rel(a, b) -> float:
    d = abs(float(a) - float(b)) / max(abs(float(b)), 1e-300)
    return d if math.isfinite(d) else math.inf


def judge(expected: list[tuple[str, dict]], records: dict[str, dict],
          captured: list[tuple[dict, dict]], limits: dict) -> dict:
    """``expected``: ``(cell id, reference answer)`` per cell of the
    window; ``records``: the store's results by cell id.  Returns the
    numbers, the cells that failed and ``correct``."""
    rows = captured_rows(captured)
    n = dict.fromkeys(NUMBERS, 0)
    gaps = {"completion_gap": [], "summary_gap": []}
    failed = 0
    for cid, ref in expected:
        bad = False
        rec = records.get(cid)
        if rec is None or rec.get("quarantined") or rec.get("compute") != "accel":
            n["cells_missing"] += 1
            bad = True
        else:
            if (rec.get("jobs_completed") != ref["jobs"]
                    or rec.get("jobs_lost") != 0):
                n["job_count_errors"] += 1
                bad = True
            g = max(_rel(rec["makespan_s"], ref["makespan_s"]),
                    _rel(rec["mean_sojourn_s"], ref["mean_sojourn_s"]))
            gaps["summary_gap"].append(g)
            bad |= not g <= limits["summary_gap"]
        comp = rows.get(ref["key"])
        if comp is None:
            n["rows_unmatched"] += 1
            bad = True
        else:
            g = float(np.max(np.abs(comp - ref["comp"]))) / ref["makespan_s"]
            g = g if math.isfinite(g) else math.inf
            gaps["completion_gap"].append(g)
            bad |= not g <= limits["completion_gap"]
        failed += bad
    for k, v in gaps.items():
        n[k] = max(v) if v else math.inf
    correct = bool(expected) and all(n[k] <= limits[k] for k in NUMBERS)
    return {"numbers": n, "failed": failed, "correct": correct}


def check_lines(numbers: dict, limits: dict) -> list[str]:
    return [f"check {k}: {numbers[k]!r} (limit {limits[k]!r})"
            for k in NUMBERS]


def check_json(numbers: dict, limits: dict) -> dict:
    def num(x):
        return x if math.isfinite(x) else str(x)
    return {k: {"value": num(numbers[k]), "limit": limits[k]}
            for k in NUMBERS}
