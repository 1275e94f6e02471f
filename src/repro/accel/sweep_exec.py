"""Vmapped sweep executor: ScenarioSpec batches on the accelerator.

The seam between the declarative scenario layer and the fixed-shape cell
(:mod:`repro.accel.cell_step`): synthesize each eligible spec's workload
host-side into a flat padded event window (the same ``build_workload``
path the Python runner uses, so the trace is *identical* — only the
execution model differs), stack cells into one ``(B, J)`` batch, and run
the whole matrix as a single compiled program.

Not every spec axis fits a fixed-shape fluid cell; :func:`accel_eligible`
is the single authority on which do (docs/accel.md#eligibility).  The
sweep entry point and the distributed-sweep worker (``worker --compute
accel``) both route ineligible cells through the unchanged Python
runner per cell — accel is an *alternative compute path*, never a
different answer for specs it can't represent.
"""

from __future__ import annotations

import contextvars
import time
from contextlib import contextmanager

import numpy as np

from repro.accel.cell_step import (
    POLICY_CODES,
    have_jax,
    pack_cells,
    run_packed,
    summarize,
)
from repro.core.simulator import auto_event_epsilon
from repro.scenarios.spec import ScenarioSpec, SweepSpec

#: Workload kinds the fluid cell can represent (generator-backed, equal
#: per-phase task durations at task_jitter=0; "trace" replays arbitrary
#: recorded durations and stays on the Python path).
_ACCEL_KINDS = ("fb", "fb_scaled")


def accel_eligible(spec: ScenarioSpec) -> tuple[bool, str]:
    """(eligible, reason-if-not) — the worker logs the reason when a
    cell falls back to the Python path."""
    if not have_jax():  # pragma: no cover - environment without jax
        return False, "jax not importable"
    if spec.workload.kind not in _ACCEL_KINDS:
        return False, f"workload kind {spec.workload.kind!r}"
    if spec.workload.task_jitter != 0.0:
        return False, "task_jitter != 0 (unequal per-phase durations)"
    if spec.scheduler.policy not in POLICY_CODES:
        return False, f"policy {spec.scheduler.policy!r}"
    if spec.scheduler.preemption != "eager":
        return False, f"preemption {spec.scheduler.preemption!r}"
    if spec.cluster.dma_bandwidth != 0.0:
        return False, "dma_bandwidth != 0 (preemption cost model)"
    if spec.faults.enabled:
        return False, "fault injection enabled"
    return True, ""


def _resolve_eps(spec: ScenarioSpec, arrivals: list[float]) -> float:
    eps = spec.event_epsilon
    if eps == "auto":
        return auto_event_epsilon(arrivals, spec.heartbeat)
    return float(eps)


class WorkloadMemo:
    """The workload parts built inside one :func:`workload_memo` scope,
    with counts of builds and of calls answered from the memo."""

    def __init__(self) -> None:
        self.parts: dict[tuple, dict | ValueError] = {}
        self.built = 0
        self.hits = 0


_MEMO: contextvars.ContextVar[WorkloadMemo | None] = contextvars.ContextVar(
    "repro_accel_workload_memo", default=None
)


@contextmanager
def workload_memo():
    """Share workload parts among the :func:`synthesize_cell` calls of
    one claim.

    Inside the scope each distinct workload (``spec.workload`` and the
    resolved host count) is built once; every other cell on it, and the
    batch's second synthesis after the eligibility dry run, reuses the
    read-only arrays.  Entering while a scope is open joins that scope,
    so one claim shares one memo.  The memo dies with the outermost
    scope; yields the :class:`WorkloadMemo` with its counts.
    """
    memo = _MEMO.get()
    if memo is not None:
        yield memo
        return
    memo = WorkloadMemo()
    token = _MEMO.set(memo)
    try:
        yield memo
    finally:
        _MEMO.reset(token)


def _build_workload_part(spec: ScenarioSpec) -> dict:
    """The per-job arrays of the spec's workload, read-only; raises
    ``ValueError`` if the job stream violates the fluid contract."""
    from repro.scenarios.runner import build_workload

    jobs, _ = build_workload(spec)
    j = len(jobs)

    def phase_arrays(tasks_of):
        n = np.zeros(j)
        d = np.ones(j)
        for i, job in enumerate(jobs):
            tasks = tasks_of(job)
            n[i] = len(tasks)
            if tasks:
                durs = {t.duration for t in tasks}
                if len(durs) > 1:
                    raise ValueError(
                        "unequal per-phase task durations; accel cell "
                        "requires task_jitter=0 generator workloads"
                    )
                d[i] = max(tasks[0].duration, 1e-9)
        return n, d

    m_n, m_d = phase_arrays(lambda job: job.map_tasks)
    r_n, r_d = phase_arrays(lambda job: job.reduce_tasks)
    weight = np.array([job.weight for job in jobs], np.float64)
    if j and not np.all(weight == weight[0]):
        raise ValueError("non-uniform job weights; accel fifo/srpt/las "
                         "keys ignore weight")
    if any(job.reduce_slowstart != 1.0 for job in jobs):
        raise ValueError("reduce_slowstart != 1.0")
    part = {
        "arrival": np.array([job.arrival_time for job in jobs], np.float64),
        "m_work": m_n * m_d,
        "m_dur": m_d,
        "m_tasks": m_n,
        "r_work": r_n * r_d,
        "r_dur": r_d,
        "r_tasks": r_n,
        "weight": weight,
        "present": np.ones(j, np.float64),
    }
    for arr in part.values():
        arr.setflags(write=False)
    return part


def _workload_part(spec: ScenarioSpec) -> dict:
    """The workload part of a cell, from the open memo when there is one.
    A ``ValueError`` is memoized too: every cell of an unfit workload
    gets the same rejection without a rebuild."""
    memo = _MEMO.get()
    if memo is None:
        return _build_workload_part(spec)
    w = spec.workload
    key = (w, w.num_hosts or spec.cluster.num_machines)
    part = memo.parts.get(key)
    if part is None:
        memo.built += 1
        try:
            part = _build_workload_part(spec)
        except ValueError as e:
            part = e
        memo.parts[key] = part
    else:
        memo.hits += 1
    if isinstance(part, ValueError):
        raise ValueError(*part.args)
    return part


def synthesize_cell(spec: ScenarioSpec) -> dict:
    """Materialize one eligible spec into flat cell params (numpy).

    Reuses the runner's ``build_workload``/``build_cluster`` so the job
    stream is bit-identical to the Python cell's; raises ``ValueError``
    if the materialized workload violates the fluid contract (unequal
    per-phase durations, non-uniform weights) — callers treat that as
    ineligibility and fall back.  Inside a :func:`workload_memo` scope
    the workload part is built once per distinct workload; its arrays
    are read-only in every case.
    """
    from repro.scenarios.runner import build_cluster

    part = _workload_part(spec)
    cluster = build_cluster(spec)
    m_work, r_work = part["m_work"], part["r_work"]

    alpha = spec.scheduler.error_alpha
    if alpha != 0.0:
        # Fig. 6 error model, same rng family/seed as the Python
        # scheduler's _perturb — one draw per phase estimate, drawn in
        # job-id order (the Python side draws at estimate-finalization
        # order, so alpha>0 cells agree in distribution, not
        # draw-for-draw; docs/accel.md#fidelity).
        rng = np.random.default_rng(spec.scheduler.error_seed)
        factors = rng.uniform(1.0 - alpha, 1.0 + alpha, size=(len(m_work), 2))
        est_m = np.maximum(m_work * factors[:, 0], 1e-9)
        est_r = np.maximum(r_work * factors[:, 1], 1e-9)
    else:
        est_m, est_r = m_work, r_work

    policy = spec.scheduler.policy
    return {
        **part,
        "est_m": est_m,
        "est_r": est_r,
        "policy": np.int32(POLICY_CODES[policy]),
        "map_slots": np.float64(
            cluster.num_machines * cluster.map_slots_per_machine
        ),
        "red_slots": np.float64(
            cluster.num_machines * cluster.reduce_slots_per_machine
        ),
        # fifo's order is static (arrival): no mid-flight re-rank needed,
        # so the only dt events are arrivals and completions.  The
        # size/age-evolving policies re-rank once per executor heartbeat,
        # the Python simulator's own decision cadence.
        "dt_cap": np.float64(
            np.inf if policy == "fifo" else spec.heartbeat
        ),
        "eps": np.float64(_resolve_eps(spec, part["arrival"].tolist())),
    }


def run_cells_accel(
    specs: list[ScenarioSpec],
    *,
    impl: str = "lax",
    t_chunk: int = 512,
    max_chunks: int = 256,
    on_chunk=None,
) -> list[dict]:
    """Run eligible specs as ONE vmapped device program; returns
    scenario-report-compatible summary dicts (the subset the sweep
    store, worker CLI, and ``matrix_report`` consume), in spec order.

    ``wall_s`` is the batch wall divided evenly across cells — the cells
    ran concurrently in one program, so per-cell wall is an accounting
    convention, not a measurement.  Opens a :func:`workload_memo` scope
    unless the caller has one open, so cells on one workload share it.
    """
    if not specs:
        return []
    # Group cells by compute class so a mixed matrix doesn't pay the
    # most expensive cell's step everywhere: fifo cells finish in tens
    # of steps (static order, no heartbeat cap) and non-hfsp cells
    # never read the virtual layer (impl "none" lets XLA dead-code the
    # water-fill entirely).  Groups share the jitted step whenever
    # their (width, chunk, impl) key matches.
    def _klass(spec: ScenarioSpec) -> str:
        return "hfsp" if spec.scheduler.policy == "hfsp" else "plain"

    out: list[dict | None] = [None] * len(specs)
    with workload_memo():
        for klass in ("plain", "hfsp"):
            idx = [i for i, s in enumerate(specs) if _klass(s) == klass]
            if not idx:
                continue
            t0 = time.perf_counter()
            cells = [synthesize_cell(specs[i]) for i in idx]
            packed = pack_cells(cells)
            final = run_packed(
                packed,
                t_chunk=t_chunk,
                max_chunks=max_chunks,
                impl=impl if klass == "hfsp" else "none",
                on_chunk=on_chunk,
            )
            wall = time.perf_counter() - t0
            for i, cell, summ in zip(idx, cells, summarize(packed, final)):
                out[i] = {
                    "spec": specs[i].to_dict(),
                    "wall_s": round(wall / len(idx), 3),
                    "compute": "accel",
                    "event_epsilon_resolved": float(cell["eps"]),
                    **summ,
                }
    return out


def run_sweep_accel(
    sweep: SweepSpec,
    *,
    impl: str = "lax",
    t_chunk: int = 512,
    max_chunks: int = 256,
    progress=None,
) -> dict[str, dict]:
    """Expand a sweep and run it: eligible cells in one vmapped batch,
    the rest through the Python runner per cell (same fallback rule as
    ``worker --compute accel``)."""
    from repro.scenarios.runner import run_scenario

    cells = sweep.expand()
    eligible: list[tuple[str, ScenarioSpec]] = []
    results: dict[str, dict] = {}
    with workload_memo():
        for cid, spec in cells:
            ok, _reason = accel_eligible(spec)
            if ok:
                try:
                    synthesize_cell(spec)
                except ValueError:
                    ok = False
            if ok:
                eligible.append((cid, spec))
            else:
                results[cid] = run_scenario(spec)
                if progress:
                    progress(cid, results[cid])
        if eligible:
            batch = run_cells_accel(
                [s for _, s in eligible],
                impl=impl,
                t_chunk=t_chunk,
                max_chunks=max_chunks,
            )
            for (cid, _), res in zip(eligible, batch):
                results[cid] = res
                if progress:
                    progress(cid, res)
    return results
