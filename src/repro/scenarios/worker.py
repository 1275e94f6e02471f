"""Distributed sweep worker: lease-claiming cell executor.

``run_worker`` is the standalone counterpart of the local self-healing
supervisor in :mod:`repro.scenarios.sweep` — any number of workers on
any number of machines point at one shared store
(``python -m repro.scenarios worker <preset> --store ...``) and the
sweep converges exactly-once:

1. **claim** — pick the first pending cell (deterministic spec order)
   not covered by a live foreign lease and claim it with a TTL'd lease
   row; losing a claim race just moves on to the next cell.
2. **compute** — run the cell in a spawned per-attempt process with PR
   6's self-healing semantics unchanged: per-attempt wall-clock
   ``timeout`` kill, bounded retry with capped exponential backoff,
   quarantine record past the retry budget.  The lease is renewed every
   ``renew_every`` seconds while the attempt runs (and across retry
   backoffs), so only a *dead* worker's lease expires.
3. **store** — append the result; a duplicate (some other worker won a
   race on this cell) is detected by the store, dropped, and counted.
   Then release the lease.
4. **converge** — loop until every cell of the sweep is stored.  With
   cells left but nothing claimable (live foreign leases), idle-poll;
   workers heartbeat each loop so ``sweep-status`` can report liveness.

A SIGKILLed worker stops renewing; once its lease TTL passes, any other
worker's claim takes the cell over (a counted *reissue*).  The attempt
child it may have left behind is harmless: results are only appended by
worker loops, and an orphaned child dies on its broken result pipe.

This module also owns the per-attempt primitives (spawned process entry
point, test-fault hooks, quarantine record) shared with the local
supervisor — workers use the ``spawn`` start method because the parent
may hold jax state (the vcluster jax backend, an accel batch), which
does not survive ``fork``, and each attempt runs on the CPU backend so
the parent alone owns the accelerator.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import time
from pathlib import Path

from repro.scenarios.lease import DEFAULT_TTL, LeaseKeeper
from repro.scenarios.runner import run_scenario
from repro.scenarios.spec import ScenarioSpec, SweepSpec
from repro.scenarios.store import SweepStore, open_store

#: Env var naming a JSON file of test-only worker fault hooks —
#: ``{"hang_once": [cell_ids], "fail_always": [cell_ids], "slow_once":
#: {"cells": [...] | "*", "seconds": s}, "state_dir": path}`` — read
#: inside the *spawned* attempt process (a spawn child cannot see parent
#: monkeypatches, so the self-healing and chaos tests inject
#: hangs/failures/delays through the environment instead).
_TEST_HOOK_ENV = "_REPRO_SWEEP_TEST_HOOK"


def _quarantine_record(cid: str, error: str, attempts: int) -> dict:
    """The poison-cell record stored in place of a scenario report."""
    return {
        "quarantined": True,
        "cell_id": cid,
        "error": error,
        "attempts": attempts,
    }


def _run_cell(payload: tuple[str, dict]) -> tuple[str, dict]:
    """Compute one cell from its serialized spec."""
    cid, spec_dict = payload
    return cid, run_scenario(ScenarioSpec.from_dict(spec_dict))


def _apply_test_hook(cid: str) -> None:
    path = os.environ.get(_TEST_HOOK_ENV)
    if not path:
        return
    with open(path) as f:
        hook = json.load(f)
    if cid in hook.get("fail_always", ()):
        raise RuntimeError(f"sweep test hook: cell {cid!r} fails")
    if cid in hook.get("hang_once", ()):
        marker = Path(hook["state_dir"]) / f"hung-{cid}"
        if not marker.exists():
            marker.write_text("hung once\n")
            time.sleep(3600.0)  # until the supervisor's timeout kills us
    slow = hook.get("slow_once") or {}
    cells = slow.get("cells", ())
    if cells == "*" or cid in cells:
        # First attempt of the cell sleeps (stretching the SIGKILL
        # window for chaos tests); reclaimed attempts run at full speed.
        marker = Path(hook["state_dir"]) / f"slow-{cid}"
        if not marker.exists():
            marker.write_text("slowed once\n")
            time.sleep(float(slow.get("seconds", 1.0)))


def _cell_worker(conn, cid: str, spec_dict: dict) -> None:
    """Spawned per-attempt process entry point: compute the cell, send
    ("ok", report) or ("err", repr) back over the pipe.

    The attempt is pinned to the CPU backend before anything can
    initialise one: the parent may own the accelerator (an accel batch,
    the jax virtual cluster), a chip belongs to one process at a time,
    and a child that reached for it would fail or hang on its lock."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_platforms", "cpu")
    try:
        _apply_test_hook(cid)
        _, result = _run_cell((cid, spec_dict))
        conn.send(("ok", result))
    except BaseException as e:  # noqa: BLE001 - reported to the supervisor
        try:
            conn.send(("err", repr(e)))
        except Exception:
            pass
    finally:
        conn.close()


def default_worker_id() -> str:
    """hostname-pid: unique per worker loop across a shared filesystem."""
    return f"{socket.gethostname()}-{os.getpid()}"


def _attempt_once(
    cid: str, spec_dict: dict, timeout: float | None, on_tick=None
) -> tuple[str, object]:
    """One supervised spawned attempt; returns ("ok", report) or
    ("err", reason).  ``on_tick`` runs every poll interval (the worker
    renews its lease there)."""
    import multiprocessing

    ctx = multiprocessing.get_context("spawn")
    parent_conn, child_conn = ctx.Pipe(duplex=False)
    proc = ctx.Process(
        target=_cell_worker, args=(child_conn, cid, spec_dict), daemon=True
    )
    proc.start()
    child_conn.close()
    started = time.monotonic()
    try:
        while True:
            if parent_conn.poll(0.1):
                try:
                    msg = parent_conn.recv()
                except (EOFError, OSError):
                    msg = ("err", "worker process died without sending a result")
                break
            if (
                timeout is not None
                and time.monotonic() - started > timeout
            ):
                msg = ("err", f"timeout: exceeded {timeout}s wall clock")
                break
            if on_tick is not None:
                on_tick()
    finally:
        parent_conn.close()
        if proc.is_alive():
            proc.terminate()
            proc.join(5.0)
            if proc.is_alive():  # pragma: no cover - hard hang
                proc.kill()
        proc.join(5.0)
    return msg


def _compute_with_retries(
    cid: str,
    spec: ScenarioSpec,
    keeper: LeaseKeeper,
    *,
    timeout: float | None,
    max_retries: int,
    retry_backoff: float,
) -> dict:
    """PR 6 self-healing semantics around ``_attempt_once``: bounded
    retry with capped exponential backoff, quarantine past the budget.
    The lease keeper ticks through attempts *and* backoff sleeps."""
    spec_dict = spec.to_dict()
    n_fails = 0
    while True:
        kind, payload = _attempt_once(cid, spec_dict, timeout, keeper.tick)
        if kind == "ok":
            return payload
        n_fails += 1
        if n_fails > max_retries:
            return _quarantine_record(cid, str(payload), n_fails)
        deadline = time.monotonic() + retry_backoff * (2.0 ** (n_fails - 1))
        while time.monotonic() < deadline:
            keeper.tick()
            time.sleep(min(0.05, max(0.0, deadline - time.monotonic())))


def _accel_fit(spec: ScenarioSpec) -> tuple[bool, str]:
    """Full accel-eligibility check for one cell: the spec-level gate
    plus a synthesis dry-run (a generator workload can still violate the
    fluid contract — unequal durations, non-uniform weights).  Under an
    open workload memo the batch reuses the dry run's build."""
    from repro.accel.sweep_exec import accel_eligible, synthesize_cell

    ok, reason = accel_eligible(spec)
    if not ok:
        return False, reason
    try:
        synthesize_cell(spec)
    except ValueError as e:
        return False, str(e)
    return True, ""


def run_worker(
    sweep: SweepSpec,
    store: SweepStore | str | Path,
    *,
    worker_id: str | None = None,
    ttl: float = DEFAULT_TTL,
    renew_every: float | None = None,
    timeout: float | None = 600.0,
    max_retries: int = 2,
    retry_backoff: float = 0.5,
    poll: float = 0.5,
    max_cells: int | None = None,
    deadline: float | None = None,
    progress=None,
    compute: str = "python",
    accel_batch: int = 32,
) -> dict:
    """Run one worker loop against a shared store until the sweep
    converges (every cell stored) or ``max_cells``/``deadline`` stops it.

    ``ttl``/``renew_every`` shape the lease protocol (renew defaults to
    ttl/3); ``timeout``/``max_retries``/``retry_backoff`` are PR 6's
    self-healing knobs, unchanged; ``poll`` is the idle wait when every
    pending cell is covered by a live foreign lease; ``deadline`` bounds
    the loop's total wall clock (seconds) — on expiry the worker exits
    with ``"stalled": True`` instead of waiting forever on leases that
    other (possibly wedged) workers hold.  Returns a summary dict with
    the cells this worker computed and the store's coordination stats.

    ``compute="accel"`` drains cells through the vmapped accel executor
    (:mod:`repro.accel.sweep_exec`) instead of spawned Python attempts:
    the worker claims up to ``accel_batch`` accel-eligible pending cells
    at once, runs them as one device program *in-process* (renewing all
    their leases between scan chunks), and appends each result under the
    usual exactly-once store rules.  Cells an accel worker cannot
    represent — fault injection, trace workloads, non-fluid generators —
    fall back to the unchanged per-cell spawned Python path, so a mixed
    sweep still converges; the fallback reasons are reported in the
    summary's ``"accel_fallbacks"``.  Each claim synthesizes under one
    workload memo (:func:`~repro.accel.sweep_exec.workload_memo`):
    ``"accel_workloads_built"`` counts the workloads built and
    ``"accel_synth_hits"`` the syntheses answered from the memo.  A batch
    that exhausts its chunk budget
    (:class:`~repro.accel.cell_step.CellBatchNotConverged`)
    demotes its cells to the Python path rather than quarantining them;
    any other accel error — a device or compile fault — fails the
    worker, so a broken device path cannot pass as a Python sweep.
    """
    if compute not in ("python", "accel"):
        raise ValueError(f"unknown compute {compute!r}")
    store = open_store(store)
    wid = worker_id or default_worker_id()
    cells = sweep.expand()
    spec_of = dict(cells)
    hashes = {cid: spec.spec_hash() for cid, spec in cells}
    t_end = None if deadline is None else time.monotonic() + deadline
    summary = {
        "worker": wid,
        "computed": [],
        "duplicates_dropped": 0,
        "claims_lost": 0,
        "leases_lost": 0,
        "stalled": False,
    }
    # Cells the accel path cannot (or failed to) run this loop; they take
    # the spawned Python attempt path instead.  {cid: reason}.
    accel_unfit: dict[str, str] = {}
    if compute == "accel":
        summary["accel_cells"] = 0
        summary["accel_batches"] = 0
        summary["accel_workloads_built"] = 0
        summary["accel_synth_hits"] = 0
        summary["accel_fallbacks"] = accel_unfit

    def _finish_one(cid: str, result: dict) -> None:
        if not store.append(cid, hashes[cid], result):
            summary["duplicates_dropped"] += 1
        store.release(cid, hashes[cid], wid)
        summary["computed"].append(cid)
        if progress is not None:
            progress(cid, result)

    def _try_accel_batch(todo: list[str], held, now: float) -> bool:
        """Claim + run one vmapped batch; True if any cell was computed.
        The eligibility dry run and the batch share one workload memo,
        so each distinct workload of the claim is built once."""
        from repro.accel.sweep_exec import workload_memo

        with workload_memo() as memo:
            try:
                return _claim_and_run(todo, held, now)
            finally:
                summary["accel_workloads_built"] += memo.built
                summary["accel_synth_hits"] += memo.hits

    def _claim_and_run(todo: list[str], held, now: float) -> bool:
        from repro.accel.cell_step import CellBatchNotConverged
        from repro.accel.sweep_exec import run_cells_accel

        budget = accel_batch
        if max_cells is not None:
            budget = min(budget, max_cells - len(summary["computed"]))
        got: list[str] = []
        for cid in todo:
            if len(got) >= budget:
                break
            if cid in accel_unfit:
                continue
            lease = held.get((cid, hashes[cid]))
            if lease is not None and lease.worker != wid and not lease.expired(now):
                continue
            ok, reason = _accel_fit(spec_of[cid])
            if not ok:
                accel_unfit[cid] = reason
                continue
            if store.claim(cid, hashes[cid], wid, ttl):
                got.append(cid)
            else:
                summary["claims_lost"] += 1
        if not got:
            return False
        keepers = [
            LeaseKeeper(store, cid, hashes[cid], wid, ttl,
                        renew_every=renew_every)
            for cid in got
        ]

        def tick_all(*_progress) -> None:
            for k in keepers:
                k.tick()

        try:
            batch = run_cells_accel(
                [spec_of[cid] for cid in got], on_chunk=tick_all
            )
        except CellBatchNotConverged as e:
            for cid in got:
                accel_unfit[cid] = f"accel batch failed: {e!r}"
                store.release(cid, hashes[cid], wid)
            return False
        for cid, result in zip(got, batch):
            _finish_one(cid, result)
        summary["accel_cells"] += len(got)
        summary["accel_batches"] += 1
        summary["leases_lost"] += sum(1 for k in keepers if k.lost)
        return True

    while True:
        store.heartbeat(
            wid,
            info={
                "host": socket.gethostname(),
                "pid": os.getpid(),
                "done": len(summary["computed"]),
            },
        )
        done = store.load()
        todo = [cid for cid, _ in cells if (cid, hashes[cid]) not in done]
        if not todo:
            break
        if t_end is not None and time.monotonic() > t_end:
            summary["stalled"] = True
            break
        now = time.time()
        held = store.leases()
        if compute == "accel" and _try_accel_batch(todo, held, now):
            if (
                max_cells is not None
                and len(summary["computed"]) >= max_cells
            ):
                break
            continue
        # Python path: one spawned self-healing attempt per claimed cell.
        # Under compute="accel" this only sees cells the batch pass left
        # behind (accel-unfit, or claims lost to other workers).
        got = None
        for cid in todo:
            lease = held.get((cid, hashes[cid]))
            if lease is not None and lease.worker != wid and not lease.expired(now):
                continue  # live foreign lease — someone is on it
            if store.claim(cid, hashes[cid], wid, ttl):
                got = cid
                break
            summary["claims_lost"] += 1
        if got is None:
            time.sleep(poll)
            continue
        keeper = LeaseKeeper(
            store, got, hashes[got], wid, ttl, renew_every=renew_every
        )
        result = _compute_with_retries(
            got,
            spec_of[got],
            keeper,
            timeout=timeout,
            max_retries=max_retries,
            retry_backoff=retry_backoff,
        )
        if not store.append(got, hashes[got], result):
            summary["duplicates_dropped"] += 1
        store.release(got, hashes[got], wid)
        if keeper.lost:
            summary["leases_lost"] += 1
        summary["computed"].append(got)
        if progress is not None:
            progress(got, result)
        if max_cells is not None and len(summary["computed"]) >= max_cells:
            break

    summary["stats"] = store.stats()
    return summary
