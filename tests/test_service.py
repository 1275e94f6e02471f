"""Live service tests: twin determinism, crash/restore, admission.

The keystone assertions:

* a live session (wall-clock master, real asyncio workers, worker
  death mid-task) replays bit-identically through the offline
  Simulator — ``completion_fingerprint(live) ==
  completion_fingerprint(twin)`` for fifo, hfsp and psbs;
* a master killed at a randomized point restores from journal +
  checkpoint with no lost and no duplicated jobs, and the final
  journal still satisfies the twin property;
* a SIGKILL'd *subprocess* master survives restart end-to-end over
  the wire (exactly-once submits via idempotency tags).
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

from repro.core.types import ClusterSpec
from repro.scenarios.trace import load_trace
from repro.service import (
    AdmissionConfig,
    AdmissionControl,
    Journal,
    LiveEngine,
    Master,
    MasterConfig,
    WorkerAgent,
    live_fingerprint,
    read_journal,
    replay_journal,
)
from repro.service.master import CHECKPOINT_KIND
from repro.service.protocol import ServiceClient

REPO = Path(__file__).resolve().parent.parent

CLUSTER = dict(
    num_machines=2, map_slots_per_machine=2, reduce_slots_per_machine=1
)
#: Fast virtual clock: 1 wall ms = 1 virtual second.
TIME_SCALE = 1000.0


def mk_job(i: int, scale: float = 1.0) -> dict:
    """Deterministic nontrivial job payload (trace task schema)."""
    return {
        "name": f"job-{i}",
        "map": [[scale * (20.0 + 7.0 * ((i + k) % 5)), [], 0]
                for k in range(2 + i % 3)],
        "reduce": [[scale * 15.0, [], 0]] if i % 2 else [],
        "weight": 1.0,
        "reduce_slowstart": 1.0,
    }


async def boot(tmp_path, policy, **cfg_kw):
    engine = LiveEngine.create(
        tmp_path / "live.jsonl", policy, ClusterSpec(**CLUSTER),
        time_scale=TIME_SCALE,
    )
    cfg_kw.setdefault("pace_wall", 0.005)
    cfg_kw.setdefault("worker_dead_wall", 0.15)
    master = Master(engine, MasterConfig(**cfg_kw))
    await master.start()
    workers = []
    for m in range(CLUSTER["num_machines"]):
        w = WorkerAgent("127.0.0.1", master.port, m, heartbeat_wall=0.03)
        await w.start()
        workers.append(w)
    return engine, master, workers


def client_submit(port: int, jobs: list[dict], user="u0") -> list[int]:
    with ServiceClient("127.0.0.1", port) as c:
        out = []
        for i, job in enumerate(jobs):
            r = c.call({"op": "submit", "user": user,
                        "tag": f"{user}-{i}", "job": job})
            assert r["ok"], r
            out.append(r["job_id"])
        return out


def run_session(coro, limit: float = 60.0):
    """Run one live session under a wall-clock limit: a lost wake-up
    fails its own test with a TimeoutError instead of hanging the run."""
    return asyncio.run(asyncio.wait_for(coro, limit))


async def drain(engine, n, timeout=20.0):
    t0 = time.monotonic()
    while len(engine.sim.result.completion) < n:
        if time.monotonic() - t0 > timeout:
            raise AssertionError(
                f"only {len(engine.sim.result.completion)}/{n} jobs "
                f"completed in {timeout}s"
            )
        await asyncio.sleep(0.01)


# ---------------------------------------------------------------------------
# Twin determinism with worker death mid-task
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["fifo", "hfsp", "psbs"])
def test_live_session_replays_bit_identically(tmp_path, policy):
    """The deterministic-twin property: live run (real wall clock, real
    sockets, a worker dying mid-task and later rejoining) == offline
    Simulator replay of the journal, to the fingerprint."""

    async def session():
        engine, master, workers = await boot(tmp_path, policy)
        loop = asyncio.get_running_loop()
        # scale=3: ~1s of wall-clock workload, so the worker death at
        # ~0.2s lands mid-task and the fault machinery must reschedule.
        jobs = [mk_job(i, scale=3.0) for i in range(16)]
        await loop.run_in_executor(None, client_submit, master.port, jobs)
        # Let the workload start, then silently kill machine 1's worker.
        await drain(engine, 2)
        await workers[1].die()
        # Master declares the crash after worker_dead_wall of silence.
        t0 = time.monotonic()
        while master.telemetry.counters["worker_crashes"] == 0:
            assert time.monotonic() - t0 < 5.0, "crash never declared"
            await asyncio.sleep(0.01)
        # Rejoin: fresh agent on the same machine -> journaled recover.
        w = WorkerAgent("127.0.0.1", master.port, 1, heartbeat_wall=0.03)
        await w.start()
        t0 = time.monotonic()
        while master.telemetry.counters["worker_rejoins"] == 0:
            assert time.monotonic() - t0 < 5.0, "rejoin never recorded"
            await asyncio.sleep(0.01)
        await drain(engine, 16)
        fp_live = live_fingerprint(engine.sim)
        completions = dict(engine.sim.result.completion)
        await master.stop()
        await w.die()
        for wk in workers:
            await wk.die()
        return fp_live, completions

    fp_live, completions = run_session(session())
    assert len(completions) == 16

    twin = replay_journal(tmp_path / "live.jsonl")
    assert live_fingerprint(twin) == fp_live
    assert twin.result.completion == completions
    # The journal recorded the death and the rejoin.
    _, entries = read_journal(tmp_path / "live.jsonl")
    kinds = [e.get("event") for e in entries]
    assert "crash" in kinds and "recover" in kinds


def test_journal_doubles_as_plain_trace(tmp_path):
    """A recorded session loads through the ordinary trace loader (event
    lines skipped), so the live workload can re-run offline as a cell."""

    async def session():
        engine, master, workers = await boot(tmp_path, "fifo")
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            None, client_submit, master.port, [mk_job(i) for i in range(5)]
        )
        await drain(engine, 5)
        await master.stop()
        for w in workers:
            await w.die()

    run_session(session())
    jobs, _, meta = load_trace(tmp_path / "live.jsonl")
    assert len(jobs) == 5
    assert meta["journal"] is True
    assert [j.job_id for j in jobs] == sorted(j.job_id for j in jobs)


def test_stop_closes_open_connections(tmp_path):
    """stop() returns while workers and a client are still connected:
    it closes every connection the server accepted, then writes the
    checkpoint and closes the journal."""
    ck = tmp_path / "ck.json"

    async def session():
        # Only stop() writes the checkpoint: the pacer's period is long.
        engine, master, workers = await boot(
            tmp_path, "fifo", checkpoint_path=str(ck),
            checkpoint_every_wall=3600.0,
        )
        while len(master.workers) < CLUSTER["num_machines"]:
            await asyncio.sleep(0.01)
        loop = asyncio.get_running_loop()
        client = await loop.run_in_executor(
            None, ServiceClient, "127.0.0.1", master.port
        )
        try:
            r = await loop.run_in_executor(None, client.call, {"op": "status"})
            assert r["workers"] == {"0": {"alive": True}, "1": {"alive": True}}
            assert not ck.exists()
            await master.stop()
            # The master closed the client's connection: EOF, no reply.
            eof = await loop.run_in_executor(None, client.read_line)
        finally:
            client.close()
            for w in workers:
                await w.die()
        return engine, eof

    engine, eof = run_session(session(), limit=5.0)
    assert eof is None
    assert engine.journal._f.closed
    assert json.loads(ck.read_text())["kind"] == CHECKPOINT_KIND


# ---------------------------------------------------------------------------
# Crash/restore at randomized kill points (S4)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_crash_restore_randomized_kill_point(tmp_path, seed):
    """Kill the master (no graceful stop, no final checkpoint) after a
    seed-randomized number of submissions; restore from the journal;
    finish the workload; assert exactly-once jobs and the twin property
    over the stitched journal."""
    import random

    rng = random.Random(seed)
    n_before = rng.randint(1, 10)
    n_total = 12

    async def phase_one():
        engine, master, workers = await boot(tmp_path, "hfsp")
        loop = asyncio.get_running_loop()
        jobs = [mk_job(i) for i in range(n_before)]
        ids = await loop.run_in_executor(
            None, client_submit, master.port, jobs
        )
        # Randomized kill point: let an arbitrary amount of work happen.
        await asyncio.sleep(rng.uniform(0.0, 0.2))
        # Simulated SIGKILL: tear down sockets only — no master.stop(),
        # no checkpoint, journal left exactly as last fsync'd.
        master._pacer.cancel()
        master._server.close()
        for w in workers:
            await w.die()
        engine.journal._f.close()
        return ids

    ids_before = run_session(phase_one())

    # Torn-tail realism: append half a line, as a crash mid-append would.
    with open(tmp_path / "live.jsonl", "a") as f:
        f.write('{"event": "adva')

    async def phase_two():
        engine = LiveEngine.restore(
            tmp_path / "live.jsonl", time_scale=TIME_SCALE
        )
        master = Master(engine, MasterConfig(
            pace_wall=0.005, worker_dead_wall=0.15))
        await master.start()
        workers = []
        for m in range(CLUSTER["num_machines"]):
            w = WorkerAgent("127.0.0.1", master.port, m, heartbeat_wall=0.03)
            await w.start()
            workers.append(w)
        loop = asyncio.get_running_loop()

        def resubmit_and_finish():
            with ServiceClient("127.0.0.1", master.port) as c:
                # Replay every pre-crash tag (client retry after losing
                # its acks) — must dedup, never duplicate.
                redone = []
                for i in range(n_before):
                    r = c.call({"op": "submit", "user": "u0",
                                "tag": f"u0-{i}", "job": mk_job(i)})
                    assert r["ok"] and r["decision"] == "dedup", r
                    redone.append(r["job_id"])
                fresh = []
                for i in range(n_before, n_total):
                    r = c.call({"op": "submit", "user": "u0",
                                "tag": f"u0-{i}", "job": mk_job(i)})
                    assert r["ok"], r
                    fresh.append(r["job_id"])
                return redone, fresh

        redone, fresh = await loop.run_in_executor(None, resubmit_and_finish)
        await drain(engine, n_total)
        fp = live_fingerprint(engine.sim)
        completions = dict(engine.sim.result.completion)
        await master.stop()
        for w in workers:
            await w.die()
        return redone, fresh, fp, completions

    redone, fresh, fp, completions = run_session(phase_two())
    # Exactly-once: pre-crash tags resolve to the original ids, fresh
    # jobs get new ids, and the union is exactly n_total distinct jobs.
    assert redone == ids_before
    assert len(set(redone + fresh)) == n_total
    assert len(completions) == n_total

    # The stitched journal (pre-crash prefix + post-restore suffix)
    # still satisfies the twin property.
    twin = replay_journal(tmp_path / "live.jsonl")
    assert live_fingerprint(twin) == fp
    assert twin.result.completion == completions


def test_journal_tail_repair(tmp_path):
    j = Journal(tmp_path / "j.jsonl", meta={
        "policy": "fifo", "cluster": CLUSTER, "heartbeat": 3.0,
        "event_epsilon": 0.0, "time_scale": 1.0,
    })
    j.append_event({"event": "advance", "t": 1.0})
    j.close()
    with open(tmp_path / "j.jsonl", "a") as f:
        f.write('{"event": "crash", "t": 2.0, "mach')  # torn mid-append
    meta, entries = read_journal(tmp_path / "j.jsonl")
    assert entries == [{"event": "advance", "t": 1.0}]
    # Reopening repairs the file, and appends continue cleanly.
    j2 = Journal(tmp_path / "j.jsonl")
    j2.append_event({"event": "advance", "t": 3.0})
    j2.close()
    _, entries = read_journal(tmp_path / "j.jsonl")
    assert [e["t"] for e in entries] == [1.0, 3.0]


# ---------------------------------------------------------------------------
# SIGKILL a real subprocess master; restart; exactly-once end to end
# ---------------------------------------------------------------------------
def _wait_port(path: Path, timeout=15.0) -> int:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if path.exists() and path.read_text().strip():
            port = int(path.read_text())
            # The port file may outlive a killed master: probe it.
            try:
                socket.create_connection(("127.0.0.1", port), 0.2).close()
                return port
            except OSError:
                pass
        time.sleep(0.05)
    raise AssertionError("master never came up")


def _spawn_master(tmp_path, tag: str) -> subprocess.Popen:
    port_file = tmp_path / f"port-{tag}"
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "repro.service", "master",
            "--journal", str(tmp_path / "live.jsonl"),
            "--checkpoint", str(tmp_path / "ck.json"),
            "--policy", "hfsp", "--machines", "2",
            "--map-slots", "2", "--reduce-slots", "1",
            "--time-scale", str(TIME_SCALE),
            "--port-file", str(port_file),
        ],
        env={**os.environ, "PYTHONPATH": str(REPO / "src")},
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    try:
        port = _wait_port(port_file)
    except AssertionError:
        proc.kill()
        raise
    return proc, port


def test_sigkill_master_restart_no_lost_or_duplicated_jobs(tmp_path):
    proc1, port1 = _spawn_master(tmp_path, "a")
    try:
        ids1 = client_submit(port1, [mk_job(i) for i in range(6)])
        os.kill(proc1.pid, signal.SIGKILL)
        proc1.wait(timeout=10)

        proc2, port2 = _spawn_master(tmp_path, "b")
        try:
            with ServiceClient("127.0.0.1", port2) as c:
                # Retry every tag: all must dedup to the original ids.
                for i in range(6):
                    r = c.call({"op": "submit", "user": "u0",
                                "tag": f"u0-{i}", "job": mk_job(i)})
                    assert r["ok"] and r["decision"] == "dedup", r
                    assert r["job_id"] == ids1[i]
                ids2 = []
                for i in range(6, 9):
                    r = c.call({"op": "submit", "user": "u0",
                                "tag": f"u0-{i}", "job": mk_job(i)})
                    assert r["ok"], r
                    ids2.append(r["job_id"])
                assert len(set(ids1 + ids2)) == 9
                # Engine completes everything without workers (they are
                # advisory); wait for it and read decision latency.
                t0 = time.monotonic()
                while True:
                    snap = c.call({"op": "status"})
                    if snap["jobs"]["completed"] >= 9:
                        break
                    assert time.monotonic() - t0 < 30.0, snap["jobs"]
                    time.sleep(0.05)
                assert snap["decision_latency_ms"]["count"] > 0
                assert snap["decision_latency_ms"]["p99"] >= 0.0
                r = c.call({"op": "shutdown"})
                assert r["ok"]
            proc2.wait(timeout=10)
        finally:
            proc2.kill()
    finally:
        proc1.kill()

    # And the whole stitched history still replays bit-identically: the
    # CLI twin agrees with itself and completed every job exactly once.
    out = subprocess.run(
        [sys.executable, "-m", "repro.service", "replay",
         "--journal", str(tmp_path / "live.jsonl")],
        env={**os.environ, "PYTHONPATH": str(REPO / "src")},
        capture_output=True, text=True, check=True,
    )
    rep = json.loads(out.stdout)
    assert rep["jobs_completed"] == 9
    twin = replay_journal(tmp_path / "live.jsonl")
    assert live_fingerprint(twin) == rep["completion_fingerprint"]


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------
def test_admission_rate_limit_and_backpressure():
    ctl = AdmissionControl(AdmissionConfig(
        max_live_jobs=2, rate_limit=10.0, burst=3, max_queue_per_user=2))
    # Burst of 3 passes the bucket; 4th (same instant) is rate-limited.
    assert ctl.offer("u", "a", 0.0, 0) == "admit"
    assert ctl.offer("u", "b", 0.0, 1) == "admit"
    assert ctl.offer("u", "c", 0.0, 2) == "queued"  # at the live ceiling
    assert ctl.offer("u", "d", 0.0, 2) == "reject-rate"
    # Tokens refill with wall time; queue fills, then rejects.
    assert ctl.offer("u", "e", 1.0, 2) == "queued"
    assert ctl.offer("u", "f", 2.0, 2) == "reject-queue"
    # Capacity frees -> drain releases FIFO per user.
    assert ctl.drain(live_jobs=0) == [("u", "c"), ("u", "e")]
    assert ctl.queued_count() == 0


def test_admission_drain_is_round_robin_across_users():
    ctl = AdmissionControl(AdmissionConfig(max_live_jobs=0))
    for k in range(3):
        assert ctl.offer("alice", f"a{k}", 0.0, 0) == "queued"
    assert ctl.offer("bob", "b0", 0.0, 0) == "queued"
    ctl.cfg.max_live_jobs = 3
    # One per user per cycle: alice cannot starve bob.
    assert ctl.drain(live_jobs=0) == [
        ("alice", "a0"), ("bob", "b0"), ("alice", "a1")]
    assert ctl.drain(live_jobs=2) == [("alice", "a2")]


def test_master_backpressure_queues_then_drains(tmp_path):
    async def session():
        engine = LiveEngine.create(
            tmp_path / "live.jsonl", "fifo", ClusterSpec(**CLUSTER),
            time_scale=TIME_SCALE,
        )
        master = Master(engine, MasterConfig(
            pace_wall=0.005,
            admission=AdmissionConfig(max_live_jobs=2),
        ))
        await master.start()
        loop = asyncio.get_running_loop()

        def burst():
            with ServiceClient("127.0.0.1", master.port) as c:
                decisions = []
                for i in range(6):
                    # scale=5: the first two jobs outlive the whole
                    # burst, so the later offers see a full live set.
                    r = c.call({"op": "submit", "user": "u0",
                                "tag": f"t{i}", "job": mk_job(i, scale=5.0)})
                    assert r["ok"], r
                    decisions.append(r["decision"])
                return decisions

        decisions = await loop.run_in_executor(None, burst)
        assert decisions[:2] == ["admit", "admit"]
        assert set(decisions[2:]) == {"queued"}
        # Queued jobs drain as completions free capacity; everything
        # eventually runs (workers are advisory, none needed).
        await drain(engine, 6)
        fp = live_fingerprint(engine.sim)
        await master.stop()
        return fp

    fp = run_session(session())
    twin = replay_journal(tmp_path / "live.jsonl")
    assert live_fingerprint(twin) == fp


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------
def test_telemetry_snapshot_vocabulary(tmp_path):
    async def session():
        engine, master, workers = await boot(tmp_path, "hfsp")
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            None, client_submit, master.port, [mk_job(i) for i in range(8)]
        )
        await drain(engine, 8)

        def pull():
            with ServiceClient("127.0.0.1", master.port) as c:
                snap = c.call({"op": "status"})
                stream = [c.call({"op": "telemetry"})]  # ticks=1 default
                return snap, stream

        snap, _ = await loop.run_in_executor(None, pull)
        await master.stop()
        for w in workers:
            await w.die()
        return snap

    snap = run_session(session())
    assert snap["jobs"]["completed"] == 8
    assert snap["jobs"]["submitted"] == 8
    for block, keys in [
        ("sojourn", ("mean_s", "p50", "p99", "p999")),
        ("slowdown", ("p50", "p99", "p999")),
        ("decision_latency_ms", ("count", "p50", "p99")),
    ]:
        for k in keys:
            assert k in snap[block], (block, k, sorted(snap[block]))
    assert 0.0 < snap["fairness"]["jain_slowdown"] <= 1.0
    assert snap["goodput"] == 1.0  # no faults injected in this session
    assert snap["workers"] == {"0": {"alive": True}, "1": {"alive": True}}
