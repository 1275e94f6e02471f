"""Frozen synthesis reference: the cell inputs a sweep cell must run on.

A copy, kept with the benchmark, of what the program's ``workload.fb``
generators (``fb_dataset`` and ``fb_scaled_dataset`` at ``task_jitter``
0), the runner's cluster shape, the ``"auto"`` event window and the
accel cell's estimate perturbation produce, reduced to the flat per-job
arrays a fluid cell runs on.  It imports nothing of the program: the
benchmark compares the rows the timed path handed to the device with
these, so a later change to the generators shows as a wrong job stream
and not as a different workload.

Random draws are made in exactly the generators' order (including the
input-host draws, whose values the fluid cell never reads but whose
draws advance the stream behind every later arrival and duration).
"""

from __future__ import annotations

import numpy as np

#: The accel cell's policy codes (a row input of the device program).
POLICY_CODES = {"fifo": 0, "hfsp": 1, "srpt": 2, "las": 3}

#: The paper's FB-dataset composition (Sect. 4.1), per 100 jobs.
CLASS_COUNTS = {"small": 53, "medium": 41, "large": 6}
LARGE_TEMPLATES = (
    (3000, 0), (3000, 0), (700, 150), (1100, 200), (1500, 250), (200, 1000),
)

#: Row inputs of one cell, in a fixed order (the match key's order).
ROW_KEYS = (
    "arrival", "m_work", "m_dur", "m_tasks", "r_work", "r_dur", "r_tasks",
    "est_m", "est_r", "weight",
)
SCALAR_KEYS = ("policy", "map_slots", "red_slots", "dt_cap", "eps")


def class_sizes(num_jobs: int) -> tuple[int, int, int]:
    scale = num_jobs / 100.0
    return tuple(max(1, round(CLASS_COUNTS[c] * scale))
                 for c in ("small", "medium", "large"))


def fb_jobs(seed: int, num_jobs: int, num_hosts: int, gen: dict):
    """``fb_dataset``: per job (arrival, n_map, map_s, n_red, red_s)."""
    rng = np.random.default_rng(seed)
    n_small, n_medium, n_large = class_sizes(num_jobs)
    shapes = []
    for _ in range(n_small):
        shapes.append((1 if rng.random() < 0.75 else 2, 0))
    for _ in range(n_medium):
        n_map = int(rng.integers(5, 501))
        n_red = 0 if rng.random() < 0.5 else int(rng.integers(2, 101))
        shapes.append((n_map, n_red))
    for i in range(n_large):
        shapes.append(LARGE_TEMPLATES[i % len(LARGE_TEMPLATES)])
    rng.shuffle(shapes)
    hosts = min(gen["replication"], num_hosts)
    out = []
    t = 0.0
    for n_map, n_red in shapes:
        t += float(rng.exponential(gen["mean_interarrival_s"]))
        map_mu = float(rng.uniform(gen["map_time_lo_s"], gen["map_time_hi_s"]))
        red_mu = float(
            rng.uniform(gen["reduce_time_lo_s"], gen["reduce_time_hi_s"])
        )
        for _ in range(n_map + n_red):  # one host draw per task, both phases
            rng.choice(num_hosts, size=hosts, replace=False)
        out.append((t, n_map, max(map_mu, 1.0), n_red, max(red_mu, 1.0)))
    return out


def fb_scaled_jobs(seed: int, num_jobs: int, num_hosts: int, gen: dict):
    """``fb_scaled_dataset``: the same mix, submission window held at the
    paper's, input hosts drawn with replacement for MAP tasks only."""
    rng = np.random.default_rng(seed)
    n_small, n_medium, n_large = class_sizes(num_jobs)
    shapes = []
    small_two = rng.random(n_small) >= 0.75
    for i in range(n_small):
        shapes.append((2 if small_two[i] else 1, 0))
    med_maps = rng.integers(5, 501, size=n_medium)
    med_has_red = rng.random(n_medium) >= 0.5
    med_reds = rng.integers(2, 101, size=n_medium)
    for i in range(n_medium):
        shapes.append(
            (int(med_maps[i]), int(med_reds[i]) if med_has_red[i] else 0)
        )
    for i in range(n_large):
        shapes.append(LARGE_TEMPLATES[i % len(LARGE_TEMPLATES)])
    rng.shuffle(shapes)
    mean_gap = gen["mean_interarrival_s"] * 100.0 / max(num_jobs, 1)
    arrivals = np.cumsum(rng.exponential(mean_gap, size=len(shapes)))
    map_mus = rng.uniform(gen["map_time_lo_s"], gen["map_time_hi_s"],
                          size=len(shapes))
    red_mus = rng.uniform(gen["reduce_time_lo_s"], gen["reduce_time_hi_s"],
                          size=len(shapes))
    hosts = min(gen["replication"], num_hosts)
    out = []
    for i, (n_map, n_red) in enumerate(shapes):
        if n_map:
            rng.integers(0, num_hosts, size=(n_map, hosts))
        out.append((float(arrivals[i]), n_map, max(float(map_mus[i]), 1.0),
                    n_red, max(float(red_mus[i]), 1.0)))
    return out


GENERATORS = {"fb": fb_jobs, "fb_scaled": fb_scaled_jobs}


def auto_epsilon(arrivals: list[float], heartbeat: float) -> float:
    """The ``"auto"`` event window: 0 for a stream no burstier than
    Poisson, else the median gap, capped at one heartbeat."""
    ts = sorted(arrivals)
    if len(ts) < 3:
        return 0.0
    gaps = [b - a for a, b in zip(ts, ts[1:])]
    mean = sum(gaps) / len(gaps)
    if mean <= 0.0:
        return float(heartbeat)
    var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
    cv = (var ** 0.5) / mean
    if cv <= 1.0:
        return 0.0
    med = sorted(gaps)[len(gaps) // 2]
    return float(min(med, heartbeat))


def workload_rows(config: dict, seed: int) -> dict:
    """The job stream of one workload seed as flat per-job arrays."""
    gen = config["generator_params"]
    jobs = GENERATORS[config["generator"]](
        seed, config["num_jobs"], config["num_machines"], gen
    )
    arrival = np.array([j[0] for j in jobs], np.float64)
    m_n = np.array([j[1] for j in jobs], np.float64)
    r_n = np.array([j[3] for j in jobs], np.float64)
    m_d = np.where(m_n > 0, [j[2] for j in jobs], 1.0)
    r_d = np.where(r_n > 0, [j[4] for j in jobs], 1.0)
    eps = config["event_epsilon"]
    if eps == "auto":
        eps = auto_epsilon([j[0] for j in jobs], config["heartbeat_s"])
    return {"arrival": arrival, "m_n": m_n, "m_d": m_d, "r_n": r_n,
            "r_d": r_d, "eps": float(eps)}


def cell_inputs(config: dict, workload: dict, policy: str, alpha: float,
                error_seed: int) -> dict:
    """One cell's row inputs: the workload plus the policy and the
    estimates perturbed by the Fig. 6 error model (one uniform factor
    per job and phase, drawn in job order)."""
    m_n, m_d, r_n, r_d = (workload[k] for k in ("m_n", "m_d", "r_n", "r_d"))
    j = len(m_n)
    if alpha != 0.0:
        rng = np.random.default_rng(error_seed)
        f = rng.uniform(1.0 - alpha, 1.0 + alpha, size=(j, 2))
        est_m = np.maximum(m_n * m_d * f[:, 0], 1e-9)
        est_r = np.maximum(r_n * r_d * f[:, 1], 1e-9)
    else:
        est_m, est_r = m_n * m_d, r_n * r_d
    machines = config["num_machines"]
    return {
        "arrival": workload["arrival"],
        "m_work": m_n * m_d, "m_dur": m_d, "m_tasks": m_n,
        "r_work": r_n * r_d, "r_dur": r_d, "r_tasks": r_n,
        "est_m": est_m, "est_r": est_r, "weight": np.ones(j),
        "policy": POLICY_CODES[policy],
        "map_slots": float(machines * config["map_slots_per_machine"]),
        "red_slots": float(machines * config["reduce_slots_per_machine"]),
        "dt_cap": np.inf if policy == "fifo" else float(config["heartbeat_s"]),
        "eps": workload["eps"],
    }


def row_key(rows: dict) -> bytes:
    """Exact identity of a cell's inputs (float64 bytes)."""
    parts = [np.asarray(rows[k], np.float64).ravel() for k in ROW_KEYS]
    parts.append(np.array([float(rows[k]) for k in SCALAR_KEYS], np.float64))
    return b"".join(p.tobytes() for p in parts)
