"""Plain numpy reference of the fluid cell: per-job completion times.

The model, stated once here without the program's code:

* Each job has a MAP and a REDUCE phase of ``n`` equal tasks of ``d``
  seconds; a phase's remaining work is fluid (``n * d`` serialized
  seconds at the start) and it may hold up to ``n`` slots while work
  remains.  REDUCE starts when MAP's work is gone.
* Slots of a phase go to live jobs greedily in ascending priority key,
  ties to the lower job index.  Keys: fifo the arrival time, srpt the
  estimate minus the work attained, las the work attained, hfsp the
  job's remaining size in a virtual cluster that shares the phase's
  slots weighted max-min fair among the jobs live in it (each capped at
  ``ceil(virtual remaining / d)`` tasks), and drains at those shares.
* Time moves from event to event: the close of the next arrival window
  (arrival + eps), the next phase completion, and for every policy but
  fifo at most one heartbeat while two or more jobs are live.
  Remainders at or below 1e-6 serialized seconds count as done.

Cells of one batch are stepped together, each row until it has no live
or pending job.  ``dtype`` is the precision the whole model is computed
in: float64 is the reference, float32 the control.
"""

from __future__ import annotations

import numpy as np

SNAP = 1e-6
TOL = 1e-12


def water_fill(caps, weights, slots, live):
    """Weighted max-min shares of ``slots`` over the ``live`` entries of
    each row, each at most its cap: share the free slots by weight, give
    the entries whose share reaches their headroom exactly that, and
    share what is left among the rest, until nobody caps or nothing is
    free."""
    w = np.where(live, weights, 0)
    c = np.where(live, caps, 0)
    alloc = np.zeros_like(c)
    active = live & (c > 0)
    free = slots.copy()
    going = (free > TOL) & active.any(axis=1)
    for _ in range(c.shape[1] + 1):
        if not going.any():
            break
        w_act = np.where(active, w, 0)
        total = w_act.sum(axis=1)
        w_ok = total > 0
        share = np.where(
            active,
            free[:, None] * w_act / np.where(w_ok, total, 1)[:, None],
            0,
        )
        headroom = c - alloc
        capped = active & (share >= headroom - TOL)
        cont = w_ok & capped.any(axis=1)
        grant_capped = np.where(capped, headroom, 0)
        grant = np.where(cont[:, None], grant_capped,
                         np.where(w_ok[:, None], share, 0))
        g = going[:, None]
        alloc = np.where(g, alloc + grant, alloc)
        free = np.where(going, np.where(cont, free - grant_capped.sum(axis=1),
                                        free), free)
        active = np.where(g, cont[:, None] & active & ~capped, active)
        going = (free > TOL) & active.any(axis=1)
    return alloc


def greedy(key, caps, slots):
    """Serve each row's jobs in ascending key (stable: index breaks ties),
    each up to its cap, until the slots run out."""
    order = np.argsort(key, axis=1, kind="stable")
    ahead = np.cumsum(np.take_along_axis(caps, order, axis=1), axis=1)
    cum = np.empty_like(ahead)
    np.put_along_axis(cum, order, ahead, axis=1)
    return np.clip(slots[:, None] - (cum - caps), 0, caps)


def init(rows: dict, dtype=np.float64) -> tuple[dict, dict]:
    """(fixed inputs, initial state) of a batch of cells.

    ``rows``: ``(B, J)`` arrays named as in ``fb_reference.ROW_KEYS`` and
    ``(B,)`` arrays of the scalars (``policy`` as the integer code)."""
    f = {k: np.asarray(rows[k], dtype) for k in (
        "arrival", "m_work", "m_dur", "m_tasks", "r_work", "r_dur",
        "r_tasks", "est_m", "est_r", "weight", "map_slots", "red_slots",
        "dt_cap", "eps")}
    f["policy"] = np.asarray(rows["policy"])[:, None]
    state = {
        "t": np.zeros(f["arrival"].shape[0], dtype),
        "m_rem": f["m_work"].copy(), "r_rem": f["r_work"].copy(),
        "vm_rem": f["est_m"].copy(), "vr_rem": f["est_r"].copy(),
        "comp": np.zeros_like(f["m_work"]),
        "done": np.zeros(f["m_work"].shape, bool),
        "steps": np.zeros(f["arrival"].shape[0], np.int64),
    }
    return f, state


def step(f: dict, s: dict) -> tuple[dict, np.ndarray]:
    """Advance every row by one event; rows with no live or pending job
    keep their state.  Returns (next state, which rows moved)."""
    dtype = s["t"].dtype
    policy = f["policy"]
    hfsp = policy == 1
    t, m_rem, r_rem = s["t"], s["m_rem"], s["r_rem"]
    vm_rem, vr_rem, done = s["vm_rem"], s["vr_rem"], s["done"]
    arrived = f["arrival"] <= t[:, None]
    live = arrived & ~done
    pending = ~arrived
    go = live.any(axis=1) | pending.any(axis=1)
    m_active = live & (m_rem > 0)
    r_active = live & (m_rem <= 0) & (r_rem > 0)
    m_cap = np.where(m_active, f["m_tasks"], 0)
    r_cap = np.where(r_active, f["r_tasks"], 0)
    att_m, att_r = f["m_work"] - m_rem, f["r_work"] - r_rem

    vm_live = hfsp & m_active & (vm_rem > 0)
    vr_live = hfsp & r_active & (vr_rem > 0)
    vm_cap = np.where(vm_live, np.clip(
        np.ceil(vm_rem / f["m_dur"] - 1e-9), 1, f["m_tasks"]), 0)
    vr_cap = np.where(vr_live, np.clip(
        np.ceil(vr_rem / f["r_dur"] - 1e-9), 1, f["r_tasks"]), 0)
    vm_alloc = water_fill(vm_cap, f["weight"], f["map_slots"], vm_live)
    vr_alloc = water_fill(vr_cap, f["weight"], f["red_slots"], vr_live)

    def key(srpt, las, virtual):
        return np.select(
            [policy == 0, policy == 2, policy == 3],
            [np.broadcast_to(f["arrival"], srpt.shape), srpt, las],
            virtual,
        )

    key_m = key(np.maximum(f["est_m"] - att_m, 0), att_m, vm_rem)
    key_r = key(np.maximum(f["est_r"] - att_r, 0), att_r, vr_rem)
    m_alloc = greedy(key_m, m_cap, f["map_slots"])
    r_alloc = greedy(key_r, r_cap, f["red_slots"])

    inf = np.array(np.inf, dtype)
    next_arr = np.where(pending, f["arrival"], inf).min(axis=1)
    dt_arr = next_arr + f["eps"] - t
    with np.errstate(divide="ignore", invalid="ignore"):
        m_fin = np.where(m_alloc > 0, m_rem / m_alloc, inf)
        r_fin = np.where(r_alloc > 0, r_rem / r_alloc, inf)
    n_live = live.sum(axis=1)
    dt_cap = np.where(n_live > 1, f["dt_cap"], inf)
    dt = np.minimum(np.minimum(dt_arr, dt_cap),
                    np.minimum(m_fin.min(axis=1), r_fin.min(axis=1)))
    dt = np.where(np.isfinite(dt), dt, 0).astype(dtype)

    d = dt[:, None]
    t2 = t + dt

    def drain(rem, alloc):
        rem2 = rem - alloc * d
        return np.where(rem2 <= SNAP, 0, rem2)

    m_rem2, r_rem2 = drain(m_rem, m_alloc), drain(r_rem, r_alloc)
    vm_rem2, vr_rem2 = drain(vm_rem, vm_alloc), drain(vr_rem, vr_alloc)
    newly = live & (m_rem2 <= 0) & (r_rem2 <= 0)

    g = go[:, None]
    return {
        "t": np.where(go, t2, t),
        "m_rem": np.where(g, m_rem2, m_rem),
        "r_rem": np.where(g, r_rem2, r_rem),
        "vm_rem": np.where(g, vm_rem2, vm_rem),
        "vr_rem": np.where(g, vr_rem2, vr_rem),
        "comp": np.where(g & newly, t2[:, None], s["comp"]),
        "done": done | (g & newly),
        "steps": s["steps"] + go,
    }, go


def simulate(rows: dict, dtype=np.float64, max_steps: int = 1_000_000):
    """Run a batch of cells to completion (see :func:`init` for
    ``rows``).  Returns ``{"comp": (B, J) completion times, "steps":
    (B,)}``."""
    f, state = init(rows, dtype)
    for _ in range(max_steps):
        state, go = step(f, state)
        if not go.any():
            return {"comp": state["comp"], "steps": state["steps"]}
    raise RuntimeError(f"fluid reference did not drain in {max_steps} steps")
