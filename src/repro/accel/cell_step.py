"""Fixed-shape jitted simulation cell (the accel fluid model).

One *cell* = one scenario (workload seed × policy × error model) run to
completion.  The Python simulator replays it as a discrete-event loop —
exact, but host-bound (seconds of wall per ``paper-fb`` cell).  This
module re-expresses the cell as a **fluid approximation** with a fully
static shape, so `vmap` can stack seeds × policies × error alphas into
one compiled program:

* **Padded event window.**  A cell is synthesized host-side into flat
  ``(J,)`` arrays (arrival time, per-phase task counts/durations,
  perturbed size estimate) padded to a bucket width; padding rows have
  ``present=0`` / ``arrival=inf`` and are provably inert (the
  padding-neutrality test pins bitwise-identical summaries across
  bucket widths).
* **Fluid service.**  Per phase the cell tracks remaining *serialized*
  work; a job's instantaneous parallelism is capped by
  ``ceil(remaining/task_duration)`` (the same effective-cap rule the
  virtual cluster uses), which serializes phase tails exactly like
  discrete task draining when per-phase task durations are equal (true
  for the fb generator at ``task_jitter=0`` — an eligibility condition,
  see docs/accel.md).  REDUCE unlocks when MAP's fluid remaining hits
  zero (``reduce_slowstart=1.0``, the fb default).
* **Event-stepped ``lax.scan``.**  Each step advances to the next
  arrival-window close or phase completion, capped at one ``heartbeat``
  for rank-evolving policies (srpt/las/hfsp re-rank on the executor
  tick in the Python simulator too; fifo's static order needs no cap).
  The PR 4 epsilon-window is the batching seam: all arrivals inside one
  window activate in a single step, so bursty traces cost one step per
  burst, not per job.  Steps after cell completion are exact no-ops
  (a carried ``go`` guard), which is what lets one fixed-length scan
  chunk serve every cell in the batch.
* **Policies as data.**  The policy is a per-cell *array* code
  (fifo=0, hfsp=1, srpt=2, las=3), so one compiled program evaluates a
  mixed-policy batch: every step computes all four priority keys and
  selects by code.  hfsp's key is a virtual-cluster proxy — a weighted
  water-fill over live jobs' effective caps (THE water-fill site:
  ``impl="lax"`` uses the ``lax.while_loop`` reference,
  ``impl="pallas"`` the real Pallas kernel from
  :mod:`repro.accel.waterfill_pallas`) feeding a projected
  virtual-finish rank, with virtually-finished ("late") jobs promoted
  to the front — HFSP's aging rule.

The driver (:func:`run_packed`) scans a fixed ``t_chunk`` steps per
jitted call and loops on the host until every cell reports done — the
same segmented-host-loop shape as ``vcluster_jax.project_finish_times``.
Recompiles are keyed by (bucket width, chunk length, water-fill impl)
only; see docs/accel.md.
"""

from __future__ import annotations

import collections
import functools

import numpy as np

#: Policy name -> cell code (a per-cell array input, NOT a static jit
#: arg — mixed-policy batches compile once).
POLICY_CODES = {"fifo": 0, "hfsp": 1, "srpt": 2, "las": 3}

#: Fluid remainders below this many serialized seconds snap to zero
#: (floating-point completion residue; keeps effective caps and dt
#: selection away from denormal-scale steps).
_SNAP = 1e-6

_PARAM_KEYS = (
    "arrival", "m_work", "m_dur", "m_tasks", "r_work", "r_dur",
    "r_tasks", "est_m", "est_r", "weight", "present",
    "policy", "map_slots", "red_slots", "dt_cap", "eps", "close",
)


#: Records :class:`RunLog` keeps: far more than the calls of one claim
#: (two) or of one benchmark window (two per matrix).
_RUN_LOG_KEEP = 1024


class RunLog:
    """Counters of the latest :func:`run_packed` calls, oldest first.

    One record per call: ``rows`` (cells in the batch), ``t_chunk``,
    ``host_syncs`` (chunks run; the host syncs once after each),
    ``event_steps`` (the cells' ``steps`` summed), ``scanned_row_steps``
    (rows x ``t_chunk`` x chunks: the steps the device scanned, done
    cells' no-op steps included) and ``window_closes`` (steps that
    ended on an arrival-window close).  ``count`` is the number of calls
    ever logged, so :meth:`since` can hand a caller exactly the calls
    made after it looked.  It keeps the last :data:`_RUN_LOG_KEEP`."""

    def __init__(self):
        self.records: collections.deque[dict] = collections.deque(
            maxlen=_RUN_LOG_KEEP)
        self.count = 0

    def append(self, record: dict) -> None:
        self.records.append(record)
        self.count += 1

    def since(self, count: int) -> list[dict]:
        """The records of the calls logged after ``count`` calls."""
        n = self.count - count
        if n > len(self.records):
            raise ValueError(f"{n} calls since {count}; the log keeps "
                             f"{len(self.records)}")
        return list(self.records)[len(self.records) - n:]


#: The process's log.  The sweep worker sums a claim's calls from it, and
#: the benchmark's readers take the calls of their window; neither can
#: hand ``run_packed`` a log of its own through the served path.
RUN_LOG = RunLog()


class CellBatchNotConverged(RuntimeError):
    """A packed batch did not drain within its chunk budget — the one
    accel failure the distributed worker demotes to the Python path
    (every other error, a device or compile fault included, fails it)."""


@functools.lru_cache(maxsize=1)
def _modules():
    try:
        import jax
        import jax.numpy as jnp
        from jax import lax
    except Exception:  # pragma: no cover - environment without jax
        return None
    return jax, jnp, lax


def have_jax() -> bool:
    return _modules() is not None


def _require():
    mods = _modules()
    if mods is None:  # pragma: no cover - environment without jax
        raise RuntimeError(
            "repro.accel requested but jax is not importable; use the "
            "Python scenario runner instead."
        )
    return mods


def _bucket(n: int) -> int:
    """Same padded-width quantization as the vcluster backend (pow2 to
    1024, then 1024 multiples) so batches re-use compiled widths."""
    if n <= 8:
        return 8
    if n <= 1024:
        return 1 << (n - 1).bit_length()
    return -(-n // 1024) * 1024


# ---------------------------------------------------------------------------
# Host-side packing
# ---------------------------------------------------------------------------
def init_state(params: dict) -> dict:
    """Initial state pytree (numpy f64) for one packed cell batch."""
    m_work = np.asarray(params["m_work"], np.float64)
    b = m_work.shape[:-1]
    return {
        "t": np.zeros(b, np.float64),
        "m_rem": m_work.copy(),
        "r_rem": np.asarray(params["r_work"], np.float64).copy(),
        "vm_rem": np.asarray(params["est_m"], np.float64).copy(),
        "vr_rem": np.asarray(params["est_r"], np.float64).copy(),
        "comp": np.zeros_like(m_work),
        "done": np.asarray(params["present"], np.float64) < 0.5,
        "steps": np.zeros(b, np.int64),
        "closes": np.zeros(b, np.int64),
    }


def pack_cells(cells: list[dict], width: int | None = None) -> dict:
    """Stack per-cell param dicts (flat ``(J_i,)`` arrays + scalars)
    into one ``(B, J)`` batch, padding every cell to a common bucket
    width.  Padding rows: ``present=0``, ``arrival=close=inf``,
    durations 1 (any positive value — they never activate).

    Each job's arrival-window close ``arrival + eps`` is computed here,
    once, in IEEE float64, and carried as a row: a step that closes a
    window lands on this number itself (see :func:`_cell_step`)."""
    jmax = max(len(c["arrival"]) for c in cells)
    width = _bucket(jmax) if width is None else width
    if width < jmax:
        raise ValueError(f"width {width} < widest cell {jmax}")
    out: dict = {}
    fills = {"arrival": np.inf, "close": np.inf, "m_dur": 1.0, "r_dur": 1.0}
    for key in _PARAM_KEYS:
        if key == "close":
            vals = [np.asarray(c["arrival"], np.float64)
                    + np.float64(c["eps"]) for c in cells]
        else:
            vals = [np.asarray(c[key]) for c in cells]
        if vals[0].ndim == 0:
            dt = np.int32 if key == "policy" else np.float64
            out[key] = np.stack(vals).astype(dt)
            continue
        fill = fills.get(key, 0.0)
        rows = np.full((len(cells), width), fill, np.float64)
        for i, v in enumerate(vals):
            rows[i, : len(v)] = v
        out[key] = rows
    return out


# ---------------------------------------------------------------------------
# The traced step (one cell; vmapped by the chunk runner)
# ---------------------------------------------------------------------------
def _greedy_alloc(jnp, key, caps, slots):
    """Priority-ordered greedy slot allocation: serve jobs in ascending
    key order, each up to its cap, until slots run out.

    Computed rank-free: ``cum_i`` = total caps of jobs at or ahead of
    job i (strictly smaller key, or equal key and <= index — the
    simulator's job-id tiebreak) via an O(J^2) comparison matrix.  At
    the padded widths this model targets (<=128) the fused matrix is
    ~10x faster per step on CPU than a vmapped stable argsort + scatter,
    and bit-identical to it; wider buckets would want the sort back."""
    j = jnp.arange(key.shape[-1])
    ahead = (key[:, None] < key[None, :]) | (
        (key[:, None] == key[None, :]) & (j[:, None] <= j[None, :])
    )
    cum = jnp.sum(jnp.where(ahead, caps[:, None], 0.0), axis=0)
    return jnp.clip(slots - (cum - caps), 0.0, caps)


def _cell_step(params, state, water_fill):
    """Advance one cell by one event window.  Pure, fixed-shape; exact
    no-op (bitwise) once the cell is done.

    A step that ends at an arrival-window close lands on the packed
    ``close`` row itself (the host's ``arrival + eps``), not on
    ``t + dt``.  With the ``"auto"`` window ``eps`` is a gap between two
    consecutive arrivals, so one window closes exactly on the next
    job's arrival, and ``arrival <= t`` decides that job at equality.
    In IEEE float64 ``t + (close - t)`` is ``close`` whenever
    ``t >= close / 2`` (the subtraction is exact), but a TPU computes
    float64 as f32 pairs, whose ``t + dt`` can land 1e-10 short: the
    job would then wait a whole further window.  Taking the close value
    keeps the comparison one between two copies of the same number."""
    _, jnp, _ = _require()
    t = state["t"]
    present = params["present"] > 0.5
    arrived = present & (params["arrival"] <= t)
    live = arrived & ~state["done"]

    # Real-cluster parallelism cap = the phase's task count while fluid
    # work remains.  NOT the vcluster's ceil(rem/duration) decay: that
    # rule assumes remaining work is packed into whole tasks, but tasks
    # started together hold their slots to the end (n equal tasks on n
    # slots finish together in one duration), so decaying the cap with
    # remaining work would serialize phase tails that actually run
    # parallel — an O(log(slots) x duration) per-phase slowdown.  With
    # the full cap the fluid phase finishes at most ~one task-duration
    # early (no wave quantization); docs/accel.md#fidelity.
    m_active = live & (state["m_rem"] > 0.0)
    m_cap = jnp.where(m_active, params["m_tasks"], 0.0)
    r_active = live & (state["m_rem"] <= 0.0) & (state["r_rem"] > 0.0)
    r_cap = jnp.where(r_active, params["r_tasks"], 0.0)

    # --- priority keys: PER PHASE, mirroring the discipline registry
    # (SRPTRank/LASRank key on the phase's estimate/attained counters,
    # HFSP on per-phase virtual clusters).  All policies are computed
    # every step and one is selected by the cell's code.
    att_m = params["m_work"] - state["m_rem"]
    att_r = params["r_work"] - state["r_rem"]
    fifo_key = params["arrival"]
    srpt_m = jnp.maximum(params["est_m"] - att_m, 0.0)
    srpt_r = jnp.maximum(params["est_r"] - att_r, 0.0)
    # hfsp: twin per-phase virtual clusters.  Each fair-shares its own
    # slot pool over the phase-live jobs (the water-fill — Pallas site)
    # and the rank is the job's remaining *virtual* phase size — the
    # FSP rule.  A job the real cluster starves still drains virtually,
    # so its rank falls toward 0 and it preempts; "late" jobs (virtual
    # size exhausted, real work left) sit at rank 0, the front of the
    # queue — HFSP's aging behavior.
    vm_live = m_active & (state["vm_rem"] > 0.0)
    vm_cap = jnp.where(
        vm_live,
        jnp.clip(
            jnp.ceil(state["vm_rem"] / params["m_dur"] - 1e-9),
            1.0,
            params["m_tasks"],
        ),
        0.0,
    )
    vm_alloc = water_fill(
        vm_cap, params["weight"], params["map_slots"], vm_live
    )
    vr_live = r_active & (state["vr_rem"] > 0.0)
    vr_cap = jnp.where(
        vr_live,
        jnp.clip(
            jnp.ceil(state["vr_rem"] / params["r_dur"] - 1e-9),
            1.0,
            params["r_tasks"],
        ),
        0.0,
    )
    vr_alloc = water_fill(
        vr_cap, params["weight"], params["red_slots"], vr_live
    )

    p = params["policy"]

    def select_key(srpt_key, las_key, hfsp_key):
        return jnp.where(
            p == POLICY_CODES["fifo"], fifo_key,
            jnp.where(
                p == POLICY_CODES["srpt"], srpt_key,
                jnp.where(p == POLICY_CODES["las"], las_key, hfsp_key),
            ),
        )

    key_m = select_key(srpt_m, att_m, state["vm_rem"])
    key_r = select_key(srpt_r, att_r, state["vr_rem"])
    m_alloc = _greedy_alloc(jnp, key_m, m_cap, params["map_slots"])
    r_alloc = _greedy_alloc(jnp, key_r, r_cap, params["red_slots"])

    # --- dt: next arrival-window close, phase completion, or heartbeat.
    # ``close`` is monotone in ``arrival``: the least pending close is
    # that of the earliest pending arrival.
    pending = present & ~arrived
    next_close = jnp.min(jnp.where(pending, params["close"], jnp.inf))
    dt_arr = next_close - t
    m_fin = jnp.where(m_alloc > 0.0, state["m_rem"] / m_alloc, jnp.inf)
    r_fin = jnp.where(r_alloc > 0.0, state["r_rem"] / r_alloc, jnp.inf)
    # The heartbeat cap exists to re-rank while jobs *compete*; with at
    # most one live job priorities are moot and the cell can jump
    # straight to the next completion/arrival (the long single-job tail
    # of an fb trace is ~40% of its steps otherwise).
    n_live = jnp.sum(live.astype(t.dtype))
    dt_cap = jnp.where(n_live > 1.0, params["dt_cap"], jnp.inf)
    dt_other = jnp.minimum(dt_cap, jnp.minimum(jnp.min(m_fin), jnp.min(r_fin)))
    dt = jnp.minimum(dt_arr, dt_other)
    go = jnp.any(live) | jnp.any(pending)
    dt = jnp.where(jnp.isfinite(dt), dt, 0.0)
    closes = jnp.isfinite(dt_arr) & (dt_arr <= dt_other)

    # --- integrate ---
    t2 = jnp.where(closes, next_close, t + dt)
    m_rem2 = state["m_rem"] - m_alloc * dt
    m_rem2 = jnp.where(m_rem2 <= _SNAP, 0.0, m_rem2)
    r_rem2 = state["r_rem"] - r_alloc * dt
    r_rem2 = jnp.where(r_rem2 <= _SNAP, 0.0, r_rem2)
    vm_rem2 = state["vm_rem"] - vm_alloc * dt
    vm_rem2 = jnp.where(vm_rem2 <= _SNAP, 0.0, vm_rem2)
    vr_rem2 = state["vr_rem"] - vr_alloc * dt
    vr_rem2 = jnp.where(vr_rem2 <= _SNAP, 0.0, vr_rem2)
    newly_done = live & (m_rem2 <= 0.0) & (r_rem2 <= 0.0)

    def sel(new, old):
        return jnp.where(go, new, old)

    return {
        "t": sel(t2, t),
        "m_rem": sel(m_rem2, state["m_rem"]),
        "r_rem": sel(r_rem2, state["r_rem"]),
        "vm_rem": sel(vm_rem2, state["vm_rem"]),
        "vr_rem": sel(vr_rem2, state["vr_rem"]),
        "comp": sel(
            jnp.where(newly_done, t2, state["comp"]), state["comp"]
        ),
        "done": sel(state["done"] | newly_done, state["done"]),
        "steps": state["steps"] + go.astype(state["steps"].dtype),
        "closes": state["closes"]
        + (go & closes).astype(state["closes"].dtype),
    }


def _make_water_fill(impl: str):
    from repro.accel import waterfill_pallas as wf

    if impl == "lax":
        return wf.water_fill_lax
    if impl == "pallas":
        return wf.water_fill_pallas
    if impl == "pallas_interpret":
        return functools.partial(wf.water_fill_pallas, interpret=True)
    if impl == "none":
        # Batches with no hfsp cell never read the virtual allocations;
        # a zero fill lets XLA dead-code the whole virtual layer (the
        # executor groups cells by compute class for exactly this).
        return lambda caps, ws, slots, live: caps * 0.0
    raise ValueError(f"unknown water-fill impl {impl!r}")


@functools.lru_cache(maxsize=None)
def _chunk_runner(width: int, t_chunk: int, impl: str):
    """jit(vmap(scan(step)))) for one (bucket width, chunk length,
    water-fill impl) triple — the complete recompile key.  Batch size,
    seeds, policies, error alphas are all *data*."""
    jax, jnp, lax = _require()
    water_fill = _make_water_fill(impl)

    def one_cell(params, state):
        def step(st, _):
            return _cell_step(params, st, water_fill), None

        st, _ = lax.scan(step, state, None, length=t_chunk)
        return st

    return jax.jit(jax.vmap(one_cell))


def run_packed(
    params: dict,
    *,
    t_chunk: int = 512,
    max_chunks: int = 256,
    impl: str = "lax",
    on_chunk=None,
) -> dict:
    """Run a packed ``(B, J)`` cell batch to completion.

    Host loop over fixed-length jitted scan chunks (the segmented shape
    of ``project_finish_times``): after each chunk only the tiny
    ``done`` mask crosses back to the host; finished cells no-op inside
    later chunks.  ``on_chunk(chunks_done, cells_done, cells_total)``
    runs between chunks — the distributed-sweep worker renews its
    leases there.  Raises if ``max_chunks`` scans don't drain the batch
    (a liveness bug, not a tuning knob — every real cell terminates):
    :class:`CellBatchNotConverged`.  Every call appends its counters to
    :data:`RUN_LOG`, one that does not drain included."""
    jax, jnp, _ = _require()
    width = int(np.asarray(params["arrival"]).shape[-1])
    runner = _chunk_runner(width, int(t_chunk), impl)
    state = init_state(params)
    b = state["t"].shape[0]
    chunks, drained = 0, False
    with jax.enable_x64(True):
        params_j = {k: jnp.asarray(v) for k, v in params.items()}
        state = {k: jnp.asarray(v) for k, v in state.items()}
        while chunks < max_chunks and not drained:
            state = runner(params_j, state)
            chunks += 1
            cell_done = np.asarray(
                jnp.all(state["done"] | (params_j["present"] < 0.5), axis=-1)
            )
            if on_chunk is not None:
                on_chunk(chunks, int(cell_done.sum()), b)
            drained = bool(cell_done.all())
    final = {k: np.asarray(v) for k, v in state.items()}
    RUN_LOG.append({
        "rows": b,
        "t_chunk": int(t_chunk),
        "host_syncs": chunks,
        "event_steps": int(final["steps"].sum()),
        "scanned_row_steps": b * int(t_chunk) * chunks,
        "window_closes": int(final["closes"].sum()),
    })
    if not drained:
        raise CellBatchNotConverged(
            f"accel cell batch did not converge in {max_chunks} "
            f"chunks of {t_chunk} steps"
        )
    return final


def summarize(params: dict, final: dict) -> list[dict]:
    """Per-cell summary dicts (the scenario-report subset the sweep
    store, worker CLI, and matrix_report consume)."""
    present = np.asarray(params["present"]) > 0.5
    arrival = np.asarray(params["arrival"])
    comp = np.asarray(final["comp"])
    out = []
    for i in range(present.shape[0]):
        m = present[i]
        soj = comp[i, m] - arrival[i, m]
        out.append({
            "jobs_completed": int(m.sum()),
            "jobs_lost": 0,
            "makespan_s": float(comp[i, m].max()) if m.any() else 0.0,
            "mean_sojourn_s": float(soj.mean()) if m.any() else 0.0,
            "accel_steps": int(np.asarray(final["steps"])[i]),
        })
    return out
