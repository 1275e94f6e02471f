"""JAX backend for the virtual cluster's numeric kernels (see docs/vcluster.md).

Provides jittable, fixed-shape replacements for the two hot loops in
:mod:`repro.core.vcluster`:

* :func:`water_fill` — the weighted max-min (water-filling) allocation,
  as a bounded ``lax.while_loop`` over redistribute rounds: one fused XLA
  program replaces O(#cap-levels) numpy round trips of Python dispatch;
* :func:`water_fill_batch` — the fill ``vmap``-ed over a leading scenario
  axis: B candidate allocations (what-if demands, per-scenario slot
  counts) price in ONE kernel dispatch instead of B Python loops — the
  speedup the scheduler-overhead microbenchmark tracks;
* :func:`project_finish_times` — the piecewise-constant PS forward
  simulation behind HFSP's schedule order, as a ``lax.while_loop`` with a
  warm-started water level (monotone across finish events) and segmented
  host-side compaction of survivors into shrinking buckets (at most one
  loop iteration per job completion, exactly like the numpy reference);
* :func:`project_finish_times_batch` — the same projection ``vmap``-ed
  over a leading scenario axis, so many what-if projections (hypothetical
  job sizes from the estimator, candidate allocations, epsilon-window
  event batches, both phases of a scheduling pass) price in one dispatch.

Shape contract (when recompiles happen)
---------------------------------------
All entry points pad inputs to the next power-of-two length (floor 8) and
mask the tail with ``present=False``.  XLA therefore compiles one program
per *bucket* (8, 16, 32, ...), not per job count: a cluster oscillating
between 900 and 1100 live jobs reuses the 1024-wide executable.  Masked
padding is exact — padded entries contribute ``0.0`` terms to every sum
and sort behind an ``inf`` rank, so the result on real entries is
bit-identical across bucket sizes (adding a float zero is exact).

Everything runs in float64 (via the scoped ``jax.enable_x64(True)``
context, so the global x64 flag — and with it the rest of the process —
is untouched) to stay within 1e-9 of the numpy reference.

JAX is imported lazily: the numpy backend, the schedulers, and the
simulator never pay the import (or require the dependency) unless a
``VirtualCluster(backend="jax")`` is actually constructed.
"""

from __future__ import annotations

import functools

import numpy as np

__all__ = [
    "have_jax",
    "water_fill",
    "water_fill_batch",
    "project_finish_times",
    "project_finish_times_batch",
]


def have_jax() -> bool:
    """True when a usable jax is importable (checked lazily, cached)."""
    return _modules() is not None


@functools.lru_cache(maxsize=1)
def _modules():
    try:
        import jax
        import jax.numpy as jnp
        from jax import lax
    except Exception:  # pragma: no cover - environment without jax
        return None
    return jax, jnp, lax


def _require():
    mods = _modules()
    if mods is None:  # pragma: no cover - environment without jax
        raise RuntimeError(
            "VirtualCluster backend 'jax' requested but jax is not "
            "importable; install jax or use backend='numpy' "
            "(REPRO_VC_BACKEND=numpy)."
        )
    return mods


def _bucket(n: int) -> int:
    """Padded buffer width for ``n`` live jobs: next power of two up to
    1024, then the next multiple of 1024 (pow2 padding wastes up to 2x
    work exactly where width dominates cost — 5000 jobs pad to 5120, not
    8192)."""
    if n <= 8:
        return 8
    if n <= 1024:
        return 1 << (n - 1).bit_length()
    return -(-n // 1024) * 1024


def _pad1(a: np.ndarray, width: int, fill: float = 0.0) -> np.ndarray:
    out = np.full(width, fill, dtype=np.float64)
    out[: len(a)] = a
    return out


# ---------------------------------------------------------------------------
# Kernels (traced under jit; see docs/vcluster.md for the math)
# ---------------------------------------------------------------------------
def _water_fill_masked(caps, ws, slots, live):
    """Weighted water-fill over the ``live`` entries, as a bounded
    ``lax.while_loop``: fill proportionally to weight, clamp at cap,
    redistribute — the exact fixed-point path of the numpy reference
    (``vcluster._water_fill``), one fused XLA round per cap level.

    A sort-based closed form is asymptotically prettier but loses badly on
    CPU: one 8k-wide f64 ``argsort`` costs ~2.5 ms under XLA while real
    trace demands converge in 1-3 redistribute rounds of cheap fused
    element-wise work.  Mirroring the reference round-for-round also keeps
    the floating-point trajectories of the two backends within a few ulp,
    which is what lets the conformance suite demand bit-identical
    *schedules*.
    """
    _, jnp, lax = _modules()
    w = jnp.where(live, ws, 0.0)
    c = jnp.where(live, caps, 0.0)

    def cond(state):
        _, active, free = state
        return (free > 1e-12) & jnp.any(active)

    def body(state):
        alloc, active, free = state
        w_act = jnp.where(active, w, 0.0)
        total_w = jnp.sum(w_act)
        w_ok = total_w > 0.0
        share = jnp.where(
            active, free * w_act / jnp.where(w_ok, total_w, 1.0), 0.0
        )
        headroom = c - alloc
        capped = active & (share >= headroom - 1e-12)
        cont = w_ok & jnp.any(capped)
        grant_capped = jnp.where(capped, headroom, 0.0)
        # Terminal round (nobody capped): everyone keeps their share and
        # the loop ends; zero total weight grants nothing (numpy's break).
        grant = jnp.where(
            cont, grant_capped, jnp.where(w_ok, share, 0.0)
        )
        alloc2 = alloc + grant
        free2 = jnp.where(cont, free - jnp.sum(grant_capped), free)
        active2 = jnp.where(cont, active & ~capped, jnp.zeros_like(active))
        return alloc2, active2, free2

    alloc0 = jnp.zeros(caps.shape, caps.dtype)
    active0 = live & (c > 0.0)
    state = (alloc0, active0, jnp.asarray(slots, caps.dtype))
    return lax.while_loop(cond, body, state)[0]


def _project_kernel(rem0, caps, ws, present, slots, now, lam0, floor):
    """PS forward simulation, mirroring ``vcluster.project_finish_times``
    event for event: at each iteration the minimal remaining/allocation
    job finishes, its slots redistribute, repeat.  At least one job
    leaves ``live`` per iteration, so the loop is bounded by the job
    count.

    Two structural exploits make this beat the numpy loop at trace scale:

    * **warm-started water level.**  Within one projection, caps and
      weights are fixed and jobs only *leave*, so the water level
      ``lam`` (allocation = ``min(cap, lam * w)``) is monotonically
      non-decreasing across events.  Each event therefore resumes the
      level fixpoint from the previous event's ``lam`` instead of
      redistributing from scratch — typically a single masked-sum
      iteration instead of a full O(#cap-levels) refill;
    * **early-stop floor.**  The loop also exits once the live count
      drops to ``floor``, letting the host wrapper compact survivors
      into a smaller padded bucket (see :func:`project_finish_times`) —
      the fixed-shape analogue of numpy's shrinking fancy-indexing.

    Returns the full carry ``(t, rem, fin, live, lam, n_live, run)``;
    ``run`` distinguishes "stopped at the floor" (True) from "drained or
    only infinite-size jobs left" (False).
    """
    _, jnp, lax = _modules()
    live0 = present & (rem0 > 0.0) & (caps > 0.0)
    pos = ws > 0.0
    fin0 = jnp.where(live0, jnp.inf, now)
    n0 = jnp.sum(live0)
    lam_init = jnp.asarray(lam0, rem0.dtype)
    capped0 = live0 & pos & (caps <= lam_init * ws + 1e-12)

    def level_step(lam_c, capped_c, part):
        cap_sum = jnp.sum(jnp.where(capped_c, caps, 0.0))
        w_unc = jnp.sum(jnp.where(part & ~capped_c, ws, 0.0))
        lam2 = jnp.where(
            w_unc > 0.0,
            (slots - cap_sum) / jnp.where(w_unc > 0.0, w_unc, 1.0),
            jnp.inf,
        )
        return jnp.maximum(lam2, lam_c)  # monotone; guards fp wobble

    def cond(state):
        return state[7] & (state[6] > floor)

    def body(state):
        t, rem, fin, live, lam, capped, n_live, _ = state
        part = live & pos
        # `capped` is maintained as a subset of `live` by the return below
        # (finished jobs leave the capped set), so no re-masking here.
        # Advance the water level from the carried state: one masked-sum
        # step, then grow the capped set only if the raised level crossed
        # a new cap/weight ratio (rare — the fixpoint loop usually skips).
        lam1 = level_step(lam, capped, part)

        def lcond(s):
            return s[2]

        def lbody(s):
            lam_c, capped_c, _ = s
            capped2 = capped_c | (part & (caps <= lam_c * ws + 1e-12))
            lam2 = level_step(lam_c, capped2, part)
            more = jnp.any(
                part & ~capped2 & (caps <= lam2 * ws + 1e-12)
            )
            return lam2, capped2, more

        more0 = jnp.any(part & ~capped & (caps <= lam1 * ws + 1e-12))
        lam_f, capped_f, _ = lax.while_loop(
            lcond, lbody, (lam1, capped, more0)
        )
        alloc = jnp.where(
            part,
            jnp.where(capped_f, caps, jnp.minimum(caps, lam_f * ws)),
            0.0,
        )
        # Raw division is safe: the mask discards the /0 lanes, and for
        # alloc > 0 the numpy reference's max(alloc, 1e-300) is a no-op.
        dt = jnp.where(live & (alloc > 0.0), rem / alloc, jnp.inf)
        dt_min = jnp.min(dt)
        finite = jnp.isfinite(dt_min)
        # Only infinite-size jobs remain: commit nothing, stop (they never
        # finish under PS, exactly like the numpy loop's break).
        t2 = jnp.where(finite, t + dt_min, t)
        rem2 = jnp.where(live, jnp.maximum(rem - alloc * dt_min, 0.0), rem)
        done = live & (dt <= dt_min + 1e-12)
        fin2 = jnp.where(done, t2, fin)
        live2 = live & ~done
        n2 = n_live - jnp.sum(done)
        return (
            t2,
            jnp.where(finite, rem2, rem),
            jnp.where(finite, fin2, fin),
            jnp.where(finite, live2, live),
            jnp.where(finite, lam_f, lam),
            jnp.where(finite, capped_f & live2, capped),
            jnp.where(finite, n2, n_live),
            finite & (n2 > 0),
        )

    state = (
        jnp.asarray(now, rem0.dtype),
        rem0,
        fin0,
        live0,
        lam_init,
        capped0,
        n0,
        n0 > 0,
    )
    return lax.while_loop(cond, body, state)


@functools.lru_cache(maxsize=1)
def _jitted():
    """Compile-once entry points (cached per padded bucket by jit)."""
    jax, _, _ = _modules()
    return {
        "fill": jax.jit(_water_fill_masked),
        "fill_batch": jax.jit(
            jax.vmap(_water_fill_masked, in_axes=(0, 0, 0, 0))
        ),
        "project": jax.jit(_project_kernel),
        "project_batch": jax.jit(
            jax.vmap(
                lambda rem, caps, ws, present, slots, now: _project_kernel(
                    rem, caps, ws, present, slots, now, 0.0, 0
                )[2],
                in_axes=(0, 0, 0, 0, 0, 0),
            )
        ),
    }


# ---------------------------------------------------------------------------
# Public numpy-in / numpy-out API
# ---------------------------------------------------------------------------
def water_fill(caps: np.ndarray, ws: np.ndarray, slots: float) -> np.ndarray:
    """Weighted max-min allocation; drop-in for ``vcluster._water_fill``."""
    jax, jnp, _ = _require()
    n = len(caps)
    if n == 0:
        return np.zeros(0)
    width = _bucket(n)
    live = np.zeros(width, dtype=bool)
    live[:n] = True
    with jax.enable_x64(True):
        out = _jitted()["fill"](
            _pad1(np.asarray(caps, np.float64), width),
            _pad1(np.asarray(ws, np.float64), width),
            jnp.asarray(float(slots), jnp.float64),
            live,
        )
    return np.asarray(out)[:n]


def water_fill_batch(
    caps_b: np.ndarray, ws_b: np.ndarray, slots
) -> np.ndarray:
    """B water-fills in one vmapped dispatch.

    ``caps_b``/``ws_b`` are (B, N) scenario matrices (candidate demand
    sets); ``slots`` is a scalar or a (B,) vector.  Replaces B sequential
    ``_water_fill`` Python loops with a single kernel launch — the
    batched-what-if fast path measured by
    ``benchmarks/bench_sched_overhead.py``.
    """
    jax, jnp, _ = _require()
    caps_b = np.asarray(caps_b, np.float64)
    if caps_b.ndim != 2:
        raise ValueError("caps_b must be (B, N)")
    b, n = caps_b.shape
    if b == 0 or n == 0:
        return np.zeros((b, n))
    width = _bucket(n)
    pad = ((0, 0), (0, width - n))
    live = np.zeros((b, width), dtype=bool)
    live[:, :n] = True
    slots_b = np.broadcast_to(np.asarray(slots, np.float64), (b,)).copy()
    with jax.enable_x64(True):
        out = _jitted()["fill_batch"](
            np.pad(caps_b, pad),
            np.pad(np.asarray(ws_b, np.float64), pad),
            slots_b,
            live,
        )
    return np.asarray(out)[:, :n]


def project_finish_times(
    rem: np.ndarray, caps: np.ndarray, ws: np.ndarray, slots: float, now: float
) -> np.ndarray:
    """PS finish times; drop-in for ``vcluster.project_finish_times``
    (array-shaped: callers keep their own id <-> index mapping).

    Segmented: the kernel stops when the live count falls to half the
    padded width, survivors are compacted into the next-smaller bucket,
    and the simulation resumes with the carried clock and water level.  Total work is geometric in the shrinking width instead of
    (#jobs x full width) — the fixed-shape counterpart of the numpy
    loop's shrinking ``caps[live]`` fancy-indexing.  Small widths
    (< 1024) run in a single segment; compaction round trips there would
    cost more than they save.
    """
    jax, jnp, _ = _require()
    n = len(rem)
    if n == 0:
        return np.zeros(0)
    rem = np.asarray(rem, np.float64)
    caps = np.asarray(caps, np.float64)
    ws = np.asarray(ws, np.float64)
    fin_out = np.empty(n)
    idx = np.arange(n)
    t = float(now)
    lam = 0.0
    while True:
        m = len(idx)
        width = _bucket(m)
        present = np.zeros(width, dtype=bool)
        present[:m] = True
        floor = width // 2 if width >= 1024 else 0
        with jax.enable_x64(True):
            state = _jitted()["project"](
                _pad1(rem, width),
                _pad1(caps, width),
                _pad1(ws, width),
                present,
                jnp.asarray(float(slots), jnp.float64),
                jnp.asarray(t, jnp.float64),
                jnp.asarray(lam, jnp.float64),
                floor,
            )
        t2, rem2, fin, live, lam2, _capped, n_live, run = (
            np.asarray(x) for x in state
        )
        fin_out[idx] = fin[:m]
        if int(n_live) == 0 or not bool(run) or floor == 0:
            return fin_out
        alive = np.flatnonzero(live[:m])
        idx = idx[alive]
        rem = rem2[:m][alive]
        caps = caps[alive]
        ws = ws[alive]
        t = float(t2)
        lam = float(lam2)


def project_finish_times_batch(
    rem_b: np.ndarray,
    caps_b: np.ndarray,
    ws_b: np.ndarray,
    slots,
    now,
    n_valid=None,
) -> np.ndarray:
    """Batched what-if projections: one dispatch for B scenarios.

    ``rem_b``/``caps_b``/``ws_b`` are (B, N) scenario matrices; ``slots``
    and ``now`` are scalars or (B,) vectors (so MAP and REDUCE — or
    scenarios at different virtual times — can share a batch).
    ``n_valid`` optionally gives the per-row live-prefix length (defaults
    to N for every row).  Returns a (B, N) matrix of absolute finish
    times; entries beyond a row's ``n_valid`` are meaningless.
    """
    jax, jnp, _ = _require()
    rem_b = np.asarray(rem_b, np.float64)
    if rem_b.ndim != 2:
        raise ValueError("rem_b must be (B, N)")
    b, n = rem_b.shape
    if b == 0 or n == 0:
        return np.zeros((b, n))
    width = _bucket(n)
    pad = ((0, 0), (0, width - n))
    rem_p = np.pad(rem_b, pad)
    caps_p = np.pad(np.asarray(caps_b, np.float64), pad)
    ws_p = np.pad(np.asarray(ws_b, np.float64), pad)
    present = np.zeros((b, width), dtype=bool)
    if n_valid is None:
        present[:, :n] = True
    else:
        for i, nv in enumerate(np.broadcast_to(n_valid, (b,))):
            present[i, : int(nv)] = True
    slots_b = np.broadcast_to(np.asarray(slots, np.float64), (b,)).copy()
    now_b = np.broadcast_to(np.asarray(now, np.float64), (b,)).copy()
    with jax.enable_x64(True):
        out = _jitted()["project_batch"](
            rem_p, caps_p, ws_p, present, slots_b, now_b
        )
    return np.asarray(out)[:, :n]
