"""The water-fill fixpoint as a Pallas TPU kernel.

``repro.core.vcluster_jax._water_fill_masked`` expresses the weighted
max-min fixpoint as a ``lax.while_loop``: fill proportionally to weight,
clamp at cap, redistribute the overflow.  That is the one genuinely
iterative kernel left in the scheduler inner loop, and the accel cell
step (:mod:`repro.accel.cell_step`) evaluates it every step for every
cell in the vmapped batch.

This module re-expresses the same fixpoint as a Pallas kernel shaped for
the TPU's compiler (Mosaic):

* **Rows in (8, W) blocks.**  The kernel takes the batch explicitly as a
  2-D ``(B, W)`` operand, ``B`` padded to a multiple of 8, one grid
  program per 8 rows; slots arrive as an ``(8, 1)`` column.  The cell
  step calls the fill once per cell under ``vmap``, so the public entry
  carries a ``custom_vmap`` rule that hands the whole batch to the 2-D
  kernel (the default Pallas batching rule would give each program a
  ``(Squeezed, W)`` block, which Mosaic refuses).
* **float32.**  Mosaic lowers no 64-bit arithmetic.  The kernel computes
  in f32, with the active set carried as an f32 mask (a bool loop carry
  does not lower either); :func:`water_fill_pallas` casts the f64 cell
  state to f32 at the call and back at the return.
* **A guarded loop.**  Each row carries the reference loop's cond
  (``free > eps and any(active)``) as a per-row flag and keeps its old
  state once that goes False, so rows that converge early are exact
  no-ops while the other rows of their block finish; the loop exits when
  no row of the block is still going (at most ``W + 1`` rounds: every
  continuing round caps at least one entry).

The contract is bit-equality with :func:`water_fill_lax` evaluated in
f32 on the same rows (pinned in interpret mode by tests/test_accel.py).
``impl="pallas"`` always compiles the kernel; interpret mode is only the
explicit ``impl="pallas_interpret"`` (CPU tests).
"""

from __future__ import annotations

import functools

import numpy as np

#: Rows per grid program: the TPU's sublane count, the second-to-last
#: block dimension Mosaic requires to be a multiple of 8.
ROWS = 8


@functools.lru_cache(maxsize=1)
def _modules():
    try:
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
    except Exception:  # pragma: no cover - environment without jax/pallas
        return None
    return jax, jnp, pl


def _require():
    mods = _modules()
    if mods is None:  # pragma: no cover - environment without jax/pallas
        raise RuntimeError(
            "repro.accel water-fill requested but jax.experimental.pallas "
            "is not importable; install jax or use the numpy/python path."
        )
    return mods


def water_fill_lax(caps, ws, slots, live):
    """The ``lax.while_loop`` reference (re-exported for the dispatch
    seam and the equivalence tests)."""
    from repro.core.vcluster_jax import _water_fill_masked

    return _water_fill_masked(caps, ws, slots, live)


def _kernel(caps_ref, ws_ref, live_ref, slots_ref, out_ref):
    """One (8, W) block of rows; every per-row scalar is an (8, 1)
    column.  Operation for operation the reference loop's body."""
    jax, jnp, _ = _modules()
    width = caps_ref.shape[-1]
    live = live_ref[...] > 0.5
    w = jnp.where(live, ws_ref[...], 0.0)
    c = jnp.where(live, caps_ref[...], 0.0)

    def row_sum(x):
        return jnp.sum(x, axis=-1, keepdims=True)

    def row_any(mask):
        return jnp.max(mask, axis=-1, keepdims=True) > 0.5

    def as_mask(b):  # explicit f32: under x64 bare 1.0/0.0 would be f64
        return jnp.where(b, jnp.float32(1.0), jnp.float32(0.0))

    def going(active, free):
        return (free > 1e-12) & row_any(active)

    def cond(state):
        it, _, active, free = state
        return (it <= width) & (jnp.max(as_mask(going(active, free))) > 0.5)

    def body(state):
        it, alloc, active_f, free = state
        go = going(active_f, free)
        active = active_f > 0.5
        w_act = jnp.where(active, w, 0.0)
        total_w = row_sum(w_act)
        w_ok = total_w > 0.0
        share = jnp.where(
            active, free * w_act / jnp.where(w_ok, total_w, 1.0), 0.0
        )
        headroom = c - alloc
        capped = active & (share >= headroom - 1e-12)
        cont = w_ok & row_any(as_mask(capped))
        grant_capped = jnp.where(capped, headroom, 0.0)
        grant = jnp.where(cont, grant_capped, jnp.where(w_ok, share, 0.0))
        alloc2 = alloc + grant
        free2 = jnp.where(cont, free - row_sum(grant_capped), free)
        active2 = as_mask(cont & active & ~capped)
        return (
            it + 1,
            jnp.where(go, alloc2, alloc),
            jnp.where(go, active2, active_f),
            jnp.where(go, free2, free),
        )

    alloc0 = jnp.zeros_like(c)
    active0 = as_mask(live & (c > 0.0))
    state = (jnp.int32(0), alloc0, active0, slots_ref[...])
    out_ref[...] = jax.lax.while_loop(cond, body, state)[1]


@functools.lru_cache(maxsize=None)
def _pallas_fill(rows: int, width: int, interpret: bool):
    """The 2-D kernel for one padded ``(rows, width)`` batch: f32
    ``caps``/``ws``/``live`` (mask) rows plus an ``(rows, 1)`` slots
    column; ``rows`` is a multiple of :data:`ROWS`."""
    jax, jnp, pl = _require()

    def rows_at(i):  # explicit i32: the f64 cell traces this under x64
        return i, jnp.int32(0)

    row_block = pl.BlockSpec((ROWS, width), rows_at)
    return pl.pallas_call(
        _kernel,
        grid=(rows // ROWS,),
        in_specs=[
            row_block,
            row_block,
            row_block,
            pl.BlockSpec((ROWS, 1), rows_at),
        ],
        out_specs=row_block,
        out_shape=jax.ShapeDtypeStruct((rows, width), jnp.float32),
        interpret=interpret,
        name="water_fill",
    )


def water_fill_rows(caps, ws, slots, live, *, interpret: bool = False):
    """The kernel over a ``(B, W)`` batch of f32 rows (``live`` an f32
    mask, ``slots`` a ``(B,)`` vector): pads ``B`` to a multiple of 8
    with dead rows, which allocate nothing, and strips them again."""
    _, jnp, _ = _require()
    b, width = caps.shape
    pad = -b % ROWS
    rows = ((0, pad), (0, 0))
    out = _pallas_fill(b + pad, width, interpret)(
        jnp.pad(caps, rows),
        jnp.pad(ws, rows),
        jnp.pad(live, rows),
        jnp.pad(slots.reshape(b, 1), rows),
    )
    return out[:b]


@functools.lru_cache(maxsize=2)
def _row_fill(interpret: bool):
    """One f32 row in, one row out; under ``vmap`` the whole batch goes
    to :func:`water_fill_rows` in one kernel call."""
    jax, jnp, _ = _require()

    @jax.custom_batching.custom_vmap
    def fill(caps, ws, slots, live):
        return water_fill_rows(
            caps[None], ws[None], slots.reshape(1), live[None],
            interpret=interpret,
        )[0]

    @fill.def_vmap
    def _fill_batched(axis_size, in_batched, caps, ws, slots, live):
        def rows(x, batched, shape):
            return x if batched else jnp.broadcast_to(x, shape)

        shape = (axis_size, caps.shape[-1])
        out = water_fill_rows(
            rows(caps, in_batched[0], shape),
            rows(ws, in_batched[1], shape),
            rows(slots, in_batched[2], (axis_size,)),
            rows(live, in_batched[3], shape),
            interpret=interpret,
        )
        return out, True

    return fill


def water_fill_pallas(caps, ws, slots, live, *, interpret: bool = False):
    """Pallas water-fill over one padded row (traced).

    Same signature and semantics as :func:`water_fill_lax`: ``live`` is a
    bool mask, the result has ``caps``' dtype.  The kernel itself runs in
    f32, so an f64 row is rounded at the call and widened at the return.
    """
    _, jnp, _ = _require()
    f32 = jnp.float32
    out = _row_fill(interpret)(
        jnp.asarray(caps, f32),
        jnp.asarray(ws, f32),
        jnp.asarray(slots, f32),
        jnp.where(live, 1.0, 0.0).astype(f32),
    )
    return out.astype(caps.dtype)


def water_fill_host(caps, ws, slots, live, *, impl: str = "pallas") -> np.ndarray:
    """numpy-in/numpy-out convenience wrapper (tests, notebooks), in f32.

    ``impl`` is ``"pallas"`` (compiled kernel), ``"pallas_interpret"``
    (the kernel in Pallas interpret mode) or ``"lax"`` (the reference).
    Every impl is evaluated in float32, the kernel's own precision, so
    the three are compared at one precision."""
    jax, jnp, _ = _require()
    caps = np.asarray(caps, np.float32)
    ws = np.asarray(ws, np.float32)
    live = np.asarray(live, bool)
    # Traced under x64, as the f64 cell traces the kernel.
    with jax.enable_x64(True):
        caps, ws, live = map(jnp.asarray, (caps, ws, live))
        slots = jnp.asarray(slots, jnp.float32)
        if impl in ("pallas", "pallas_interpret"):
            out = water_fill_pallas(caps, ws, slots, live,
                                    interpret=impl == "pallas_interpret")
        elif impl == "lax":
            out = water_fill_lax(caps, ws, slots, live)
        else:
            raise ValueError(f"unknown water-fill impl {impl!r}")
    return np.asarray(out)
