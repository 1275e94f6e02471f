"""Compile the device path for a described TPU v5e chip (no chip needed).

The TPU compiler is installed with jax and compiles for a topology that
is described, not attached.  These compiles catch what interpret mode
cannot — Mosaic's block-shape and dtype rules for the Pallas water-fill,
and the f64 cell programs XLA must emulate — before any chip time is
spent.  The topology is built in a module fixture (never at import),
and all these compiles stay in this one file, so under several test
workers only the worker given this file loads the TPU library.
"""

import os

import numpy as np
import pytest

pytest.importorskip("jax")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.accel.cell_step import _PARAM_KEYS, _chunk_runner  # noqa: E402
from repro.accel.waterfill_pallas import water_fill_pallas  # noqa: E402

BATCH = 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("width", (32, 128, 1024))
def test_pallas_waterfill_compiles_for_v5e(one_chip, width):
    """The cell step's call pattern: one row per cell under ``vmap``,
    which the kernel's batching rule turns into one 2-D kernel call."""
    fill = jax.jit(jax.vmap(
        lambda caps, ws, slots, live: water_fill_pallas(
            caps, ws, slots, live > 0.5
        )
    ))
    rows = _spec((BATCH, width), jnp.float32, one_chip)
    compiled = fill.lower(
        rows, rows, _spec((BATCH,), jnp.float32, one_chip), rows
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_chunk_runner(sharding, width: int, impl: str) -> str:
    with jax.enable_x64(True):
        params = {
            k: _spec((BATCH, width), jnp.float64, sharding)
            for k in _PARAM_KEYS
        }
        for k in ("map_slots", "red_slots", "dt_cap", "eps"):
            params[k] = _spec((BATCH,), jnp.float64, sharding)
        params["policy"] = _spec((BATCH,), jnp.int32, sharding)
        row = _spec((BATCH, width), jnp.float64, sharding)
        state = {
            "t": _spec((BATCH,), jnp.float64, sharding),
            "m_rem": row, "r_rem": row, "vm_rem": row, "vr_rem": row,
            "comp": row,
            "done": _spec((BATCH, width), np.bool_, sharding),
            "steps": _spec((BATCH,), jnp.int64, sharding),
            "closes": _spec((BATCH,), jnp.int64, sharding),
        }
        compiled = _chunk_runner(width, 512, impl).lower(
            params, state
        ).compile()
    return compiled.as_text()


@pytest.mark.parametrize("impl", ("lax", "none", "pallas"))
def test_chunk_runner_compiles_for_v5e_f64(one_chip, impl):
    """The whole f64 scan chunk at the paper cell's padded width (100
    jobs -> 128) and the default chunk length; only ``pallas`` carries
    the kernel (``none`` dead-codes the water-fill)."""
    text = _compile_chunk_runner(one_chip, 128, impl)
    assert ("tpu_custom_call" in text) == (impl == "pallas")


@pytest.mark.parametrize("impl", ("lax", "none"))
def test_chunk_runner_compiles_for_v5e_f64_at_width_1024(one_chip, impl):
    """FB-2009's 600 jobs pad to 1024: the W^2 allocator and the ``lax``
    water-fill (the hfsp group) or none (the plain group) at that width."""
    text = _compile_chunk_runner(one_chip, 1024, impl)
    assert "tpu_custom_call" not in text
