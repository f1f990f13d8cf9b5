"""Production mesh construction (assignment §Multi-pod dry-run step 1).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state.  Single pod = 16×16 chips (v5e pod, 2-D torus
ICI); multi-pod adds a leading ``pod`` axis (2 pods = 512 chips) for
inter-pod data parallelism over DCN.

The ``sfc_order`` flag applies the paper's space-filling-curve placement
insight to the *device order* used to build the mesh: logical mesh rows
walk the physical 2-D torus along a boustrophedon curve so that ring
collectives over the ``model`` axis are nearest-neighbour (see
core/hetero.py and DESIGN.md §3).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

from repro.core.sfc import curve_positions


def _mesh_kwargs(n):
    """Every mesh axis is Auto: GSPMD propagates shardings from the plan's
    constraints (the model code names no explicit axes)."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False, sfc_order: str = "") -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    avail = jax.devices()
    if len(avail) < n:
        raise RuntimeError(
            f"production mesh needs {n} devices, found {len(avail)} — run via "
            f"launch/dryrun.py (which forces 512 host devices) or on real hw")
    devices = np.asarray(avail[:n])
    if sfc_order:
        devices = devices[sfc_device_order(shape, sfc_order)]
    return jax.make_mesh(shape, axes, devices=list(devices),
                         **_mesh_kwargs(len(shape)))


def sfc_device_order(shape, curve: str = "boustrophedon") -> np.ndarray:
    """Permutation of flat device ids so the trailing 2-D (data, model) grid
    enumerates physical chips along ``curve`` on the 16×16 torus."""
    rows, cols = shape[-2], shape[-1]
    pods = int(np.prod(shape[:-2])) if len(shape) > 2 else 1
    pos = curve_positions(curve, cols, rows)        # (rows*cols, 2) (x, y)
    flat = pos[:, 1] * cols + pos[:, 0]             # physical id per curve step
    order = np.concatenate([p * rows * cols + flat for p in range(pods)])
    return order


def small_mesh(data: int = 2, model: int = 2) -> Mesh:
    """A ``(data, model)`` mesh over the first ``data * model`` devices of
    ``jax.devices()`` (chips, or forced host devices on the CPU)."""
    n = data * model
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:n],
                         **_mesh_kwargs(2))
