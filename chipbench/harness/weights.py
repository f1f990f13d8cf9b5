"""Weights made from the run's seed, on the device, in one jitted call.

The benchmark owns the weights: it makes them in its own layout (one array
per kind, layers stacked on the first axis), hands the program a tree that
points at the same buffers in the program's layout, and gives the plain
reference the same arrays in its own layout.  Nothing the program computes
reaches the reference.  The family file (``chipbench/models/<family>.py``)
gives the names, shapes and types, and the program's tree.

A configuration that states a ``mesh`` has its weights made over the
cell's chips, replicated on each, as the program's own launcher places
them: where weights go on a mesh is the program's choice, and the
benchmark makes no other.  The random bits do not depend on the layout,
so a seed gives the same values on one chip and on a mesh.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def mesh(conf: dict, devices: list) -> Optional[Mesh]:
    """The configuration's mesh over ``devices`` (axes in the order the
    file names them), or None where it states none."""
    axes = conf.get("mesh")
    if not axes:
        return None
    grid = np.asarray(devices).reshape(tuple(axes.values()))
    return Mesh(grid, tuple(axes), axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make(fam, m: dict, seed: int, mesh_: Optional[Mesh] = None) -> dict:
    """Every weight of the model, drawn from ``seed`` in one jitted call:
    on the default device, or replicated over ``mesh_``."""
    spec = fam.shapes(m)
    names = sorted(spec)

    def init(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            shape, dtype, std = spec[name]
            out[name] = (jax.random.normal(k, shape, dtype)
                         * jnp.asarray(std, dtype))
        return out

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    if mesh_ is None:
        return jax.jit(init)(key)
    return jax.jit(init, out_shardings=NamedSharding(
        mesh_, PartitionSpec()))(key)
