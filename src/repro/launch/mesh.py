"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax device
state — the dry-run sets XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.config import MULTI_POD_MESH, SINGLE_POD_MESH, MeshConfig


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: sharding is steered by
    ``with_sharding_constraint`` (``distributed/sharding.py``), which only
    names Auto axes, not by the Explicit axes ``make_mesh`` now defaults to."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh(cfg: MeshConfig):
    return auto_mesh(cfg.shape, cfg.axis_names)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices are available."""
    return auto_mesh((data, model), ("data", "model"))


def mesh_config(multi_pod: bool) -> MeshConfig:
    return MULTI_POD_MESH if multi_pod else SINGLE_POD_MESH
