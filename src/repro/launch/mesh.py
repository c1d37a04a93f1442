"""Production mesh construction.

Built as functions (never module-level constants) so importing this module
never touches jax device state — only launch/dryrun.py sets the 512-device
XLA host-platform flag, and only in its own process.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.dist.spec import MeshCfg

SINGLE_POD = MeshCfg(tp=16, dp=16, pods=1)
MULTI_POD = MeshCfg(tp=16, dp=16, pods=2)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def mesh_cfg_for(*, multi_pod: bool = False) -> MeshCfg:
    return MULTI_POD if multi_pod else SINGLE_POD


def make_mesh_from_cfg(mesh_cfg: MeshCfg):
    """Arbitrary-geometry mesh (tests use small ones, e.g. 2x2x2)."""
    if mesh_cfg.tp == 1 and mesh_cfg.dshards == 1:
        return None
    return _auto_mesh(mesh_cfg.shape, mesh_cfg.axis_names)


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``. JAX defaults new meshes
    to ``Explicit`` axes (sharding in types), which rejects the gathers
    and scatters the steps run on replicated operands; every step here
    places its own collectives through ``shard_map`` and leaves the rest
    to the partitioner."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
