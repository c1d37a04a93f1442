"""The repo's one ``shard_map`` entry point.

Replication checking defaults to *off*: the custom-VJP collective pairs
in :mod:`repro.core.collectives` and the transport layer intentionally
produce device-varying intermediates that the checker rejects.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False, **kw):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma, **kw,
    )
