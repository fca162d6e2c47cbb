"""shard_map adapter: every call site imports this one spelling
(``check_rep`` is this repo's name for ``jax.shard_map``'s
``check_vma``), so an API change is a one-file fix."""
from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, mesh, in_specs, out_specs, check_rep=True):
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=check_rep)
