"""``shard_map`` with the replication-check flag under its current name.

The installed jax exposes ``jax.shard_map`` with a ``check_vma`` keyword;
this wrapper keeps call sites to one import and makes the flag optional.
"""
from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool | None = None):
    kwargs = {"mesh": mesh, "in_specs": in_specs, "out_specs": out_specs}
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return _shard_map(f, **kwargs)
