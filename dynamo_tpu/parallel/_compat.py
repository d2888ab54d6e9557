"""`jax.shard_map` with this repo's default: `check_vma` off unless asked for
(the step bodies mix manual and GSPMD-partitioned axes)."""

from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, **kw):
    kw.setdefault("check_vma", False)
    return _shard_map(f, **kw)
