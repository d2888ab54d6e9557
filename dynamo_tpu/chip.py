"""Which device this process runs on — said, never assumed.

A measurement path that finds no chip fails; it does not fall back to the
CPU and write CPU times under a chip's name.  The planner's profiler sweep
calls `require_tpu` before its first device phase and puts
`device_identity()` into what it records.
"""

from __future__ import annotations

import sys
from typing import Dict


def device_identity() -> Dict[str, object]:
    """The device as JAX reports it: platform, kind and count."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def require_tpu(what: str) -> Dict[str, object]:
    """Exit non-zero unless the default backend is `tpu`; otherwise return
    the device identity for the caller's record."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(
            f"{what}: the JAX backend is {backend!r}, not 'tpu' — refusing "
            "to measure (a CPU time is never written under a device "
            "metric's name)")
    return device_identity()
