"""Device mesh + sharding placement.

The TPU-native replacement for the reference's delegated parallelism
(SURVEY.md §2.6: the reference passes `--tp/--ep/--dp` flags into vLLM /
SGLang whose NCCL does the work; here the mesh and shardings ARE the
mechanism — XLA inserts the collectives over ICI).

Axes: `dp` (data/replica), `tp` (tensor), `sp` (sequence/context),
`ep` (expert — aliases onto tp's devices by default, the common TPU MoE
layout).  Pipeline stages are separate meshes handled in pipeline.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import ModelConfig, kv_cache_pspec, param_pspecs


@dataclass(frozen=True)
class ParallelConfig:
    dp: int = 1
    tp: int = 1
    # sequence parallelism: sp > 1 shards prefill over the prompt axis
    # (ring attention, parallel/sp_prefill.py).  Composes with tp: the
    # mesh becomes dp×sp×tp, heads sharded over tp within each sp shard.
    sp: int = 1
    # pipeline parallelism: pp > 1 stages the layer stack (params AND the
    # KV cache's layer axis) over a pp mesh axis (parallel/pp_engine.py).
    # Composes with dp and tp (each stage's params/KV shard over tp via
    # GSPMD inside the manual-over-pp program); sp stays exclusive.
    pp: int = 1

    @property
    def world(self) -> int:
        return self.dp * self.tp * self.sp * self.pp

    def validate(self, n_devices: int) -> None:
        if self.world > n_devices:
            raise ValueError(
                f"dp*tp*sp*pp = {self.world} > available devices {n_devices}"
            )
        if self.pp > 1 and self.sp > 1:
            raise ValueError(
                "pp composes with dp and tp (sp ring prefill within a "
                "pp stage is not supported — set sp = 1)"
            )


def make_mesh(pcfg: ParallelConfig, devices: Optional[Sequence] = None) -> Mesh:
    """The serving mesh over `devices` (default: all visible).  A mesh
    smaller than the device set takes the first `world` of them, so
    `--tp 2` runs on a four-chip host; pass an explicit subset to choose
    which."""
    devices = list(devices if devices is not None else jax.devices())
    pcfg.validate(len(devices))
    devices = devices[:pcfg.world]
    if pcfg.pp > 1:
        # tp innermost: a stage's tensor-parallel collectives ride the
        # tightest ICI links; pp ring shifts cross the next ring out
        arr = np.array(devices).reshape(pcfg.dp, pcfg.pp, pcfg.tp)
        return Mesh(arr, axis_names=("dp", "pp", "tp"))
    if pcfg.sp > 1:
        # sp meshes always carry a tp axis (size 1 when unused) so param
        # and KV specs are one convention everywhere
        arr = np.array(devices).reshape(pcfg.dp, pcfg.sp, pcfg.tp)
        return Mesh(arr, axis_names=("dp", "sp", "tp"))
    arr = np.array(devices).reshape(pcfg.dp, pcfg.tp)
    return Mesh(arr, axis_names=("dp", "tp"))


def shard_params(params, cfg: ModelConfig, mesh: Mesh):
    """Place a param pytree onto the mesh: megatron TP specs over the tp
    axis (int8-quantized {"q","s"} leaves shard q like the weight and
    the scale on the weight's output axis), replicated over dp and sp
    (those axes parallelize batch and sequence, not weights)."""
    from ..models.quantization import quantize_pspecs
    from .multihost import host_array_to_global

    specs = quantize_pspecs(params, param_pspecs(cfg))
    return jax.tree.map(
        lambda x, s: host_array_to_global(mesh, s, x), params, specs
    )


def shard_kv_cache(kv, mesh: Mesh, pool_axes=None):
    from .multihost import host_array_to_global

    spec = kv_cache_pspec(pool_axes=pool_axes)
    return jax.tree.map(
        lambda x, s: host_array_to_global(mesh, s, x), kv, spec
    )


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
