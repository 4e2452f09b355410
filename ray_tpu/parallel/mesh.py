"""Device-mesh construction for TPU slices.

TPU-native replacement for the reference's process-group bootstrap
(reference: python/ray/train/torch/config.py:148-200 `_TorchBackend.on_start`
runs `dist.init_process_group`; python/ray/util/collective rendezvous at
util/collective/collective_group/nccl_collective_group.py:28). Here the
"process group" is a `jax.sharding.Mesh` over named axes; collectives are
emitted by XLA from pjit/shard_map and ride the ICI interconnect.

Axis convention (outer → inner, i.e. slower → faster varying over the
physical device order):

    ("data", "fsdp", "pipe", "expert", "seq", "tensor")

`tensor` is innermost so tensor-parallel collectives (the most
latency-sensitive: per-layer all-reduce/all-gather) map onto nearest-
neighbour ICI links; `data` is outermost so data-parallel gradient
reductions (once per step, bandwidth-bound, overlappable) take the long
paths / DCN when spanning slices. This mirrors how the scaling-book
recipe lays out meshes, not how the reference lays out NCCL ranks.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_PIPE = "pipe"
AXIS_EXPERT = "expert"
AXIS_SEQ = "seq"
AXIS_TENSOR = "tensor"

# Outer-to-inner physical order (see module docstring).
MESH_AXIS_ORDER: Tuple[str, ...] = (
    AXIS_DATA, AXIS_FSDP, AXIS_PIPE, AXIS_EXPERT, AXIS_SEQ, AXIS_TENSOR,
)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes of each parallelism axis. -1 on at most one axis means
    "absorb all remaining devices" (like torch's world-size inference,
    reference: train/torch/config.py:129-145 torchelastic env wiring —
    but resolved at mesh-build time instead of env-var time)."""

    data: int = -1
    fsdp: int = 1
    pipe: int = 1
    expert: int = 1
    seq: int = 1
    tensor: int = 1

    def axis_sizes(self) -> Dict[str, int]:
        return {
            AXIS_DATA: self.data,
            AXIS_FSDP: self.fsdp,
            AXIS_PIPE: self.pipe,
            AXIS_EXPERT: self.expert,
            AXIS_SEQ: self.seq,
            AXIS_TENSOR: self.tensor,
        }

    def resolve(self, n_devices: int) -> Dict[str, int]:
        """Resolve -1 axes against the device count; validate the product."""
        sizes = self.axis_sizes()
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {wild}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {fixed} ({sizes})")
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        return sizes


def make_mesh(config: Optional[MeshConfig] = None,
              devices: Optional[Sequence] = None,
              *,
              allow_split_physical_axes: bool = True):
    """Build a `jax.sharding.Mesh` with the standard axis names.

    On real TPU slices this delegates to `mesh_utils.create_device_mesh`,
    which arranges devices so that inner mesh axes ride contiguous ICI
    rings; on CPU (the chip-free test ladder, SURVEY.md §4) it is a
    simple reshape of the flat device list.
    """
    import jax
    import numpy as np

    if devices is None:
        devices = jax.devices()
    config = config or MeshConfig()
    sizes = config.resolve(len(devices))
    shape = tuple(sizes[a] for a in MESH_AXIS_ORDER)

    mesh_devices = arrange_devices(
        shape, devices,
        allow_split_physical_axes=allow_split_physical_axes)
    return jax.sharding.Mesh(mesh_devices, MESH_AXIS_ORDER)


def arrange_devices(shape: Tuple[int, ...], devices: Sequence, *,
                    allow_split_physical_axes: bool = True):
    """Arrange devices into `shape`: ICI-aware on TPU via
    mesh_utils.create_device_mesh (which raises for a shape the slice's
    topology cannot carry — there is no flat-order fallback, inner-axis
    collectives would silently cross slow links), plain reshape
    elsewhere. Shared by single-slice and per-slice (multislice) mesh
    construction."""
    import numpy as np

    if devices and getattr(devices[0], "platform", "cpu") == "tpu":
        from jax.experimental import mesh_utils
        return mesh_utils.create_device_mesh(
            shape, devices=list(devices),
            allow_split_physical_axes=allow_split_physical_axes)
    return np.asarray(devices).reshape(shape)


def get_abstract_mesh(config: MeshConfig, n_devices: int):
    """An `AbstractMesh` for shape-only work (compile-ahead, cost models)
    without touching devices."""
    import jax

    sizes = config.resolve(n_devices)
    shape = tuple(sizes[a] for a in MESH_AXIS_ORDER)
    return jax.sharding.AbstractMesh(shape, MESH_AXIS_ORDER)


