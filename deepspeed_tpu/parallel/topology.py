"""Device-mesh topology: the TPU-native analog of process groups.

The reference wires parallelism with explicit process groups
(deepspeed/utils/groups.py, runtime/pipe/topology.py:244
``PipeModelDataParallelTopology``). On TPU the same roles become named axes
of one ``jax.sharding.Mesh``; XLA derives the collectives from sharding
annotations, so "creating a group" reduces to "declaring an axis".

Axis roles (product of sizes == device count):

  pp    pipeline stages (collective-permute between stages; usually spans DCN)
  dp    pure data-parallel replicas (ZeRO-0 style; also the hpZ outer axis —
        params replicated here, optimizer state may shard over it)
  fsdp  ZeRO-sharded data parallel (params/grads/opt-state shard here)
  ep    expert parallel (MoE experts shard here; batch also shards here for
        non-MoE parts — reference expert_data_parallel groups
        utils/groups.py:304)
  sp    Ulysses/ring sequence parallel (activations shard on sequence dim)
  tp    tensor parallel (innermost: adjacent devices, fastest ICI hops)

Axis order puts tp innermost so TP collectives ride nearest-neighbour ICI,
and pp outermost so stage boundaries can sit across slices/DCN — the
ICI-vs-DCN analog of the reference's NVLink-vs-IB distinction (SURVEY §5).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.utils.logging import logger

# canonical axis order, outermost → innermost
MESH_AXES = ("pp", "dp", "fsdp", "ep", "sp", "tp")

# logical→mesh axis names for activations
BATCH_AXES = ("dp", "fsdp", "ep")  # batch dim shards over all data axes
SEQ_AXIS = "sp"


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Requested per-axis degrees. ``-1`` on at most one axis = absorb the
    remaining devices (like the reference letting dp = world/(tp*pp*ep),
    utils/groups.py)."""

    pp: int = 1
    dp: int = -1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def sizes(self, n_devices: int) -> Dict[str, int]:
        req = {a: getattr(self, a) for a in MESH_AXES}
        for a, v in req.items():
            if v != -1 and v < 1:
                raise ValueError(f"mesh axis '{a}' size must be >= 1 or -1, got {v}")
        free = [a for a, v in req.items() if v == -1]
        if len(free) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {free}")
        fixed = math.prod(v for v in req.values() if v != -1)
        if free:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"device count {n_devices} not divisible by fixed axes product {fixed}"
                )
            req[free[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh axes product {fixed} != device count {n_devices}"
            )
        return req


def build_mesh(
    topo: TopologyConfig | Dict[str, int] | None = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the framework's single device mesh.

    Devices are laid out so the innermost axes (tp, sp) map to adjacent
    devices. On real TPU slices ``jax.devices()`` order already follows the
    torus; ``mesh_utils.create_device_mesh`` improves ICI contiguity.
    """
    if devices is None:
        devices = jax.devices()
    if topo is None:
        topo = TopologyConfig()
    elif isinstance(topo, dict):
        unknown = set(topo) - set(MESH_AXES)
        if unknown:
            raise ValueError(
                f"unknown mesh axes {sorted(unknown)}; valid axes: {MESH_AXES}"
            )
        topo = TopologyConfig(**topo)
    sizes = topo.sizes(len(devices))
    shape = tuple(sizes[a] for a in MESH_AXES)
    # topology-aware on a TPU slice (ICI-contiguous inner axes), row-major
    # on the CPU simulator; a device set it cannot lay out is an error
    device_array = mesh_utils.create_device_mesh(
        shape, devices=list(devices), allow_split_physical_axes=True)
    mesh = Mesh(device_array, MESH_AXES)
    logger.info(
        "mesh: "
        + " × ".join(f"{a}={sizes[a]}" for a in MESH_AXES if sizes[a] > 1 or a == "dp")
    )
    return mesh


# ---------------------------------------------------------------------------
# group-size queries (reference: deepspeed/utils/groups.py getters)
# ---------------------------------------------------------------------------

_GLOBAL_MESH: Optional[Mesh] = None


def set_global_mesh(mesh: Mesh) -> None:
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_global_mesh() -> Mesh:
    if _GLOBAL_MESH is None:
        raise RuntimeError(
            "no global mesh set; call deepspeed_tpu.initialize() or "
            "topology.set_global_mesh(mesh) first"
        )
    return _GLOBAL_MESH


class use_mesh:
    """Scope the global mesh to one engine's mesh for the duration of a
    step/trace. Two engines in one process each set the global mesh at
    init; whichever initialized LAST would otherwise win inside the
    other's traces (constraints, vocab-parallel lookups), compiling
    against the wrong device assignment."""

    def __init__(self, mesh: Mesh):
        self._mesh = mesh

    def __enter__(self):
        global _GLOBAL_MESH
        self._prev = _GLOBAL_MESH
        _GLOBAL_MESH = self._mesh

    def __exit__(self, *a):
        global _GLOBAL_MESH
        _GLOBAL_MESH = self._prev
        return False


def _axis_size(mesh: Optional[Mesh], axis: str) -> int:
    mesh = mesh or get_global_mesh()
    return mesh.shape[axis]


def get_data_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    """Total data parallelism = dp × fsdp × ep (reference
    groups._get_data_parallel_world_size)."""
    mesh = mesh or get_global_mesh()
    return math.prod(mesh.shape[a] for a in BATCH_AXES)


def get_model_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh, "tp")


def get_tensor_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh, "tp")


def get_pipeline_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh, "pp")


def get_expert_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh, "ep")


def get_sequence_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh, "sp")


def get_fsdp_world_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh, "fsdp")


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a [batch, ...] host array: batch over all data axes,
    sequence dim (dim 1) over sp if present."""
    return NamedSharding(mesh, PartitionSpec(BATCH_AXES, SEQ_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
