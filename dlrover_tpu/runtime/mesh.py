"""Device-mesh runtime: the communicator fabric all parallelism builds on.

TPU-native replacement for the reference's process-group runtime
(ref ``atorch/atorch/distributed/distributed.py:323-432``,
``create_parallel_group`` with named dims like ``[("tensor",4),("data",2)]``;
see SURVEY.md §2.5/§2.7).  Where the reference creates NCCL process groups per
named dim, we build one ``jax.sharding.Mesh`` whose named axes *are* the
parallel dims; XLA lowers collectives onto ICI (intra-slice) or DCN
(inter-slice) according to device placement, so "which wire a collective rides"
is decided by mesh layout, not by backend selection.

Axis layout policy (innermost = most bandwidth-hungry, rides ICI neighbors):

    data > fsdp > pipe > expert > seq > tensor

``data`` is the outermost axis so that when a job spans multiple slices the
pure-data-parallel gradient all-reduce is the only collective crossing DCN.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from dlrover_tpu.common.log import default_logger as logger

# Mesh axis names, outermost (DCN-friendly) to innermost (ICI-friendly).
DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"
SEQ_AXIS = "seq"
TENSOR_AXIS = "tensor"

MESH_AXES: Tuple[str, ...] = (
    DATA_AXIS,
    FSDP_AXIS,
    PIPE_AXIS,
    EXPERT_AXIS,
    SEQ_AXIS,
    TENSOR_AXIS,
)


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """Degrees of each parallelism dimension.

    The equivalent of the reference's ``create_parallel_group`` spec: one named
    size per dim.  ``data`` may be -1 meaning "use all remaining devices".
    ``dcn_data`` splits the data axis across slices (DCN) when a job spans
    more than one TPU slice.
    """

    data: int = -1
    fsdp: int = 1
    pipe: int = 1
    expert: int = 1
    seq: int = 1
    tensor: int = 1
    dcn_data: int = 1

    def sizes(self, num_devices: int) -> Dict[str, int]:
        fixed = self.fsdp * self.pipe * self.expert * self.seq * self.tensor
        data = self.data
        if data == -1:
            if num_devices % fixed:
                raise ValueError(
                    f"{num_devices} devices not divisible by non-data "
                    f"parallel degree {fixed}"
                )
            data = num_devices // fixed
        total = data * fixed
        if total != num_devices:
            raise ValueError(
                f"parallel degrees {self} multiply to {total}, "
                f"but {num_devices} devices are available"
            )
        return {
            DATA_AXIS: data,
            FSDP_AXIS: self.fsdp,
            PIPE_AXIS: self.pipe,
            EXPERT_AXIS: self.expert,
            SEQ_AXIS: self.seq,
            TENSOR_AXIS: self.tensor,
        }

    @property
    def model_parallel_degree(self) -> int:
        return self.fsdp * self.pipe * self.expert * self.seq * self.tensor


def build_mesh(
    config: ParallelConfig,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the job-wide device mesh.

    For multi-slice jobs (``dcn_data > 1``) we use a hybrid mesh so the data
    axis crosses DCN while every other axis stays inside a slice's ICI domain
    (the TPU analogue of the reference keeping NCCL rings inside NVLink
    islands).
    """
    devices = list(devices if devices is not None else jax.devices())
    sizes = config.sizes(len(devices))
    shape = [sizes[a] for a in MESH_AXES]
    if config.dcn_data > 1:
        if sizes[DATA_AXIS] % config.dcn_data:
            raise ValueError(
                f"data degree {sizes[DATA_AXIS]} not divisible by "
                f"dcn_data {config.dcn_data}"
            )
        ici_shape = list(shape)
        ici_shape[0] = sizes[DATA_AXIS] // config.dcn_data
        dcn_shape = [1] * len(MESH_AXES)
        dcn_shape[0] = config.dcn_data
        device_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices, allow_split_physical_axes=True
        )
    else:
        try:
            device_array = mesh_utils.create_device_mesh(
                shape, devices=devices, allow_split_physical_axes=True
            )
        except (ValueError, NotImplementedError) as e:
            # No topology-aware assignment for this shape: neighbours on
            # an axis may then not be ICI neighbours.
            logger.warning(
                "create_device_mesh failed (%s: %s); reshaping "
                "jax.devices() order into the mesh", type(e).__name__, e,
            )
            device_array = np.asarray(devices).reshape(shape)
    mesh = Mesh(device_array, MESH_AXES)
    logger.info(
        "built mesh %s over %d devices (platform=%s)",
        dict(zip(mesh.axis_names, mesh.devices.shape)),
        len(devices),
        devices[0].platform,
    )
    return mesh


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    device = device or jax.devices()[0]
    return Mesh(
        np.asarray([device]).reshape((1,) * len(MESH_AXES)), MESH_AXES
    )


def factor_devices(
    n: int, priority: Sequence[str] = (TENSOR_AXIS, PIPE_AXIS, DATA_AXIS)
) -> Dict[str, int]:
    """Greedily split ``n`` devices over axes in ``priority`` order by
    round-robin assigning the smallest remaining prime factor.  Axes not in
    ``priority`` stay at 1; include ``"data"`` in ``priority`` for it to
    receive a share."""
    sizes = {a: 1 for a in MESH_AXES}
    remaining = n
    idx = 0
    while remaining > 1:
        p = _smallest_prime_factor(remaining)
        sizes[priority[idx]] *= p
        remaining //= p
        idx = (idx + 1) % len(priority)
    return sizes


def _smallest_prime_factor(n: int) -> int:
    for p in range(2, int(math.isqrt(n)) + 1):
        if n % p == 0:
            return p
    return n


def current_mesh():
    """The ambient mesh (``jax.set_mesh``), or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def activate_mesh(mesh):
    """Context manager making ``mesh`` ambient for tracing and execution."""
    return jax.set_mesh(mesh)


def shard_map_compat(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication (vma) checking off."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def shard_local(fn, *, in_specs, out_specs):
    """``fn`` run on each device's local block of its operands.

    Every Pallas kernel reachable under a mesh goes through here: a Mosaic
    kernel is a custom call the SPMD partitioner refuses to split ("Mosaic
    kernels cannot be automatically partitioned"), so the kernel must see
    its local block and the partitioner must never meet it.  Interpret mode
    lowers a kernel to plain HLO that GSPMD does partition, which is how
    the CPU-mesh tests passed without this.  ``in_specs``/``out_specs``
    name the axes the operands may stay sharded on; an operand sharded on
    an axis its spec omits is gathered at the boundary.  With no ambient
    mesh, or a mesh of one device, ``fn`` is returned as it is and the
    lowered program is unchanged.
    """
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return fn
    return shard_map_compat(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs
    )


def mesh_axis_size(axis: str) -> int:
    """Size of a named axis on the ambient mesh; 1 when no mesh is set or
    the axis is absent.  Model code gates explicit collectives (Ulysses
    a2a, grouped-MoE dispatch) on this."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.axis_sizes)).get(axis, 1)


def local_device_count() -> int:
    return jax.local_device_count()


def slice_topology() -> Dict:
    """Discover the TPU slice topology visible to this process.

    The analogue of the reference's cluster quota/device discovery
    (ref ``dlrover/python/master/cluster/quota.py``).  Returns a dict usable
    by the master to reason about slice granularity.
    """
    devices = jax.devices()
    platform = devices[0].platform if devices else "none"
    info: Dict = {
        "platform": platform,
        "num_devices": len(devices),
        "num_local_devices": jax.local_device_count(),
        "num_hosts": jax.process_count(),
        "host_index": jax.process_index(),
    }
    if platform == "tpu" and hasattr(devices[0], "coords"):
        coords = np.asarray([d.coords for d in devices])
        info["topology"] = "x".join(
            str(int(coords[:, i].max()) + 1) for i in range(coords.shape[1])
        )
        if hasattr(devices[0], "slice_index"):
            info["num_slices"] = len({getattr(d, "slice_index", 0) for d in devices})
    return info
