"""Device-mesh construction for sharded extraction.

The reference scales by launching N independent single-GPU processes over a
shared filesystem (reference README.md:70-84, utils/utils.py:151-176 — the
shuffled work list IS its distribution layer). The TPU-native design keeps
that shared-nothing elasticity contract *across hosts* (see
:mod:`.worklist`) and adds *in-graph* parallelism within a slice:

  * ``data`` axis — data parallelism over stack windows / frame batches
    (the reference's per-process parallelism, moved inside one XLA program);
  * ``time`` axis — sequence parallelism over temporal flow pairs: a stack
    of S+1 frames yields S independent RAFT pairs, and long videos yield
    many stacks, so the temporal dimension shards cleanly with no halo
    (SURVEY.md §5.7: temporal tiling is the long-context analog here).

Collectives ride ICI inside the mesh; DCN/filesystem only carries the
work-list and the output files.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = 'data'
TIME_AXIS = 'time'


def factor_mesh_shape(n: int, time_parallel: Optional[int] = None) -> Tuple[int, int]:
    """Split ``n`` devices into (data, time) axis sizes.

    Defaults to the largest power-of-two time axis ≤ 2 — flow pairs within a
    stack are plentiful (stack_size ≥ 10), but data parallelism over stacks
    has better arithmetic intensity per shard, so it gets the larger axis.
    """
    if time_parallel is None:
        time_parallel = 2 if n % 2 == 0 and n > 1 else 1
    if n % time_parallel != 0:
        raise ValueError(f'{n} devices do not factor into time={time_parallel}')
    return n // time_parallel, time_parallel


def make_mesh(n_devices: Optional[int] = None,
              time_parallel: Optional[int] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 2-D (data, time) mesh over the available (or given) devices.

    ``n_devices=0`` auto-detects: the mesh spans EVERY available (or
    given) device — the ``mesh_devices=0`` config spelling for "use the
    whole slice". An over-ask raises here with the device counts named,
    instead of surfacing later as an opaque XLA placement error.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None and n_devices != 0:
        if n_devices > len(devices):
            raise ValueError(
                f'requested {n_devices} devices, have {len(devices)}')
        devices = devices[:n_devices]
    shape = factor_mesh_shape(len(devices), time_parallel)
    grid = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(grid, (DATA_AXIS, TIME_AXIS))


def round_batch_to_data_axis(batch_size: int, mesh: Mesh) -> int:
    """Smallest multiple of the mesh's data-axis size ≥ ``batch_size`` —
    the global batch an in-graph data-parallel extractor compiles for."""
    d = mesh.shape[DATA_AXIS]
    return -(-batch_size // d) * d


def plan_device_batch(capacity: int, mesh: Mesh) -> int:
    """Global packed batch for a data-parallel mesh: ``capacity`` window
    slots PER device shard (the per-device batch the family's step was
    tuned for), so the packer plans ``capacity × ndev`` slots and every
    device runs at its single-chip batch shape. Raises a clear error —
    not a downstream XLA shape error — when the plan can't fill a shard.
    """
    ndev = mesh.shape[DATA_AXIS]
    capacity = int(capacity)
    if capacity < 1:
        raise ValueError(
            f'mesh-sharded packed batch planning needs capacity >= 1 per '
            f'device shard (got capacity={capacity} over {ndev} '
            f'data-parallel devices): capacity × ndev is the global device '
            f'batch — raise batch_size or lower mesh_devices')
    return capacity * ndev


def shard_error(batch: int, mesh: Mesh) -> Optional[str]:
    """Why a GLOBAL batch of ``batch`` rows cannot shard over the mesh's
    data axis, or None when it can. The non-raising form of
    :func:`require_shardable` — the vft-programs shardability rule
    (``analysis/programs.py``) turns the message into a finding instead
    of an exception."""
    ndev = mesh.shape[DATA_AXIS]
    if batch % ndev != 0 or batch // ndev < 1:
        return (
            f'packed batch {batch} cannot shard over {ndev} data-parallel '
            f'devices: the global batch must be a positive multiple of the '
            f'device count (capacity × ndev planning — see '
            f'plan_device_batch)')
    return None


def require_shardable(batch: int, mesh: Mesh) -> int:
    """Validate that a GLOBAL batch splits evenly over the data axis,
    raising a named error instead of letting ``device_put`` fail with an
    XLA sharding/shape error. Returns the per-shard capacity."""
    err = shard_error(batch, mesh)
    if err is not None:
        raise ValueError(err)
    return batch // mesh.shape[DATA_AXIS]


def replicated(mesh: Mesh) -> NamedSharding:
    """Sharding for params: one full copy per device (models are ≤100s MB —
    SURVEY.md §2.3: tensor parallelism is not needed, replicate per chip)."""
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis sharding over the data axis (stack windows / frames)."""
    return NamedSharding(mesh, P(DATA_AXIS))


def pair_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis sharding over BOTH axes — each device gets a contiguous
    run of rows; no halo exchange is needed because all-pairs correlation
    is local to a pair. Used for the (B·S, …) flow-pair/cnet tensors (even
    split) and the B·(S+1) unique-frames tensor feeding fnet, where the +1
    halo leaves the last shards padded by ≤1 frame (see
    raft.forward_stack_pairs)."""
    return NamedSharding(mesh, P((DATA_AXIS, TIME_AXIS)))
