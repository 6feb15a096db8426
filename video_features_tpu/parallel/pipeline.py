"""Sharded extraction pipeline: the fused two-stream step over a device mesh.

Where the reference runs one python loop per GPU process (reference
main.py:47-48) and scales by launching more processes, this module compiles
ONE program over a (data, time) mesh:

  * stack windows shard over ``data`` (in-graph data parallelism);
  * RAFT flow pairs additionally spread over ``time`` (sequence parallelism
    over the temporal axis — the pairs are independent, so XLA inserts only
    the reshard collectives at the sub-graph boundary, and they ride ICI);
  * params are replicated (SURVEY.md §2.3 — nets are small; TP buys nothing).

Outputs land fully replicated so the host can write `.npy` files under the
same idempotent-output contract the reference uses for elasticity.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import Mesh

from video_features_tpu.extract.i3d import fused_two_stream_step
from video_features_tpu.parallel.mesh import (
    batch_sharding, pair_sharding, replicated,
)


def build_sharded_two_stream_step(mesh: Mesh,
                                  streams: Tuple[str, ...] = ('rgb', 'flow'),
                                  donate_stacks: bool = False,
                                  pins=None, raft_iters=None):
    """jit-compiled ``step(params, stacks, pads, crop_size=…,
    resize_to=…)`` over ``mesh``.

    ``stacks`` is (B, stack+1, H, W, 3) with B divisible by the data-axis
    size; ``pads`` is the static (top, bottom, left, right) /8 padding tuple
    from raft.pad_to_multiple; ``resize_to`` (static; None = off) runs the
    bit-exact in-graph PIL resize (device_resize) before everything else —
    per-sample work that composes with the data sharding, though each
    distinct (pads, crop_size, resize_to) triple is its own executable.
    Returns {stream: (B, 1024)} replicated.

    pjit rejects kwargs when in_shardings is given, so the static args are
    positional here (argnums 2/3/4) and ``streams`` is baked per-build.
    """
    def constrain_pairs(t: jax.Array) -> jax.Array:
        return jax.lax.with_sharding_constraint(t, pair_sharding(mesh))

    # the mesh's devices say where the program runs — drive the RAFT
    # corr-lookup dispatch from them, not the process default backend
    platform = mesh.devices.flat[0].platform

    # named like the single-device step (ExtractI3D.step_name), so the
    # device trace shows jit_i3d_two_stream_step on either path
    def i3d_two_stream_step(params, stacks, pads, crop_size, resize_to):
        kw = {} if raft_iters is None else {'raft_iters': raft_iters}
        return fused_two_stream_step(params, stacks, pads, streams,
                                     constrain_pairs=constrain_pairs,
                                     crop_size=crop_size, platform=platform,
                                     pins=pins, resize_to=resize_to, **kw)

    jitted = jax.jit(
        i3d_two_stream_step,
        static_argnums=(2, 3, 4),
        in_shardings=(replicated(mesh), batch_sharding(mesh)),
        out_shardings=replicated(mesh),
        donate_argnums=(1,) if donate_stacks else (),
    )

    def call(params, stacks, pads, crop_size=224, resize_to=None):
        # resize_to: the in-graph bit-exact PIL resize (device_resize) —
        # per-sample work, so it composes with the data sharding with no
        # extra collectives
        return jitted(params, stacks, pads, crop_size, resize_to)

    return call


def put_replicated(mesh: Mesh, params):
    """Place a params pytree on every device of the mesh."""
    return jax.device_put(params, replicated(mesh))


def put_batch(mesh: Mesh, batch):
    """Shard a host batch over the data axis of the mesh."""
    return jax.device_put(batch, batch_sharding(mesh))


def setup_data_parallel(device: str, batch_size: int, params):
    """One-stop in-graph DP setup for a batch-sharding extractor.

    Returns ``(mesh, global_batch, replicated_params, put_batch_fn)``: a
    data-only mesh over this host's local devices of ``device``'s platform,
    the batch size rounded up to fill the data axis, the params placed on
    every device, and a batch-placement callable. Feeding jit functions
    these shardings makes XLA compile one pjit program — no per-extractor
    sharding code needed.
    """
    from functools import partial

    from video_features_tpu.parallel.mesh import (
        make_mesh, round_batch_to_data_axis,
    )
    from video_features_tpu.utils.device import jax_devices_all

    mesh = make_mesh(devices=jax_devices_all(device), time_parallel=1)
    return (mesh, round_batch_to_data_axis(batch_size, mesh),
            put_replicated(mesh, params), partial(put_batch, mesh))
