"""Sequence-parallel (ring) attention over a device mesh axis.

The reference has no sequence parallelism (SURVEY.md §2.3 — its long-video
story is sliding windows on one device). Here, token sequences that exceed
one chip's HBM — e.g. a whole video's worth of temporal tokens — shard over
the mesh's ``time`` axis, and attention runs as a KV ring over ICI
(:func:`video_features_tpu.ops.attention.ring_attention`).

``sequence_sharded_attention`` is the array-level entry: give it global
(B, S, H, D) arrays (or arrays already placed with a sequence sharding) and
a mesh; it shard_maps the ring kernel over the chosen axis.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from video_features_tpu.ops.attention import ring_attention
from video_features_tpu.parallel.mesh import TIME_AXIS


def sequence_sharding(mesh: Mesh, axis: str = TIME_AXIS) -> NamedSharding:
    """Sharding that splits the sequence dim of (B, S, H, D) over ``axis``."""
    return NamedSharding(mesh, P(None, axis, None, None))


def sequence_sharded_attention(mesh: Mesh, q: jax.Array, k: jax.Array,
                               v: jax.Array, axis: str = TIME_AXIS,
                               scale: Optional[float] = None) -> jax.Array:
    """Ring attention with q/k/v sequence-sharded over ``mesh[axis]``.

    The axis size must divide S. The result carries the same sequence
    sharding as the inputs; only ring-neighbor ppermute traffic crosses
    devices — no all-gather, so per-device memory stays O(S/n · S/n) for
    scores and O(S/n) for KV.
    """
    spec = P(None, axis, None, None)
    fn = shard_map(
        partial(ring_attention, axis_name=axis, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
