"""Learned sparse attention's selection of keys: the lightning indexer.

DeepSeek-V3.2's indexer (its sparse attention, "DSA"), as the full-attention
layers of a ``dots3_note`` trunk run it: for every query ``t`` it scores
every key ``u ≤ t`` with a small multi-head product of its own, and the
attention that follows may read only the ``topk`` best-scoring keys of each
query (all of them while ``t < topk``):

    q_I = c_q W_qI → (S, heads, dim)          c_q the normed query latent
    k_I = LayerNorm(x W_kI) → (S, dim)        gain and bias, eps 1e-6
          rotary (half-split) on dims 0…rope−1 of every q_I head and of k_I
    w   = x W_w · heads^-½ → (S, heads)
    I[t, u] = Σ_j w[t, j] · ReLU(q_I[t, j] · k_I[u] · dim^-½),   u ≤ t
    keep[t, u] = u ≤ t ∧ u among the topk largest I[t, ·]

The selection is the mathematics, not an approximation of it: a row's
threshold is its ``topk``-th largest score ``tau`` and ``last``, the
highest index kept on it, and a key is kept where its score lies above
``tau``, or on it at an index up to ``last`` — the lowest indices first, as
``lax.top_k`` takes them — so each row keeps exactly ``min(t + 1, topk)``
keys, ties and all (−0.0 and +0.0 one tie). Queries run in blocks of
``block`` rows against the keys up to the block's last, so a block's (rows,
heads, keys) products are the only wide temporary; a block that lies wholly
before ``topk`` keeps its whole triangle and scores nothing. The result
leaves packed, 32 keys an int32 word (``ops.attention.pack_keep``): what
both causal paths read.

The scores and the thresholds each have two forms, chosen from the
platform, the shapes and the ambient precision (static at trace time; no
switch). The scores, by :func:`resolve_index`: XLA's — a block's (rows,
heads, keys) products, rectified, weighted and summed over the heads, as
XLA fuses them — is the CPU path and the oracle; on a TPU the Mosaic kernel
``index_scores`` of ``ops/pallas_index.py``, one call a window-layer over
the scored blocks' triangle, whose products never leave VMEM. The
thresholds, by :func:`resolve_threshold`, where that kernel's table is
there to read: XLA's — ``lax.top_k``, a full sort of each row
(:func:`thresholds`) — is the CPU path and the oracle; on a TPU the Mosaic
kernel ``topk_threshold`` of the same module, one call a window-layer that
bisects each row's keys on their bits in VMEM. The projections, the key
norm, rotary and the bits from the thresholds (:func:`kept`) are XLA's in
all of them. Each form also counts the rows whose threshold the tie rule
settled: more keys on ``tau`` than the row keeps (``topk_ties``).

Two departures from the published indexer, neither of which changes a
selection in exact arithmetic: its Hadamard rotation of ``q_I`` and ``k_I``
(orthogonal on both sides, so ``q_I · k_I`` is unchanged) is not applied,
and its scores are not stored in fp8 (float32, under the program's matmul
precision).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from video_features_tpu.ops.attention import pack_keep, rotary_half

# the indexer's key LayerNorm's epsilon (DeepSeek-V3.2's; no config key)
LN_EPS = 1e-6
# query rows scored at a time: in XLA's form (256, 64 heads, 8,192 keys)
# float32 is the widest temporary, 537 MB
BLOCK = 256


def layer_norm(x: jax.Array, gain: jax.Array, bias: jax.Array,
               eps: float = LN_EPS) -> jax.Array:
    mean = x.mean(axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gain + bias


def resolve_index(platform: str, s: int, heads: int, dim: int, block: int,
                  precision: Optional[str]) -> str:
    """Which form :func:`select_keys` scores with for windows of ``s``
    positions, ``heads`` heads of ``dim`` and query blocks of ``block`` rows
    on ``platform`` under the ambient matmul ``precision``: 'kernel'
    (``ops/pallas_index.py``'s ``index_scores``) or 'xla'.

    The kernel applies on a TPU where the blocks and key tiles are whole
    (``s`` a multiple of both), a block's rows and a head's width are whole
    128-lane groups, a block's packed queries fit the kernel's VMEM, and the
    precision is one the kernel has a lane for (``KERNEL_PASSES``).
    Anywhere else — the CPU, where it would run interpreted; ragged shapes;
    'highest' — XLA's form runs, which is also the oracle the kernel is
    tested against."""
    from video_features_tpu.ops import pallas_index as kernel
    from video_features_tpu.ops.attention import KERNEL_PASSES
    if platform != 'tpu' or precision not in KERNEL_PASSES:
        return 'xla'
    block = min(block, s)
    if (s % block or s % kernel.key_tile(s) or block % kernel.LANES
            or dim % kernel.LANES):
        return 'xla'
    if kernel.query_vmem_bytes(heads, dim, block,
                               KERNEL_PASSES[precision]) \
            > kernel.QUERY_VMEM_BYTES:
        return 'xla'
    return 'kernel'


def resolve_threshold(platform: str, s: int, block: int) -> str:
    """Which form finds the thresholds of the scored rows of a window of
    ``s`` positions in query blocks of ``block`` rows, where
    ``index_scores``' table holds their scores: 'kernel'
    (``ops/pallas_index.py``'s ``topk_threshold``) or 'xla'.

    The kernel applies on a TPU where the blocks are whole (``s`` a
    multiple of ``block``), a block's rows and each of its key tiles are
    whole 128-lane groups (a key tile is ``block`` wide), and a block's
    scores and keys fit the kernel's VMEM. It makes no product, so the
    precision does not enter. Anywhere else ``lax.top_k`` runs, which is
    also the oracle the kernel is tested against."""
    from video_features_tpu.ops import pallas_index as kernel
    if platform != 'tpu':
        return 'xla'
    block = min(block, s)
    if s % block or block % kernel.LANES:
        return 'xla'
    if kernel.threshold_vmem_bytes(s, block) > kernel.THRESHOLD_VMEM_BYTES:
        return 'xla'
    return 'kernel'


def scored_blocks(s: int, topk: int, block: int = BLOCK) -> List[int]:
    """The query blocks of a window of ``s`` positions that are scored:
    those whose last row sees more than ``topk`` keys (block indices)."""
    block = min(block, s)
    return [b for b in range(s // block) if (b + 1) * block > topk]


def thresholds(scores: jax.Array, topk: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """XLA's form: (rows, keys) scores (−inf where a key is not visible) →
    ``(tau, last, tied)``: (rows, 1) the ``topk``-th largest score, (rows,
    1) the highest index ``lax.top_k`` took on it, (rows,) the rows with
    more visible keys on ``tau`` than it took. −0.0 is made +0.0 first:
    ``lax.top_k`` orders −0.0 below +0.0, the comparisons of :func:`kept`
    do not."""
    scores = jnp.where(scores == 0, 0.0, scores)
    values, index = lax.top_k(scores, topk)
    tau = values[:, -1:]
    on = values == tau
    last = jnp.max(jnp.where(on, index, -1), axis=-1, keepdims=True)
    tied = (tau[:, 0] > -jnp.inf) & (
        jnp.sum(scores == tau, axis=-1) > jnp.sum(on, axis=-1))
    return tau, last, tied


def kept(scores: jax.Array, tau: jax.Array, last: jax.Array) -> jax.Array:
    """(rows, keys) bool: the keys above a row's threshold ``tau``, and
    those on it up to index ``last``."""
    col = lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    return (scores > tau) | ((scores == tau) & (col <= last))


def top_keys(scores: jax.Array, topk: int) -> jax.Array:
    """(rows, keys) scores (−inf where a key is not visible) → bool: the
    ``topk`` keys ``lax.top_k`` takes, as a mask — those above the
    ``topk``-th score, and of those on it the lowest indices, as far as
    ``lax.top_k`` took them."""
    tau, last, _ = thresholds(scores, topk)
    return kept(scores, tau, last)


def select_keys(x: jax.Array, c_q: jax.Array, wq: jax.Array, wk: jax.Array,
                k_gain: jax.Array, k_bias: jax.Array, w_weights: jax.Array,
                *, heads: int, dim: int, rope: int, topk: int, theta: float,
                block: int = BLOCK, kernel_passes: Optional[int] = None,
                threshold_kernel: bool = False
                ) -> Tuple[jax.Array, jax.Array]:
    """One window's selection: (S, D) normed input ``x`` and (S, r_q) normed
    query latent ``c_q`` → ((S, S/32) int32, the keys each query keeps as
    packed bits (module doc); int32, the scored rows the tie rule settled).
    ``wq`` is (r_q, heads · dim), ``wk`` (D, dim), ``w_weights`` (D,
    heads). ``kernel_passes`` (1 or 3 bf16 passes a product; None: XLA's
    form) scores through ``ops/pallas_index.py``, where
    :func:`resolve_index` says it applies; ``threshold_kernel`` then finds
    the thresholds there too, where :func:`resolve_threshold` says so."""
    s = x.shape[0]
    block = min(block, s)
    if s % block:
        raise ValueError(f'the indexer scores {s} queries in blocks of '
                         f'{block}: no whole number of them')
    positions = jnp.arange(s)
    q = jnp.dot(c_q, wq).reshape(s, heads, dim)
    q = jnp.concatenate(
        [rotary_half(q[..., :rope], positions, theta), q[..., rope:]], -1)
    k = layer_norm(jnp.dot(x, wk), k_gain, k_bias)
    k = jnp.concatenate(
        [rotary_half(k[:, None, :rope], positions, theta)[:, 0],
         k[:, rope:]], -1)
    w = jnp.dot(x, w_weights) * heads ** -0.5
    scored = scored_blocks(s, topk, block)
    table = found = None
    if kernel_passes is not None and scored:
        from video_features_tpu.ops import pallas_index
        table = pallas_index.index_scores(q, k, w * dim ** -0.5, scored,
                                          block, kernel_passes)
        if threshold_kernel:
            found = pallas_index.topk_threshold(table, scored, block, topk)
    packed, tied = [], jnp.int32(0)
    for q0 in range(0, s, block):
        keys = q0 + block                       # the block's last row sees
        row = q0 + lax.broadcasted_iota(jnp.int32, (block, keys), 0)
        causal = lax.broadcasted_iota(jnp.int32, (block, keys), 1) <= row
        if keys <= topk:
            keep = causal
        else:
            if table is None:
                dots = jnp.einsum('tjd,ud->tju', q[q0:keys], k[:keys]) \
                    * dim ** -0.5
                scores = jnp.where(causal, jnp.einsum(
                    'tju,tj->tu', jax.nn.relu(dots), w[q0:keys]), -jnp.inf)
            else:
                r = q0 - scored[0] * block
                scores = table[r:r + block, :keys]
            if found is None:
                tau, last, ties = thresholds(scores, topk)
                tied = tied + jnp.sum(ties, dtype=jnp.int32)
            else:
                tau, last = (a[r:r + block] for a in found[:2])
            keep = causal & kept(scores, tau, last)
        packed.append(pack_keep(jnp.pad(keep, ((0, 0), (0, s - keys)))))
    if found is not None:
        tied = jnp.sum(found[2], dtype=jnp.int32)
    return jnp.concatenate(packed, axis=0), tied
