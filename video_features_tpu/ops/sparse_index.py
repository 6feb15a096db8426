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

The selection is the mathematics, not an approximation of it: the
``topk``-th largest score of a row is found by ``lax.top_k`` and a key is
kept where its score lies above it, or on it among the lowest indices
``lax.top_k`` itself takes — so each row keeps exactly ``min(t + 1, topk)``
keys, ties and all. Queries run in blocks of ``block`` rows against the
keys up to the block's last, so a block's (rows, heads, keys) products are
the only wide temporary; a block that lies wholly before ``topk`` keeps its
whole triangle and scores nothing. The result leaves packed, 32 keys an
int32 word (``ops.attention.pack_keep``): what both causal paths read.

The scores have two forms, chosen by :func:`resolve_index` from the
platform, the shapes and the ambient precision (static at trace time; no
switch). XLA's — a block's (rows, heads, keys) products, rectified,
weighted and summed over the heads, as XLA fuses them — is the CPU path and
the oracle. On a TPU it is the Mosaic kernel ``index_scores`` of
``ops/pallas_index.py``, one call a window-layer over the scored blocks'
triangle, whose products never leave VMEM. The projections, the key norm,
rotary, the thresholds and the bits are XLA's in both.

Two departures from the published indexer, neither of which changes a
selection in exact arithmetic: its Hadamard rotation of ``q_I`` and ``k_I``
(orthogonal on both sides, so ``q_I · k_I`` is unchanged) is not applied,
and its scores are not stored in fp8 (float32, under the program's matmul
precision).
"""
from __future__ import annotations

from typing import List, Optional

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


def scored_blocks(s: int, topk: int, block: int = BLOCK) -> List[int]:
    """The query blocks of a window of ``s`` positions that are scored:
    those whose last row sees more than ``topk`` keys (block indices)."""
    block = min(block, s)
    return [b for b in range(s // block) if (b + 1) * block > topk]


def top_keys(scores: jax.Array, topk: int) -> jax.Array:
    """(rows, keys) scores (−inf where a key is not visible) → bool: the
    ``topk`` keys ``lax.top_k`` takes, as a mask — those above the
    ``topk``-th score, and of those on it the lowest indices, as far as
    ``lax.top_k`` took them."""
    values, index = lax.top_k(scores, topk)
    tau = values[:, -1:]
    last = jnp.max(jnp.where(values == tau, index, -1), axis=-1,
                   keepdims=True)
    col = lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    return (scores > tau) | ((scores == tau) & (col <= last))


def select_keys(x: jax.Array, c_q: jax.Array, wq: jax.Array, wk: jax.Array,
                k_gain: jax.Array, k_bias: jax.Array, w_weights: jax.Array,
                *, heads: int, dim: int, rope: int, topk: int, theta: float,
                block: int = BLOCK, kernel_passes: Optional[int] = None
                ) -> jax.Array:
    """One window's selection: (S, D) normed input ``x`` and (S, r_q) normed
    query latent ``c_q`` → (S, S/32) int32, the keys each query keeps as
    packed bits (module doc). ``wq`` is (r_q, heads · dim), ``wk`` (D,
    dim), ``w_weights`` (D, heads). ``kernel_passes`` (1 or 3 bf16 passes a
    product; None: XLA's form) scores through ``ops/pallas_index.py``,
    where :func:`resolve_index` says it applies."""
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
    table = None
    if kernel_passes is not None and scored:
        from video_features_tpu.ops.pallas_index import index_scores
        table = index_scores(q, k, w * dim ** -0.5, scored, block,
                             kernel_passes)
    packed = []
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
            keep = causal & top_keys(scores, topk)
        packed.append(pack_keep(jnp.pad(keep, ((0, 0), (0, s - keys)))))
    return jnp.concatenate(packed, axis=0)
