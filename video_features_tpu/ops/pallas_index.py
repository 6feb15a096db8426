"""The lightning indexer's scores as one Pallas TPU kernel: ``index_scores``.

``ops/sparse_index.py::select_keys``'s XLA form computes each query block's
(rows, heads, keys) products and sums them over the heads behind a ReLU.
How XLA lays that out is its own choice: in dots3-note.corpus's whole step
it fuses product, ReLU, weights and head sum into one pass a block (8.99 ms
a window-layer, 90 % of the three-pass ceiling); compiled alone at the same
shapes it writes the (256, 64, keys) float32 products to HBM and reads them
back (24.3 ms). Here the products of a (query block, key tile) pair live
only in VMEM whatever XLA decides, and what reaches HBM is the (rows, keys)
float32 score matrix that ``top_keys`` reads (one v5e chip, PERF.md §5):

    I[t, u] = Σ_j w[t, j] · ReLU(q[t, j] · k[u]),   −inf where u > t

* **one call a window-layer**: the grid is the scored blocks' triangle, one
  step a (query block, key tile) pair, the pairs' indices scalar-prefetched,
  the key tiles of a block in order, so a block's queries are fetched once;
  a key tile past a block's last row is never visited. Every step writes
  its tile whole; only a block's split queries carry from its first key
  tile to its others;
* **a step**: for each head ``j`` one product ``k_tile · q_jᵀ`` (keys on
  sublanes, the block's rows on lanes), rectified and scaled by the head's
  weights, a row over the lanes, into a float32 accumulator; then the
  causal mask, and the tile leaves transposed to (rows, keys);
* **the passes are made here**, as in ``ops/pallas_attention.py``: 3
  (ambient ``high``) lays a key as [hi lo hi] and a query as [hi; hi; lo]
  along the contracted axis, bf16 parts of the float32 value
  (``pallas_attention._split``), so ONE product sums hi·hi + lo·hi + hi·lo
  — XLA's own three passes; 1 (ambient ``default``) is the heads alone. A
  block's queries are split once, by its first key tile, into VMEM scratch
  that its other tiles read; a key tile is split by its step. Split by XLA
  before the call instead, the scores read 2.4e-3 from XLA's three passes
  on the chip — one pass's error, as if every remainder were zero (XLA may
  keep float32 where a rounding to bf16 and back was written: its excess
  precision) — and 2.2e-5 split here. The weights and accumulator are
  float32.

The caller's queries (S, heads, dim) enter heads-major with positions on
lanes, (heads, dim, S) float32 (the fusion that applies rotary writes them
so); keys (S, dim) float32; weights (heads, 1, S) float32, the score scale
folded in. CPU tests run the same body interpreted
(``pltpu.force_tpu_interpret_mode()``, ``tests/test_pallas_index.py``).
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from video_features_tpu.ops.pallas_attention import LANES, _ONE_PASS, _split

NAME = 'index_scores'
# keys a grid step scores: 9.15 ms a window-layer at the cell's shapes, 9.28
# at 512 (one v5e chip, PERF.md §6)
KEY_TILE = 256
# a block's float32 queries double-buffered and their packed bf16 parts
# (64 heads × 128 × 256: 16.8 + 12.6 MB at three passes in the cell), beside
# the key tile, the weights, the accumulator and a head's product
VMEM_LIMIT_BYTES = 64 * 2 ** 20
QUERY_VMEM_BYTES = 40 * 2 ** 20


def key_tile(s: int) -> int:
    """Keys a grid step scores for a window of ``s`` positions."""
    return min(KEY_TILE, s)


def query_vmem_bytes(heads: int, dim: int, block: int, passes: int) -> int:
    """VMEM a block's queries take: float32, double-buffered, and packed."""
    return heads * dim * block * (2 * 4 + passes * 2)


def _parts(x: jax.Array, passes: int, key: bool) -> list:
    """A float32 operand's bf16 parts for one product of ``passes`` passes:
    [hi lo hi] for a key, [hi hi lo] for a query, or [hi]."""
    if passes == 1:
        return [x.astype(jnp.bfloat16)]
    hi, lo = _split(x)
    return [p.astype(jnp.bfloat16) for p in ((hi, lo, hi) if key
                                             else (hi, hi, lo))]


def _kernel(qb_ref, kt_ref, q_ref, k_ref, w_ref, out_ref, packed_ref, *,
            heads: int, passes: int):
    i = pl.program_id(0)
    dim, block = q_ref.shape[1:]
    tile = k_ref.shape[0]

    # a block's first key tile splits its queries, once, into VMEM
    @pl.when(kt_ref[i] == 0)
    def _():
        def pack(j, carry):
            for n, part in enumerate(_parts(q_ref[j], passes, key=False)):
                packed_ref[j, n * dim:(n + 1) * dim, :] = part
            return carry
        lax.fori_loop(0, heads, pack, 0)

    k = jnp.concatenate(_parts(k_ref[...], passes, key=True), axis=1)
    # the heads unrolled: 9.15 ms a window-layer where a loop of 8 heads a
    # trip takes 9.75 (one v5e chip, PERF.md §6)
    acc = jnp.zeros((tile, block), jnp.float32)
    for j in range(heads):
        dots = jnp.dot(k, packed_ref[j], **_ONE_PASS)      # (keys, rows)
        acc = acc + w_ref[j] * jnp.maximum(dots, 0.0)
    key = kt_ref[i] * tile + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    row = qb_ref[i] * block + lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    out_ref[...] = jnp.where(key <= row, acc, -jnp.inf).T


def index_scores(q: jax.Array, k: jax.Array, w: jax.Array,
                 blocks: Sequence[int], block: int, passes: int
                 ) -> jax.Array:
    """The scores of the query blocks ``blocks`` (block indices, ascending
    and consecutive, ``block`` rows each) against every key up to each
    block's last row: ``q`` (S, heads, dim), ``k`` (S, dim), ``w`` (S,
    heads) float32 — the score scale already in ``w`` — → (len(blocks) ·
    block, S) float32, −inf above the diagonal; columns past a tile that
    holds a block's last row are not written. ``passes`` (1 or 3) bf16
    passes a product."""
    s, heads, dim = q.shape
    tile = key_tile(s)
    first = blocks[0]
    pairs = [(b, t) for b in blocks for t in range(((b + 1) * block - 1)
                                                   // tile + 1)]
    qb = jnp.array([b for b, _ in pairs], jnp.int32)
    kt = jnp.array([t for _, t in pairs], jnp.int32)
    f32 = jnp.float32
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(len(pairs),),
        in_specs=[
            pl.BlockSpec((heads, dim, block),
                         lambda i, qb, kt: (0, 0, qb[i])),
            pl.BlockSpec((tile, dim), lambda i, qb, kt: (kt[i], 0)),
            pl.BlockSpec((heads, 1, block), lambda i, qb, kt: (0, 0, qb[i])),
        ],
        out_specs=pl.BlockSpec((block, tile),
                               lambda i, qb, kt: (qb[i] - first, kt[i])),
        scratch_shapes=[pltpu.VMEM((heads, passes * dim, block),
                                   jnp.bfloat16)],
    )
    return pl.pallas_call(
        partial(_kernel, heads=heads, passes=passes),
        grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((len(blocks) * block, s), f32),
        compiler_params=pltpu.CompilerParams(
            # a block's key tiles read the parts its first one packed
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name=NAME,
    )(qb, kt, q.astype(f32).transpose(1, 2, 0), k.astype(f32),
      w.astype(f32).T[:, None, :])
