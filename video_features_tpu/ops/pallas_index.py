"""The lightning indexer's scores and the thresholds of their top-k as two
Pallas TPU kernels: ``index_scores`` and ``topk_threshold``.

``ops/sparse_index.py::select_keys``'s XLA form computes each query block's
(rows, heads, keys) products and sums them over the heads behind a ReLU.
How XLA lays that out is its own choice: in dots3-note.corpus's whole step
it fuses product, ReLU, weights and head sum into one pass a block (8.99 ms
a window-layer, 90 % of the three-pass ceiling); compiled alone at the same
shapes it writes the (256, 64, keys) float32 products to HBM and reads them
back (24.3 ms). Here the products of a (query block, key tile) pair live
only in VMEM whatever XLA decides, and what reaches HBM is the (rows, keys)
float32 score matrix that the thresholds read (one v5e chip, PERF.md §5):

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
folded in.

``topk_threshold`` reads that matrix and gives each row what
``sparse_index.thresholds`` takes from ``lax.top_k``'s full sort — ``tau``,
the ``topk``-th largest score, and ``last``, the highest index kept on it —
with no sort; XLA then compares and packs the bits as before:

* **one call a window-layer**, one grid step a scored block: its scores
  are copied into VMEM a (block, block) tile at a time up to its last row
  (the columns past it are never written, and never read), the next
  block's copy started before this one's count; each score becomes an
  int32 key in float order (−0.0 as +0.0), the diagonal tile's keys past
  a row the lowest key;
* **a group of 32 rows at a time** bisects on the keys' bits: 32 rounds
  from the sign bit down, each one count a row of the keys ≥ the
  candidate, kept where that count is still ≥ ``topk``; then one pass
  counts the keys above ``tau`` and on it. Where some row of the group
  has more keys on ``tau`` than the ``room`` left (the tie rule settles
  it), a second bisection over the column index finds the room-th one,
  lowest first, as ``lax.top_k`` takes them; where none has, it is
  skipped and ``last`` is the last column. A row that sees no more than
  ``topk`` keys keeps them all (``tau`` −inf).

No product, so no pass count: the result is exact under every precision.
CPU tests run both bodies interpreted (``pltpu.force_tpu_interpret_mode()``,
``tests/test_pallas_index.py``, ``tests/test_topk_threshold.py``).
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
# index_scores: a block's float32 queries double-buffered and their packed
# bf16 parts (64 heads × 128 × 256: 16.8 + 12.6 MB at three passes in the
# cell), beside the key tile, the weights, the accumulator and a head's
# product; topk_threshold: a block's scores twice and its keys (25.2 MB in
# the cell), beside its output block and a row group's counts
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


# -- the threshold of each row's top-k ---------------------------------------

THRESHOLD_NAME = 'topk_threshold'
# rows the bisection counts at a time: a group's counts stay in vregs
GROUP = 32
# a block's (rows, keys) scores twice over (the one counted, the next
# copying) and as order keys
THRESHOLD_VMEM_BYTES = 48 * 2 ** 20
_INT_MIN = -2 ** 31


def threshold_vmem_bytes(s: int, block: int) -> int:
    """VMEM a block of ``block`` rows × ``s`` keys takes: its float32
    scores twice, its int32 keys once."""
    return 3 * block * s * 4


def _order_key(bits: jax.Array) -> jax.Array:
    """float32 bits (as int32) → int32 keys in the floats' order: a negative
    float's bits below the sign run backwards; −0.0 takes +0.0's key, so a
    tie of keys is a tie of floats."""
    key = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    return jnp.where(key == -1, 0, key)


def _threshold_kernel(table_ref, out_ref, buf, keys_ref, sem, *,
                      first: int, topk: int, bits: int):
    i = pl.program_id(0)
    slot = i % 2
    block, s = buf.shape[1:]

    def copies(step, into, start: bool):
        """The DMAs of scored block ``step``'s keys, a (block, block) tile
        at a time up to its last row: columns past it are not read."""
        rows = pl.ds(pl.multiple_of(step * block, block), block)

        def tile(c, carry):
            cols = pl.ds(pl.multiple_of(c * block, block), block)
            copy = pltpu.make_async_copy(table_ref.at[rows, cols],
                                         buf.at[into, :, cols], sem.at[into])
            if start:
                copy.start()
            else:
                copy.wait()
            return carry
        lax.fori_loop(0, first + step + 1, tile, 0)

    @pl.when(i == 0)
    def _():
        copies(0, 0, True)

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        copies(i + 1, 1 - slot, True)

    copies(i, slot, False)
    b = first + i
    tiles = b + 1

    # the scores → order keys, once; the lowest key where a key lies after
    # the row, which no candidate reaches
    def to_keys(c, carry):
        cols = pl.ds(pl.multiple_of(c * block, block), block)
        keys_ref[:, cols] = _order_key(lax.bitcast_convert_type(
            buf[slot, :, cols], jnp.int32))
        return carry
    lax.fori_loop(0, tiles, to_keys, 0)
    diag = pl.ds(pl.multiple_of(b * block, block), block)
    row = lax.broadcasted_iota(jnp.int32, (block, block), 0)
    col = lax.broadcasted_iota(jnp.int32, (block, block), 1)
    keys_ref[:, diag] = jnp.where(col <= row, keys_ref[:, diag], _INT_MIN)

    def count(rows, *tests):
        """Per row of the group, how many of its keys pass each test
        (keys, the tile's first column) → bool."""
        def tile(c, accs):
            start = pl.multiple_of(c * block, block)
            x = keys_ref[rows, pl.ds(start, block)]
            return tuple(a + t(x, start).astype(jnp.int32)
                         for a, t in zip(accs, tests))
        zero = jnp.zeros((GROUP, block), jnp.int32)
        accs = lax.fori_loop(0, tiles, tile, (zero,) * len(tests))
        return [jnp.sum(a, axis=1, keepdims=True) for a in accs]

    lane = lax.broadcasted_iota(jnp.int32, (GROUP, out_ref.shape[1]), 1)

    def group(g, carry):
        rows = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)

        # the largest key v with topk keys ≥ v, from the sign bit down; it
        # stays at the bottom where a row sees no more than topk keys (and
        # keeps them all)
        def value_bit(r, v):
            cand = v ^ lax.shift_left(jnp.int32(1), 31 - r)
            n, = count(rows, lambda x, _: x >= cand)
            return jnp.where(n >= topk, cand, v)
        v = lax.fori_loop(0, 32, value_bit,
                          jnp.full((GROUP, 1), _INT_MIN, jnp.int32))
        above, on = count(rows, lambda x, _: x > v, lambda x, _: x == v)
        room = topk - above
        tied = (v != _INT_MIN) & (on > room)

        # the room-th key on v by its column, lowest first
        def column_bit(r, p):
            cand = p | lax.shift_left(jnp.int32(1), bits - 1 - r)
            n, = count(rows, lambda x, start: (x == v) & (
                start + lax.broadcasted_iota(jnp.int32, x.shape, 1) < cand))
            return jnp.where(n < room, cand, p)

        def settle():
            p = lax.fori_loop(0, bits, column_bit,
                              jnp.zeros((GROUP, 1), jnp.int32))
            return jnp.where(tied, p, s - 1)
        last = lax.cond(jnp.max(tied.astype(jnp.int32)) > 0, settle,
                        lambda: jnp.full((GROUP, 1), s - 1, jnp.int32))
        # v's float bits (−inf at the bottom)
        tau = jnp.where(v == _INT_MIN, -8388608,
                        jnp.where(v < 0, v ^ 0x7FFFFFFF, v))
        out_ref[rows, :] = jnp.where(
            lane == 0, tau, jnp.where(lane == 1, last, tied.astype(jnp.int32)))
        return carry
    lax.fori_loop(0, block // GROUP, group, 0)


def topk_threshold(table: jax.Array, blocks: Sequence[int], block: int,
                   topk: int) -> tuple:
    """Each scored row's threshold from ``index_scores``' table (the query
    blocks ``blocks``, ascending and consecutive, ``block`` rows each, their
    scores against every key up to each block's last row; −inf above the
    diagonal): ``(tau, last, tied)`` — (rows, 1) float32 the ``topk``-th
    largest score (−inf where a row sees no more than ``topk`` keys), (rows,
    1) int32 the highest column kept among the keys on ``tau``
    (``lax.top_k``'s rule: the lowest first), and (rows,) bool the rows
    with more keys on ``tau`` than it keeps."""
    rows, s = table.shape
    out = pl.pallas_call(
        partial(_threshold_kernel, first=blocks[0], topk=topk,
                bits=s.bit_length()),
        grid=(len(blocks),),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((2, block, s), jnp.float32),
                        pltpu.VMEM((block, s), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            # a step copies the next one's scores while it counts its own
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name=THRESHOLD_NAME,
    )(table)
    return (lax.bitcast_convert_type(out[:, :1], jnp.float32), out[:, 1:2],
            out[:, 2] > 0)
