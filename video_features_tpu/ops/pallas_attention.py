"""Pallas TPU kernel for causal attention: the score tile never leaves VMEM.

``ops/attention.py::_causal_blockwise`` is the same arithmetic as a chain of
XLA fusions, and on the chip every (query tile × key tile) score tensor of
it crosses HBM about five times (written by QKᵀ, read for the row max, read
and written by the exponential, read by PV): at latent attention's shapes —
8,192 positions, 32 heads, 192-wide q/k, 128-wide v — 134 MB a tile pair,
which bounds the scan by bytes that no one needs outside the tile (PERF.md
§6, PR 30). Here one Mosaic kernel, named ``causal_attention`` in traces,
makes QKᵀ → online softmax → PV per (head, query tile, key tile) with the
running max, denominator and accumulator in VMEM scratch:

* the grid is (window, head, query tile, key tile), key tile innermost; key
  tiles above the diagonal are neither computed nor fetched, and the
  triangle mask is applied only on tiles the diagonal crosses;
* q/k and v keep their own head widths (192 and 128 in the cell: v is not
  padded to q's width), float32 in and out, float32 accumulation;
* **the passes are made here.** The ambient matmul precision cannot make
  them inside a kernel (Mosaic refuses anything but one bf16 pass a dot), so
  the caller says how many bf16 passes a float32 product takes: 3 (ambient
  ``high``, what ``precision=mixed`` runs) splits each operand into a bf16
  head and a bf16 remainder and sums hi·hi + hi·lo + lo·hi in float32 —
  XLA's own three-pass product — for QKᵀ and PV alike; 1 (ambient
  ``default``) is the head alone. The three products of QKᵀ are ONE
  contraction over the parts laid side by side ([hi hi lo] against
  [hi lo hi]: 576 columns for 192, padded to 640 = five 128-lane passes
  where three separate products take six). ``highest`` has no lane here: it
  keeps the XLA path (``ops.attention.resolve_causal``);
* **every element is split once, in VMEM.** A query tile is packed when its
  first key tile arrives. A key/value tile is packed by the query tile
  whose diagonal crosses it — its first use — into a copy of the head's
  packed keys and values that stays in VMEM for the query tiles after it
  (14.7 MB in the cell); the float32 block's index map waits on that tile
  until then and never returns to it, so Q, K and V are each read from HBM
  once and XLA writes no split, concatenated or padded copy of them;
* q and k may come as column groups (latent attention's nope and rope
  parts; a key group of one head is shared by all heads), so the caller
  never writes their concatenation either;
* **grouped-query heads are the same kernel on a taller tile.** Where k and
  v have fewer heads than q (query head j reads key-value head ``j div
  group``), a grid step is one key-value head and its ``group`` query heads:
  they are ``group`` adjacent column blocks of (B, S, H·D) as it stands —
  four 64-wide heads are two whole 128-lane blocks, which is what lets a
  64-wide value head in — and ride the score tile's rows, head j at rows
  j·block_q …, each row with its own running max, denominator and
  accumulator. One QKᵀ and one PV serve the group, a key/value tile is
  packed once for all its heads (8,192 × (256 + 128) bf16 = 6.3 MB a
  key-value head in lfm2-moe's cell) and read from VMEM once a tile pair;
  nothing is repeated, folded or padded outside the kernel. A 64-wide head
  packs as [hi hi lo 0]: one 256-column contraction. Equal head counts
  (latent attention) are the case ``group == 1``: every step a group adds
  is behind a static ``group > 1``, so their module holds none of it.

* **a window is the same kernel on a shorter key axis.** Where query i sees
  keys i − W + 1 … i (``window=W``, a sliding layer), the grid's key axis
  counts from the first tile that holds a key the query tile sees and has as
  many steps as the widest such band has tiles (``resident_tiles``: five
  tiles of 512 keys for 128 positions under 2,048; steps past the diagonal
  tile do nothing), the head's resident packed keys and values are a *ring*
  of as many slots (tile t in slot ``t mod ring``, packed by the first query
  tile that reaches it over the tile ``ring`` before it, which no one sees
  any more: 3.3 MB, whatever the sequence), the float32 block's index map
  moves only onto a tile no earlier query tile reached, and the mask falls
  on the tiles the diagonal or the band's lower edge crosses; a row that
  sees no key of such an edge tile passes through it unchanged. That call is
  named ``window_attention`` in traces. ``window=None`` and ``window ≥ S``
  are the plain triangle, every windowed step behind a static ``window is
  not None``. A group of eight 128-wide query heads (trinity-mini's: 128
  positions × 8 heads a score tile) is the grouped lane's second shape;
  latent attention's column groups under a window of 513 (dots3-note's
  sliding layers: 512 positions a score tile over the two key tiles of 512
  its band crosses) the windowed lane's third.
* **a selection of keys is the triangle under a second mask.** Learned
  sparse attention (``ops/sparse_index.py``) keeps, for each query, the keys
  its indexer scores highest; that selection arrives as bits, 32 keys an
  int32 word (``ops.attention.pack_keep``: 8.4 MB a window-layer at 8,192
  positions), and a grid step reads its (query tile, key tile)'s words
  beside the causal test — a key tile of 1,024 is eight 128-lane bit planes
  of one group of words, unpacked by shifts — so every tile up to the
  diagonal is computed and masked. A row may see no key of a tile and
  passes it unchanged. That call is named ``sparse_attention`` in traces;
  every step of the lane is behind a static ``keep``.

Keys and values enter heads-major, (B, H, S, width) — a block is one head's
(tile, width) slab; the caller's (B, S, H, width) is transposed here, which
XLA folds into the producing product's output layout — and so do the queries
of equal head counts; a group's queries enter as (B, S, H·width), no copy.
The output leaves as (B, S, H·v_dim), which is how ``o_proj`` reads it. CPU
tests run the same kernel body under ``interpret=True``
(tests/test_attention.py, tests/test_window_attention.py).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from video_features_tpu.ops.attention import KEEP_BITS, keep_lanes

LANES = 128
NAME = 'causal_attention'
# the same kernel under a window: the name its pallas_call carries in traces
WINDOW_NAME = 'window_attention'
# and under a selection of keys
SPARSE_NAME = 'sparse_attention'
# the kernel's tiles at the cells' shapes — score-tile rows (a group's heads
# share them: ``tiles``) and keys: my chip runs, PRs 30 and 36 (PERF.md §6)
BLOCK_Q = 1024
BLOCK_K = 1024
# under a window the key tile is half that: the band's two edges are masked
# tiles computed whole, and at 2,048 keys a query five tiles of 512 waste
# less than three of 1,024 (29.7 against 34.7 ms a window-layer at 32,768
# positions, 43.9 at 2,048: my chip run, PR 38)
WINDOW_BLOCK_K = 512
# a head's packed keys and values stay in VMEM for all of its query tiles
# (8,192 × (640 + 256) bf16 = 14.7 MB in joyai's cell; 32,768 × (384 + 256)
# bf16 = 41.9 MB in a full layer of trinity-mini's, 170.6 ms a window-layer
# where the XLA tiles take 315: my chip run, PR 38; under a window only the
# band's ring), beside a (1024, 1024) score tile with its exponential and
# the two bf16 parts of it (12 MB) and the double-buffered float32 blocks
# (6 MB): past Mosaic's 16 MB default, inside a v5e core's 128 MB.
# ``resolve_causal`` keeps sequences whose packed keys and values pass
# KV_VMEM_BYTES on the XLA path.
VMEM_LIMIT_BYTES = 96 * 2 ** 20
KV_VMEM_BYTES = 48 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))      # contract both operands' last axis
# every product in the kernel is bf16 × bf16 into float32, said outright: the
# ambient precision reaches a kernel's dots, and Mosaic refuses 'high'
_ONE_PASS = dict(precision=lax.Precision.DEFAULT,
                 preferred_element_type=jnp.float32)


def packed_widths(qk_dims: Sequence[int], v_dim: int, passes: int
                  ) -> Tuple[int, int]:
    """Columns of a packed query/key row (the contraction QKᵀ runs over:
    every column group's three parts, each group padded to whole 128-lane
    chunks) and of a packed value row ([hi | lo] of v)."""
    if passes == 1:
        return sum(qk_dims), v_dim
    return sum(3 * d + -3 * d % LANES for d in qk_dims), 2 * v_dim


def _split(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """float32 → (head, remainder), both float32 and both exact in bf16's 8
    bits once cast: head + remainder is x to 16 bits."""
    head = x.astype(jnp.bfloat16).astype(jnp.float32)
    return head, x - head


def _arrange(a: jax.Array, b: jax.Array, c: jax.Array) -> jax.Array:
    """Three float32 (n, D) parts of a row → the bf16 (n, C) row one
    contraction runs over: [a b c] for each whole 128-lane chunk of D, then
    the remainder's three side by side, zero-padded to a whole chunk. At
    D = 192: three aligned chunks, then [a | b] and [c | 0] in 64-lane
    halves — only the remainder's pieces change lanes."""
    dim = a.shape[1]
    full = dim - dim % LANES
    cols = []
    for j in range(0, full, LANES):
        cols += [x[:, j:j + LANES] for x in (a, b, c)]
    if dim > full:
        cols += [x[:, full:] for x in (a, b, c)]
        pad = -3 * dim % LANES
        if pad:
            cols.append(jnp.zeros((a.shape[0], pad), a.dtype))
    return jnp.concatenate(cols, axis=1).astype(jnp.bfloat16)


def _pack_qk(refs, passes: int, key: bool, scale: float = 1.0,
             head: Tuple[int, int] = (0, 1)) -> jax.Array:
    """The column groups of a query (``· scale``) or key tile, packed side
    by side: q's parts are [hi hi lo] against k's [hi lo hi]. ``head`` =
    (j, group) packs query head j of blocks that hold a key-value head's
    ``group`` query heads side by side."""
    j, group = head
    cols = []
    for ref in refs:
        if group == 1:
            x = ref[...]
        else:
            width = ref.shape[-1] // group
            x = ref[:, j * width:(j + 1) * width]
        x = x * scale if scale != 1.0 else x
        if passes == 1:
            cols.append(x.astype(jnp.bfloat16))
            continue
        hi, lo = _split(x)
        cols.append(_arrange(hi, lo, hi) if key else _arrange(hi, hi, lo))
    return jnp.concatenate(cols, axis=1)


def _pack_v(v: jax.Array, passes: int) -> jax.Array:
    if passes == 1:
        return v.astype(jnp.bfloat16)
    return jnp.concatenate(_split(v), axis=1).astype(jnp.bfloat16)


def _first_masked_tile(qi, block_q: int, block_k: int):
    """Index of the first key tile the diagonal crosses for a query tile:
    the tiles before it lie wholly at or before the tile's first row."""
    return (qi * block_q + 1) // block_k


def _last_key_tile(qi, block_q: int, block_k: int):
    """Index of the last key tile a query tile sees (its last row's)."""
    return (qi * block_q + block_q - 1) // block_k


def _first_key_tile(qi, block_q: int, block_k: int, window: int):
    """Index of the first key tile that holds a key a query tile sees under
    a window: its first row's oldest, ``window - 1`` positions back."""
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k


def resident_tiles(s: int, block_q: int, block_k: int,
                   window: Optional[int] = None) -> int:
    """Key tiles a head's packed keys and values keep in VMEM: every tile of
    the sequence, or under a ``window`` shorter than it the most one query
    tile reaches (the band: the ring's slots, and the key-tile axis of the
    windowed grid)."""
    if window is None or window >= s:
        return s // block_k
    return max(int(_last_key_tile(qi, block_q, block_k))
               - max(qi * block_q - (window - 1), 0) // block_k + 1
               for qi in range(s // block_q))


def _tile_positions(shape, block_q: int, group: int) -> jax.Array:
    """Each score row's position in its query tile: the row index, and with
    a group's heads stacked on the rows, block_q a head, its index within
    its head."""
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    return row if group == 1 else lax.rem(row, block_q)


def _kept(keep_ref, ki, block_k: int, lanes: int) -> jax.Array:
    """The (rows, block_k) selection of key tile ``ki`` from its group's
    packed words (``ops.attention.pack_keep``): the tile is ``block_k /
    lanes`` consecutive bit planes of the group, each ``lanes`` keys wide,
    laid side by side."""
    words = keep_ref[...]
    first = lax.rem(ki * block_k, KEEP_BITS * lanes) // lanes
    planes = [lax.shift_right_logical(words, jnp.full(words.shape, first + j,
                                                      jnp.int32)) & 1
              for j in range(block_k // lanes)]
    return jnp.concatenate(planes, axis=1) != 0


def _kernel(*refs, groups: int, group: int, block_q: int, block_k: int,
            v_dim: int, passes: int, scale: float, window: Optional[int],
            ring: int, keep: int = 0):
    q_refs, k_refs = refs[:groups], refs[groups:2 * groups]
    rest = list(refs[2 * groups:])
    v_ref = rest.pop(0)
    keep_ref = rest.pop(0) if keep else None
    o_ref, qc_ref, kc_ref, vc_ref, m_ref, l_ref, acc_ref = rest
    qi, kj = pl.program_id(2), pl.program_id(3)
    first_masked = _first_masked_tile(qi, block_q, block_k)
    last = _last_key_tile(qi, block_q, block_k)
    if window is None:
        ki = kj
        rows = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
    else:
        # the grid's key axis counts from the query tile's first visible
        # tile, and a tile's packed copy sits in the ring's slot ki mod ring
        ki = kj + _first_key_tile(qi, block_q, block_k, window)
        rows = pl.ds(pl.multiple_of(lax.rem(ki, ring) * block_k, block_k),
                     block_k)
    # a key-value head's ``group`` query heads ride the score tile's rows:
    # head j is rows j·block_q … of the packed queries, the running max, the
    # denominator and the accumulator, so one QKᵀ and one PV serve them all
    heads = [slice(j * block_q, (j + 1) * block_q) for j in range(group)]

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        if group == 1:
            qc_ref[...] = _pack_qk(q_refs, passes, key=False, scale=scale)
        else:
            for j, at in enumerate(heads):
                qc_ref[at, :] = _pack_qk(q_refs, passes, key=False,
                                         scale=scale, head=(j, group))

    def accumulate(masked: bool):
        s = lax.dot_general(qc_ref[...], kc_ref[rows, :], _NT, **_ONE_PASS)
        seen = _kept(keep_ref, ki, block_k, keep) if keep else None
        if masked:
            row = qi * block_q + _tile_positions(s.shape, block_q, group)
            col = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            causal = col <= row
            if window is not None:
                causal = jnp.logical_and(causal, col > row - window)
            seen = causal if seen is None else jnp.logical_and(seen, causal)
        if seen is not None:
            s = jnp.where(seen, s, -jnp.inf)
        # key 0 is in the first tile and every row sees it, so m_new is
        # finite from the first step on and no exp sees -inf - -inf
        m_prev = m_ref[...]
        m_new = m_base = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        if (masked and window is not None) or keep:
            # not so under a window: a row may see no key of the tile the
            # band's lower edge crosses (its oldest key lies in the next),
            # nor under a selection, whose first kept key may lie tiles
            # later: the row then exponentiates against a finite stand-in,
            # p and alpha come out 0 and its carry passes through
            m_base = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.exp(s - m_base)
        alpha = jnp.exp(m_prev - m_base)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = vc_ref[rows, :]
        p_hi = p.astype(jnp.bfloat16)
        if passes == 3:
            p_lo = (p - p_hi.astype(jnp.float32)).astype(jnp.bfloat16)
            # p_hi · [v_hi | v_lo] in one product, p_lo · v_hi in a second
            both = jnp.dot(p_hi, v, **_ONE_PASS)
            pv = (both[:, :v_dim] + both[:, v_dim:]
                  + jnp.dot(p_lo, v[:, :v_dim], **_ONE_PASS))
        else:
            pv = jnp.dot(p_hi, v, **_ONE_PASS)
        acc_ref[...] = alpha * acc_ref[...] + pv

    def pack_keys():
        kc_ref[rows, :] = _pack_qk(k_refs, passes, key=True)
        vc_ref[rows, :] = _pack_v(v_ref[...], passes)

    if window is None:
        @pl.when(ki < first_masked)
        def _():
            accumulate(masked=False)

        # a tile the diagonal crosses holds keys no earlier query tile saw:
        # this is its first use, so its float32 block is in (the index map
        # held it back until now) and is packed into the head's VMEM copy
        # here, once
        @pl.when(jnp.logical_and(ki >= first_masked, ki <= last))
        def _():
            if block_q % block_k == 0:
                pack_keys()
            else:
                # a query tile that ends inside a key tile leaves it to be
                # crossed again: the first to cross it packs, the rest reuse
                pl.when(_last_key_tile(qi - 1, block_q, block_k) < ki)(
                    pack_keys)
            accumulate(masked=True)
    else:
        # the band: tiles first … last of this query tile, ``ring`` grid
        # steps of which those past ``last`` do nothing. A tile no earlier
        # query tile reached is packed into its slot of the ring (over the
        # tile ``ring`` before it, which no query tile sees any more); the
        # mask falls on the tiles the diagonal or the band's lower edge
        # (a key the tile's last row no longer sees) crosses
        seen = ki <= last
        edge = ki * block_k <= qi * block_q + block_q - 1 - window
        masked = jnp.logical_or(ki >= first_masked, edge)
        pl.when(jnp.logical_and(
            seen, _last_key_tile(qi - 1, block_q, block_k) < ki))(pack_keys)
        pl.when(jnp.logical_and(seen, jnp.logical_not(masked)))(
            partial(accumulate, masked=False))
        pl.when(jnp.logical_and(seen, masked))(
            partial(accumulate, masked=True))

    @pl.when(ki == last)
    def _():
        out = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        # the group's heads leave side by side: query head g·group + j is
        # column block j of key-value head g's output block
        o_ref[...] = out if group == 1 else jnp.concatenate(
            [out[at] for at in heads], axis=1)


Parts = Union[jax.Array, Sequence[jax.Array]]


def tiles(s: int, group: int = 1, window: Optional[int] = None
          ) -> Tuple[int, int]:
    """The shipped (query, key) tiles for ``s`` positions: a score tile has
    at most BLOCK_Q rows — ``group`` query heads at the largest power of
    two of positions that leaves — and BLOCK_K keys; under a ``window``
    shorter than the sequence WINDOW_BLOCK_K keys and no more positions
    than that (a band of 513 keys crosses two tiles of 512 for 512
    positions, three of 512 for 1,024)."""
    block_q = 1 << max(BLOCK_Q // group, 1).bit_length() - 1
    block_k = BLOCK_K
    if window is not None and window < s:
        block_q, block_k = min(block_q, WINDOW_BLOCK_K), WINDOW_BLOCK_K
    return min(block_q, s), min(block_k, s)


def causal_attention(q: Parts, k: Parts, v: jax.Array, scale: float,
                     passes: int, block_q: Optional[int] = None,
                     block_k: Optional[int] = None,
                     interpret: bool = False,
                     window: Optional[int] = None,
                     keep: Optional[jax.Array] = None) -> jax.Array:
    """softmax(QKᵀ·scale + causal mask)V over (B, S, H, D) float32 tensors
    (v may be narrower), float32 (B, S, H, v's width) out, ``passes`` (1 or
    3) bf16 passes a product. With a ``window`` shorter than S, position i
    sees keys i − window + 1 … i: the call is then named
    ``window_attention``, its grid's key axis is the band's tiles and the
    head's resident packed keys and values a ring of as many
    (:func:`resident_tiles`); ``window`` None or ≥ S is the plain triangle.

    ``q`` and ``k`` may each come as a sequence of column groups whose
    concatenation along D is the head — latent attention's (nope, rope) —
    so that no one has to write the concatenation to memory; a key group
    with ONE head is shared by all heads (its rotary key). ``k`` and ``v``
    may have a whole fraction of q's heads (grouped-query: query head j
    reads key-value head ``j div group``); a grid step is then one
    key-value head and its ``group`` query heads, whose columns — adjacent
    in (B, S, H·D) — must fill whole 128-lane blocks. S must be a multiple
    of both tiles (by default :func:`tiles`) and every group's width of 64;
    compiled (not interpreted), the key tile, the score tile's rows and the
    output block's columns (``group`` · v's width) must be multiples of
    128. One trace event a call, whatever B.

    ``keep`` ((B, S, S/32) int32, ``ops.attention.pack_keep``: a selection
    of keys a query, learned sparse attention) lets a query see only the
    selected keys at or before it: every key tile up to the diagonal is
    computed under the tile's bits, read beside it as (block_q, lanes)
    words, and the call is named ``sparse_attention``. Equal head counts
    and no window; a key tile must be whole bit planes of one group of the
    packing."""
    if passes not in (1, 3):
        raise ValueError(f'causal_attention makes 1 or 3 bf16 passes, not '
                         f'{passes}')
    q_parts, k_parts = (tuple(x) if isinstance(x, (tuple, list)) else (x,)
                        for x in (q, k))
    widths = [x.shape[-1] for x in q_parts]
    if widths != [x.shape[-1] for x in k_parts]:
        raise ValueError(f'causal_attention: query groups {widths} and key '
                         f'groups {[x.shape[-1] for x in k_parts]} differ')
    if any(w % (LANES // 2) for w in widths):
        raise ValueError(f'causal_attention: query/key groups of {widths} '
                         f'columns are no multiples of {LANES // 2}')
    b, s, h, _ = q_parts[0].shape
    kv_heads, v_dim = v.shape[2:]
    if h % kv_heads:
        raise ValueError(f'causal_attention: {h} query heads are no whole '
                         f'number of groups of {kv_heads} key-value heads')
    group = h // kv_heads
    if any(x.shape[2] not in (kv_heads, 1) for x in k_parts):
        raise ValueError(
            f'causal_attention: key groups of '
            f'{[x.shape[2] for x in k_parts]} heads beside {kv_heads} value '
            f'heads (a key group has as many, or one for all)')
    if group > 1 and any(group * w % LANES for w in (*widths, v_dim)):
        raise ValueError(
            f'causal_attention: {group} query heads a key-value head of '
            f'{widths} query/key and {v_dim} value columns fill no whole '
            f'{LANES}-lane blocks')
    block_q, block_k = (min(given or shipped, s) for given, shipped
                        in zip((block_q, block_k), tiles(s, group, window)))
    if s % block_q or s % block_k:
        raise ValueError(f'causal_attention: {s} positions are no multiple '
                         f'of the tiles ({block_q}, {block_k})')
    if window is not None and window < 1:
        raise ValueError(f'causal_attention: a window of {window} keys sees '
                         f'nothing')
    if window is not None and window >= s:
        window = None
    lanes = 0
    if keep is not None:
        lanes = keep_lanes(s)
        if group > 1 or window is not None:
            raise ValueError('causal_attention: a selection of keys takes '
                             'equal head counts and no window')
        if keep.shape != (b, s, s // KEEP_BITS):
            raise ValueError(f'causal_attention: the selection is '
                             f'{keep.shape}, not {(b, s, s // KEEP_BITS)} '
                             f'packed words')
        if block_k % lanes or KEEP_BITS * lanes % block_k:
            raise ValueError(f'causal_attention: key tiles of {block_k} are '
                             f'no whole bit planes of a group of '
                             f'{KEEP_BITS * lanes} keys')
    ring = resident_tiles(s, block_q, block_k, window)

    def heads_major(parts):
        """(B, S, H, width) → (B, H, S, width): a block is one head's (tile,
        width) slab."""
        return [jnp.asarray(x, jnp.float32).transpose(0, 2, 1, 3)
                for x in parts]

    if group == 1:
        q_parts = heads_major(q_parts)
    else:
        # a key-value head's query heads are adjacent columns of (B, S, H·D)
        # as it stands: a block is their (tile, group · width) slab
        q_parts = [jnp.asarray(x, jnp.float32).reshape(b, s, -1)
                   for x in q_parts]
    k_parts, (vt,) = heads_major(k_parts), heads_major((v,))
    c_qk, c_v = packed_widths(widths, v_dim, passes)

    def kv_tile(qi, ki):
        # a key tile is fetched for the query tile whose diagonal crosses it
        # and never again (its packed copy stays in VMEM): before the
        # diagonal the map waits on the first crossed tile, above it on the
        # last, and an unchanged index fetches nothing
        if window is None:
            return jnp.clip(ki, _first_masked_tile(qi, block_q, block_k),
                            _last_key_tile(qi, block_q, block_k))
        # under a window ``ki`` counts from the band's first tile, and only
        # a tile no earlier query tile reached is new; with none new the
        # map stays on the last
        new = _last_key_tile(qi - 1, block_q, block_k) + 1
        return jnp.minimum(
            jnp.maximum(ki + _first_key_tile(qi, block_q, block_k, window),
                        new), _last_key_tile(qi, block_q, block_k))

    def spec(x, block, tile):
        shared = x.shape[1] == 1        # one head for all: block 0 always
        return pl.BlockSpec(
            (None, None, block, x.shape[-1]),
            lambda bi, hi, qi, ki: (bi, 0 if shared else hi, tile(qi, ki), 0))

    def q_spec(x):
        if group > 1:
            return pl.BlockSpec((None, block_q, x.shape[-1] // kv_heads),
                                lambda bi, hi, qi, ki: (bi, qi, hi))
        return spec(x, block_q, lambda qi, ki: qi)

    def kv_spec(x):
        return spec(x, block_k, kv_tile)

    extra_in, extra_specs = (), []
    name = NAME if window is None else WINDOW_NAME
    if keep is not None:
        # a query tile's words of the group that holds key tile ki (held on
        # the last tile past the diagonal, where the steps do nothing)
        extra_in, name = (keep,), SPARSE_NAME
        extra_specs = [pl.BlockSpec(
            (None, block_q, lanes),
            lambda bi, hi, qi, ki: (bi, qi, jnp.minimum(
                ki, _last_key_tile(qi, block_q, block_k)) * block_k
                // (KEEP_BITS * lanes)))]

    rows = group * block_q
    out = pl.pallas_call(
        partial(_kernel, groups=len(widths), group=group, block_q=block_q,
                block_k=block_k, v_dim=v_dim, passes=passes, scale=scale,
                window=window, ring=ring, keep=lanes),
        grid=(b, kv_heads, s // block_q, ring),
        in_specs=[*map(q_spec, q_parts), *map(kv_spec, k_parts),
                  kv_spec(vt), *extra_specs],
        out_specs=pl.BlockSpec((None, block_q, group * v_dim),
                               lambda bi, hi, qi, ki: (bi, qi, hi)),
        out_shape=jax.ShapeDtypeStruct((b, s, h * v_dim), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, c_qk), jnp.bfloat16),
                        pltpu.VMEM((ring * block_k, c_qk), jnp.bfloat16),
                        pltpu.VMEM((ring * block_k, c_v), jnp.bfloat16),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, v_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # query tiles of a head run in order: each packs the key tiles
            # its diagonal crosses for the tiles after it
            dimension_semantics=('parallel', 'parallel', 'arbitrary',
                                 'arbitrary'),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(*q_parts, *k_parts, vt, *extra_in)
    return out.reshape(b, s, h, v_dim)
