"""Attention ops: dense, blockwise (flash-style), and ring sequence-parallel.

The reference's models are convolutional or clip-local, so it has no
long-sequence machinery at all (SURVEY.md §2.3, §5.7) — long videos are
handled by sliding windows. This framework treats long-context as
first-class: token sequences too large for one device's HBM (e.g. every
frame's ViT tokens of a long video treated as one temporal sequence) are
sharded over a mesh axis and attended with **ring attention** — KV shards
rotate around the ring via ``lax.ppermute`` (ICI neighbor exchange, no
all-gather) while each device accumulates its queries' online softmax.

Causal language trunks (``models/latent_moe.py``, ``models/hybrid_trunk.py``)
take the same blockwise path with ``causal=True``: queries are tiled too, a
query tile scans only the key tiles at or before it, the value head may be
narrower than the query/key head (latent attention: 192-wide q/k, 128-wide
v), and keys and values may have fewer heads than the queries
(grouped-query attention: 32 query heads reading 8 key-value heads); under a
``window`` a query tile starts at the tile of its oldest visible key, so the
tiles below the band cost nothing either (sliding-window layers).
:func:`rotary_interleaved` is their position code; :func:`rotary_half` is
the half-split form of the same rotation (``models/retention_trunk.py``).

The three XLA paths compute bit-comparable results (same online-softmax
math, f32 accumulation, the ambient matmul precision on every product):

  * :func:`dense_attention` — one fused XLA softmax(QKᵀ)V; the baseline.
  * :func:`blockwise_attention` — ``lax.scan`` over KV chunks with running
    (max, denom, out) — O(S·block) memory instead of O(S²), single device.
  * :func:`ring_attention` — blockwise over the mesh axis; memory AND
    compute sharded. Use under ``shard_map`` with the sequence axis split.

A selection of keys (learned sparse attention: ``ops/sparse_index.py`` picks
a query's keys) reaches both causal paths as bits, 32 keys a word
(:func:`pack_keep`); a query then sees the selected keys at or before it.

A fourth path is a kernel, for the causal case alone
(``ops/pallas_attention.py``, named ``causal_attention`` in traces,
``window_attention`` under a window, ``sparse_attention`` under a selection
of keys): the same
online softmax with the score tile kept in VMEM, which the XLA tiles write to
HBM several times a tile pair. It makes its bf16 passes itself (three under
ambient ``high``, one under ``default``; ``highest`` has no lane in it), so
it agrees with the causal XLA path to the rounding of those passes in another
summation order (≈ 2e-5 rel-L2 on a window of the benchmark cell, both
within 7e-5 of ``highest``: PERF.md §6, PR 30), not to the bit.
:func:`resolve_causal` says where it applies — a TPU, tile-aligned shapes, a
precision it has a lane for — and the causal path's callers ask it (latent
attention with equal head counts, grouped-query attention with its key-value
head's query heads a grid step); the XLA path is what runs everywhere else
and what the kernel is tested against.

Shapes follow (B, S, H, D) [batch, sequence, heads, head_dim].
"""
from __future__ import annotations

from functools import cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _scale(q: jax.Array, scale: Optional[float]) -> float:
    return scale if scale is not None else q.shape[-1] ** -0.5


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    scale: Optional[float] = None) -> jax.Array:
    """softmax(QKᵀ·scale)V over (B, S, H, D) tensors."""
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * _scale(q, scale)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v)


def _online_block(q, m, l, o, kb, vb, scale, valid=None):
    """One online-softmax accumulation step against KV block (kb, vb).

    ``valid`` masks keys out of the softmax (scores → -inf ⇒ p → 0):
    (block_size,) bool for padded keys, or (sq, 1, block_size) for the
    causal diagonal tile; fully-masked blocks leave the carry unchanged
    because m_new falls back to the running max.
    """
    s = jnp.einsum('bqhd,bkhd->bqhk', q, kb).astype(jnp.float32) * scale
    if valid is not None:
        s = jnp.where(valid, s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    # m_new stays -inf until the first unmasked key (a fully-padded shard
    # can be processed first under ring sharding); exponentiate against a
    # finite stand-in so exp(-inf - -inf) never makes a NaN — p and alpha
    # are then exactly 0 and the carry passes through unchanged.
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe)
    alpha = jnp.exp(m - m_safe)
    l_new = l * alpha + p.sum(axis=-1, keepdims=True)
    o_new = o * alpha + jnp.einsum('bqhk,bkhd->bqhd', p,
                                   vb.astype(jnp.float32))
    return m_new, l_new, o_new


def _online_init(q, v_dim: Optional[int] = None):
    b, sq, h, d = q.shape
    m = jnp.full((b, sq, h, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, sq, h, 1), jnp.float32)
    o = jnp.zeros((b, sq, h, d if v_dim is None else v_dim), jnp.float32)
    return m, l, o


# ambient matmul precisions (``jax.default_matmul_precision``) the causal
# kernel has a lane for, as bf16 passes a float32 product: unset, 'default'
# and its alias 'bfloat16' are the TPU's one pass, 'high' is three. 'highest'
# (six) and every other name have none and keep the XLA path, so no setting
# gets fewer passes than it asks for
KERNEL_PASSES = {None: 1, 'default': 1, 'bfloat16': 1, 'high': 3}


def resolve_causal(platform: str, s: int, qk_dim: int, v_dim: int,
                   precision: Optional[str], heads: int = 1,
                   kv_heads: int = 1, window: Optional[int] = None,
                   keep: bool = False) -> str:
    """Which causal attention compiles for ``s`` positions on ``platform``
    under the ambient matmul ``precision``: 'kernel' (the fused Mosaic
    kernel, ops/pallas_attention.py) or 'xla' (:func:`blockwise_attention`
    with ``causal=True``). ``heads`` query heads read ``kv_heads`` key-value
    heads; only their ratio, the group, matters (1 when left out). Under a
    ``window`` (a query sees its own key and the ``window - 1`` before it)
    the kernel keeps only the band's key tiles resident, so its VMEM test
    is over those and not over the sequence. ``keep`` asks for the kernel's
    keep lane (a selection of keys as packed bits, :func:`pack_keep`): equal
    head counts, no window, and key tiles that lie inside one group of the
    packing.

    The kernel applies on a TPU, where the sequence is a whole number of its
    tiles and a key-value head's packed keys and values fit its VMEM budget,
    the group's value heads fill whole 128-lane blocks (its output block is
    one key-value head's query heads' columns: 128-wide value heads alone,
    64-wide ones from a group of two on), the query/key head is a multiple
    of 64, and the ambient precision is one it has a lane for
    (``KERNEL_PASSES``). Anywhere else — the CPU, where it would run
    interpreted; ragged or odd shapes; 'highest' — the XLA path runs, which
    is also the oracle the kernel is tested against. All of it is static at
    trace time, so the choice compiles away; there is no switch. Latent
    attention (``models/latent_moe.py::mla_block``) asks here and hands the
    kernel its heads as column groups; grouped-query attention
    (``models/hybrid_trunk.py::attention_block``) asks with its head counts
    and hands it fewer key-value heads."""
    from video_features_tpu.ops import pallas_attention as kernel
    if (platform != 'tpu' or precision not in KERNEL_PASSES
            or heads % kv_heads):
        return 'xla'
    group = heads // kv_heads
    block_q, block_k = kernel.tiles(s, group, window)
    if s % block_q or s % block_k:
        return 'xla'
    packed = sum(kernel.packed_widths((qk_dim,), v_dim,
                                      KERNEL_PASSES[precision]))
    resident = block_k * kernel.resident_tiles(s, block_q, block_k, window)
    if (group * block_q % 128 or block_k % 128
            or group * v_dim % 128 or qk_dim % 64
            or (group > 1 and group * qk_dim % 128)
            or 2 * resident * packed > kernel.KV_VMEM_BYTES):
        return 'xla'
    if keep:
        # a key tile's bits are whole planes of one group of words
        lanes = keep_lanes(s) if s % KEEP_BITS == 0 else 0
        if (group > 1 or window is not None or not lanes
                or block_k % lanes or KEEP_BITS * lanes % block_k):
            return 'xla'
    return 'kernel'


# -- a selection of keys as bits (learned sparse attention) -------------------
#
# A query's selected keys travel as bits along the key axis, 32 to an int32
# word: a (S, S) selection is (S, S/32) words, 8.4 MB at 8,192 positions
# where a byte a key would be 67 MB (and the kernel re-reads it for every
# head). The words of a row come in groups of ``lanes``: key u is bit b of
# word l of group g where u = g · 32 · lanes + b · lanes + l — so one bit
# plane of a group is ``lanes`` consecutive keys, and a key tile of 1,024 is
# eight whole 128-lane planes of its group's words.
KEEP_BITS = 32


def keep_lanes(s: int) -> int:
    """Words a group of the packing spans for ``s`` keys: 128 (4,096 keys a
    group) where ``s`` is a whole number of such groups, else all of a
    row's ``s / 32`` words as one group."""
    if s % KEEP_BITS:
        raise ValueError(f'a selection of keys packs 32 to a word: {s} keys '
                         f'are no multiple of 32')
    lanes = 128
    return lanes if s % (KEEP_BITS * lanes) == 0 else s // KEEP_BITS


def pack_keep(keep: jax.Array) -> jax.Array:
    """(..., S) bool → (..., S/32) int32: the selection as bits."""
    s = keep.shape[-1]
    lanes = keep_lanes(s)
    bits = keep.reshape(*keep.shape[:-1], s // (KEEP_BITS * lanes),
                        KEEP_BITS, lanes).astype(jnp.int32)
    shift = jnp.arange(KEEP_BITS, dtype=jnp.int32)[:, None]
    # distinct bits: their sum is their OR, bit 31 included
    return (bits << shift).sum(axis=-2, dtype=jnp.int32).reshape(
        *keep.shape[:-1], s // KEEP_BITS)


def unpack_keep(words: jax.Array, s: int) -> jax.Array:
    """(..., S/32) int32 → (..., S) bool: :func:`pack_keep` undone."""
    lanes = keep_lanes(s)
    grouped = words.reshape(*words.shape[:-1], s // (KEEP_BITS * lanes), 1,
                            lanes)
    shift = jnp.arange(KEEP_BITS, dtype=jnp.int32)[:, None]
    # an arithmetic shift: the bit that lands lowest is the one asked for
    return (jnp.right_shift(grouped, shift) & 1).astype(bool).reshape(
        *words.shape[:-1], s)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        block_size: int = 512,
                        scale: Optional[float] = None,
                        causal: bool = False,
                        window: Optional[int] = None,
                        keep: Optional[jax.Array] = None) -> jax.Array:
    """Memory-efficient attention: scan over KV blocks, O(S·block) memory.

    Ragged S is handled by zero-padding KV to a block multiple and masking
    the padded keys out of the online softmax — a ViT token count
    (grid² + 1 cls) is never block-aligned, and this is the production path
    for high-resolution inputs past BLOCKWISE_THRESHOLD tokens.

    ``causal=True`` (self-attention, S a block multiple): position i sees
    keys 0…i. ``v`` may have another head width than ``q``/``k``, and ``k``
    and ``v`` a whole fraction of ``q``'s heads (grouped-query). With a
    ``window`` (causal only) position i sees keys i − window + 1 … i; with
    ``keep`` ((B, S, S/32) int32, :func:`pack_keep`; causal only) only the
    keys whose bit is set among those before it.
    """
    if (window is not None or keep is not None) and not causal:
        raise ValueError('a window or a selection of keys is a causal '
                         'layer\'s: causal=True')
    if causal:
        return _causal_blockwise(q, k, v, block_size, _scale(q, scale),
                                 window, keep)
    b, sk, h, d = k.shape
    block_size = min(block_size, sk)
    pad = (-sk) % block_size
    sc = _scale(q, scale)
    valid = None
    if pad:
        k = jnp.pad(k, [(0, 0), (0, pad), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, pad), (0, 0), (0, 0)])
        valid = (jnp.arange(sk + pad) < sk).reshape(-1, block_size)
    n_blocks = (sk + pad) // block_size
    kb = k.reshape(b, n_blocks, block_size, h, d).swapaxes(0, 1)
    vb = v.reshape(b, n_blocks, block_size, h, d).swapaxes(0, 1)

    def step(carry, blk):
        if valid is None:
            kv_k, kv_v = blk
            mask = None
        else:
            kv_k, kv_v, mask = blk
        m, l, o = _online_block(q, *carry, kv_k, kv_v, sc, valid=mask)
        return (m, l, o), None

    xs = (kb, vb) if valid is None else (kb, vb, valid)
    (m, l, o), _ = lax.scan(step, _online_init(q, v.shape[-1]), xs)
    return (o / l).astype(q.dtype)


def _causal_blockwise(q, k, v, block_size: int, scale: float,
                      window: Optional[int] = None,
                      keep: Optional[jax.Array] = None) -> jax.Array:
    """Causal self-attention, tiled both ways: query tile i scans key tiles
    0…i-1 unmasked and then its own diagonal tile under the triangle, so
    the tiles above the diagonal cost nothing (a scan over all keys with a
    mask would compute, and throw away, half of S²).

    Under a ``window`` (position p sees keys p − window + 1 … p) the tiles
    below the band cost nothing either: query tile i starts at the tile
    that holds its first row's oldest key, takes the one or two tiles the
    band's lower edge crosses under that edge's mask, scans the whole ones
    between and ends on its diagonal tile. ``window`` None or ≥ S is the
    plain triangle, the same program as without the argument.

    Grouped-query heads (``k`` and ``v`` with fewer heads than ``q``, query
    head j reading key-value head ``j div group``) ride the query axis: a
    position's ``group`` query heads of one key-value head are laid side by
    side as ``group`` query rows of that head, so the tiles' products keep
    one head count, no key or value is copied, and a key tile is read once
    for the whole group.

    Under ``keep`` (a selection of keys as packed bits, equal head counts,
    no window) every tile up to the diagonal is visited and takes the
    selection's bits as its mask: a query tile's rows are unpacked once and
    ride the scan beside the key tiles they mask."""
    if keep is not None:
        return _selected_blockwise(q, k, v, block_size, scale, keep)
    b, s, h, _ = q.shape
    if k.shape[1] != s:
        raise ValueError(f'causal attention is self-attention: q has {s} '
                         f'positions, k has {k.shape[1]}')
    block_size = min(block_size, s)
    if s % block_size:
        raise ValueError(f'causal attention needs the sequence ({s}) to be '
                         f'a multiple of block_size ({block_size})')
    kv_heads = k.shape[2]
    if h % kv_heads:
        raise ValueError(f'{h} query heads are no whole number of groups '
                         f'of {kv_heads} key-value heads')
    group = h // kv_heads
    n_blocks = s // block_size
    kb = k.reshape(b, n_blocks, block_size, kv_heads, -1).swapaxes(0, 1)
    vb = v.reshape(b, n_blocks, block_size, kv_heads, -1).swapaxes(0, 1)
    pos = jnp.arange(block_size)
    q_pos, q_rows = pos, block_size
    if group > 1:
        # (b, s, kv·group, d) → (b, s·group, kv, d): row p·group + r is
        # position p's query head kv·group + r
        q = q.reshape(b, s, kv_heads, group, -1).swapaxes(2, 3).reshape(
            b, s * group, kv_heads, -1)
        q_pos, q_rows = jnp.repeat(pos, group), block_size * group
    triangle = (q_pos[:, None] >= pos[None, :])[:, None, :]   # (q, 1, k)
    if window is not None and window < 1:
        raise ValueError(f'a window of {window} keys sees nothing')
    if window is not None and window >= s:
        window = None
    if window is not None and window < block_size:
        # the band's lower edge crosses the diagonal tile too
        triangle &= (q_pos[:, None] - pos[None, :] < window)[:, None, :]

    @cache
    def band(tiles_back: int):
        """(q, 1, k): which keys of the tile ``tiles_back`` before the
        query tile's own are no more than window − 1 positions back (one
        mask a distance: every query tile meets the same one or two)."""
        return (q_pos[:, None] + tiles_back * block_size - pos[None, :]
                < window)[:, None, :]

    out = []
    for i in range(n_blocks):
        qi = q[:, i * q_rows:(i + 1) * q_rows]

        def step(carry, blk, qi=qi):
            return _online_block(qi, *carry, *blk, scale), None

        carry = _online_init(qi, v.shape[-1])
        first = whole = 0
        if window is not None:
            # the tile of the first row's oldest key, and the first tile
            # whose every key the last row still sees
            first = max(i * block_size - window + 1, 0) // block_size
            whole = min(max(-((window - (i + 1) * block_size) // block_size),
                            first), i)
        for j in range(first, whole):
            carry = _online_block(qi, *carry, kb[j], vb[j], scale,
                                  valid=band(i - j))
        if i > whole:
            carry, _ = lax.scan(step, carry, (kb[whole:i], vb[whole:i]))
        _, l, o = _online_block(qi, *carry, kb[i], vb[i], scale,
                                valid=triangle)
        out.append(o / l)
    out = jnp.concatenate(out, axis=1)
    if group > 1:
        out = out.reshape(b, s, group, kv_heads, -1).swapaxes(2, 3).reshape(
            b, s, h, -1)
    return out.astype(q.dtype)


def _selected_blockwise(q, k, v, block_size: int, scale: float,
                        keep: jax.Array) -> jax.Array:
    """:func:`_causal_blockwise` under a selection of keys: query tile i
    scans key tiles 0…i−1 under their bits and ends on its diagonal tile
    under the triangle and its bits. A row that sees no key of a tile passes
    it unchanged (``_online_block``'s finite stand-in)."""
    b, s, h, _ = q.shape
    if k.shape[1] != s or k.shape[2] != h or v.shape[2] != h:
        raise ValueError('a selection of keys is self-attention over equal '
                         'head counts')
    if keep.shape != (b, s, s // KEEP_BITS):
        raise ValueError(f'the selection is {keep.shape}, not '
                         f'{(b, s, s // KEEP_BITS)} packed words')
    block_size = min(block_size, s)
    if s % block_size:
        raise ValueError(f'causal attention needs the sequence ({s}) to be '
                         f'a multiple of block_size ({block_size})')
    n_blocks = s // block_size
    kb = k.reshape(b, n_blocks, block_size, h, -1).swapaxes(0, 1)
    vb = v.reshape(b, n_blocks, block_size, h, -1).swapaxes(0, 1)
    pos = jnp.arange(block_size)
    triangle = (pos[:, None] >= pos[None, :])[:, None, :]     # (q, 1, k)

    def step(qi, carry, blk):
        kb_j, vb_j, seen = blk
        return _online_block(qi, *carry, kb_j, vb_j, scale, valid=seen), None

    out = []
    for i in range(n_blocks):
        qi = q[:, i * block_size:(i + 1) * block_size]
        # (b, rows, s) bits → per key tile (tiles, b, rows, 1, keys)
        seen = jnp.moveaxis(unpack_keep(
            keep[:, i * block_size:(i + 1) * block_size], s).reshape(
                b, block_size, n_blocks, 1, block_size), 2, 0)
        carry = _online_init(qi, v.shape[-1])
        if i:
            carry, _ = lax.scan(partial(step, qi), carry,
                                (kb[:i], vb[:i], seen[:i]))
        _, l, o = _online_block(qi, *carry, kb[i], vb[i], scale,
                                valid=triangle & seen[i])
        out.append(o / l)
    return jnp.concatenate(out, axis=1).astype(q.dtype)


def rotary_interleaved(x: jax.Array, positions: jax.Array,
                       theta: float) -> jax.Array:
    """Rotary position code on interleaved pairs: ``(x[2i], x[2i+1])`` is
    the complex number rotated by ``positions · theta^(-2i/d)``. ``x`` is
    (..., S, H, d) with d even, ``positions`` (S,); the pairs stay where
    they are (no half-split re-layout), so q and k rotated alike keep their
    dot product's meaning."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq   # (S, d/2)
    cos = jnp.cos(angle)[:, None, :]
    sin = jnp.sin(angle)[:, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    re, im = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([re * cos - im * sin, re * sin + im * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rotary_half(x: jax.Array, positions: jax.Array,
                theta: float) -> jax.Array:
    """Rotary position code in the half-split form (GPT-NeoX, Qwen):
    ``(x[i], x[i + d/2])`` is the complex number rotated by
    ``positions · theta^(-2i/d)`` — the same angles as
    :func:`rotary_interleaved`, the pair's two members half a head apart.
    ``x`` is (..., S, H, d) with d even, ``positions`` (S,)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq   # (S, d/2)
    cos = jnp.cos(angle)[:, None, :]
    sin = jnp.sin(angle)[:, None, :]
    x32 = x.astype(jnp.float32)
    re, im = x32[..., :d // 2], x32[..., d // 2:]
    out = jnp.concatenate([re * cos - im * sin, re * sin + im * cos], axis=-1)
    return out.astype(x.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str,
                   scale: Optional[float] = None,
                   kv_valid: Optional[jax.Array] = None) -> jax.Array:
    """Sequence-parallel attention over a mesh axis (call under shard_map).

    Each device holds one (B, S/n, H, D) shard of q, k, v. KV shards rotate
    one ring hop per step (``lax.ppermute`` — neighbor traffic over ICI);
    after n steps every query has attended every key. Online softmax makes
    the accumulation order-invariant, so results match dense attention on
    the unsharded sequence to fp tolerance.

    ``kv_valid`` (S/n,) bool masks this device's PADDED key positions out
    of every query's softmax (it rotates around the ring with its KV
    shard) — how ragged token counts (e.g. a ViT's grid²+1) shard over a
    mesh axis that does not divide them. Rows of fully-masked q padding
    produce garbage (denominator from real keys only) — slice them off
    after gathering.
    """
    n = lax.psum(1, axis_name)
    sc = _scale(q, scale)
    perm = [(j, (j + 1) % n) for j in range(n)]
    synthesized_mask = kv_valid is None
    if synthesized_mask:
        kv_valid = jnp.ones(k.shape[1], bool)

    def step(i, carry):
        m, l, o, kb, vb, maskb = carry
        m, l, o = _online_block(q, m, l, o, kb, vb, sc, valid=maskb)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        maskb = lax.ppermute(maskb, axis_name, perm)
        return m, l, o, kb, vb, maskb

    # mark the constant-valued init as device-varying so the loop carry
    # type-checks under shard_map's varying-axis typing
    def cast(t):
        return lax.pcast(t, axis_name, to='varying')
    m, l, o = (cast(t) for t in _online_init(q))
    if synthesized_mask:   # caller-provided masks are already device-varying
        kv_valid = cast(kv_valid)
    # n-1 rotations interleaved with compute; the final block needs no send.
    m, l, o, kb, vb, maskb = lax.fori_loop(
        0, n - 1, step, (m, l, o, k, v, kv_valid))
    m, l, o = _online_block(q, m, l, o, kb, vb, sc, valid=maskb)
    return (o / l).astype(q.dtype)
