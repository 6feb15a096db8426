"""Pallas TPU kernel for RAFT's correlation-pyramid window lookup.

The reference implements the lookup (reference models/raft/raft_src/corr.py:29-50)
as 81 independent bilinear samples per pixel per pyramid level — a gather of
``N·(2r+1)²·4corners·levels`` scattered elements from HBM on every one of the
20 GRU iterations. Gathers are the one access pattern TPUs do poorly;
:func:`lookup_corr_planes` removes them entirely using two structural facts:

1. The window offsets are **integers** (``d ∈ {-r..r}``), so the fractional
   part of every sample coordinate in a window is the same — all 81 samples
   share ONE pair of bilinear weights ``(fy, fx)``. The whole window is a
   4-term blend of integer-aligned ``(2r+2)×(2r+2)`` window values.
2. A grid step holds **1,024 pixels as one (8, 128) vreg plane**, 8 sublanes
   by 128 lanes, and a level's ``(h, w)`` plane on the block's LEADING axes:
   ``(h, w, 8, 128)``. Each window value is then a chain of selects over
   whole vregs — ``S_k[h] = corr[h, bx + k]`` picked by one compare of the
   (8, 128) integer base against each column index, and the same over rows —
   with no cross-sublane reduction, no dynamic slicing and no relayout; each
   of a level's 81 outputs is one full vreg. A window index off the plane
   never matches, which reproduces ``grid_sample(padding_mode='zeros')``
   without any pre-padding.

Pixels are ordered ``(h, w, b)`` — the batch minor, as the update scan lays
its planes out — and padded to whole tiles (:func:`pixel_rows`). The four
level calls write ONE ``(levels·81, rows, 128)`` buffer in place
(``input_output_aliases``), level ``l`` rows ``81·l … 81·l + 80`` of its
leading axis, and ``convc1`` contracts that axis directly
(:func:`conv_from_lanes`): nothing is concatenated, transposed or re-tiled
between the calls and the product. A level whose plane is larger than
``BLOCK_BYTES`` a tile is cut into chunks of whole rows (a stretch of one row
where even a row is larger), the sums carried across grid steps
(:func:`chunks`); the one algorithm at every shape.

The output keeps the reference's dy-major ordering (see models/raft.py
lookup_corr — the reference adds ``(dy, dx)`` deltas onto ``(x, y)``
centroids, corr.py:38-44). It is the lookup ``models/raft.py::resolve_lookup``
picks on a TPU; elsewhere the dense-matmul ``lookup_corr_dense`` in
models/raft.py runs instead, and the XLA gather ``lookup_corr`` there is the
oracle the tests compare both against (tests/test_corr_lookup.py). CPU tests
run the same kernel body under ``interpret=True``.

Numerics: each window value is one element of the level, exactly, and the
blends are the reference's 4-term expressions; per-element differences from
the XLA gather path are fp-reorder noise of the bilinear sum (~1e-6 on real
corr magnitudes). Under RAFT's trained (contracting) update dynamics that
stays within the 2e-3 torch-parity tolerance; with random weights the
iteration is non-contracting and amplifies ulp noise, so cross-path tests
compare at few iterations only.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES          # pixels a grid step: one (8, 128) plane
BLOCK_BYTES = 8 * 2 ** 20        # the most of a level's plane a step holds


def pixel_rows(n: int) -> int:
    """Rows of 128 lanes that hold ``n`` pixels, in whole tiles."""
    return -(-n // TILE) * SUBLANES


def _to_rows(v: jax.Array) -> jax.Array:
    """(..., n) → (..., pixel_rows(n), 128), zero padded."""
    n = v.shape[-1]
    v = jnp.pad(v, [(0, 0)] * (v.ndim - 1)
                + [(0, pixel_rows(n) * LANES - n)])
    return v.reshape(*v.shape[:-1], -1, LANES)


def pixels(planes: jax.Array) -> jax.Array:
    """(C, B, H, W) planes → (C, rows, 128): pixels in (h, w, b) order."""
    return _to_rows(planes.transpose(0, 2, 3, 1).reshape(planes.shape[0], -1))


def unpack(buf: jax.Array, shape: Tuple[int, int, int]) -> jax.Array:
    """A (C, rows, 128) :func:`lookup_corr_planes` result → (B, H, W, C)."""
    b, h, w = shape
    c = buf.shape[0]
    return buf.reshape(c, -1)[:, :b * h * w].reshape(c, h, w, b).transpose(
        3, 1, 2, 0)


def conv_from_lanes(buf: jax.Array, kernel: jax.Array, bias: jax.Array,
                    shape: Tuple[int, int, int]) -> jax.Array:
    """The 1×1 convolution ``conv(unpack(buf, shape), kernel, bias=bias)``
    over ``buf``'s leading (channel) axis as it stands — (C, rows, 128) in,
    (B, H, W, O) out: the same products and sums, the order of summation
    aside. It is a 1-wide convolution whose one spatial axis is ``rows``
    and whose batch is the 128 lanes, so ``(rows, 128)`` is never split:
    the product writes (rows, 128, O), whose pixel axes are major, and
    their (h, w, b) order becomes (B, H, W) without moving a byte where B
    is a multiple of 8. (As an einsum the compiler moves the split of
    ``rows`` into the product, which then wants the channels between
    ``rows`` and the lanes: a transposing copy of the buffer.)"""
    b, h, w = shape
    o = kernel.shape[-1]
    out = jax.lax.conv_general_dilated(
        buf, kernel.reshape(-1, o, 1).astype(buf.dtype), (1,), 'VALID',
        dimension_numbers=('CWN', 'IOW', 'WNC'))         # (rows, 128, O)
    out = out.reshape(-1, o)[:b * h * w].reshape(h, w, b, o)
    return out.transpose(2, 0, 1, 3) + bias.astype(out.dtype)


def prep_pyramid_lanes(pyramid: Sequence[jax.Array],
                       batch: int) -> List[jax.Array]:
    """(N, h, w, 1) levels of ``batch`` maps, N in (b, hq, wq) order → the
    kernel's (h, w, rows, 128) levels, pixels in (hq, wq, b) order."""
    out = []
    for corr in pyramid:
        n, h, w, _ = corr.shape
        c = corr.reshape(batch, n // batch, h, w).transpose(2, 3, 1, 0)
        out.append(_to_rows(c.reshape(h, w, n)))
    return out


def prep_pyramid_lanes_fused(fmap1: jax.Array, fmap2: jax.Array,
                             levels: int = 4) -> List[jax.Array]:
    """Feature maps → the kernel's pyramid DIRECTLY, no (N, h, w) detour
    and no giant-volume pooling.

    Two compounding reformulations over ``build_corr_pyramid`` +
    :func:`prep_pyramid_lanes`, which materialise the level-0 volume in
    (N, h, w) layout, physically transpose it to the kernel's layout, then
    average-pool the volume three times — the worst HBM pattern of the
    fused step's fixed phase.

    The einsum emits straight into (h, w, hq, wq, b) order and the levels
    pool over the LEADING axes (the (rows, 128) pixel tile stays minor,
    sequential HBM traffic). Same valid 2×2/stride-2 window set as
    ``avg_pool`` (odd trailing row/col dropped); numerics at 1e-9-class
    reassociation noise vs the two-step path, pinned by
    tests/test_corr_lookup.py.

    Tried and rejected: pooling commutes with the dot product, so each
    level can be computed as ⟨f1, avgpool^L(fmap2)⟩ with no giant-volume
    pooling at all — faster alone, slower in the fused step: re-reading
    the f1 operand for four einsums costs the composed graph more than
    the volume pooling it saves. End to end decides, not the isolated
    number.
    """
    B, H, W, D = fmap1.shape
    f1 = fmap1.reshape(B, H * W, D)
    corr = jnp.einsum('bnd,bhwd->hwnb', f1, fmap2) / jnp.sqrt(
        jnp.asarray(D, fmap1.dtype))
    corr = _to_rows(corr.reshape(H, W, H * W * B))
    out = [corr]
    for _ in range(levels - 1):
        h, w, rows, _ = corr.shape
        h2, w2 = h // 2, w // 2
        corr = corr[:h2 * 2, :w2 * 2].reshape(
            h2, 2, w2, 2, rows, LANES).mean((1, 3))
        out.append(corr)
    return out


def chunks(h: int, w: int) -> Tuple[int, int]:
    """The (h, w) chunk of a level's plane that one grid step holds: the
    whole plane where its ``h·w·4 KiB`` fit ``BLOCK_BYTES``, else whole rows,
    else a stretch of one row — each the largest that divides its axis, so
    no step reads past the plane."""
    cap = BLOCK_BYTES // (TILE * 4)              # positions a step: 2,048
    if h * w <= cap:
        return h, w
    if w <= cap:
        return max(d for d in range(1, h + 1)
                   if h % d == 0 and d * w <= cap), w
    return 1, max(d for d in range(1, cap + 1) if w % d == 0)


def vmem_bytes(hc: int, wc: int, p1: int) -> int:
    """The VMEM a call's blocks and scratch need — the corr chunk, the four
    coordinate planes and the 81 outputs double-buffered, the x pass's rows
    and the carried sums — with 8 MiB for what the compiler keeps besides."""
    vreg = TILE * 4
    return (2 * (hc * wc + 4 + p1 * p1) + p1 * hc + (p1 + 1) * (hc + p1)
            ) * vreg + 8 * 2 ** 20


def _lanes_kernel(p1: int, hc: int, wc: int, nh: int, nw: int,
                  aliased: bool):
    """Kernel over one level's (hc, wc) chunk and one 1,024-pixel tile;
    p1 = 2r+1. Grid (tile, h chunk, w chunk)."""
    p2 = p1 + 1
    r = (p1 - 1) // 2

    def window(sums, base, values):
        """sums[k] ← values[u] where ``base + k == u``: a chain of selects
        over the chunk's indices. One index matches a tap, or none off the
        plane (zeros padding), so each sum is one element, exactly."""
        masks = {}
        for u, value in enumerate(values):
            for k in range(p2):
                if u - k not in masks:
                    masks[u - k] = base == u - k
                sums[k] = jnp.where(masks[u - k], value, sums[k])
        return sums

    def kernel(xi_ref, yi_ref, fx_ref, fy_ref, corr_ref, *refs):
        refs = list(refs[1:] if aliased else refs)   # the buffer: untouched
        out_ref, rows_ref = refs.pop(0), refs.pop(0)
        xacc_ref = refs.pop(0) if nw > 1 else None   # x sums across w chunks
        yacc_ref = refs.pop(0) if nh > 1 else None   # y sums across h chunks
        jh, jw = pl.program_id(1), pl.program_id(2)
        fx, fy = fx_ref[...], fy_ref[...]            # (8, 128)
        # the chunk column (row) that the window's first tap falls on
        bx = xi_ref[...] - (r + jw * wc)
        by = yi_ref[...] - (r + jh * hc)
        zero = jnp.zeros((SUBLANES, LANES), corr_ref.dtype)

        def blend_x(h, s):
            for i in range(p1):
                rows_ref[i, h] = (1 - fx) * s[i] + fx * s[i + 1]

        def x_row(h, _):                             # S_k[h] = corr[h, bx + k]
            s = ([xacc_ref[h, k] for k in range(p2)] if nw > 1
                 else [zero] * p2)
            s = window(s, bx, [corr_ref[h, w] for w in range(wc)])
            if nw > 1:
                for k in range(p2):
                    xacc_ref[h, k] = s[k]
            else:
                blend_x(h, s)
            return 0

        def y_pass():                  # V_ik = rows_i[by + k], then 81 outputs
            for i in range(p1):
                v = (tuple(yacc_ref[i, k] for k in range(p2)) if nh > 1
                     else (zero,) * p2)
                v = jax.lax.fori_loop(0, hc, lambda h, v: tuple(window(
                    list(v), by - h, [rows_ref[i, h]])), v)
                if nh > 1:
                    for k in range(p2):
                        yacc_ref[i, k] = v[k]
                    pl.when(jh == nh - 1)(lambda: write(i, v))
                else:
                    write(i, v)

        def write(i, v):
            for j in range(p1):
                out_ref[i * p1 + j] = (1 - fy) * v[j] + fy * v[j + 1]

        if nw > 1:
            @pl.when(jw == 0)
            def _():
                xacc_ref[...] = jnp.zeros_like(xacc_ref)
        if nh > 1:
            @pl.when((jh == 0) & (jw == 0))
            def _():
                yacc_ref[...] = jnp.zeros_like(yacc_ref)

        jax.lax.fori_loop(0, hc, x_row, 0)
        if nw > 1:
            @pl.when(jw == nw - 1)
            def _():
                for h in range(hc):
                    blend_x(h, [xacc_ref[h, k] for k in range(p2)])
                y_pass()
        else:
            y_pass()

    return kernel


def _lookup_level(corr: jax.Array, xi: jax.Array, yi: jax.Array,
                  fx: jax.Array, fy: jax.Array, level: int, levels: int,
                  radius: int, buf: Optional[jax.Array],
                  interpret: bool) -> jax.Array:
    """One (h, w, rows, 128) level + its centroids' (rows, 128) integer
    bases and fractions → the (levels·81, rows, 128) result buffer with
    this level's 81 rows written: into ``buf`` in place, or a new buffer
    (level 0), whose other rows the later levels write."""
    h, w, rows, _ = corr.shape
    hc, wc = chunks(h, w)
    nh, nw = h // hc, w // wc
    p1 = 2 * radius + 1
    vec = pl.BlockSpec((SUBLANES, LANES), lambda t, i, j: (t, 0))
    in_specs = [vec] * 4 + [pl.BlockSpec((hc, wc, SUBLANES, LANES),
                                         lambda t, i, j: (i, j, t, 0))]
    operands = [xi, yi, fx, fy, corr]
    if buf is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(buf)
    vreg = (SUBLANES, LANES)
    scratch = [pltpu.VMEM((p1, hc, *vreg), corr.dtype)]
    if nw > 1:
        scratch.append(pltpu.VMEM((hc, p1 + 1, *vreg), corr.dtype))
    if nh > 1:
        scratch.append(pltpu.VMEM((p1, p1 + 1, *vreg), corr.dtype))
    return pl.pallas_call(
        _lanes_kernel(p1, hc, wc, nh, nw, buf is not None),
        grid=(rows // SUBLANES, nh, nw),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((p1 * p1, *vreg),
                               lambda t, i, j: (level, t, 0)),
        out_shape=jax.ShapeDtypeStruct((levels * p1 * p1, rows, LANES),
                                       corr.dtype),
        scratch_shapes=scratch,
        input_output_aliases={5: 0} if buf is not None else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary'),
            vmem_limit_bytes=vmem_bytes(hc, wc, p1)),
        interpret=interpret,
        name='raft_corr_lookup_lanes',
    )(*operands)


def lookup_corr_planes(prepped: Sequence[jax.Array], planes: jax.Array,
                       radius: int = 4, interpret: bool = False) -> jax.Array:
    """The lookup over a :func:`prep_pyramid_lanes_fused` pyramid, the
    centroids given as (2, B, H, W) planes — x then y. Returns the
    (levels·(2r+1)², rows, 128) buffer the four level calls write, pixels in
    :func:`pixels` order: :func:`conv_from_lanes` contracts it,
    :func:`unpack` gives (B, H, W, levels·(2r+1)²).

    Same values as models/raft.py lookup_corr (dy-major ordering, zeros
    padding): channel ``81·l + i·(2r+1) + j`` samples level ``l`` at
    ``(x + d[i], y + d[j])``.
    """
    xy = pixels(planes)                                  # (2, rows, 128)
    buf = None
    for level, corr in enumerate(prepped):
        c = xy / (2.0 ** level)
        base = jnp.floor(c)
        frac = (c - base).astype(corr.dtype)
        base = base.astype(jnp.int32)
        buf = _lookup_level(corr, base[0], base[1], frac[0], frac[1], level,
                            len(prepped), radius, buf, interpret)
    return buf


def lookup_corr_lanes(prepped: Sequence[jax.Array], coords: jax.Array,
                      radius: int = 4, interpret: bool = False) -> jax.Array:
    """:func:`lookup_corr_planes` for (B, H, W, 2) ``(x, y)`` centroids,
    returning (B, H, W, levels·(2r+1)²): the signature of models/raft.py's
    other lookups."""
    buf = lookup_corr_planes(prepped, jnp.moveaxis(coords, -1, 0), radius,
                             interpret)
    return unpack(buf, coords.shape[:3])
