"""Pallas TPU kernels for RAFT's correlation-pyramid window lookup.

Two kernels live here. The lane-packed :func:`lookup_corr_lanes` (bottom of
file) is the production TPU default (auto-dispatched by
models/raft.py::_resolve_auto_lookup; 14.3 → 26.9 clips/s/chip on the fused
I3D bench on v5e). The window-slice :func:`lookup_corr` below is the
``VFT_RAFT_LOOKUP=pallas`` alternate formulation of the same op; off-TPU the
dense-matmul lookup_corr_dense in models/raft.py is used instead.

The reference implements the lookup (reference models/raft/raft_src/corr.py:29-50)
as 81 independent bilinear samples per pixel per pyramid level — a gather of
``N·(2r+1)²·4corners·levels`` scattered elements from HBM on every one of the
20 GRU iterations. Gathers are the one access pattern TPUs do poorly; this
kernel removes them entirely using two structural facts:

1. The window offsets are **integers** (``d ∈ {-r..r}``), so the fractional
   part of every sample coordinate in a window is the same — all 81 samples
   share ONE pair of bilinear weights ``(wy, wx)``. The whole window is a
   single integer-aligned ``(2r+2)×(2r+2)`` patch read plus a 4-term blend
   of its shifted ``(2r+1)×(2r+1)`` views.
2. ``grid_sample(padding_mode='zeros')`` semantics can be *pre-baked* by
   zero-padding each pyramid level once, outside the 20-iteration scan, so
   the patch read needs no bounds masking inside the kernel.

Each pyramid level is padded by ``PAD = 2r+3`` and stored **transposed**
``(N, wp, hp)`` so the kernel can emit the reference's dy-major output
ordering (see models/raft.py lookup_corr — the reference adds ``(dy, dx)``
deltas onto ``(x, y)`` centroids, corr.py:38-44) without an in-kernel
transpose. Per pixel the kernel does one dynamic-slice VMEM read and four
fused multiply-adds over a 9×9 tile; per-pixel scalars (patch origin and
bilinear weights) arrive through SMEM blocks.

CPU tests run the same kernel under ``interpret=True``.

Numerics: the kernel is exact in ordering and padding semantics vs the XLA
gather path; per-element differences are fp-reorder noise (~1e-6 on real
corr magnitudes). Under RAFT's trained (contracting) update dynamics that
stays within the 2e-3 torch-parity tolerance; with random weights the
iteration is non-contracting and amplifies ulp noise, so cross-path tests
compare at few iterations only.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_N = 32


def _pad_block(n: int) -> int:
    return -n % BLOCK_N


def prep_pyramid(pyramid: Sequence[jax.Array], radius: int) -> List[jax.Array]:
    """Zero-pad + transpose each level once, outside the GRU scan.

    pyramid levels: (N, h, w, 1) → (N', w + 2·PAD, h + 2·PAD), padded with
    zeros (matching the reference's zeros padding_mode) and transposed so the
    kernel reads dy-major windows contiguously. N is also rounded up to a
    BLOCK_N multiple here — once, outside the 20-iteration GRU scan — so the
    per-iteration lookup never copies the pyramid.
    """
    pad = 2 * radius + 3
    out = []
    for corr in pyramid:
        c = jnp.squeeze(corr, -1)
        c = jnp.pad(c, [(0, _pad_block(c.shape[0])), (pad, pad), (pad, pad)])
        out.append(jnp.swapaxes(c, 1, 2))
    return out


def _level_kernel(p1: int):
    """Kernel over one pyramid level; p1 = 2r+1 (window side)."""
    p2 = p1 + 1

    def kernel(xs_ref, ys_ref, wx_ref, wy_ref, corr_ref, out_ref):
        hp = corr_ref.shape[2]

        def body(k, _):
            xs = xs_ref[k, 0]
            ys = ys_ref[k, 0]
            wx = wx_ref[k, 0]
            wy = wy_ref[k, 0]
            # corr is transposed: leading spatial dim is x, trailing is y.
            # Mosaic allows a dynamic-start slice on the sublane dim (xs) but
            # the lane dim demands 128-aligned starts — so read the full lane
            # extent and select the p2 columns at dynamic ys with a one-hot
            # matmul (iota-compare builds the selector; the MXU does the
            # "slice").
            rows = corr_ref[k, pl.ds(xs, p2), :]                  # (p2, hp)
            col = jax.lax.broadcasted_iota(jnp.int32, (hp, p2), 0)
            j = jax.lax.broadcasted_iota(jnp.int32, (hp, p2), 1)
            sel = (col == ys + j).astype(rows.dtype)              # (hp, p2)
            patch = jax.lax.dot_general(
                rows, sel, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)               # (p2, p2)
            out_ref[k, :, :] = (
                (1 - wx) * (1 - wy) * patch[0:p1, 0:p1]
                + wx * (1 - wy) * patch[1:p2, 0:p1]
                + (1 - wx) * wy * patch[0:p1, 1:p2]
                + wx * wy * patch[1:p2, 1:p2]
            )
            return 0

        jax.lax.fori_loop(0, out_ref.shape[0], body, 0)

    return kernel


def _lookup_level(corr_t: jax.Array, coords: jax.Array, radius: int,
                  interpret: bool) -> jax.Array:
    """One prepped level (N', wp, hp) + (N, 2) coords → (N, (2r+1)²).

    N' is the BLOCK_N-rounded row count from :func:`prep_pyramid`; only the
    per-call scalars are padded here. Output element ``i·(2r+1)+j`` is the
    sample at ``(x + d[i], y + d[j])`` — the reference's dy-major ordering.
    """
    n = coords.shape[0]
    n_pad, wp, hp = corr_t.shape
    assert n_pad == n + _pad_block(n), (n_pad, n)
    pad = 2 * radius + 3
    w, h = wp - 2 * pad, hp - 2 * pad
    p1 = 2 * radius + 1

    # Clamp so every window lands inside the zero-padded array. Anything
    # clamped was ≥ 1px outside the map on every sample → exactly 0 under
    # zeros padding, which the pad region reproduces.
    x = jnp.clip(coords[:, 0], -radius - 2.0, w + radius + 1.0)
    y = jnp.clip(coords[:, 1], -radius - 2.0, h + radius + 1.0)
    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    xs = (x0.astype(jnp.int32) - radius + pad)[:, None]
    ys = (y0.astype(jnp.int32) - radius + pad)[:, None]
    wx = (x - x0).astype(corr_t.dtype)[:, None]
    wy = (y - y0).astype(corr_t.dtype)[:, None]

    extra = _pad_block(n)
    if extra:
        xs, ys = (jnp.pad(a, [(0, extra), (0, 0)]) for a in (xs, ys))
        wx, wy = (jnp.pad(a, [(0, extra), (0, 0)]) for a in (wx, wy))

    scalar_spec = pl.BlockSpec((BLOCK_N, 1), lambda i: (i, 0),
                               memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _level_kernel(p1),
        grid=(n_pad // BLOCK_N,),
        in_specs=[scalar_spec, scalar_spec, scalar_spec, scalar_spec,
                  pl.BlockSpec((BLOCK_N, wp, hp), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((BLOCK_N, p1, p1), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad, p1, p1), corr_t.dtype),
        interpret=interpret,
        # the name the device trace and the lowered module show the
        # Mosaic call under (one call per pyramid level)
        name='raft_corr_lookup',
    )(xs, ys, wx, wy, corr_t)
    return out[:n].reshape(n, p1 * p1)


def lookup_corr(prepped: Sequence[jax.Array], coords: jax.Array,
                radius: int = 4, interpret: bool = False) -> jax.Array:
    """Sample (2r+1)² windows at every level of a prepped pyramid.

    prepped: output of :func:`prep_pyramid`; coords: (B, H, W, 2) level-0
    (x, y) pixel positions. Returns (B, H, W, levels·(2r+1)²), bit-identical
    in ordering and padding semantics to the XLA gather path
    (models/raft.py lookup_corr).
    """
    b, hh, ww, _ = coords.shape
    flat = coords.reshape(b * hh * ww, 2)
    out = [_lookup_level(corr_t, flat / (2.0 ** i), radius, interpret)
           for i, corr_t in enumerate(prepped)]
    return jnp.concatenate(out, axis=-1).reshape(b, hh, ww, -1)


# ---------------------------------------------------------------------------
# Lane-packed variant: 128 pixels per lane tile, mask-reduce window sums.
#
# The window-slice kernel above iterates pixels serially; this one packs 128
# pixels into the lane dimension and extracts windows with iota-compare
# masks + reductions — pure VPU work with no dynamic slicing at all, so it
# both satisfies Mosaic's layout rules and vectorizes fully. Out-of-range
# window indices simply never match the iota, which reproduces the
# reference's zeros padding_mode without any pre-padding.

LANES = 128


def prep_pyramid_lanes(pyramid: Sequence[jax.Array]) -> List[jax.Array]:
    """(N, h, w, 1) levels → (h, w, N') with N' padded to a LANES multiple."""
    out = []
    for corr in pyramid:
        c = jnp.squeeze(corr, -1)                        # (N, h, w)
        pad = -c.shape[0] % LANES
        c = jnp.pad(c, [(0, pad), (0, 0), (0, 0)])
        out.append(c.transpose(1, 2, 0))                 # (h, w, N')
    return out


def prep_pyramid_lanes_fused(fmap1: jax.Array, fmap2: jax.Array,
                             levels: int = 4) -> List[jax.Array]:
    """Feature maps → lane-layout pyramid DIRECTLY, no (N, h, w) detour
    and no giant-volume pooling.

    Two compounding reformulations over ``build_corr_pyramid`` +
    :func:`prep_pyramid_lanes` (which materialized the ~2 GB level-0
    volume in (N, h, w) layout, physically transposed it to the kernel's
    (h, w, N') layout, then average-pooled the volume three times — the
    worst HBM pattern in the fused step, 106.8 ms of the 362 ms fixed
    phase at batch-16 CLI geometry vs a ~10-20 ms traffic floor):

    The einsum emits straight into (h, w, b·n) lane order and the
    levels pool over the LEADING axes (lane dim stays minor, sequential
    HBM traffic): 106.8 → 74.8 ms isolated, headline 9.44 → 9.69
    clips/s. Same valid 2×2/stride-2 window set as ``avg_pool`` (odd
    trailing row/col dropped); numerics at 1e-9-class reassociation
    noise vs the two-step path, pinned by tests/test_pallas_corr.py.

    Tried and rejected: pooling commutes with the dot product, so each
    level can be computed as ⟨f1, avgpool^L(fmap2)⟩ with no giant-volume
    pooling at all — 74.8 → 32.1 ms ISOLATED, but 9.69 → 9.53 clips/s
    in the fused step (consistent across runs): re-reading the ~360 MB
    f1 operand for four einsums costs the composed graph more than the
    volume pooling it saves. End-to-end wins; the isolated number lies.
    """
    B, H, W, D = fmap1.shape
    f1 = fmap1.reshape(B, H * W, D)
    corr_t = jnp.einsum('bnd,bhwd->hwbn', f1, fmap2) / jnp.sqrt(
        jnp.asarray(D, fmap1.dtype))
    corr_t = corr_t.reshape(H, W, B * H * W)
    pad = -corr_t.shape[-1] % LANES
    corr_t = jnp.pad(corr_t, [(0, 0), (0, 0), (0, pad)])
    out = [corr_t]
    for _ in range(levels - 1):
        h, w, n = corr_t.shape
        h2, w2 = h // 2, w // 2
        corr_t = corr_t[:h2 * 2, :w2 * 2].reshape(h2, 2, w2, 2, n).mean((1, 3))
        out.append(corr_t)
    return out


def _lanes_kernel(p1: int, h: int, w: int):
    """Kernel over one level, one 128-pixel lane tile; p1 = 2r+1."""
    p2 = p1 + 1
    r = (p1 - 1) // 2

    def kernel(xi_ref, yi_ref, fx_ref, fy_ref, corr_ref, out_ref):
        corr = corr_ref[...]                              # (h, w, LANES)
        fx = fx_ref[0, :]                                 # (LANES,)
        fy = fy_ref[0, :]
        xi = xi_ref[0, :]
        yi = yi_ref[0, :]
        iota_w = jax.lax.broadcasted_iota(jnp.int32, (w, LANES), 0)
        iota_h = jax.lax.broadcasted_iota(jnp.int32, (h, LANES), 0)

        # x pass: S_k[h, n] = Σ_w corr[h, w, n] · [w == xi_n + (k - r)]
        s = []
        for k in range(p2):
            mask = (iota_w == (xi[None, :] + (k - r))).astype(corr.dtype)
            s.append(jnp.sum(corr * mask[None, :, :], axis=1))   # (h, LANES)
        # bilinear x blend: consecutive sums share the shifted index
        rows = [(1 - fx)[None, :] * s[i] + fx[None, :] * s[i + 1]
                for i in range(p1)]                              # 9 × (h, LANES)

        # y pass: the k-masks are row-independent, so compute them once and
        # contract every row against them; single stacked store at the end
        # (81 scattered single-sublane stores compile poorly)
        masks_h = [(iota_h == (yi[None, :] + (k - r))).astype(corr.dtype)
                   for k in range(p2)]
        outs = []
        for i in range(p1):
            v = [jnp.sum(rows[i] * masks_h[k], axis=0) for k in range(p2)]
            outs.extend((1 - fy) * v[j] + fy * v[j + 1] for j in range(p1))
        out_ref[...] = jnp.stack(outs, axis=0)                   # (81, LANES)

    return kernel


def _lookup_level_lanes(corr_t: jax.Array, coords: jax.Array, radius: int,
                        interpret: bool) -> jax.Array:
    """One (h, w, N') level + (N, 2) coords → (N, (2r+1)²)."""
    n = coords.shape[0]
    h, w, n_pad = corr_t.shape
    p1 = 2 * radius + 1

    x = coords[:, 0]
    y = coords[:, 1]
    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    xi = x0.astype(jnp.int32)[None, :]                   # window base (x)
    yi = y0.astype(jnp.int32)[None, :]
    fx = (x - x0).astype(corr_t.dtype)[None, :]
    fy = (y - y0).astype(corr_t.dtype)[None, :]

    extra = n_pad - n
    if extra:
        xi, yi, fx, fy = (jnp.pad(a, [(0, 0), (0, extra)])
                          for a in (xi, yi, fx, fy))

    vec_spec = pl.BlockSpec((1, LANES), lambda t: (0, t),
                            memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _lanes_kernel(p1, h, w),
        grid=(n_pad // LANES,),
        in_specs=[vec_spec, vec_spec, vec_spec, vec_spec,
                  pl.BlockSpec((h, w, LANES), lambda t: (0, 0, t),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((p1 * p1, LANES), lambda t: (0, t),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((p1 * p1, n_pad), corr_t.dtype),
        interpret=interpret,
        name='raft_corr_lookup_lanes',
    )(xi, yi, fx, fy, corr_t)
    return out[:, :n].T                                  # (N, 81)


def lookup_corr_lanes(prepped: Sequence[jax.Array], coords: jax.Array,
                      radius: int = 4, interpret: bool = False) -> jax.Array:
    """Lane-packed lookup over a :func:`prep_pyramid_lanes` pyramid.

    Same output as models/raft.py lookup_corr (dy-major ordering, zeros
    padding): element ``i·(2r+1)+j`` samples ``(x + d[i], y + d[j])``.
    """
    b, hh, ww, _ = coords.shape
    flat = coords.reshape(b * hh * ww, 2)
    out = [_lookup_level_lanes(corr_t, flat / (2.0 ** i), radius, interpret)
           for i, corr_t in enumerate(prepped)]
    return jnp.concatenate(out, axis=-1).reshape(b, hh, ww, -1)
