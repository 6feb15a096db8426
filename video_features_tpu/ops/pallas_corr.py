"""Pallas TPU kernel for RAFT's correlation-pyramid window lookup.

The reference implements the lookup (reference models/raft/raft_src/corr.py:29-50)
as 81 independent bilinear samples per pixel per pyramid level — a gather of
``N·(2r+1)²·4corners·levels`` scattered elements from HBM on every one of the
20 GRU iterations. Gathers are the one access pattern TPUs do poorly;
:func:`lookup_corr_lanes` removes them entirely using two structural facts:

1. The window offsets are **integers** (``d ∈ {-r..r}``), so the fractional
   part of every sample coordinate in a window is the same — all 81 samples
   share ONE pair of bilinear weights ``(fy, fx)``. The whole window is a
   4-term blend of integer-aligned ``(2r+2)×(2r+2)`` window sums.
2. 128 pixels are packed into the lane dimension (levels stored
   ``(h, w, N')``) and windows are extracted with iota-compare masks +
   reductions — pure VPU work with no dynamic slicing at all, so it both
   satisfies Mosaic's layout rules and vectorizes fully. Out-of-range
   window indices simply never match the iota, which reproduces
   ``grid_sample(padding_mode='zeros')`` without any pre-padding.

The output keeps the reference's dy-major ordering (see models/raft.py
lookup_corr — the reference adds ``(dy, dx)`` deltas onto ``(x, y)``
centroids, corr.py:38-44). It is the lookup ``models/raft.py::resolve_lookup``
picks on a TPU within the VMEM budget; elsewhere the dense-matmul
``lookup_corr_dense`` in models/raft.py runs instead, and the XLA gather
``lookup_corr`` there is the oracle the tests compare both against
(tests/test_corr_lookup.py). CPU tests run the same kernel body under
``interpret=True``.

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
    :func:`prep_pyramid_lanes`, which materialise the level-0 volume in
    (N, h, w) layout, physically transpose it to the kernel's (h, w, N')
    layout, then average-pool the volume three times — the worst HBM
    pattern of the fused step's fixed phase.

    The einsum emits straight into (h, w, b·n) lane order and the
    levels pool over the LEADING axes (lane dim stays minor, sequential
    HBM traffic). Same valid 2×2/stride-2 window set as ``avg_pool`` (odd
    trailing row/col dropped); numerics at 1e-9-class reassociation
    noise vs the two-step path, pinned by tests/test_corr_lookup.py.

    Tried and rejected: pooling commutes with the dot product, so each
    level can be computed as ⟨f1, avgpool^L(fmap2)⟩ with no giant-volume
    pooling at all — faster alone, slower in the fused step: re-reading
    the f1 operand for four einsums costs the composed graph more than
    the volume pooling it saves. End to end decides, not the isolated
    number.
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


def _lookup_level_lanes(corr_t: jax.Array, x: jax.Array, y: jax.Array,
                        radius: int, interpret: bool) -> jax.Array:
    """One (h, w, N') level + the (N,) x and y of its centroids →
    (N, (2r+1)²)."""
    n = x.shape[0]
    h, w, n_pad = corr_t.shape
    p1 = 2 * radius + 1

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


def lookup_corr_planes(prepped: Sequence[jax.Array], planes: jax.Array,
                       radius: int = 4, interpret: bool = False) -> jax.Array:
    """Lane-packed lookup over a :func:`prep_pyramid_lanes` pyramid, the
    centroids given as (2, B, H, W) planes — x then y: flattened, a plane IS
    the kernel's lane-dense (1, N) vector, so nothing is cut out of a
    2-wide minor axis once a level. Returns (B, H, W, levels·(2r+1)²).

    Same output as models/raft.py lookup_corr (dy-major ordering, zeros
    padding): element ``i·(2r+1)+j`` samples ``(x + d[i], y + d[j])``.
    """
    x, y = planes.reshape(2, -1)
    out = [_lookup_level_lanes(corr_t, x / (2.0 ** i), y / (2.0 ** i),
                               radius, interpret)
           for i, corr_t in enumerate(prepped)]
    return jnp.concatenate(out, axis=-1).reshape(*planes.shape[1:], -1)


def lookup_corr_lanes(prepped: Sequence[jax.Array], coords: jax.Array,
                      radius: int = 4, interpret: bool = False) -> jax.Array:
    """:func:`lookup_corr_planes` for (B, H, W, 2) ``(x, y)`` centroids:
    the signature of models/raft.py's other lookups."""
    return lookup_corr_planes(prepped, jnp.moveaxis(coords, -1, 0), radius,
                              interpret)
