"""The selective state-space scan of Mamba-2 (SSD: "state space duality").

Dao & Gu, "Transformers are SSMs" (arXiv:2405.21060). For one head ``h`` of
``P`` channels, positions ``t`` of one window, a step size ``Δ_t > 0``, a
decay rate ``A < 0`` a head, and an input and an output projection ``B_t``,
``C_t`` of ``N`` numbers that every head shares (one group):

    state_t = exp(Δ_t A) · state_{t−1} + Δ_t · B_t ⊗ x_t      (N, P)    (1)
    y_t     = C_t · state_t + D · x_t                                   (2)

from a zero state at the window's start. Unrolled, (1)–(2) are a causal
attention whose weights are ``C_t · B_s`` times a decay:

    y_t = Σ_{s ≤ t} (C_t · B_s) · exp(cs_t − cs_s) · Δ_s x_s + D x_t,
    cs_t = Σ_{r ≤ t} Δ_r A                                              (3)

What runs is the **chunked form** (:func:`ssd_chunked`): chunks of ``Q``
positions; inside a chunk (3) restricted to the chunk, with ``cs`` the
chunk's own cumulative sum, kept in float32; every earlier position through
the state at the chunk's start, ``exp(cs_t) · C_t · state``; and (1) for
the whole chunk at once at its end, each position's input decayed to the
chunk's end. The decay of a pair is the exponential of a *difference* of
cumulative sums, masked above the diagonal before it is exponentiated, so
no exponential of a positive number is formed. A window that is no whole
number of chunks is padded with zero steps (no decay, no input) and cut
back.

``C Bᵀ`` is one ``Q × Q`` product a chunk, shared by every head.

The scan has two forms, chosen by :func:`resolve_ssd` from the platform,
the shapes and the ambient precision (static at trace time; no switch).
XLA's — ``lax.scan`` over chunks with the ``(H, N, P)`` state as the carry,
the decay matrices of all heads written out a chunk at a time — is the CPU
path and the oracle. On a TPU it is the Mosaic kernel ``ssd_scan`` of
``ops/pallas_ssd.py``, which keeps the state and every decay matrix in
VMEM. The step sizes, ``Δ·A`` and its cumulative sums are XLA's in both.

The state accumulates in float32 whatever the ambient matmul precision; the
products follow it (the kernel: ``ops.attention.KERNEL_PASSES`` bf16 passes).

Shapes: ``x`` (S, H·P) — the heads' channels side by side, as the mixer's
input projection writes them —, ``dt`` (S, H) — Δ, after its softplus —,
``a`` (H,) — A —, ``b``, ``c`` (S, N), ``d`` (H,); out (S, H·P).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def resolve_ssd(platform: str, s: int, heads: int, head_dim: int,
                state_dim: int, chunk: int, precision: Optional[str]) -> str:
    """Which form :func:`ssd_chunked` compiles for windows of ``s``
    positions, ``heads`` heads of ``head_dim`` channels, a state of
    ``state_dim`` and chunks of ``chunk`` on ``platform`` under the ambient
    matmul ``precision``: 'kernel' (``ops/pallas_ssd.py``'s ``ssd_scan``) or
    'xla'.

    The kernel applies on a TPU at whole chunks (``s`` a multiple of
    ``chunk``), where a chunk and the state are whole 128-lane blocks, a
    head's channels divide 128 lanes (a grid step takes heads in whole
    128-lane groups), the heads divide into the kernel's grid steps, and the
    precision is one the kernel has a lane for (``KERNEL_PASSES``).
    Anywhere else — the CPU, where it would run interpreted; a ragged
    window; 'highest' — XLA's form runs, which is also the oracle the
    kernel is tested against."""
    from video_features_tpu.ops import pallas_ssd as kernel
    from video_features_tpu.ops.attention import KERNEL_PASSES
    if platform != 'tpu' or precision not in KERNEL_PASSES:
        return 'xla'
    if (s % chunk or chunk % kernel.LANES or state_dim % kernel.LANES
            or kernel.LANES % head_dim):
        return 'xla'
    group = kernel.heads_per_step(heads, head_dim)
    if heads % group or group * head_dim % kernel.LANES:
        return 'xla'
    return 'kernel'


def chunk_decays(dt: jax.Array, a: jax.Array, chunk: int) -> jax.Array:
    """(S, H) step sizes and (H,) rates → (S, H) ``cs``: the cumulative sum
    of ``Δ·A`` from each chunk's start up to and including the position,
    float32 (S a multiple of ``chunk``). A tree of float32 adds
    (``lax.associative_scan``), not ``jnp.cumsum``: in the whole step
    compiled for a TPU, ``jnp.cumsum`` here left the features as far from
    the reference as one bf16 pass does (PERF.md §6), and a decay is the
    exponential of a difference of these sums."""
    s, h = dt.shape
    da = dt.astype(jnp.float32) * a.astype(jnp.float32)
    return lax.associative_scan(jnp.add, da.reshape(s // chunk, chunk, h),
                                axis=1).reshape(s, h)


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d: jax.Array, chunk: int,
                kernel_passes: Optional[int] = None) -> jax.Array:
    """The chunked form (module doc) over one window: ``y`` (S, H·P).
    A window shorter than ``chunk`` is one chunk; a ragged tail is padded
    with zero steps and cut back. ``kernel_passes`` (1 or 3 bf16 passes a
    product; None: XLA's form) sends the scan through
    ``ops/pallas_ssd.py``, where :func:`resolve_ssd` says it applies."""
    s, width = x.shape
    h = dt.shape[1]
    p = width // h
    chunk = min(chunk, s)
    n = -(-s // chunk)
    if n * chunk > s:
        x, dt, b, c = (jnp.pad(v, [(0, n * chunk - s)] + [(0, 0)] * (v.ndim - 1))
                       for v in (x, dt, b, c))
    f32 = jnp.float32
    cs = chunk_decays(dt, a, chunk)
    if kernel_passes is not None:
        from video_features_tpu.ops.pallas_ssd import ssd_scan
        y = ssd_scan(x, dt, cs, b, c, d, chunk, kernel_passes)
        return y[:s].astype(x.dtype)

    def blocks(v):
        return v.reshape((n, chunk) + v.shape[1:])

    pos = jnp.arange(chunk)
    seen = pos[:, None] >= pos[None, :]                     # (t, s)

    def step(state, blk):
        xi, dti, csi, bi, ci = blk
        pairs = jnp.einsum('tn,sn->ts', ci, bi, preferred_element_type=f32)
        csh = csi.T                                         # (h, t)
        decay = jnp.exp(jnp.where(seen, csh[:, :, None] - csh[:, None, :],
                                  -jnp.inf))
        dx = dti.astype(f32)[..., None] * xi                # (s, h, p)
        y = jnp.einsum('hts,shp->thp', pairs * decay, dx,
                       preferred_element_type=f32)
        # every earlier position, through the state at the chunk's start
        y = y + jnp.exp(csi)[..., None] * jnp.einsum(
            'tn,hnp->thp', ci, state, preferred_element_type=f32)
        # (1) for the whole chunk: each input decayed to the chunk's end
        to_end = jnp.exp(csi[-1] - csi)[..., None]          # (s, h, 1)
        state = jnp.exp(csi[-1])[:, None, None] * state + jnp.einsum(
            'sn,shp->hnp', bi, to_end * dx, preferred_element_type=f32)
        return state, y

    state = jnp.zeros((h, b.shape[-1], p), f32)
    _, y = lax.scan(step, state, (blocks(x.reshape(n * chunk, h, p)),
                                  blocks(dt), blocks(cs), blocks(b),
                                  blocks(c)))
    y = y.reshape(n * chunk, width)[:s] + jnp.repeat(d, p) * x[:s]
    return y.astype(x.dtype)
