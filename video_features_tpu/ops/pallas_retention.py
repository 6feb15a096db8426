"""Pallas TPU kernels for power retention's state: φ never leaves VMEM.

``ops/retention.py::retention_chunked`` reads the carried state through
``φ(q) · S`` and writes it through ``φ(k)ᵀ v``. As XLA fusions, φ of a
chunk's queries is first written to HBM — 40 heads × 512 positions × 8,256
float32 = 676 MB in ``brumby.corpus``, built by 65 fusions of one lane
rotation and one product each — and then read back by the product that
contracts it away (PERF.md §5, PR 31: 2.47 ms of a chunk-layer's 3.90 for
0.66 ms of MXU work). Here two Mosaic kernels form φ a block at a time from
the resident q (or k) tile and feed it to the MXU in the same breath:

* block ``r`` of φ is ``coef_r · x · roll(x, −r)`` over the head's ``d``
  lanes (``power_features``: ``coef_0 = 1``, else √2; at ``r = d/2`` only
  the first half of the lanes is a new pair) — one rotation (``pltpu.roll``,
  the shift a loop index), one multiply, float32, √2 folded into ``x`` once
  a tile in float32;
* **state read** (``retention_read`` in traces): per key-value head, the
  rows of its query heads (R · chunk of them) against the head's
  ``(D, d_v)`` state: ``Σ_r φ_r(q) @ S[r·d:(r+1)·d]``, accumulated in the
  float32 output block. The state is split into its bf16 parts once a head,
  into VMEM scratch, the half block padded there to a whole one with zero
  rows (so φ's duplicate half meets zeros: + 0.8 % MXU work, no lane slice);
* **state update** (``retention_update``): per key-value head,
  ``S[r·d:(r+1)·d] ← decay · S[r·d:(r+1)·d] + φ_r(k)ᵀ @ v``, in place. The
  keys come *transposed*, ``(d, chunk)`` — features on sublanes, positions
  on lanes — so that ``φ_r(k)ᵀ`` is a sublane rotation of the tile and the
  product a plain (rows of D) × (positions) × (d_v) one with v as the
  stationary operand; several blocks are stacked a product so the MXU
  streams more rows a weight tile. The decay of each key to the chunk's end
  is the caller's, folded into ``v``; the old state's decay over the chunk
  is a scalar a head, applied with the sum as the product leaves the MXU;
* **the passes are made here**, as in ``ops/pallas_attention.py`` (Mosaic
  makes one bf16 pass a dot): 3 (ambient ``high``) splits φ and the state
  (or v) into a bf16 head and remainder and contracts ``[hi hi lo]`` against
  ``[hi; lo; hi]`` in one product; 1 (ambient ``default``) is the heads
  alone. State, φ and accumulators are float32. ``highest`` has no lane
  here (``ops.retention.resolve_retention``).

CPU tests run the same bodies interpreted, under
``pltpu.force_tpu_interpret_mode()`` (tests/test_pallas_retention.py).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from video_features_tpu.ops.pallas_attention import LANES, _ONE_PASS, _split

READ_NAME = 'retention_read'
UPDATE_NAME = 'retention_update'
# rows of a head's queries the read kernel takes at a time (all 5 × 512 of
# a brumby.corpus chunk), the rotations its loop makes a step and the blocks
# the update stacks a product: my chip runs, PR 32 (PERF.md §6)
READ_ROWS = 2560
READ_UNROLL = 4
UPDATE_BLOCKS = 9
# the read holds a head's state three times (the float32 block double
# buffered, its bf16 parts: 14.8 MB at d = 128 under three passes) beside
# the row tile's φ and parts: past Mosaic's 16 MB default, well inside a v5e
# core's 128 MB. ``resolve_retention`` keeps heads whose state passes
# STATE_VMEM_BYTES on the XLA path (d = 256: 59 MB).
VMEM_LIMIT_BYTES = 64 * 2 ** 20
STATE_VMEM_BYTES = 32 * 2 ** 20
ROOT2 = math.sqrt(2.0)


def full_blocks(d: int) -> int:
    """Whole ``d``-lane blocks of φ (rotations 0 … d/2 − 1); the half block
    of rotation d/2 follows them."""
    return d // 2


def state_vmem_bytes(d: int, d_v: int, passes: int) -> int:
    """VMEM the read kernel holds a head's state in: the float32 block twice
    (the pipeline's two buffers) and its bf16 parts."""
    return d * (d + 1) // 2 * d_v * (2 * 4 + 2 * passes)


def row_tile(t: int, block_rows: int = READ_ROWS) -> int:
    """Rows the read kernel takes at a time of a head's ``t``: all of them,
    or their largest divisor that is at most ``block_rows`` and a whole
    number of 8-row sublane tiles."""
    if t <= block_rows:
        return t
    for rows in range(block_rows - block_rows % 8, 0, -8):
        if t % rows == 0:
            return rows
    raise ValueError(f'state_read: {t} rows have no divisor of at most '
                     f'{block_rows} that is a multiple of 8')


def _lane_parts(phi: jax.Array, passes: int) -> jax.Array:
    """A float32 operand → the bf16 one its product runs over: itself, or
    ``[hi hi lo]`` side by side on the contraction (last) axis."""
    if passes == 1:
        return phi.astype(jnp.bfloat16)
    hi, lo = _split(phi)
    return jnp.concatenate([hi, hi, lo], axis=1).astype(jnp.bfloat16)


def _row_parts(w: jax.Array, passes: int) -> jax.Array:
    """The other operand: ``[hi; lo; hi]`` stacked on the contraction
    (first) axis, so the one product is hi·hi + hi·lo + lo·hi."""
    if passes == 1:
        return w.astype(jnp.bfloat16)
    hi, lo = _split(w)
    return jnp.concatenate([hi, lo, hi], axis=0).astype(jnp.bfloat16)


def _read_kernel(q_ref, s_ref, o_ref, y_ref, sp_ref, *, d: int, passes: int,
                 unroll: int):
    n_full = full_blocks(d)
    kw = d * passes                      # packed state rows a block

    # the head's state → its bf16 parts, once, for all of its row tiles
    @pl.when(pl.program_id(1) == 0)
    def _():
        def pack(r, carry):
            rows = pl.ds(pl.multiple_of(r * d, d), d)
            sp_ref[pl.ds(pl.multiple_of(r * kw, kw), kw), :] = _row_parts(
                s_ref[rows, :], passes)
            return carry
        lax.fori_loop(0, n_full, pack, 0)
        half = s_ref[n_full * d:, :]
        sp_ref[n_full * kw:, :] = _row_parts(
            jnp.concatenate([half, jnp.zeros_like(half)], axis=0), passes)

    x = q_ref[...]
    y_ref[...] = x * ROOT2
    o_ref[...] = jnp.dot(_lane_parts(x * x, passes), sp_ref[:kw, :],
                         **_ONE_PASS)

    def step(i, carry):
        for j in range(unroll):
            r = 1 + i * unroll + j
            # roll(x, −r): lane a holds x[(a + r) mod d]
            phi = y_ref[...] * pltpu.roll(q_ref[...], d - r, 1)
            o_ref[...] += jnp.dot(
                _lane_parts(phi, passes),
                sp_ref[pl.ds(pl.multiple_of(r * kw, kw), kw), :], **_ONE_PASS)
        return carry

    # rotations 1 … d/2: the last is the half block, against zero-padded rows
    lax.fori_loop(0, n_full // unroll, step, 0)


def state_read(q: jax.Array, state: jax.Array, passes: int,
               block_rows: int = READ_ROWS,
               unroll: int = READ_UNROLL) -> jax.Array:
    """``φ(q) · S`` a key-value head: ``q`` (G, T, d) float32 — the T rows
    of a head's query group — and ``state`` (G, d(d+1)/2, d_v) float32 →
    (G, T, d_v) float32, ``passes`` (1 or 3) bf16 passes the product. The
    rows go :func:`row_tile` at a time; compiled, ``d`` and ``d_v`` must be
    whole 128-lane blocks."""
    g, t, d = q.shape
    d_v = state.shape[-1]
    n_blocks = full_blocks(d) + 1
    rows = row_tile(t, block_rows)
    while full_blocks(d) % unroll:
        unroll -= 1
    return pl.pallas_call(
        partial(_read_kernel, d=d, passes=passes, unroll=unroll),
        grid=(g, t // rows),
        in_specs=[pl.BlockSpec((None, rows, d), lambda gi, ti: (gi, ti, 0)),
                  pl.BlockSpec((None, state.shape[1], d_v),
                               lambda gi, ti: (gi, 0, 0))],
        out_specs=pl.BlockSpec((None, rows, d_v),
                               lambda gi, ti: (gi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((g, t, d_v), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32),
                        pltpu.VMEM((n_blocks * d * passes, d_v),
                                   jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            # a head's row tiles run in order: the first packs its state
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name=READ_NAME,
    )(q, state)


def _update_kernel(w_ref, kt_ref, v_ref, s_ref, o_ref, y_ref, vp_ref, *,
                   d: int, passes: int, blocks: int):
    n_full = full_blocks(d)
    decay = w_ref[pl.program_id(0)]
    vp_ref[...] = _row_parts(v_ref[...], passes)
    xt = kt_ref[...]
    y_ref[...] = xt * ROOT2
    o_ref[:d, :] = decay * s_ref[:d, :] + jnp.dot(
        _lane_parts(xt * xt, passes), vp_ref[...], **_ONE_PASS)

    def phi_t(r):
        # φ_r(k)ᵀ: sublane a holds k[(a + r) mod d], positions on lanes
        return _lane_parts(y_ref[...] * pltpu.roll(kt_ref[...], d - r, 0),
                           passes)

    def step(i, carry):
        first = 1 + i * blocks
        rows = pl.ds(pl.multiple_of(first * d, d), blocks * d)
        lhs = jnp.concatenate([phi_t(first + j) for j in range(blocks)],
                              axis=0)
        o_ref[rows, :] = decay * s_ref[rows, :] + jnp.dot(
            lhs, vp_ref[...], **_ONE_PASS)
        return carry

    lax.fori_loop(0, (n_full - 1) // blocks, step, 0)
    o_ref[n_full * d:, :] = decay * s_ref[n_full * d:, :] + jnp.dot(
        phi_t(n_full)[:d // 2], vp_ref[...], **_ONE_PASS)


def state_update(state: jax.Array, decay: jax.Array, kt: jax.Array,
                 v: jax.Array, passes: int,
                 blocks: int = UPDATE_BLOCKS) -> jax.Array:
    """``decay · S + φ(k)ᵀ v`` a key-value head, the state updated in place:
    ``state`` (G, d(d+1)/2, d_v) float32, ``decay`` (G,) float32 (the old
    state's from the chunk's start to its end), ``kt`` (G, d, c) float32 — a
    chunk's keys, transposed — and ``v`` (G, c, d_v) float32 (each row
    already decayed to the chunk's end) → the new state. The decay and the
    sum are float32, made on the product's way out of the MXU: the state
    crosses HBM once each way and ``φ(k)ᵀ v`` never."""
    g, d, c = kt.shape
    d_v = v.shape[-1]
    n_full = full_blocks(d)
    while (n_full - 1) % blocks:
        blocks -= 1
    head = pl.BlockSpec((None, state.shape[1], d_v), lambda gi: (gi, 0, 0))
    return pl.pallas_call(
        partial(_update_kernel, d=d, passes=passes, blocks=blocks),
        grid=(g,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((None, d, c), lambda gi: (gi, 0, 0)),
                  pl.BlockSpec((None, c, d_v), lambda gi: (gi, 0, 0)),
                  head],
        out_specs=head,
        out_shape=jax.ShapeDtypeStruct(state.shape, jnp.float32),
        input_output_aliases={3: 0},
        scratch_shapes=[pltpu.VMEM((d, c), jnp.float32),
                        pltpu.VMEM((c * passes, d_v), jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel',),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name=UPDATE_NAME,
    )(decay, kt, v, state)
