"""The Mamba-2 SSD scan as one Pallas TPU kernel: ``ssd_scan``.

``ops/ssd.py::ssd_chunked``'s XLA form writes every head's ``Q × Q`` decay
matrix of a chunk to HBM and reads it back for the product that consumes it
(64 heads × 256 × 256 float32 = 16.8 MB a chunk at granite-4.0-h-micro's
widths, 128 chunks a window-layer), and carries the ``(H, N, P)`` state
through HBM between chunks. Here one grid step is one chunk of ``Q``
positions for a group of heads, and nothing but the inputs and the output
crosses HBM:

* **grid** ``(head groups, chunks)``, the chunk axis sequential; a group is
  :func:`heads_per_step` heads, whole 128-lane groups of ``x`` (two 64-wide
  heads a lane group). The group's state, ``(lane groups, N, 128)`` float32,
  lives in VMEM scratch across its chunks and is zeroed at the first;
* **a chunk**: ``C Bᵀ`` (``Q × Q`` over ``N``) once, since every head shares
  B and C; then for each lane group its heads' step sizes and cumulative
  decays spread over their lanes, ``Δ ⊙ x``, and per head the decay mask
  ``exp(cs_t − cs_s)`` (masked above the diagonal before the ``exp``) times
  ``C Bᵀ``, against ``Δ ⊙ x`` with the other heads' lanes zeroed — in the
  three ``Q/2 × Q/2`` blocks on and below the diagonal, the fourth being
  all masked; the read of the carried state, ``exp(cs_t) · C · state``; the
  skip ``D ⊙ x``; and the state's update, ``exp(cs_Q) · state + Bᵀ
  (exp(cs_Q − cs_s) · Δ ⊙ x)``;
* **the passes are made here**, as in ``ops/pallas_attention.py`` (Mosaic
  makes one bf16 pass a dot): 3 (ambient ``high``) splits both operands into
  a bf16 head and remainder and adds ``hi·lo + lo·hi`` to ``hi·hi``; 1
  (ambient ``default``) is the heads alone. Decays, state and accumulators
  are float32.

The step sizes, ``Δ·A`` and the chunk's cumulative sums come in from XLA
(``ops/ssd.py::chunk_decays``), as columns (positions on sublanes) and as
rows (positions on lanes), so that a decay matrix is one broadcast
difference. CPU tests run the same body interpreted
(``pltpu.force_tpu_interpret_mode()``, ``tests/test_ssd.py``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from video_features_tpu.ops.pallas_attention import LANES, _ONE_PASS, _split

NAME = 'ssd_scan'
# heads a grid step: all 64 of granite-micro.corpus's, so B and C are read
# once a window-layer (one v5e chip: 3.12 ms a window-layer, 3.41 at 16
# heads a step; PERF.md §5)
HEADS_PER_STEP = 64
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def heads_per_step(heads: int, head_dim: int) -> int:
    """Heads one grid step takes: ``HEADS_PER_STEP`` or all of them where
    there are fewer, and never less than one 128-lane group."""
    return min(heads, max(HEADS_PER_STEP, LANES // head_dim))


def _mm(a: jax.Array, b: jax.Array, passes: int) -> jax.Array:
    """``a @ b`` of two float32 operands in ``passes`` bf16 passes."""
    if passes == 1:
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       **_ONE_PASS)
    a_hi, a_lo = (t.astype(jnp.bfloat16) for t in _split(a))
    b_hi, b_lo = (t.astype(jnp.bfloat16) for t in _split(b))
    return (jnp.dot(a_hi, b_lo, **_ONE_PASS) + jnp.dot(a_lo, b_hi, **_ONE_PASS)
            + jnp.dot(a_hi, b_hi, **_ONE_PASS))


def _kernel(x_ref, dt_ref, cs_ref, csr_ref, bt_ref, c_ref, d_ref, y_ref,
            state_ref, *, head_dim: int, passes: int):
    chunk = x_ref.shape[0]
    half = chunk // 2
    per_lanes = LANES // head_dim

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    c = c_ref[...]
    bt = bt_ref[...]
    pairs = _mm(c, bt, passes)                       # (t, s): C_t · B_s
    # (row half, column half) of the blocks on and below the diagonal
    halves = (slice(None, half), slice(half, None))
    blocks = {(r, k): pairs[halves[r], halves[k]]
              for r, k in ((0, 0), (1, 0), (1, 1))}
    seen = (lax.broadcasted_iota(jnp.int32, (half, half), 0)
            >= lax.broadcasted_iota(jnp.int32, (half, half), 1))
    lane = lax.broadcasted_iota(jnp.int32, (chunk, LANES), 1)
    owns = [(lane >= i * head_dim) & (lane < (i + 1) * head_dim)
            for i in range(per_lanes)]
    dt, cs, csr = dt_ref[...], cs_ref[...], csr_ref[...]

    for j in range(state_ref.shape[0]):
        lanes = slice(j * LANES, (j + 1) * LANES)
        heads = range(j * per_lanes, (j + 1) * per_lanes)

        def spread(v):
            """(Q, heads) columns → (Q, 128): each head's over its lanes."""
            out = jnp.broadcast_to(v[:, heads[0]:heads[0] + 1],
                                   (chunk, LANES))
            for i, h in enumerate(heads):
                if i:
                    out = jnp.where(owns[i], v[:, h:h + 1], out)
            return out

        x = x_ref[:, lanes]
        cs_lanes = spread(cs)
        dx = spread(dt) * x
        acc = (jnp.exp(cs_lanes) * _mm(c, state_ref[j], passes)
               + d_ref[:, lanes] * x)
        rows = [acc[:half], acc[half:]]
        for i, h in enumerate(heads):
            mine = dx if per_lanes == 1 else jnp.where(owns[i], dx, 0.0)
            for (r, k), block in blocks.items():
                gap = cs[halves[r], h:h + 1] - csr[h:h + 1, halves[k]]
                if r == k:                           # on the diagonal
                    gap = jnp.where(seen, gap, -jnp.inf)
                rows[r] += _mm(block * jnp.exp(gap), mine[halves[k]], passes)
        y_ref[:half, lanes] = rows[0]
        y_ref[half:, lanes] = rows[1]
        last = cs_lanes[chunk - 1:chunk, :]
        state_ref[j] = jnp.exp(last) * state_ref[j] + _mm(
            bt, jnp.exp(last - cs_lanes) * dx, passes)


def ssd_scan(x: jax.Array, dt: jax.Array, cs: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, chunk: int, passes: int
             ) -> jax.Array:
    """One window's scan with its ``D`` skip: ``x`` (S, H·P) float32 — the
    heads' channels side by side —, ``dt`` (S, H) step sizes, ``cs`` (S, H)
    each chunk's cumulative ``Δ·A`` (``ops/ssd.py::chunk_decays``), ``b``,
    ``c`` (S, N), ``d`` (H,) → ``y`` (S, H·P) float32, ``passes`` (1 or 3)
    bf16 passes a product. S is a whole number of chunks; a grid step takes
    :func:`heads_per_step` heads."""
    s, width = x.shape
    h = dt.shape[1]
    p = width // h
    n_state = b.shape[1]
    hb = heads_per_step(h, p)
    g = h // hb
    f32 = jnp.float32

    def columns(v):                                  # (g, S, hb)
        return v.astype(f32).reshape(s, g, hb).transpose(1, 0, 2)

    rows = cs.astype(f32).reshape(s, g, hb).transpose(1, 2, 0)  # (g, hb, S)
    d_lanes = jnp.repeat(d.astype(f32), p)[None]     # (1, H·P)
    lanes = pl.BlockSpec((chunk, hb * p), lambda gi, ci: (ci, gi))
    per_head = pl.BlockSpec((None, chunk, hb), lambda gi, ci: (gi, ci, 0))
    return pl.pallas_call(
        partial(_kernel, head_dim=p, passes=passes),
        grid=(g, s // chunk),
        in_specs=[lanes, per_head, per_head,
                  pl.BlockSpec((None, hb, chunk), lambda gi, ci: (gi, 0, ci)),
                  pl.BlockSpec((n_state, chunk), lambda gi, ci: (0, ci)),
                  pl.BlockSpec((chunk, n_state), lambda gi, ci: (ci, 0)),
                  pl.BlockSpec((1, hb * p), lambda gi, ci: (0, gi))],
        out_specs=lanes,
        out_shape=jax.ShapeDtypeStruct((s, width), f32),
        scratch_shapes=[pltpu.VMEM((hb * p // LANES, n_state, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            # a group's chunks run in order: each reads the state the one
            # before it left
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name=NAME,
    )(x.astype(f32), columns(dt), columns(cs), rows, b.astype(f32).T,
      c.astype(f32), d_lanes)
