"""Gated power retention: attention whose weight is the squared query-key
product, run as a linear recurrence over a symmetric-power feature map.

Gelada, Buckman, Zhang, Bach, "Scaling Context Requires Rethinking
Attention" (arXiv:2507.04239). For one key-value head with its group of
query heads, positions ``t`` of one window, head width ``d``, a learned
forget gate ``γ_t ≤ 0`` (the log of a sigmoid) and ``p = 2``:

    a_ts = (q_t · k_s)² · exp(γ_{s+1} + … + γ_t)            s ≤ t     (1)
    y_t  = Σ_s a_ts v_s / (Σ_s a_ts + ε)                               (2)

``a ≥ 0``, so the sum normalises and there is no softmax. With the feature
map ``φ(x) ∈ R^D``, ``D = d(d+1)/2`` — ``x_a²`` and ``√2·x_a x_b`` for
``a < b``, so ``φ(q)·φ(k) = (q·k)²`` — the same function is a recurrence
over a state of ``D × (d_v + 1)`` numbers a key-value head:

    S_t = e^{γ_t} S_{t−1} + φ(k_t) v_tᵀ      z_t = e^{γ_t} z_{t−1} + φ(k_t)   (3)
    y_t = S_tᵀ φ(q_t) / (z_t · φ(q_t) + ε)                               (4)

What runs is the **chunked form** (:func:`retention_chunked`): ``lax.scan``
over chunks of ``c`` positions; inside a chunk (1)–(2) restricted to the
chunk with the decay taken from the chunk's start, the state at the chunk's
start supplying every earlier position through (4), numerators and
denominators of the two parts added before the one division, and (3)
applied for the whole chunk at once at its end. Its cost a position does not
grow with the window: ``D·(d_v + 1)`` multiply-adds a query head and as many
a key-value head, plus ``c`` pairs. A window that is no whole number of
chunks is padded up to one with zero keys and values (their weight is 0 and
causality keeps every earlier position as it was) and cut back. The tests
hold it to (1)–(2) and to (3)–(4), both written out there.

The two products with φ — the state read ``φ(q)·S`` and the state update
``φ(k)ᵀv`` — have two forms, chosen by :func:`resolve_retention` from the
platform, the shapes and the ambient precision (static at trace time, no
switch). XLA's writes φ out (:func:`power_features`) and contracts it with
an einsum: the CPU path and the oracle. On a TPU at 128-wide heads they are
the Mosaic kernels of ``ops/pallas_retention.py``, which form φ a 128-lane
block at a time in VMEM beside the MXU product that consumes it (at
brumby.corpus's widths φ of a chunk's queries is 676 MB that XLA writes to
HBM and reads back; PERF.md §6, PR 32). The scan over chunks, the pairs
inside a chunk, the normaliser and the division are XLA's in both.

The normaliser is held as the symmetric ``d × d`` matrix ``Z_t = e^{γ_t}
Z_{t−1} + k_t k_tᵀ`` whose upper triangle ``z`` is (the same 8,256 numbers at
d = 128): ``z · φ(q) = qᵀ Z q``, a ``d``-wide product where the vector form
would read the 8,256-wide ``φ(q)`` of every query head a second time.

The state and the normaliser are float32 and accumulate in float32 whatever
the ambient matmul precision; the products follow it. A scalar scale on
``q·k`` cancels in (2) and (4): there is none.

Shapes: ``q`` (S, G, R, d) — G key-value heads, R query heads each —
``k`` (S, G, d), ``v`` (S, G, d_v), ``log_gate`` (S, G); out (S, G, R, d_v).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-6
State = Tuple[jax.Array, jax.Array]      # S (G, D, d_v), Z (G, d, d); float32


def feature_dim(d: int) -> int:
    return d * (d + 1) // 2


def power_features(x: jax.Array) -> jax.Array:
    """φ: (..., d) → (..., d(d+1)/2) with ``φ(q)·φ(k) = (q·k)²``.

    The entries are those of the upper triangle of ``x xᵀ`` (off-diagonal
    ones × √2), in the order of the circulant diagonals: first ``x_a²``,
    then ``x_a · x_{(a+r) mod d}`` for ``r = 1, 2, …`` — every unordered pair
    lies at one circular distance ``r ≤ d/2``, and at ``r = d/2`` (d even)
    the first half of the rotation holds each pair once. So φ is ``d/2 + 1``
    lane rotations and products of whole ``d``-wide rows, no gather and no
    ragged slice: at d = 128 every piece is a whole 128-lane block."""
    d = x.shape[-1]
    root2 = math.sqrt(2.0)
    parts = [x * x]
    for r in range(1, (d - 1) // 2 + 1):
        parts.append(root2 * x * jnp.roll(x, -r, axis=-1))
    if d % 2 == 0:
        parts.append(root2 * x[..., :d // 2] * x[..., d // 2:])
    return jnp.concatenate(parts, axis=-1)


def init_state(g: int, d: int, d_v: int) -> State:
    return (jnp.zeros((g, feature_dim(d), d_v), jnp.float32),
            jnp.zeros((g, d, d), jnp.float32))


def resolve_retention(platform: str, d: int, d_v: int, chunk: int,
                      precision: Optional[str]) -> str:
    """Which form of the state products :func:`retention_chunked` compiles
    for heads ``d`` / ``d_v`` wide and chunks of ``chunk`` positions on
    ``platform`` under the ambient matmul ``precision``: 'kernel' (φ formed
    in VMEM by the Mosaic kernels of ops/pallas_retention.py) or 'state'
    (XLA's: φ written out and contracted by an einsum).

    The kernels apply on a TPU, where both head widths are whole 128-lane
    blocks (a block of φ is one rotation of a head's lanes), so is the
    chunk (the update kernel holds a chunk's positions on the lanes; the
    read kernel's row tile is then a whole divisor of a head's rows), a
    head's state fits the read kernel's VMEM budget (d = 128 does, d = 256
    does not) and the ambient precision is one the kernels have a lane for
    (``ops.attention.KERNEL_PASSES``). Anywhere else — the CPU, where they
    would run interpreted; narrow heads; a short or ragged chunk; 'highest'
    — XLA's form runs, which is also the oracle the kernels are tested
    against. All of it is static at trace time; there is no switch.
    ``models/retention_trunk.py::retention_block``, the scan's one caller,
    asks here."""
    from video_features_tpu.ops import pallas_retention as kernel
    from video_features_tpu.ops.attention import KERNEL_PASSES
    if platform != 'tpu' or precision not in KERNEL_PASSES:
        return 'state'
    if (d % kernel.LANES or d_v % kernel.LANES or chunk % kernel.LANES
            or kernel.state_vmem_bytes(d, d_v, KERNEL_PASSES[precision])
            > kernel.STATE_VMEM_BYTES):
        return 'state'
    return 'kernel'


def retention_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                      log_gate: jax.Array, chunk: int,
                      state: Optional[State] = None,
                      eps: float = EPS, kernel_passes: Optional[int] = None
                      ) -> Tuple[jax.Array, State]:
    """The chunked form (module doc): ``(y (S, G, R, d_v), the state after
    the last position)``. ``state`` is the one to start from (None: empty,
    the window's first position sees itself alone); handing a window's
    final state to the next call continues the sequence. A window shorter
    than ``chunk`` is one chunk; a ragged tail is padded (module doc), which
    leaves the state as the last real position left it.

    ``kernel_passes`` (1 or 3 bf16 passes a product; None: XLA's form) sends
    the two products with φ through ``ops/pallas_retention.py``, where
    :func:`resolve_retention` says it applies."""
    s, g, r, d = q.shape
    d_v = v.shape[-1]
    chunk = min(chunk, s)
    n = -(-s // chunk)
    if n * chunk > s:
        q, k, v, log_gate = (
            jnp.pad(a, [(0, n * chunk - s)] + [(0, 0)] * (a.ndim - 1))
            for a in (q, k, v, log_gate))
    f32 = jnp.float32
    if kernel_passes is not None:
        from video_features_tpu.ops import pallas_retention
    # chunk-major, heads before positions: a head's rows are one slab
    qc = q.reshape(n, chunk, g, r, d).transpose(0, 2, 3, 1, 4)
    kc = k.reshape(n, chunk, g, d).transpose(0, 2, 1, 3)
    # the update kernel reads a chunk's keys with positions on the lanes
    ktc = None if kernel_passes is None else kc.astype(f32).swapaxes(2, 3)
    vc = v.reshape(n, chunk, g, d_v).transpose(0, 2, 1, 3)
    # the decay from the chunk's start up to and including each position
    gc = jnp.cumsum(log_gate.astype(f32).reshape(n, chunk, g), axis=1
                    ).transpose(0, 2, 1)
    pos = jnp.arange(chunk)
    seen = pos[:, None] >= pos[None, :]                     # (t, s)

    def step(carry, blk):
        big_s, big_z = carry
        qi, ki, vi, gi, kti = blk
        # inside the chunk: (1)-(2), the decay as exp of a difference that
        # is masked before it is exponentiated (above the diagonal it is > 0)
        scores = jnp.einsum('grtd,gsd->grts', qi, ki,
                            preferred_element_type=f32)
        decay = jnp.exp(jnp.where(seen, gi[:, :, None] - gi[:, None, :],
                                  -jnp.inf))
        a = scores * scores * decay[:, None]
        num = jnp.einsum('grts,gsv->grtv', a, vi, preferred_element_type=f32)
        den = a.sum(axis=-1)
        # every earlier position, through the state at the chunk's start
        if kernel_passes is None:
            from_state = jnp.einsum(
                'grtD,gDv->grtv', power_features(qi.astype(f32)), big_s,
                preferred_element_type=f32)
        else:
            from_state = pallas_retention.state_read(
                qi.astype(f32).reshape(g, r * chunk, d), big_s,
                kernel_passes).reshape(g, r, chunk, d_v)
        from_start = jnp.exp(gi)[:, None, :]                # (g, 1, t)
        num = num + from_start[..., None] * from_state
        q_z = jnp.einsum('grtd,gde->grte', qi, big_z,
                         preferred_element_type=f32)
        den = den + from_start * (q_z * qi).sum(axis=-1)
        y = num / (den[..., None] + eps)
        # (3) for the whole chunk: each key decayed from its position to
        # the chunk's end, the old state from the start to the end
        to_end = jnp.exp(gi[:, -1:] - gi)[..., None]        # (g, s, 1)
        whole = jnp.exp(gi[:, -1])[:, None, None]
        if kernel_passes is None:
            big_s = whole * big_s + jnp.einsum(
                'gsD,gsv->gDv', power_features(ki.astype(f32)) * to_end, vi,
                preferred_element_type=f32)
        else:
            big_s = pallas_retention.state_update(
                big_s, whole[:, 0, 0], kti, vi.astype(f32) * to_end,
                kernel_passes)
        big_z = whole * big_z + jnp.einsum(
            'gsd,gse->gde', ki * to_end, ki, preferred_element_type=f32)
        return (big_s, big_z), y.astype(q.dtype)

    carry = init_state(g, d, d_v) if state is None else state
    carry, y = lax.scan(step, carry, (qc, kc, vc, gc, ktc))
    # (n, g, r, chunk, d_v) → (S, g, r, d_v)
    return y.transpose(0, 3, 1, 2, 4).reshape(n * chunk, g, r, d_v)[:s], carry

