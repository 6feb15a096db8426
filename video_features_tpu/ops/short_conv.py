"""The gated short convolution (LFM2's ``conv`` operator).

A sequence mixer that looks back a fixed, short distance: of its input's
projection ``[B ‖ C ‖ h] = x W_in`` the gate ``B`` opens ``h`` position by
position, a depthwise causal convolution of ``taps`` positions mixes what
came through along the sequence, the gate ``C`` opens the result, and
``W_out`` projects it back:

    u = B ⊙ h
    c_t = Σ_j w[j] ⊙ u_{t − (taps − 1) + j}      j = 0 … taps − 1
    y = (C ⊙ c) W_out

with ``u`` zero before position 0, no bias and no activation
(``transformers``' ``Lfm2ShortConv``: ``conv_L_cache`` taps, ``conv_bias:
false``). The last tap weighs the position itself, the first the one
``taps − 1`` back.

On the chip that is two lane-dense products over all the tokens of a
device step around ``taps − 1`` shifts along the sequence axis. The shifts
are per window: a window's first positions see zeros, never the tail of
the window that shares the step with it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_taps(u: jax.Array, w: jax.Array) -> jax.Array:
    """The depthwise causal convolution alone: ``u`` (B, S, D), ``w``
    (taps, D) → (B, S, D); position t of a window is
    ``Σ_j w[j] ⊙ u[t − (taps − 1) + j]``, zeros before the window's start."""
    taps, s = w.shape[0], u.shape[1]
    out = u * w[taps - 1]
    for back in range(1, min(taps, s)):
        shifted = jnp.pad(u[:, :s - back], ((0, 0), (back, 0), (0, 0)))
        out = out + shifted * w[taps - 1 - back]
    return out


def gated_short_conv(x: jax.Array, w_in: jax.Array, w_conv: jax.Array,
                     w_out: jax.Array) -> jax.Array:
    """(B, S, D) windows → (B, S, D). ``w_in`` (D, 3·D) writes the column
    groups ``B ‖ C ‖ h``, ``w_conv`` is (taps, D), ``w_out`` (D, D)."""
    d = x.shape[-1]
    bch = jnp.dot(x, w_in)
    gate_in, gate_out, h = (bch[..., :d], bch[..., d:2 * d], bch[..., 2 * d:])
    return jnp.dot(gate_out * causal_taps(gate_in * h, w_conv), w_out)
