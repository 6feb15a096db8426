"""Functional NN building blocks (channels-last, XLA/TPU-native).

Every model in this framework is a pure function ``forward(params, x)`` over a
nested params pytree whose keys mirror the source torch ``state_dict`` names
(see video_features_tpu/transplant). Layouts are TPU-optimal channels-last:
images are NHWC, videos are NDHWC (D = time); conv kernels are stored
spatial-major with I/O last (HWIO / DHWIO) so XLA tiles them straight onto the
MXU without relayout.

Numerics parity notes (vs torch, for checkpoint-transplant fidelity):
  * conv: torch symmetric int padding → explicit (lo, hi) pairs here; TF-SAME
    asymmetric padding (I3D) is also expressible per-edge.
  * conv_space_to_depth: a strided stem over 2-3 channels leaves the MXU's
    contraction lanes empty, so I3D's first convolution folds its strided
    taps into channels and runs at stride 1 — the same products and sums.
    ``conv`` is not the place for that test: a branch inside it would
    re-lower every family's program, so a model opts in at the call site.
  * conv_from_planes / conv_to_planes: the same pathology at stride 1 —
    RAFT's 2 flow components as a convolution's input or output channels —
    answered by keeping the few channels as planes and folding taps into
    channels on the way in, into output planes on the way out.
  * batch norm is inference-only: y = (x - mean) / sqrt(var + eps) * γ + β
    with running statistics — matches torch .eval() semantics.
  * max pool with ceil_mode / TF-SAME is built from explicit -inf padding.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Array = jax.Array
IntOrTuple = Union[int, Sequence[int]]

# -- fp32 accumulation islands (the bf16 fast lane) --------------------------
#
# Under ``compute_dtype=bfloat16`` activations flow bf16 end to end, but
# a few ops accumulate MANY terms whose bf16 rounding compounds past the
# per-family parity bounds: normalization statistics (mean/var over
# thousands of elements), softmax (exp + sum), and pooling sums. Each such
# op below detects a bf16 input, computes in float32, and casts the result
# back — an explicit, local "island" rather than a global policy, so the
# float32 lane's graph is BYTE-IDENTICAL to the pre-lane programs (the
# branch is trace-time static on the abstract dtype; PROGRAMS.lock.json
# pins that). Matmuls/convs need no island: the MXU accumulates fp32
# internally for bf16 operands.


def _tuple(v: IntOrTuple, n: int) -> Tuple[int, ...]:
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    assert len(v) == n, f'expected {n} values, got {v}'
    return v


def _pad_pairs(padding: Union[IntOrTuple, Sequence[Tuple[int, int]], str], n: int):
    """Normalize padding to lax explicit (lo, hi) pairs, or pass 'SAME'/'VALID'."""
    if isinstance(padding, str):
        return padding
    if isinstance(padding, int):
        return [(padding, padding)] * n
    padding = list(padding)
    if padding and isinstance(padding[0], (tuple, list)):
        return [tuple(p) for p in padding]
    return [(p, p) for p in padding]


def conv(x: Array, kernel: Array, stride: IntOrTuple = 1,
         padding: Union[IntOrTuple, Sequence[Tuple[int, int]], str] = 0,
         dilation: IntOrTuple = 1, groups: int = 1,
         bias: Optional[Array] = None) -> Array:
    """N-D convolution, channels-last. kernel: (*spatial, I/groups, O)."""
    n = kernel.ndim - 2
    spec = {1: ('NWC', 'WIO', 'NWC'),
            2: ('NHWC', 'HWIO', 'NHWC'),
            3: ('NDHWC', 'DHWIO', 'NDHWC')}[n]
    out = lax.conv_general_dilated(
        x, kernel.astype(x.dtype),
        window_strides=_tuple(stride, n),
        padding=_pad_pairs(padding, n),
        rhs_dilation=_tuple(dilation, n),
        dimension_numbers=spec,
        feature_group_count=groups,
    )
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


def conv_space_to_depth(x: Array, kernel: Array, stride: IntOrTuple = 1,
                        padding: Union[IntOrTuple,
                                       Sequence[Tuple[int, int]]] = 0,
                        bias: Optional[Array] = None) -> Array:
    """``conv`` of a strided convolution, computed at stride 1 over an input
    whose strided taps are folded into channels: the same products and the
    same sums, the order of summation aside.

    The MXU contracts over channels, so a stem with 3 input channels fills 3
    of its 128 lanes. Two folds, both derived from the arguments:

    * the last spatial dimension, if strided: its ``k`` taps become channels
      (``k`` strided slices side by side, ``k·C`` channels, one tap left);
    * every other strided dimension, space-to-depth: a tap ``t = s·a + r`` is
      block ``a`` of ``ceil(k/s)`` and offset ``r`` inside the block; the
      ``r`` of all dimensions become channels (the padded input reshaped
      into blocks of ``s``, the kernel padded with zero taps to
      ``s·ceil(k/s)`` and reshaped the same way), which leaves
      ``out[o] = Σ_a x2[o + a] · w2[a]``.

    I3D's 7×7×7 stride-2 stem over 3 channels becomes 4×4×1 over 84. The last
    dimension is not folded into blocks as well because that de-interleaves
    the array's minor dimension, which costs the TPU more than the
    convolution it saves, and carries a zero tap. Stride 1 folds nothing and
    falls through to ``conv``.
    """
    n = kernel.ndim - 2
    strides = _tuple(stride, n)
    if all(s == 1 for s in strides):
        return conv(x, kernel, stride, padding, bias=bias)
    pads = _pad_pairs(padding, n)
    zero = jnp.zeros((), x.dtype)
    if strides[-1] > 1:
        k, s = kernel.shape[n - 1], strides[-1]
        x = lax.pad(x, zero, [(0, 0, 0)] * n + [(*pads[-1], 0), (0, 0, 0)])
        span = (x.shape[n] - k) // s * s + 1
        x = jnp.concatenate(
            [lax.slice_in_dim(x, t, t + span, stride=s, axis=n)
             for t in range(k)], axis=-1)
        kernel = kernel.reshape(*kernel.shape[:n - 1], 1, -1,
                                kernel.shape[-1])
        strides, pads = strides[:-1] + (1,), pads[:-1] + [(0, 0)]
    sizes = kernel.shape[:n]
    taps = [-(-k // s) for k, s in zip(sizes, strides)]
    blocks, edges = [], []
    for size, k, s, t, (lo, hi) in zip(x.shape[1:-1], sizes, strides, taps,
                                       pads):
        blocks.append((size + lo + hi - k) // s + t)
        # the blocks the outputs read, no more: the high edge grows by the
        # zero taps' reach or loses what no output window covers
        edges.append((lo, s * blocks[-1] - size - lo, 0))
    def phases_to_channels(a: Array, lead: int, outer) -> Array:
        """(*lead, o_1·s_1, …, o_n·s_n, C, *rest) → (*lead, o_1, …, o_n,
        s_1·…·s_n·C, *rest)."""
        rest = a.shape[lead + n + 1:]
        a = a.reshape(*a.shape[:lead],
                      *(v for os in zip(outer, strides) for v in os), -1,
                      *rest)
        split = lead + 2 * n
        a = a.transpose(*range(lead), *range(lead, split, 2),
                        *range(lead + 1, split, 2), *range(split, a.ndim))
        return a.reshape(*a.shape[:lead + n], -1, *rest)

    # padded apart from W's: one pad before the slices measured slower
    x = lax.pad(x, zero, [(0, 0, 0)] + edges + [(0, 0, 0)])
    x = phases_to_channels(x, 1, blocks)
    kernel = jnp.pad(kernel, [(0, t * s - k) for t, s, k in
                              zip(taps, strides, sizes)] + [(0, 0), (0, 0)])
    kernel = phases_to_channels(kernel, 0, taps)
    return conv(x, kernel, 1, 'VALID', bias=bias)


def conv_from_planes(planes: Array, kernel: Array,
                     bias: Optional[Array] = None) -> Array:
    """``conv(x, kernel, padding=k // 2)`` at stride 1 for an input of FEW
    channels that is held as planes — (C, B, H, W) in, (B, H, W, O) out:
    the same products and the same sums, the order of summation aside.

    As channels-last the C components would be the minor axis — C of the
    TPU's 128 lanes, the tensor padded 128 / C times over — and each of the
    ``kh·kw`` taps an MXU product C wide. Here W's ``kw`` taps become
    channels (``kw`` shifted slices of every plane side by side: ``kw·C``
    channels, built batch-minor, W's shift a stride between rows) and a
    ``kh × 1`` convolution over H is left. RAFT's ``convf1`` — 7×7 over the
    2 flow components → 128 — becomes 7×1 over 14. Measured beside the
    other folds (PERF.md §6, PR 34): all 98 taps stacked for one product
    costs twice this one's time, in the 69 MB stack; a Toeplitz product
    along W is slower than the convolution it replaces. The kernel keeps
    its (kh, kw, C, O) checkpoint layout and is reshaped here, inside the
    jitted step; sizes are odd.
    """
    kh, kw, c, o = kernel.shape
    assert kh % 2 and kw % 2 and planes.shape[0] == c, (kernel.shape,
                                                         planes.shape)
    w = planes.shape[-1]
    x = jnp.pad(planes, [(0, 0), (0, 0), (0, 0), (kw // 2, kw // 2)])
    taps = jnp.stack([x[ch, :, :, j:j + w] for j in range(kw)
                      for ch in range(c)], axis=-1)          # (B, H, W, kw·C)
    return conv(taps, kernel.reshape(kh, 1, kw * c, o),
                padding=[(kh // 2, kh // 2), (0, 0)], bias=bias)


def conv_to_planes(x: Array, kernel: Array,
                   bias: Optional[Array] = None) -> Array:
    """``conv(x, kernel, padding=k // 2)`` at stride 1 onto FEW output
    channels, returned as planes — (B, H, W, C) in, (O, B, H, W) out: the
    mirror of :func:`conv_from_planes`, the same products and sums.

    A convolution onto O channels fills O of the MXU's 128 output columns
    once a tap. Here ONE 1×1 product writes all ``kh·kw·O`` partial planes
    (x is read once) and the taps are ``kh·kw`` shifted adds of planes,
    which are lane-dense. RAFT's flow head (3×3, 256 → 2) becomes one
    product onto 18 planes and 9 adds.
    """
    kh, kw, c, o = kernel.shape
    assert kh % 2 and kw % 2, kernel.shape
    b, h, w, _ = x.shape
    folded = kernel.transpose(2, 0, 1, 3).reshape(c, kh * kw * o)
    parts = jnp.einsum('bhwc,ck->kbhw', x, folded.astype(x.dtype))
    parts = jnp.pad(parts.reshape(kh, kw, o, b, h, w),
                    [(0, 0)] * 4 + [(kh // 2, kh // 2), (kw // 2, kw // 2)])
    out = sum(parts[i, j, :, :, i:i + h, j:j + w]
              for i in range(kh) for j in range(kw))
    if bias is not None:
        out = out + bias.astype(out.dtype)[:, None, None, None]
    return out


def batch_norm(x: Array, p: Dict[str, Array], eps: float = 1e-5) -> Array:
    """Inference-mode batch norm over the trailing channel axis.

    ``p`` holds torch-named entries: weight (γ), bias (β), running_mean,
    running_var. Affine params may be absent (γ=1, β=0).
    """
    if x.dtype == jnp.bfloat16:
        # fp32 island: the rsqrt(var+eps) fold and the (x-mean)*inv
        # arithmetic run fp32, result cast back (BatchNorm statistics
        # island of the bf16 fast lane)
        return batch_norm(x.astype(jnp.float32), p, eps).astype(x.dtype)
    mean = p['running_mean'].astype(x.dtype)
    var = p['running_var'].astype(x.dtype)
    inv = lax.rsqrt(var + jnp.asarray(eps, x.dtype))
    out = (x - mean) * inv
    if 'weight' in p:
        out = out * p['weight'].astype(x.dtype)
    if 'bias' in p:
        out = out + p['bias'].astype(x.dtype)
    return out


def instance_norm(x: Array, p: Dict[str, Array], eps: float = 1e-5) -> Array:
    """InstanceNorm over spatial dims (channels-last), matching torch
    InstanceNorm2d (affine optional, no running stats — RAFT's fnet)."""
    if x.dtype == jnp.bfloat16:
        # fp32 island: per-sample statistics over whole spatial planes
        return instance_norm(x.astype(jnp.float32), p, eps).astype(x.dtype)
    axes = tuple(range(1, x.ndim - 1))
    mean = x.mean(axis=axes, keepdims=True)
    var = x.var(axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + jnp.asarray(eps, x.dtype))
    if 'weight' in p:
        out = out * p['weight'].astype(x.dtype)
    if 'bias' in p:
        out = out + p['bias'].astype(x.dtype)
    return out


def group_norm(x: Array, p: Dict[str, Array], num_groups: int,
               eps: float = 1e-5) -> Array:
    """GroupNorm (channels-last), matching torch nn.GroupNorm."""
    if x.dtype == jnp.bfloat16:
        # fp32 island: per-group statistics
        return group_norm(x.astype(jnp.float32), p, num_groups,
                          eps).astype(x.dtype)
    *lead, c = x.shape
    g = num_groups
    xg = x.reshape(*lead, g, c // g)
    axes = tuple(range(1, x.ndim - 1)) + (x.ndim,)
    mean = xg.mean(axis=axes, keepdims=True)
    var = xg.var(axis=axes, keepdims=True)
    out = ((xg - mean) * lax.rsqrt(var + jnp.asarray(eps, x.dtype))).reshape(x.shape)
    if 'weight' in p:
        out = out * p['weight'].astype(x.dtype)
    if 'bias' in p:
        out = out + p['bias'].astype(x.dtype)
    return out


def linear(x: Array, p: Dict[str, Array]) -> Array:
    """Dense layer; p['weight'] is stored transplanted as (I, O)."""
    out = x @ p['weight'].astype(x.dtype)
    if 'bias' in p:
        out = out + p['bias'].astype(x.dtype)
    return out


def relu(x: Array) -> Array:
    return jax.nn.relu(x)


def softmax(x: Array, axis: int = -1) -> Array:
    """softmax with the bf16 fast lane's fp32 island: exp + normalizing
    sum run fp32 for bf16 input (compounded rounding across wide
    attention rows is exactly what the per-family parity bounds can't
    absorb), result cast back; float32 input takes ``jax.nn.softmax``
    verbatim — the identical graph every call site lowered before."""
    if x.dtype == jnp.bfloat16:
        return jax.nn.softmax(x.astype(jnp.float32),
                              axis=axis).astype(x.dtype)
    return jax.nn.softmax(x, axis=axis)


def max_pool(x: Array, window: IntOrTuple, stride: Optional[IntOrTuple] = None,
             padding: Union[IntOrTuple, Sequence[Tuple[int, int]], str] = 0) -> Array:
    """Max pooling over the spatial dims of channels-last input."""
    n = x.ndim - 2
    window = _tuple(window, n)
    stride = window if stride is None else _tuple(stride, n)
    pads = _pad_pairs(padding, n)
    if not isinstance(pads, str):
        pads = [(0, 0)] + list(pads) + [(0, 0)]
    return lax.reduce_window(
        x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        lax.max,
        window_dimensions=(1,) + window + (1,),
        window_strides=(1,) + stride + (1,),
        padding=pads if not isinstance(pads, str) else pads,
    )


def avg_pool(x: Array, window: IntOrTuple, stride: Optional[IntOrTuple] = None,
             padding: Union[IntOrTuple, Sequence[Tuple[int, int]]] = 0,
             count_include_pad: bool = True) -> Array:
    """Average pooling matching torch AvgPool semantics."""
    if x.dtype == jnp.bfloat16:
        # fp32 island: window sums accumulate fp32 (also sidesteps the
        # float init_value / bf16 operand dtype mismatch in reduce_window)
        return avg_pool(x.astype(jnp.float32), window, stride, padding,
                        count_include_pad).astype(x.dtype)
    n = x.ndim - 2
    window = _tuple(window, n)
    stride = window if stride is None else _tuple(stride, n)
    pads = [(0, 0)] + list(_pad_pairs(padding, n)) + [(0, 0)]
    summed = lax.reduce_window(
        x, 0.0, lax.add,
        window_dimensions=(1,) + window + (1,),
        window_strides=(1,) + stride + (1,),
        padding=pads,
    )
    if count_include_pad:
        return summed / np.prod(window)
    ones = jnp.ones(x.shape[:-1] + (1,), x.dtype)
    counts = lax.reduce_window(
        ones, 0.0, lax.add,
        window_dimensions=(1,) + window + (1,),
        window_strides=(1,) + stride + (1,),
        padding=pads,
    )
    return summed / counts


def adaptive_avg_pool(x: Array, output_size: int = 1) -> Array:
    """AdaptiveAvgPool to (1,1,...) == global mean over spatial dims."""
    assert output_size == 1, 'only global pooling is used by these models'
    if x.dtype == jnp.bfloat16:
        # fp32 island: the global-pooling mean over thousands of
        # spatial positions is the single widest accumulation in the
        # conv families — and it feeds the feature output directly
        return x.astype(jnp.float32).mean(
            axis=tuple(range(1, x.ndim - 1))).astype(x.dtype)
    return x.mean(axis=tuple(range(1, x.ndim - 1)))


def same_padding_tf(in_size: int, kernel: int, stride: int,
                    dilation: int = 1) -> Tuple[int, int]:
    """TF-SAME per-edge (lo, hi) padding — asymmetric, extra on the high side.

    This is the semantics I3D inherited from its TF origin (reference
    models/i3d/i3d_src/i3d_net.py:8-34 emulates it in torch with ConstantPad3d;
    here it is just explicit lax padding).
    """
    eff_k = (kernel - 1) * dilation + 1
    out = -(-in_size // stride)  # ceil
    pad = max(0, (out - 1) * stride + eff_k - in_size)
    return pad // 2, pad - pad // 2


def ceil_mode_padding(in_size: int, kernel: int, stride: int) -> Tuple[int, int]:
    """Torch ceil_mode pooling → (0, extra) high-side padding."""
    out_ceil = -(-(in_size - kernel) // stride) + 1
    needed = (out_ceil - 1) * stride + kernel - in_size
    return 0, max(0, needed)
