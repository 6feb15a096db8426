"""Plain layers shared by the benchmark's references.

Straightforward ``jax.numpy`` / ``lax`` in float32, channels-last, no kernels,
no fusion tricks. Nothing here imports the program. Every multiply-add that a
convolution or a contraction makes is counted while the function is traced
(:class:`Ops.macs`), so a reference also yields its own model-FLOP count
(2 FLOPs per multiply-add) without asking a compiler.

``Ops(mode)`` fixes how contractions are computed:

* ``'highest'``  float32 operands, ``lax.Precision.HIGHEST`` (the reference);
* ``'bfloat16'`` operands rounded to bfloat16, float32 accumulation: what a
  one-pass MXU matmul does, and the *control* of "How correct is decided"
  (the nearest precision below the configuration's 3-pass ``high``). Unlike
  a precision flag it also takes effect on the CPU, where the tests run it.
"""
from __future__ import annotations

import math
from contextlib import contextmanager

import jax.numpy as jnp
from jax import lax

MODES = ('highest', 'bfloat16')


class Ops:
    def __init__(self, mode: str = 'highest') -> None:
        if mode not in MODES:
            raise ValueError(f'mode must be one of {MODES}, got {mode!r}')
        self.mode = mode
        self.macs = 0          # multiply-adds traced so far
        self._repeat = 1

    # -- counting ---------------------------------------------------------
    @contextmanager
    def repeat(self, n: int):
        """Context: what is traced inside runs ``n`` times (a scan body)."""
        saved, self._repeat = self._repeat, self._repeat * n
        try:
            yield
        finally:
            self._repeat = saved

    def _count(self, macs: int) -> None:
        self.macs += int(macs) * self._repeat

    def _operands(self, a, b):
        if self.mode == 'bfloat16':
            return a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), None
        return a, b, lax.Precision.HIGHEST

    # -- contractions -----------------------------------------------------
    def conv(self, x, w, stride=1, padding=0, groups=1, bias=None):
        """N-D convolution. x: (B, *spatial, C); w: (*kernel, C/groups, O).
        ``padding``: int, per-dim ints, or per-dim (lo, hi) pairs."""
        n = w.ndim - 2
        stride = (stride,) * n if isinstance(stride, int) else tuple(stride)
        if isinstance(padding, int):
            padding = [(padding, padding)] * n
        else:
            padding = [(p, p) if isinstance(p, int) else tuple(p)
                       for p in padding]
        letters = 'DHW'[3 - n:]
        spec = (f'N{letters}C', f'{letters}IO', f'N{letters}C')
        a, b, prec = self._operands(x, w)
        out = lax.conv_general_dilated(
            a, b, window_strides=stride, padding=padding,
            dimension_numbers=spec, feature_group_count=groups,
            precision=prec, preferred_element_type=jnp.float32)
        self._count(math.prod(out.shape) * math.prod(w.shape[:-1]))
        if bias is not None:
            out = out + bias
        return out

    def einsum(self, spec: str, a, b):
        x, y, prec = self._operands(a, b)
        out = jnp.einsum(spec, x, y, precision=prec,
                         preferred_element_type=jnp.float32)
        ins, res = spec.split('->')
        sizes = {}
        for letters, arr in zip(ins.split(','), (a, b)):
            sizes.update(zip(letters, arr.shape))
        contracted = set(sizes) - set(res)
        self._count(math.prod(out.shape)
                    * math.prod(sizes[c] for c in contracted))
        return out


# -- the rest: no contraction, nothing to count, no precision to choose -----

def batch_norm(x, p, prefix, eps=1e-5):
    """Inference batch norm with running statistics (torch ``.eval()``)."""
    inv = p[f'{prefix}.weight'] / jnp.sqrt(p[f'{prefix}.running_var'] + eps)
    return (x - p[f'{prefix}.running_mean']) * inv + p[f'{prefix}.bias']


def instance_norm(x, eps=1e-5):
    """torch InstanceNorm2d without affine: per sample and channel over the
    spatial dims, biased variance."""
    axes = tuple(range(1, x.ndim - 1))
    mean = x.mean(axis=axes, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=axes, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps)


def _window(window, stride, padding):
    return ((1,) + tuple(window) + (1,), (1,) + tuple(stride) + (1,),
            [(0, 0)] + [tuple(p) for p in padding] + [(0, 0)])


def max_pool(x, window, stride, padding):
    """Max over windows; padded positions never win (−inf)."""
    dims, strides, pads = _window(window, stride, padding)
    return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pads)


def avg_pool(x, window, stride, padding=None):
    """Mean over full windows (no padding used by these models)."""
    padding = padding or [(0, 0)] * len(window)
    dims, strides, pads = _window(window, stride, padding)
    return lax.reduce_window(x, 0.0, lax.add, dims, strides,
                             pads) / math.prod(window)


def tf_same(in_size: int, kernel: int, stride: int):
    """TensorFlow 'SAME' padding of one dim: output ceil(in / stride), the
    odd cell on the high side."""
    out = -(-in_size // stride)
    pad = max((out - 1) * stride + kernel - in_size, 0)
    return pad // 2, pad - pad // 2


def center_crop_offsets(h: int, w: int, size: int):
    """torchvision CenterCrop: ``int(round((h - size) / 2.0))``."""
    return int(round((h - size) / 2.0)), int(round((w - size) / 2.0))
