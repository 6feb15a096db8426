"""I3D (Inception-v1 inflated 3-D ConvNet, two-stream rgb/flow).

Functional re-implementation of the architecture behind the reference i3d
extractor (reference models/i3d/i3d_src/i3d_net.py, 431 LoC — a TF-port):

  * TF-SAME padding approximated as pad = max(kernel - stride, 0), split
    low = pad//2 / high = pad - low (:8-25). In JAX this is just explicit
    per-edge lax padding — no ConstantPad3d workaround needed;
  * max pools zero-pad (not -inf!) with the same rule, then pool with
    ceil_mode (:108-120) — reproduced here literally: explicit 0-pad, then
    ceil-mode high-side -inf padding;
  * 9 inception Mixed blocks, avg_pool (2,7,7) stride 1, and a
    ``features=True`` path that squeezes + means over time to 1024-d
    (:238-264); classifier head is a 1×1×1 conv with bias (:265-274).

Params mirror the torch state_dict (conv3d_1a_7x7.conv3d.weight, …).
Layout NDHWC; rgb input (B,T,224,224,3) in [-1,1], flow (B,T,224,224,2).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from video_features_tpu.ops.nn import (
    avg_pool, batch_norm, conv, conv_space_to_depth, relu,
)

Params = Dict[str, Any]

FEAT_DIM = 1024

# Mixed blocks: name -> (in, [b0, b1_mid, b1_out, b2_mid, b2_out, b3])
MIXED_CFGS = {
    'mixed_3b': (192, [64, 96, 128, 16, 32, 32]),
    'mixed_3c': (256, [128, 128, 192, 32, 96, 64]),
    'mixed_4b': (480, [192, 96, 208, 16, 48, 64]),
    'mixed_4c': (512, [160, 112, 224, 24, 64, 64]),
    'mixed_4d': (512, [128, 128, 256, 24, 64, 64]),
    'mixed_4e': (512, [112, 144, 288, 32, 64, 64]),
    'mixed_4f': (528, [256, 160, 320, 32, 128, 128]),
    'mixed_5b': (832, [256, 160, 320, 32, 128, 128]),
    'mixed_5c': (832, [384, 192, 384, 48, 128, 128]),
}


def tf_same_pads(kernel: Tuple[int, ...], stride: Tuple[int, ...]):
    """pad = max(k - s, 0) split (lo = pad//2, hi = rest) per dim."""
    pads = []
    for k, s in zip(kernel, stride):
        p = max(k - s, 0)
        pads.append((p // 2, p - p // 2))
    return pads


def unit3d(p: Params, x: jax.Array, kernel: Tuple[int, int, int],
           stride: Tuple[int, int, int] = (1, 1, 1), use_bn: bool = True,
           activation: bool = True, conv=conv) -> jax.Array:
    """Unit3Dpy: SAME conv (+ bias) → BN → ReLU (reference i3d_net.py:37-105)."""
    x = conv(x, p['conv3d']['weight'], stride=stride,
             padding=tf_same_pads(kernel, stride),
             bias=p['conv3d'].get('bias'))
    if use_bn:
        x = batch_norm(x, p['batch3d'])
    if activation:
        x = relu(x)
    return x


def max_pool_tf(x: jax.Array, kernel: Tuple[int, int, int],
                stride: Tuple[int, int, int]) -> jax.Array:
    """MaxPool3dTFPadding: explicit ZERO pad (k-s rule) then ceil-mode pool.

    The zero pad (not -inf) is a quirk of the reference (:108-120); inputs are
    post-ReLU so results coincide, but we reproduce it literally.
    """
    from video_features_tpu.ops.nn import ceil_mode_padding, max_pool

    pads = tf_same_pads(kernel, stride)
    x = jnp.pad(x, [(0, 0)] + [(lo, hi) for lo, hi in pads] + [(0, 0)])
    # torch ceil_mode: windows clipped at the edge == -inf high-side padding
    extra = [ceil_mode_padding(x.shape[i + 1], k, s)
             for i, (k, s) in enumerate(zip(kernel, stride))]
    return max_pool(x, kernel, stride=stride, padding=extra)


def mixed(p: Params, x: jax.Array) -> jax.Array:
    b0 = unit3d(p['branch_0'], x, (1, 1, 1))
    b1 = unit3d(p['branch_1']['1'],
                unit3d(p['branch_1']['0'], x, (1, 1, 1)), (3, 3, 3))
    b2 = unit3d(p['branch_2']['1'],
                unit3d(p['branch_2']['0'], x, (1, 1, 1)), (3, 3, 3))
    b3 = unit3d(p['branch_3']['1'],
                max_pool_tf(x, (3, 3, 3), (1, 1, 1)), (1, 1, 1))
    return jnp.concatenate([b0, b1, b2, b3], axis=-1)


def forward(params: Params, x: jax.Array, features: bool = True):
    """(B, T, 224, 224, C) → (B, 1024) features, or (softmax, logits)."""
    # 3 (rgb) or 2 (flow) input channels leave the MXU's contraction lanes
    # empty: the strided stem runs at stride 1 over its taps folded into
    # channels; the scope names its ops in the compiled HLO and the trace
    with jax.named_scope('i3d_stem'):
        x = unit3d(params['conv3d_1a_7x7'], x, (7, 7, 7), (2, 2, 2),
                   conv=conv_space_to_depth)
    x = max_pool_tf(x, (1, 3, 3), (1, 2, 2))
    x = unit3d(params['conv3d_2b_1x1'], x, (1, 1, 1))
    x = unit3d(params['conv3d_2c_3x3'], x, (3, 3, 3))
    x = max_pool_tf(x, (1, 3, 3), (1, 2, 2))
    x = mixed(params['mixed_3b'], x)
    x = mixed(params['mixed_3c'], x)
    x = max_pool_tf(x, (3, 3, 3), (2, 2, 2))
    for name in ('mixed_4b', 'mixed_4c', 'mixed_4d', 'mixed_4e', 'mixed_4f'):
        x = mixed(params[name], x)
    x = max_pool_tf(x, (2, 2, 2), (2, 2, 2))
    x = mixed(params['mixed_5b'], x)
    x = mixed(params['mixed_5c'], x)
    x = avg_pool(x, (2, x.shape[2], x.shape[3]), stride=1)   # (B, T', 1, 1, 1024)
    if features:
        return x.reshape(x.shape[0], x.shape[1], -1).mean(axis=1)
    logits = conv(x, params['conv3d_0c_1x1']['conv3d']['weight'],
                  bias=params['conv3d_0c_1x1']['conv3d']['bias'])
    logits = logits.reshape(logits.shape[0], logits.shape[1], -1).mean(axis=1)
    return jax.nn.softmax(logits, axis=-1), logits


def init_state_dict(seed: int = 0, modality: str = 'rgb',
                    num_classes: int = 400) -> Dict[str, np.ndarray]:
    """Random torch-layout state_dict with the reference I3D naming/shapes."""
    rng = np.random.RandomState(seed)
    sd: Dict[str, np.ndarray] = {}
    in_channels = 3 if modality == 'rgb' else 2

    def unit(name, i, o, k, bias=False, bn=True):
        kt, kh, kw = (k, k, k) if isinstance(k, int) else k
        sd[f'{name}.conv3d.weight'] = rng.randn(o, i, kt, kh, kw).astype(np.float32) * 0.05
        if bias:
            sd[f'{name}.conv3d.bias'] = rng.randn(o).astype(np.float32) * 0.05
        if bn:
            sd[f'{name}.batch3d.weight'] = rng.rand(o).astype(np.float32) + 0.5
            sd[f'{name}.batch3d.bias'] = rng.randn(o).astype(np.float32) * 0.1
            sd[f'{name}.batch3d.running_mean'] = rng.randn(o).astype(np.float32) * 0.1
            sd[f'{name}.batch3d.running_var'] = rng.rand(o).astype(np.float32) + 0.5

    unit('conv3d_1a_7x7', in_channels, 64, 7)
    unit('conv3d_2b_1x1', 64, 64, 1)
    unit('conv3d_2c_3x3', 64, 192, 3)
    for name, (cin, (b0, b1m, b1o, b2m, b2o, b3)) in MIXED_CFGS.items():
        unit(f'{name}.branch_0', cin, b0, 1)
        unit(f'{name}.branch_1.0', cin, b1m, 1)
        unit(f'{name}.branch_1.1', b1m, b1o, 3)
        unit(f'{name}.branch_2.0', cin, b2m, 1)
        unit(f'{name}.branch_2.1', b2m, b2o, 3)
        unit(f'{name}.branch_3.1', cin, b3, 1)
    unit('conv3d_0c_1x1', 1024, num_classes, 1, bias=True, bn=False)
    return sd
