"""Plain reference: torchvision ResNet-50 frame features.

Written from He et al. 2016 ("Deep Residual Learning", bottleneck ResNet-50,
layers 3-4-6-3) as torchvision ships it (v1.5: the stride sits on the 3×3
convolution of a bottleneck), with the ``IMAGENET1K_V1`` preset of the
published extractor: every frame → Pillow bilinear resize of the short side
to 256 → centre crop 224 → [0, 1] → ImageNet mean/std → backbone → global
average pool → 2048 numbers. float32, ``highest``; inference batch norm.

Departures from the published pipeline: none in the mathematics. Decoding is
OpenCV's, frame by frame from the start of the file.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from _layers import batch_norm, center_crop_offsets, max_pool
from _video import read_frames, resize_short_side

LAYERS = (3, 4, 6, 3)
PLANES = (64, 128, 256, 512)
EXPANSION = 4
RESIZE, CROP = 256, 224
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)
FEATURE_DIM = 2048
UNIT = 'frame'


def param_specs():
    """{checkpoint key of the program's config: parameter list}."""
    from weights import bn_specs
    specs = [('conv1.weight', 'conv', (7, 7, 3, 64), 1.0)]
    specs += bn_specs('bn1', 64)
    cin = 64
    for li, (blocks, planes) in enumerate(zip(LAYERS, PLANES), start=1):
        cout = planes * EXPANSION
        for bi in range(blocks):
            base = f'layer{li}.{bi}'
            stride = 2 if (li > 1 and bi == 0) else 1
            specs.append((f'{base}.conv1.weight', 'conv',
                          (1, 1, cin, planes), 1.0))
            specs += bn_specs(f'{base}.bn1', planes)
            specs.append((f'{base}.conv2.weight', 'conv',
                          (3, 3, planes, planes), 1.0))
            specs += bn_specs(f'{base}.bn2', planes)
            specs.append((f'{base}.conv3.weight', 'conv',
                          (1, 1, planes, cout), 1.0))
            # a modest gain on the residual branch keeps the trunk's size
            # steady over the sixteen blocks
            specs += bn_specs(f'{base}.bn3', cout, gamma=0.5)
            if stride != 1 or cin != cout:
                specs.append((f'{base}.downsample.0.weight', 'conv',
                              (1, 1, cin, cout), 1.0))
                specs += bn_specs(f'{base}.downsample.1', cout)
            cin = cout
    specs.append(('fc.weight', 'linear', (FEATURE_DIM, 1000), 1.0))
    specs.append(('fc.bias', 'bias', (1000,), 1.0))
    return {'checkpoint_path': specs}


def rows_of(n_frames: int, cfg=None) -> int:
    """One feature row per decoded frame."""
    return int(n_frames)


def load_units(video_path: str, rows, cfg=None) -> np.ndarray:
    """The model inputs of the given rows: (n, 224, 224, 3) uint8."""
    rows = list(rows)
    frames = read_frames(video_path, upto=max(rows) + 1)
    out = []
    for r in rows:
        f = resize_short_side(frames[r], RESIZE)
        i, j = center_crop_offsets(f.shape[0], f.shape[1], CROP)
        out.append(f[i:i + CROP, j:j + CROP])
    return np.stack(out)


def unit_shape(cfg=None):
    return (CROP, CROP, 3), np.uint8


def _bottleneck(ops, p, base, x, stride):
    out = jnp.maximum(batch_norm(ops.conv(x, p[f'{base}.conv1.weight']),
                                 p, f'{base}.bn1'), 0)
    out = jnp.maximum(batch_norm(
        ops.conv(out, p[f'{base}.conv2.weight'], stride=stride, padding=1),
        p, f'{base}.bn2'), 0)
    out = batch_norm(ops.conv(out, p[f'{base}.conv3.weight']),
                     p, f'{base}.bn3')
    if f'{base}.downsample.0.weight' in p:
        x = batch_norm(ops.conv(x, p[f'{base}.downsample.0.weight'],
                                stride=stride), p, f'{base}.downsample.1')
    return jnp.maximum(out + x, 0)


def forward(ops, params, units):
    """(n, 224, 224, 3) uint8 → (n, 2048) float32."""
    p = params['checkpoint_path']
    x = units.astype(jnp.float32) / 255.0
    x = (x - jnp.asarray(MEAN, jnp.float32)) / jnp.asarray(STD, jnp.float32)
    x = ops.conv(x, p['conv1.weight'], stride=2, padding=3)
    x = jnp.maximum(batch_norm(x, p, 'bn1'), 0)
    x = max_pool(x, (3, 3), (2, 2), [(1, 1), (1, 1)])
    for li, blocks in enumerate(LAYERS, start=1):
        for bi in range(blocks):
            stride = 2 if (li > 1 and bi == 0) else 1
            x = _bottleneck(ops, p, f'layer{li}.{bi}', x, stride)
    return x.mean(axis=(1, 2))
