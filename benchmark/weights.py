"""Seeded weights in the checkpoint layout the program loads.

A reference lists its parameters as ``(name, kind, shape, scale)`` with the
shapes channels-last (``(*kernel, in, out)`` for a convolution, ``(in, out)``
for a dense layer): the layout of the ``.npz`` archives the program reads
without torch (``<flat.dotted.name>`` → array). The benchmark draws them from
``--seed``, writes one archive per checkpoint key under the run's tmp
directory and hands the paths to the program; the reference reads the same
arrays. The program never sees the seed.

Convolutions are He-normal (std = sqrt(2 / fan_in) × scale), so activations
keep their size through fifty layers; batch-norm statistics are near the
identity with some spread, so no channel dies.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, List, Tuple

import numpy as np

Spec = Tuple[str, str, tuple, float]   # name, kind, shape, scale


def make(specs: List[Spec], seed: int, group: str) -> Dict[str, np.ndarray]:
    rng = np.random.Generator(np.random.PCG64(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, zlib.crc32(group.encode())]))
    out: Dict[str, np.ndarray] = {}
    for name, kind, shape, scale in specs:
        shape = tuple(shape)
        if kind in ('conv', 'linear'):
            fan_in = math.prod(shape[:-1])
            w = rng.standard_normal(shape, dtype=np.float32)
            w *= np.float32(scale * math.sqrt(2.0 / fan_in))
        elif kind in ('bias', 'bn_bias', 'bn_mean'):
            w = rng.standard_normal(shape, dtype=np.float32)
            w *= np.float32(0.1 * scale)
        elif kind in ('bn_weight', 'bn_var'):
            w = (0.8 + 0.4 * rng.random(shape, dtype=np.float32)) \
                * np.float32(scale)
        else:
            raise ValueError(f'{name}: unknown parameter kind {kind!r}')
        out[name] = w
    return out


def bn_specs(name: str, channels: int, gamma: float = 1.0) -> List[Spec]:
    return [(f'{name}.weight', 'bn_weight', (channels,), gamma),
            (f'{name}.bias', 'bn_bias', (channels,), 1.0),
            (f'{name}.running_mean', 'bn_mean', (channels,), 1.0),
            (f'{name}.running_var', 'bn_var', (channels,), 1.0)]


def save(params: Dict[str, np.ndarray], path: str) -> str:
    np.savez(path, **params)
    return path


def load(path: str) -> Dict[str, np.ndarray]:
    with np.load(path) as data:
        return {k: data[k] for k in data.files}
