"""Operations and bytes of RAFT's correlation lookup, from its shapes.

One call looks up, for each of ``pairs × h8 × w8`` positions and each of
``levels`` pyramid levels, a ``(2·radius+1)²`` window of bilinear samples of
that position's correlation plane (zero outside).

* operations: every sample is 4 multiply-adds (the bilinear blend of 4
  neighbours), 2 FLOPs each;
* bytes: what any implementation has to move — per position and level the
  ``(2·radius+2)²`` correlation values the window touches (consecutive taps
  share neighbours, so a window of 9×9 samples reads 10×10 values), the two
  coordinates, and the ``levels·(2·radius+1)²`` results written, all float32.
  A kernel that streams the whole plane per position moves more; that shows as
  a low share, not as a different yardstick.

The share of the roofline is ``max(flops / peak, bytes / bandwidth)`` over the
device time of the lookup's events; the reader says which bound applies.
"""
from __future__ import annotations

F32 = 4


def shapes(cfg: dict, batch: int) -> dict:
    """The lookup's shapes in a cell: ``batch`` stacks of ``stack`` pairs."""
    k = cfg['kernel_shapes']
    return {'pairs': batch * k['pairs_per_unit'], 'h8': k['h8'],
            'w8': k['w8'], 'levels': k['levels'], 'radius': k['radius']}


def flops(pairs: int, h8: int, w8: int, levels: int = 4,
          radius: int = 4) -> int:
    taps = (2 * radius + 1) ** 2
    return pairs * h8 * w8 * levels * taps * 4 * 2


def bytes_moved(pairs: int, h8: int, w8: int, levels: int = 4,
                radius: int = 4) -> int:
    positions = pairs * h8 * w8
    taps = (2 * radius + 1) ** 2
    touched = (2 * radius + 2) ** 2
    per_position = levels * touched + 2 + levels * taps
    return positions * per_position * F32


def min_seconds(peaks: dict, **shape) -> tuple:
    """(least seconds one call can take on this chip, which bound it is)."""
    t_flops = flops(**shape) / peaks['bf16_flops_per_s']
    t_bytes = bytes_moved(**shape) / peaks['hbm_bytes_per_s']
    return (t_bytes, 'bytes') if t_bytes >= t_flops else (t_flops, 'flops')
