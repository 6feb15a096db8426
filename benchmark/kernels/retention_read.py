"""Operations and bytes of one chunk-layer's state read, from its shapes.

Power retention's chunked scan (``ops/retention.py``) reads the carried
state once a chunk and layer: every one of the chunk's ``positions`` in every
one of the ``heads`` query heads contracts its ``d(d+1)/2``-wide feature map
φ(q) with the ``(d(d+1)/2, v_dim)`` state of its key-value head. One call is
that read for one chunk of one layer of one window.

* operations: a (position, query head) row is one multiply-add a state
  entry, 2 FLOPs each: ``2 · heads · positions · d(d+1)/2 · v_dim``, counted
  at ONE pass — 2 · 40 · 512 · 8,256 · 128 = 43.3 GFLOP in the cell. That is
  the model's work whatever implements it: three bf16 passes a float32
  product (``precision=mixed``) are three times the MXU work for the same
  count, so under three passes the share cannot pass a third. Forming φ (a
  rotation and a product a feature) is the kernel's own business and earns
  nothing, nor does the half block's padding to a whole one;
* bytes: what any implementation has to move — q and the state read once and
  the output written once, float32. φ(q) is no one's business outside the
  kernel (676 MB a call in the cell, against 48.3 MB counted here): an
  implementation that writes it to memory moves more, and that shows as a
  low share.

The share of the roofline is ``max(flops / peak, bytes / bandwidth)`` over the
device time of the kernel's events; the reader says which bound applies.

**What one trace event covers:** one chunk of one layer of one window. The
program calls the kernel in the body of the scan over a window's chunks
(``ops/retention.py::retention_chunked``), one
``pallas_call(name='retention_read')`` an iteration, so a step of one
window of 64 chunks through 4 layers is 256 events; ``EVENTS_PER_CALL`` is 1
and ``shapes`` takes no notice of the batch. ``EVENT_MATCH`` finds those
events on the ``XLA Ops`` line: the compiler names the HLO instruction after
the kernel (``%retention_read.<n> = … custom-call(…)``), as it names
``%causal_attention.<n>``; the state update beside it is
``%retention_update.<n>`` and does not match.

``metrics/retention_read_roofline.json`` (PR 37, ``brumby.corpus``) reads
this file through ``readers/kernel_roofline.py`` with ``match`` =
``EVENT_MATCH`` and ``events_per_call`` = ``EVENTS_PER_CALL``.

The chunk is the program's constant (``models/retention_trunk.py::
RETENTION_CHUNK``; a window shorter than it is one chunk), read from there;
the window's positions and the heads' widths are keys of the configuration.
"""
from __future__ import annotations

F32 = 4
EVENTS_PER_CALL = 1
EVENT_MATCH = (r'^%retention_read[\w.\-]* = .*custom-call\(.*'
               r'custom_call_target="tpu_custom_call"')


def chunk_positions(cfg: dict) -> int:
    from video_features_tpu.models.retention_trunk import RETENTION_CHUNK
    o = cfg['overrides']
    return min(RETENTION_CHUNK, o['stack_size'] * o['patch_grid'] ** 2)


def shapes(cfg: dict, batch: int) -> dict:
    """One chunk-layer's shapes in a cell (``batch`` windows a step are
    ``batch`` times as many events, not a larger call)."""
    return {'positions': chunk_positions(cfg),
            'heads': cfg['num_attention_heads'],
            'kv_heads': cfg['num_key_value_heads'],
            'd': cfg['head_dim'], 'v_dim': cfg['head_dim']}


def feature_dim(d: int) -> int:
    return d * (d + 1) // 2


def flops(positions: int, heads: int, kv_heads: int, d: int,
          v_dim: int) -> int:
    return 2 * heads * positions * feature_dim(d) * v_dim


def bytes_moved(positions: int, heads: int, kv_heads: int, d: int,
                v_dim: int) -> int:
    q = heads * positions * d
    state = kv_heads * feature_dim(d) * v_dim
    out = heads * positions * v_dim
    return (q + state + out) * F32


def min_seconds(peaks: dict, **shape) -> tuple:
    """(least seconds one call can take on this chip, which bound it is)."""
    t_flops = flops(**shape) / peaks['bf16_flops_per_s']
    t_bytes = bytes_moved(**shape) / peaks['hbm_bytes_per_s']
    return (t_bytes, 'bytes') if t_bytes >= t_flops else (t_flops, 'flops')
