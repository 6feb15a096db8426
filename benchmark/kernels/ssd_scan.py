"""Operations and bytes of one window-layer's SSD scan, from its shapes.

The Mamba-2 mixer's selective scan (``ops/ssd.py``; on the chip the Mosaic
kernel ``ssd_scan`` of ``ops/pallas_ssd.py``) runs a window of ``positions``
in chunks of ``chunk``. One call is the whole scan of one window in one
layer, every head and chunk: the grid walks head groups and chunks inside
the call. For each chunk of Q positions the model's work is

* ``C Bᵀ`` over the chunk's causal pairs, once for all heads (B and C are
  shared: one group): ``Q(Q+1)/2 · state`` multiply-adds;
* the intra-chunk product, each head's pairs against ``Δ ⊙ x``:
  ``heads · Q(Q+1)/2 · head_dim``;
* the read of the carried state and its update: ``2 · heads · Q · state ·
  head_dim``;

2 FLOPs a multiply-add, counted at ONE pass — 104.3 GFLOP a window-layer at
granite-4.0-h-micro's widths. Three bf16 passes a float32 product
(``precision=mixed``) are three times the MXU work for the same count, and
the kernel computes three of each chunk's four quarter blocks (the fourth is
all masked) and two 64-wide heads a 128-lane product: none of that earns
anything. The decays (an ``exp`` a pair and head) are VPU work and earn
nothing either.

Bytes are what any implementation has to move: x, Δ, B and C read once and
y written once, float32 — 1.116 GB a window-layer, so the call is
bytes-bound at 1.362 ms on a v5e. The kernel also reads each chunk's
cumulative decays twice (as columns and as rows) and B and C once a head
group: its own business, which shows as a lower share.

**What one trace event covers:** one window-layer. The mixer runs a window at
a time (``lax.map`` over the step's windows) and makes one
``pallas_call(name='ssd_scan')`` there, so a step of one window through 18
Mamba layers is 18 events; ``EVENTS_PER_CALL`` is 1 and ``shapes`` takes no
notice of the batch. ``EVENT_MATCH`` finds those events on the ``XLA Ops``
line: the compiler names the HLO instruction after the kernel
(``%ssd_scan.<n> = … custom-call(…)``).

``metrics/ssd_scan_roofline.json`` reads this file through
``readers/kernel_roofline.py`` with ``match`` = ``EVENT_MATCH`` and
``events_per_call`` = ``EVENTS_PER_CALL``. The shapes are keys of the
configuration (``mamba_*``) and the window its overrides' ``stack_size ·
patch_grid²``.
"""
from __future__ import annotations

F32 = 4
EVENTS_PER_CALL = 1
EVENT_MATCH = (r'^%ssd_scan[\w.\-]* = .*custom-call\(.*'
               r'custom_call_target="tpu_custom_call"')


def shapes(cfg: dict, batch: int) -> dict:
    """One window-layer's shapes in a cell (``batch`` windows a step are
    ``batch`` events, not a larger call)."""
    window = cfg['overrides']
    return {'positions': int(window['stack_size'])
            * int(window['patch_grid']) ** 2,
            'chunk': cfg['mamba_chunk_size'], 'heads': cfg['mamba_n_heads'],
            'head_dim': cfg['mamba_d_head'], 'state': cfg['mamba_d_state']}


def chunk_macs(chunk: int, heads: int, head_dim: int, state: int) -> int:
    """Multiply-adds of one whole chunk (module doc)."""
    pairs = chunk * (chunk + 1) // 2
    return (pairs * state + heads * pairs * head_dim
            + 2 * heads * chunk * state * head_dim)


def flops(positions: int, chunk: int, heads: int, head_dim: int,
          state: int) -> int:
    whole, tail = divmod(positions, chunk)
    macs = whole * chunk_macs(chunk, heads, head_dim, state)
    if tail:
        macs += chunk_macs(tail, heads, head_dim, state)
    return 2 * macs


def bytes_moved(positions: int, chunk: int, heads: int, head_dim: int,
                state: int) -> int:
    x_and_y = 2 * positions * heads * head_dim
    return (x_and_y + positions * heads + 2 * positions * state) * F32


def min_seconds(peaks: dict, **shape) -> tuple:
    """(least seconds one call can take on this chip, which bound it is)."""
    t_flops = flops(**shape) / peaks['bf16_flops_per_s']
    t_bytes = bytes_moved(**shape) / peaks['hbm_bytes_per_s']
    return (t_bytes, 'bytes') if t_bytes >= t_flops else (t_flops, 'flops')
