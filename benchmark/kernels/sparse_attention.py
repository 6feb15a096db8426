"""Operations and bytes of one window's attention under a learned selection
of keys, from its shapes.

One call is one window of one full latent-attention layer of a ``dots3_note``
trunk: ``positions`` queries over ``heads`` equal heads, query/key heads
``qk_dim`` wide and value heads ``v_dim`` wide, query t attending the
``topk`` keys its indexer selected among those at or before it —
``Σ_t min(t + 1, topk)`` selected (query, key) pairs a head.

* operations: a selected pair is one multiply-add a query/key column (the
  score) and one a value column (its share of the output), 2 FLOPs each:
  ``2 · Σ_t min(t + 1, topk) · heads · (qk_dim + v_dim)``, counted at ONE
  pass. That is the model's work whatever implements it: three bf16 passes a
  float32 product (``precision=mixed``) are three times the MXU work for the
  same count, so under three passes the share cannot pass a third; the
  pairs of the triangle a kernel computes under the mask and throws away
  earn nothing (at 8,192 positions the selection keeps 43.7 % of the
  triangle), nor do the soft-max's exponentials, nor the indexer, which is
  not this call;
* bytes: what any implementation has to move — Q read and the output
  written once a head, K and V read once a head, float32 (latent attention's
  rotary key is one head shared by all; the count takes the expanded head,
  as the FLOPs do) — and the selection once, as bits: ``positions² / 8``.

The share of the roofline is ``max(flops / peak, bytes / bandwidth)`` over the
device time of the kernel's events; the reader says which bound applies.

**What one trace event covers:** one window of one full layer. The program
calls the kernel inside the layer's loop over the step's windows
(``models/latent_moe.py::hidden_states``: ``lax.map`` over the batch), one
``pallas_call(name='sparse_attention')`` an iteration, so a step of two
windows and two full layers is 4 events; ``EVENTS_PER_CALL`` is 1 and
``shapes`` takes no notice of the batch. ``EVENT_MATCH`` finds those events
on the ``XLA Ops`` line: the compiler names the HLO instruction after the
kernel (``%sparse_attention.<n> = … custom-call(…)``). The sliding layers'
calls beside them are ``%window_attention.<n>`` and do not match.

``metrics/sparse_attention_roofline.json`` (``dots3-note.corpus``) reads this
file through ``readers/kernel_roofline.py``. The shapes are the
configuration's own keys (``num_attention_heads``, ``qk_nope_head_dim +
qk_rope_head_dim``, ``v_head_dim``, ``index_topk``), the window's positions
``stack_size · patch_grid²`` of its ``overrides``: 8,192 × 128 heads ×
(192 + 128) over 2,048 keys a query → 1.2027 TFLOP, 2.69 GB a call; 6.10 ms
at 197 TFLOP/s, FLOPs-bound.
"""
from __future__ import annotations

F32 = 4
EVENTS_PER_CALL = 1
EVENT_MATCH = (r'^%sparse_attention[\w.\-]* = .*custom-call\(.*'
               r'custom_call_target="tpu_custom_call"')


def shapes(cfg: dict, batch: int) -> dict:
    """One window-layer's shapes in a cell (``batch`` windows a step are
    ``batch`` events, not a larger call)."""
    window = cfg['overrides']
    return {'positions': int(window['stack_size'])
            * int(window['patch_grid']) ** 2,
            'topk': cfg['index_topk'], 'heads': cfg['num_attention_heads'],
            'qk_dim': cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim'],
            'v_dim': cfg['v_head_dim']}


def selected_pairs(positions: int, topk: int) -> int:
    """Σ_t min(t + 1, topk) over t = 0 … positions − 1."""
    k = min(topk, positions)
    return k * (k + 1) // 2 + (positions - k) * k


def flops(positions: int, topk: int, heads: int, qk_dim: int,
          v_dim: int) -> int:
    return 2 * selected_pairs(positions, topk) * heads * (qk_dim + v_dim)


def bytes_moved(positions: int, topk: int, heads: int, qk_dim: int,
                v_dim: int) -> int:
    return (positions * 2 * heads * (qk_dim + v_dim) * F32
            + positions * positions // 8)


def min_seconds(peaks: dict, **shape) -> tuple:
    """(least seconds one call can take on this chip, which bound it is)."""
    t_flops = flops(**shape) / peaks['bf16_flops_per_s']
    t_bytes = bytes_moved(**shape) / peaks['hbm_bytes_per_s']
    return (t_bytes, 'bytes') if t_bytes >= t_flops else (t_flops, 'flops')
