"""Operations and bytes of one window's sliding-window attention, from its
shapes.

One call is one window of one sliding layer: ``positions`` queries over
``heads`` query heads, query/key and value heads ``qk_dim`` / ``v_dim`` wide
over ``kv_heads`` key-value heads, query i attending keys i − window + 1 … i
— ``Σᵢ min(i + 1, window)`` visible (query, key) pairs a query head, the
band and not the triangle.

* operations: a visible pair is one multiply-add a query/key column (the
  score) and one a value column (its share of the output), 2 FLOPs each:
  ``2 · Σᵢ min(i + 1, window) · heads · (qk_dim + v_dim)``, counted at ONE
  pass. That is the model's work whatever implements it: three bf16 passes a
  float32 product (``precision=mixed``) are three times the MXU work for the
  same count, so under three passes the share cannot pass a third; whole
  key tiles computed under the mask where the band's edge or the diagonal
  crosses them earn nothing, nor do the soft-max's exponentials;
* bytes: what any implementation has to move — Q read and the output written
  once a query head, K and V read once a key-value head, float32. The score
  tile is no one's business outside the kernel.

The share of the roofline is ``max(flops / peak, bytes / bandwidth)`` over the
device time of the kernel's events; the reader says which bound applies.

**What one trace event covers:** one window of one sliding layer. The
program calls the kernel inside the layer's loop over the step's windows
(``models/hybrid_trunk.py::hidden_states``: ``lax.map`` over the batch),
one ``pallas_call(name='window_attention')`` an iteration, so a step of one
window and six sliding layers is 6 events; ``EVENTS_PER_CALL`` is 1 and
``shapes`` takes no notice of the batch. ``EVENT_MATCH`` finds those events
on the ``XLA Ops`` line: the compiler names the HLO instruction after the
kernel (``%window_attention.<n> = … custom-call(…)``). The same layers'
full-causal siblings are ``%causal_attention.<n>`` and do not match (nor
does this name match ``kernels/causal_attention.py``'s pattern).

``metrics/window_attention_roofline.json`` (PR 38, ``trinity-mini.corpus``)
reads this file through ``readers/kernel_roofline.py``. The shapes are the
configuration's own keys (``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``sliding_window``), the window's positions ``stack_size ·
patch_grid²`` of its ``overrides``: 32,768 × 32 / 4 × 128 under a window of
2,048 → 1.065 TFLOP, 1.21 GB a call; FLOPs-bound.
"""
from __future__ import annotations

F32 = 4
EVENTS_PER_CALL = 1
EVENT_MATCH = (r'^%window_attention[\w.\-]* = .*custom-call\(.*'
               r'custom_call_target="tpu_custom_call"')


def shapes(cfg: dict, batch: int) -> dict:
    """One window-layer's shapes in a cell (``batch`` windows a step are
    ``batch`` events, not a larger call)."""
    window = cfg['overrides']
    heads = cfg['num_attention_heads']
    width = cfg.get('head_dim', cfg['hidden_size'] // heads)
    return {'positions': int(window['stack_size'])
            * int(window['patch_grid']) ** 2,
            'window': cfg['sliding_window'], 'heads': heads,
            'kv_heads': cfg['num_key_value_heads'], 'qk_dim': width,
            'v_dim': width}


def visible_pairs(positions: int, window: int) -> int:
    """Σᵢ min(i + 1, window) over i = 0 … positions − 1."""
    w = min(window, positions)
    return w * (w + 1) // 2 + (positions - w) * w


def flops(positions: int, window: int, heads: int, kv_heads: int,
          qk_dim: int, v_dim: int) -> int:
    return 2 * visible_pairs(positions, window) * heads * (qk_dim + v_dim)


def bytes_moved(positions: int, window: int, heads: int, kv_heads: int,
                qk_dim: int, v_dim: int) -> int:
    return positions * (heads + kv_heads) * (qk_dim + v_dim) * F32


def min_seconds(peaks: dict, **shape) -> tuple:
    """(least seconds one call can take on this chip, which bound it is)."""
    t_flops = flops(**shape) / peaks['bf16_flops_per_s']
    t_bytes = bytes_moved(**shape) / peaks['hbm_bytes_per_s']
    return (t_bytes, 'bytes') if t_bytes >= t_flops else (t_flops, 'flops')
