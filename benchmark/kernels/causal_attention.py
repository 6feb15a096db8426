"""Operations and bytes of one window's causal attention, from its shapes.

One call is one window of one layer: ``positions`` queries over ``heads``
query heads, query/key heads ``qk_dim`` wide and value heads ``v_dim`` wide
over ``kv_heads`` key-value heads, query i attending keys 0…i — S(S+1)/2
visible (query, key) pairs a query head.

* operations: a visible pair is one multiply-add a query/key column (the
  score) and one a value column (its share of the output), 2 FLOPs each:
  ``2 · S(S+1)/2 · heads · (qk_dim + v_dim)``, counted at ONE pass. That is
  the model's work whatever implements it: a program that makes three bf16
  passes a float32 product (``precision=mixed``) does three times the MXU
  work for the same count, so under three passes the share cannot pass a
  third; the soft-max's exponentials are not counted. Grouped queries change
  nothing here: every query head meets every visible key;
* bytes: what any implementation has to move — Q read and the output written
  once a query head, K and V read once a key-value head, float32. (Latent
  attention's rotary key is one head shared by all; the count takes the
  expanded head, as the FLOPs do.) The score tile is no one's business
  outside the kernel: an implementation that writes it to memory moves
  more, and that shows as a low share.

The share of the roofline is ``max(flops / peak, bytes / bandwidth)`` over the
device time of the kernel's events; the reader says which bound applies.

**What one trace event covers:** one window of one layer. The program calls
the kernel inside the layer's loop over the step's windows
(``models/latent_moe.py::hidden_states``, ``models/hybrid_trunk.py``'s
likewise: ``lax.map`` over the batch), one
``pallas_call(name='causal_attention')`` an iteration, so a step of 4
windows and 5 layers is 20 events (8 with two attention layers);
``EVENTS_PER_CALL`` is 1 and ``shapes`` takes no notice of the batch.
``EVENT_MATCH`` finds those events on the
``XLA Ops`` line: the compiler names the HLO instruction after the kernel
(``%causal_attention.<n> = … custom-call(…)``), as it names the lookup's
``%raft_corr_lookup_lanes.<n>``.

``metrics/causal_attention_roofline.json`` (PR 37) reads this file through
``readers/kernel_roofline.py`` with ``match`` = ``EVENT_MATCH`` and
``events_per_call`` = ``EVENTS_PER_CALL``, in the two cells whose step calls
the kernel:

* latent attention (``joyai-flash.corpus``; the configuration has
  ``qk_nope_head_dim`` …): ``num_attention_heads`` equal heads of
  ``qk_nope_head_dim + qk_rope_head_dim`` and ``v_head_dim`` — 687.3 GFLOP a
  call;
* grouped-query attention (``lfm2-moe.corpus``; no such key):
  ``num_attention_heads`` query heads over ``num_key_value_heads``, ``qk_dim =
  v_dim = head_dim``, which is ``hidden_size / num_attention_heads`` where the
  configuration spells none (64 there, as its ``assumed.head_dim`` says) —
  274.9 GFLOP a call.

The window's positions are ``stack_size · patch_grid²`` of the configuration's
``overrides`` where it spells them (a cell with a window of its own), else of
the program's shipped ``configs/lm.yml`` (8,192), which such a cell runs.
"""
from __future__ import annotations

from pathlib import Path

F32 = 4
EVENTS_PER_CALL = 1
EVENT_MATCH = (r'^%causal_attention[\w.\-]* = .*custom-call\(.*'
               r'custom_call_target="tpu_custom_call"')
LM_YML = (Path(__file__).resolve().parents[2] / 'video_features_tpu'
          / 'configs' / 'lm.yml')


def window_positions(cfg: dict) -> int:
    window = cfg.get('overrides', {})
    if not {'stack_size', 'patch_grid'} <= set(window):
        import yaml
        window = yaml.safe_load(LM_YML.read_text())
    return int(window['stack_size']) * int(window['patch_grid']) ** 2


def shapes(cfg: dict, batch: int) -> dict:
    """One window-layer's shapes in a cell (``batch`` windows a step are
    ``batch`` events, not a larger call)."""
    heads = cfg['num_attention_heads']
    if 'qk_nope_head_dim' in cfg:                     # latent attention
        qk_dim = cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim']
        v_dim = cfg['v_head_dim']
    else:                                             # grouped queries
        qk_dim = v_dim = cfg.get('head_dim', cfg['hidden_size'] // heads)
    return {'positions': window_positions(cfg), 'heads': heads,
            'kv_heads': cfg['num_key_value_heads'], 'qk_dim': qk_dim,
            'v_dim': v_dim}


def flops(positions: int, heads: int, kv_heads: int, qk_dim: int,
          v_dim: int) -> int:
    pairs = positions * (positions + 1) // 2
    return 2 * pairs * heads * (qk_dim + v_dim)


def bytes_moved(positions: int, heads: int, kv_heads: int, qk_dim: int,
                v_dim: int) -> int:
    return positions * (heads + kv_heads) * (qk_dim + v_dim) * F32


def min_seconds(peaks: dict, **shape) -> tuple:
    """(least seconds one call can take on this chip, which bound it is)."""
    t_flops = flops(**shape) / peaks['bf16_flops_per_s']
    t_bytes = bytes_moved(**shape) / peaks['hbm_bytes_per_s']
    return (t_bytes, 'bytes') if t_bytes >= t_flops else (t_flops, 'flops')
