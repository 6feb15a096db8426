"""Operations and bytes of one window's causal attention, from its shapes.

One call is one window of one layer: ``positions`` queries over ``heads``
heads, query/key heads ``qk_dim`` wide and value heads ``v_dim`` wide, query
i attending keys 0…i — S(S+1)/2 visible (query, key) pairs a head.

* operations: a visible pair is one multiply-add a query/key column (the
  score) and one a value column (its share of the output), 2 FLOPs each:
  ``2 · S(S+1)/2 · heads · (qk_dim + v_dim)``, counted at ONE pass. That is
  the model's work whatever implements it: a program that makes three bf16
  passes a float32 product (``precision=mixed``) does three times the MXU
  work for the same count, so under three passes the share cannot pass a
  third; the soft-max's exponentials are not counted;
* bytes: what any implementation has to move — Q, K and V read once and the
  output written once, float32. (Latent attention's rotary key is one head
  shared by all; the count takes the expanded head, as the FLOPs do.) The
  score tile is no one's business outside the kernel: an implementation
  that writes it to memory moves more, and that shows as a low share.

The share of the roofline is ``max(flops / peak, bytes / bandwidth)`` over the
device time of the kernel's events; the reader says which bound applies.

**What one trace event covers:** one window of one layer. The program calls
the kernel inside the layer's loop over the step's windows
(``models/latent_moe.py::hidden_states``, ``lax.map`` over the batch), one
``pallas_call(name='causal_attention')`` an iteration, so a step of 4
windows and 5 layers is 20 events; ``EVENTS_PER_CALL`` is 1 and ``shapes``
takes no notice of the batch. ``EVENT_MATCH`` finds those events on the
``XLA Ops`` line: the compiler names the HLO instruction after the kernel
(``%causal_attention.<n> = … custom-call(…)``), as it names the lookup's
``%raft_corr_lookup_lanes.<n>``.

**No metric reads this file yet.** ``causal_attention_roofline`` (reader
``kernel_roofline``, ``match`` = ``EVENT_MATCH``, ``events_per_call`` =
``EVENTS_PER_CALL``, ``moves`` ``clips_per_s``, ``workloads``
``["joyai-flash.corpus"]``) needs its entry in ``BENCHMARK.json`` and its
file under ``metrics/``, and ``tests/bench/test_joyai_flash.py`` pins the
cell's per-layer metrics as a set: a file only a ``benchmark`` PR may edit
(PERF.md §7, PR 30).

The window's positions are no key of the benchmark's configuration file (the
program's shipped ``configs/lm.yml`` has them: ``stack_size`` ×
``patch_grid``²), so they are read from there; heads and widths are keys of
the configuration.
"""
from __future__ import annotations

from pathlib import Path

F32 = 4
EVENTS_PER_CALL = 1
EVENT_MATCH = (r'^%causal_attention[\w.\-]* = .*custom-call\(.*'
               r'custom_call_target="tpu_custom_call"')
LM_YML = (Path(__file__).resolve().parents[2] / 'video_features_tpu'
          / 'configs' / 'lm.yml')


def window_positions() -> int:
    import yaml
    lm = yaml.safe_load(LM_YML.read_text())
    return int(lm['stack_size']) * int(lm['patch_grid']) ** 2


def shapes(cfg: dict, batch: int) -> dict:
    """One window-layer's shapes in a cell (``batch`` windows a step are
    ``batch`` events, not a larger call)."""
    return {'positions': window_positions(),
            'heads': cfg['num_attention_heads'],
            'qk_dim': cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim'],
            'v_dim': cfg['v_head_dim']}


def flops(positions: int, heads: int, qk_dim: int, v_dim: int) -> int:
    pairs = positions * (positions + 1) // 2
    return 2 * pairs * heads * (qk_dim + v_dim)


def bytes_moved(positions: int, heads: int, qk_dim: int, v_dim: int) -> int:
    return positions * heads * (2 * qk_dim + 2 * v_dim) * F32


def min_seconds(peaks: dict, **shape) -> tuple:
    """(least seconds one call can take on this chip, which bound it is)."""
    t_flops = flops(**shape) / peaks['bf16_flops_per_s']
    t_bytes = bytes_moved(**shape) / peaks['hbm_bytes_per_s']
    return (t_bytes, 'bytes') if t_bytes >= t_flops else (t_flops, 'flops')
