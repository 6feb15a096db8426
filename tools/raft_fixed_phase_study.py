#!/usr/bin/env python3
"""Arithmetic floor for RAFT's FIXED phase (VERDICT r4 task 4).

The refinement iteration got a closed floor argument in round 3 (0.88
TFLOP, hand-kernel tie — docs/benchmarks.md "Why a fused GRU kernel…"),
but the fixed phase — encoders + correlation pyramid + convex upsample,
~28% of the mixed-precision fused step — stayed dark. This tool gives
each fixed-phase piece the same treatment at the EXACT shapes the fused
batch-16 step runs (stack 16, 256×344 padded frames → 272 unique fnet
frames, 256 cnet frames, 32×43 /8 feature maps):

  * wall time per fused-step-equivalent (scan-inside-jit, value fetch —
    bench.py methodology),
  * FLOPs from XLA's cost_analysis of the identical sub-graph,
  * achieved TFLOP/s and % of v5e dense-bf16 peak (197 TFLOP/s),

so the phase's remaining headroom is a number per piece, not a guess.

    python tools/raft_fixed_phase_study.py              # real TPU
    BENCH_PLATFORM=cpu python tools/raft_fixed_phase_study.py  # smoke

One JSON line per piece + a totals line; markdown table on stderr.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

V5E_BF16_PEAK_TFLOPS = 197.0


def measure(jax, device, name, fn, args, ambient, iters):
    """(seconds per call, flops per call) for fn(*args) under ambient
    matmul precision — scan over ``iters`` DISTINCT input batches inside
    one jit (a loop-invariant operand would let XLA hoist the whole pure
    sub-graph out of the loop and divide the time by iters), checksum
    fetch; flops from cost_analysis of the single-call graph."""
    from jax import lax

    # distinct per-iteration inputs: tile + tiny per-slice perturbation
    stacked = tuple(
        np.stack([a + np.float32(i) * np.float32(1e-3)
                  for i in range(iters)]) for a in args)
    dev_args = jax.device_put(stacked, device)

    def one(xs):
        with jax.default_matmul_precision(ambient):
            out = fn(*xs)
        leaves = jax.tree_util.tree_leaves(out)
        return sum(x.sum().astype(np.float32) for x in leaves)

    lowered = jax.jit(one).lower(tuple(a[0] for a in stacked))
    ca = lowered.compile().cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get('flops', float('nan')))

    def chained(xs):
        def body(acc, sl):
            return acc + one(sl), None
        acc, _ = lax.scan(body, np.float32(0), xs)
        return acc

    jitted = jax.jit(chained)
    assert np.isfinite(float(jitted(dev_args)))       # compile + warm
    t0 = time.perf_counter()
    assert np.isfinite(float(jitted(dev_args)))
    sec = (time.perf_counter() - t0) / iters
    return name, sec, flops


def main() -> int:
    import jax
    if os.environ.get('BENCH_PLATFORM'):
        jax.config.update('jax_platforms', os.environ['BENCH_PLATFORM'])
    from functools import partial

    from video_features_tpu.models import raft as raft_model
    from video_features_tpu.ops import pallas_corr
    from video_features_tpu.ops.precision import MIXED_AMBIENT
    from video_features_tpu.transplant.torch2jax import transplant
    from video_features_tpu.utils.device import (
        enable_compilation_cache, jax_device,
    )

    platform = jax.devices()[0].platform
    on_accel = platform != 'cpu'
    enable_compilation_cache('auto', platform)
    device = jax_device(platform)
    ambient = os.environ.get('BENCH_PRECISION_AMBIENT', MIXED_AMBIENT)
    iters = int(os.environ.get('BENCH_ITERS', 4 if on_accel else 1))

    params = jax.device_put(transplant(raft_model.init_state_dict()),
                            device)
    # fused batch-16 step shapes (stack 16): 16·17 = 272 unique frames,
    # 16·16 = 256 pairs/cnet frames; /8 maps 32×43×256
    B = 16 if on_accel else 1
    S = 16
    h, w = (256, 344) if on_accel else (64, 88)
    h8, w8 = h // 8, w // 8
    n_uniq, n_pairs = B * (S + 1), B * S
    rng = np.random.RandomState(0)
    frames = rng.randint(0, 255, (n_uniq, h, w, 3)).astype(np.float32)
    first = frames[:n_pairs]
    fmap = 0.1 * rng.randn(n_pairs, h8, w8, 256).astype(np.float32)
    fmap2 = 0.1 * rng.randn(n_pairs, h8, w8, 256).astype(np.float32)
    net = rng.randn(n_pairs, h8, w8, 128).astype(np.float32)
    dflow = rng.randn(n_pairs, h8, w8, 2).astype(np.float32)

    def norm_fnet(x):
        return raft_model.basic_encoder(
            params['fnet'], raft_model._normalize_frames(x), 'instance')

    def cnet(x):
        return raft_model.basic_encoder(
            params['cnet'], raft_model._normalize_frames(x), 'batch')

    def pyramid_prep(f1, f2):
        # the PRODUCTION lanes path: transpose-free fused prep (round 5).
        # The superseded two-step path (build_corr_pyramid +
        # prep_pyramid_lanes) measured 106.8 ms at this geometry; keep
        # measuring the shipped one.
        if on_accel:
            return pallas_corr.prep_pyramid_lanes_fused(
                f1, f2, levels=raft_model.CORR_LEVELS)
        return raft_model.build_corr_pyramid(f1, f2)

    def mask_upsample(n, d):
        u = params['update_block']
        t = raft_model.relu(raft_model._conv_b(u['mask']['0'], n, padding=1))
        mask = 0.25 * raft_model._conv_b(u['mask']['2'], t)
        return raft_model.upsample_flow(d, mask)

    pieces = [
        (f'fnet ({n_uniq} frames {h}x{w})', norm_fnet, (frames,)),
        (f'cnet ({n_pairs} frames)', cnet, (first,)),
        ('corr pyramid + lanes prep', pyramid_prep, (fmap, fmap2)),
        ('mask head + convex upsample', mask_upsample, (net, dflow)),
    ]
    rows = []
    for name, fn, args in pieces:
        rows.append(measure(jax, device, name, fn, args, ambient, iters))

    md = ['| piece | ms/step | GFLOPs | TFLOP/s | % v5e bf16 peak |',
          '|---|---|---|---|---|']
    tot_s = tot_f = 0.0
    for name, sec, flops in rows:
        tflops = flops / sec / 1e12
        mfu = tflops / V5E_BF16_PEAK_TFLOPS * 100
        tot_s += sec
        tot_f += flops
        print(json.dumps({
            'piece': name, 'ms_per_step': round(sec * 1e3, 2),
            'gflops': round(flops / 1e9, 2),
            'achieved_tflops': round(tflops, 2),
            'mfu_pct_v5e_bf16': round(mfu, 2), 'ambient': ambient,
        }), flush=True)
        md.append(f'| {name} | {sec * 1e3:.1f} | {flops / 1e9:.1f} | '
                  f'{tflops:.1f} | {mfu:.1f}% |')
    print(json.dumps({
        'piece': 'TOTAL fixed phase', 'ms_per_step': round(tot_s * 1e3, 2),
        'gflops': round(tot_f / 1e9, 2),
        'achieved_tflops': round(tot_f / tot_s / 1e12, 2),
        'mfu_pct_v5e_bf16': round(
            tot_f / tot_s / 1e12 / V5E_BF16_PEAK_TFLOPS * 100, 2),
    }), flush=True)
    md.append(f'| **total** | {tot_s * 1e3:.1f} | {tot_f / 1e9:.1f} | '
              f'{tot_f / tot_s / 1e12:.1f} | '
              f'{tot_f / tot_s / 1e12 / V5E_BF16_PEAK_TFLOPS * 100:.1f}% |')
    print('\n'.join(md), file=sys.stderr)
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
