#!/usr/bin/env python3
"""MFU accounting: FLOPs/clip → achieved TFLOP/s → % of the chip's bf16 peak.

VERDICT r4 weak-point 4: rates like "289 clips/s" are unanchored without
a FLOP denominator — good, or 10× off peak? This tool computes, for
every BASELINE family plus the fused i3d step at BOTH geometries:

  * FLOPs per work unit from XLA's own ``compile().cost_analysis()`` of
    the production step (the same jitted fn the extractor calls). XLA
    counts multiply+add as 2 FLOPs, so resnet50@224 reports ~8.0 G —
    the canonical number.
  * the measured in-graph rate (bench.py's shared scan harness, fresh).
  * achieved TFLOP/s = FLOPs/unit × rate, and % of the running chip's
    dense bf16 peak, looked up by ``device_kind`` in
    :data:`BF16_PEAK_TFLOPS` — a device that is not in the table is an
    error, never a default (so there is no CPU mode: a CPU rate over a
    TPU peak is not a number).

Precision caveat printed with the table: at ``mixed`` (3-pass bf16)
every matmul EXECUTES ~3× its nominal FLOPs, so hardware occupancy on
matmul-dominated graphs is ≈3× the quoted model-FLOPs utilization —
MFU here is deliberately model-FLOPs-based (the useful-work number),
matching how the scaling literature quotes it.

    python tools/mfu_table.py                 # full table
    python tools/mfu_table.py s3d             # one family

Prints one JSON line per row (family, unit, gflops_per_unit, rate,
achieved_tflops, mfu_pct) then a markdown table on stderr for docs.
"""
from __future__ import annotations

import json
import os
import sys
from functools import partial
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

# dense bf16 peak per chip, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).
BF16_PEAK_TFLOPS = {'TPU v5 lite': 197.0}


def bf16_peak_tflops(device_kind: str) -> float:
    try:
        return BF16_PEAK_TFLOPS[device_kind]
    except KeyError:
        raise SystemExit(
            f'mfu_table: no bf16 peak recorded for device_kind '
            f'{device_kind!r} (known: {sorted(BF16_PEAK_TFLOPS)}) — add '
            'its published peak with the source, do not borrow another '
            "chip's") from None


def _flops_of(jitted_lowered) -> float:
    comp = jitted_lowered.compile()
    ca = comp.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return float(ca.get('flops', float('nan')))


def fused_i3d_row(jax, ambient, pins, device, platform, h, w, batch,
                  label):
    """(label, 'clips', flops_per_clip, rate) for the fused two-stream
    step at one geometry — rate via bench.py's bench_ingraph harness."""
    from bench import bench_ingraph
    from video_features_tpu.extract.i3d import fused_two_stream_step
    from video_features_tpu.models import i3d as i3d_model
    from video_features_tpu.models import raft as raft_model
    from video_features_tpu.transplant.torch2jax import transplant

    params = jax.device_put({
        'rgb': transplant(i3d_model.init_state_dict(modality='rgb')),
        'flow': transplant(i3d_model.init_state_dict(modality='flow')),
        'raft': transplant(raft_model.init_state_dict()),
    }, device)
    stack = int(os.environ.get('BENCH_STACK', 16))
    pads = tuple(raft_model.pad_to_multiple(
        np.zeros((1, h, w, 1), np.float32))[1])

    def step(p, stacks):
        with jax.default_matmul_precision(ambient):
            return fused_two_stream_step(
                p, stacks, pads=pads, streams=('rgb', 'flow'),
                crop_size=min(224, h, w), platform=platform, pins=pins)

    x = np.zeros((batch, stack + 1, h, w, 3), np.float32)
    flops = _flops_of(jax.jit(step).lower(params, x)) / batch
    iters = int(os.environ.get('BENCH_ITERS', 4))
    rate = bench_ingraph(jax, ambient, pins, device, platform, params,
                         stack, h, w, batch, iters)
    return label, 'clips', flops, rate


def family_rows(jax, ambient, device, picks):
    """picks: None → every family; a list (possibly empty) → exactly
    those families (so `mfu_table.py i3d` runs NO family rows, not all)."""
    from bench import bench_family_ingraph
    from tools.family_precision_study import _family_specs
    from video_features_tpu.transplant.torch2jax import transplant

    iters = int(os.environ.get('BENCH_ITERS', 4))
    for fam, (init_fn, step_fn, bshape, unit, imap,
              count) in _family_specs(on_accel=True).items():
        if picks is not None and fam not in picks:
            continue
        params = jax.device_put(transplant(init_fn()), device)

        def step(p, x):
            with jax.default_matmul_precision(ambient):
                return step_fn(p, x)

        x = np.zeros(bshape, np.float32)
        n_units = count if count is not None else bshape[0]
        flops = _flops_of(jax.jit(step).lower(params, x)) / n_units
        rate = bench_family_ingraph(jax, ambient, device, init_fn,
                                    step_fn, bshape, imap, count, iters,
                                    transplant)
        yield fam, unit.split('/')[0], flops, rate


def main() -> int:
    import jax
    from video_features_tpu.ops.precision import MIXED_AMBIENT, MIXED_PINS
    from video_features_tpu.utils.device import (
        enable_compilation_cache, jax_device,
    )

    platform = jax.devices()[0].platform
    device_kind = jax.devices()[0].device_kind
    peak = bf16_peak_tflops(device_kind)     # before any measurement
    enable_compilation_cache('auto', platform)
    device = jax_device(platform)
    precision = os.environ.get('BENCH_PRECISION', 'mixed')
    ambient, pins = ((MIXED_AMBIENT, MIXED_PINS) if precision == 'mixed'
                     else (precision, None))
    picks = sys.argv[1:]

    rows = []
    if not picks or 'i3d' in picks:
        h, w, batch = 256, 340, 16
        rows.append(fused_i3d_row(jax, ambient, pins, device, platform,
                                  h, w, batch, f'i3d_fused_{h}x{w}'))
        rows.append(fused_i3d_row(jax, ambient, pins, device,
                                  platform, 224, 224, batch,
                                  'i3d_fused_224px'))
    rows.extend(family_rows(
        jax, ambient, device,
        None if not picks else [p for p in picks if p != 'i3d']))

    md = ['| step | GFLOPs/unit | measured rate | achieved TFLOP/s | '
          f'% of {device_kind} bf16 peak |', '|---|---|---|---|---|']
    for label, unit, flops, rate in rows:
        tflops = flops * rate / 1e12
        mfu = tflops / peak * 100
        print(json.dumps({
            'step': label, 'unit': unit, 'precision': precision,
            'gflops_per_unit': round(flops / 1e9, 2),
            'rate': round(rate, 2),
            'achieved_tflops': round(tflops, 2),
            'device_kind': device_kind,
            'bf16_peak_tflops': peak,
            'mfu_pct_bf16': round(mfu, 2),
        }), flush=True)
        md.append(f'| {label} | {flops / 1e9:.1f} | {rate:.1f} {unit}/s '
                  f'| {tflops:.1f} | {mfu:.1f}% |')
    print('\n'.join(md), file=sys.stderr)
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
