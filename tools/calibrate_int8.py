#!/usr/bin/env python3
"""Calibrate a family's int8 weight lane and pin its scale table.

The int8 lane (``compute_dtype=int8``, ops/quant.py) quantizes conv/linear
weights per-output-channel at transplant time. The scales are
weight-derived (amax/127) and therefore deterministic, but this tool makes
them an EXPLICIT, pinned artifact:

  1. derives the per-tensor scale table from the checkpoint exactly as a
     build would (``ops/quant.derive_scales`` over the transplanted flat
     dict — same eligibility rule, same zero-guards);
  2. measures the family's feature rel-L2 drift (fp32 lane vs int8 lane,
     identical inputs — ``ops/precision.rel_l2``, the ONE parity metric)
     over N corpus videos, or over synthetic frame batches when no corpus
     is given;
  3. writes the table checkpoint-adjacent (``<ckpt>.int8-scales.npz``,
     ``ops/quant.scale_table_path``) with the measured drift in its
     metadata. Every subsequent build of that checkpoint on the int8 lane
     consumes the pinned table verbatim (torch2jax.load_torch_checkpoint)
     — reproducible across checkpoint re-exports that perturb weight
     bytes — and the measured number is checkable against the family's
     ``INT8_REL_L2_BOUNDS`` entry.

Prints ONE JSON line (the repo's bench/tool stdout contract): the family,
per-video drift, the pinned bound, and where the table landed.

    python tools/calibrate_int8.py resnet --checkpoint-path ck.pth \
        --videos a.mp4 b.mp4
    python tools/calibrate_int8.py clip            # synthetic calibration
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def build_lane(feature_type: str, compute_dtype: str, args_overrides,
               tmp_root: str):
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor
    overrides = {
        'video_paths': ['__calibrate_int8__.mp4'],
        'compute_dtype': compute_dtype,
        'output_path': f'{tmp_root}/out_{compute_dtype}',
        'tmp_path': f'{tmp_root}/tmp_{compute_dtype}',
    }
    overrides.update(args_overrides)
    return create_extractor(load_config(feature_type, overrides=overrides))


def synthetic_batches(ex, n: int, seed: int = 0):
    """N deterministic uint8 batches at the family's compiled geometry —
    the no-corpus fallback; weight-only quantization drift is
    input-robust, so synthetic frames rank scale tables faithfully even
    though a corpus measurement is the number to publish."""
    rng = np.random.RandomState(seed)
    h, w = ex.host_transform(
        np.zeros((256, 256, 3), np.uint8)).shape[:2]
    for _ in range(n):
        yield rng.randint(0, 255,
                          (ex.batch_size, h, w, 3)).astype(np.uint8)


def measure(ex_f32, ex_int8, videos, n_synthetic: int):
    """Per-input rel-L2 of the int8 lane vs fp32 on identical inputs —
    real corpus videos through the real extract path when given, else
    synthetic batches through the real jitted steps."""
    import jax

    from video_features_tpu.ops.precision import rel_l2
    drifts = []
    if videos:
        for v in videos:
            ref = ex_f32.extract(v)[ex_f32.feature_type]
            fast = ex_int8.extract(v)[ex_int8.feature_type]
            drifts.append({'input': v, 'rel_l2': rel_l2(ref, fast),
                           'max_abs': float(np.abs(ref - fast).max())})
        return drifts
    for i, batch in enumerate(synthetic_batches(ex_f32, n_synthetic)):
        dev = jax.device_put(batch)
        ref = np.asarray(ex_f32._step(ex_f32.params, dev))
        fast = np.asarray(ex_int8._step(ex_int8.params, dev))
        drifts.append({'input': f'synthetic[{i}]',
                       'rel_l2': rel_l2(ref, fast),
                       'max_abs': float(np.abs(ref - fast).max())})
    return drifts


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog='calibrate-int8',
        description='pin a per-family int8 scale table + measured drift '
                    '(ops/quant.py; docs/design.md precision ladder)')
    parser.add_argument('feature_type',
                        help='an INT8_FEATURES family (resnet/clip/timm)')
    parser.add_argument('--checkpoint-path',
                        help='checkpoint to calibrate; the table lands at '
                             '<ckpt>.int8-scales.npz. Omitted = random '
                             'weights (drift measurement only, no table '
                             'to pin)')
    parser.add_argument('--model-name', help='family model/arch override')
    parser.add_argument('--videos', nargs='*', default=[],
                        help='corpus videos to measure drift over '
                             '(default: synthetic batches)')
    parser.add_argument('--n-synthetic', type=int, default=4,
                        help='synthetic calibration batches when no '
                             'corpus is given (default 4)')
    parser.add_argument('--out', help='scale table path override')
    parser.add_argument('--device', default=None,
                        help='device override (default: config default)')
    args = parser.parse_args(argv)

    from video_features_tpu.ops.precision import (
        INT8_REL_L2_BOUNDS, check_compute_dtype,
    )
    from video_features_tpu.ops.quant import (
        derive_scales, save_scale_table, scale_table_path,
    )
    from video_features_tpu.transplant.torch2jax import _flatten
    # fail exactly like a build would for a refusing family
    check_compute_dtype(args.feature_type, 'int8')

    import tempfile
    tmp_root = tempfile.mkdtemp(prefix='calibrate_int8_')
    overrides = {}
    if args.checkpoint_path:
        overrides['checkpoint_path'] = args.checkpoint_path
    else:
        overrides['allow_random_weights'] = True
    if args.model_name:
        overrides['model_name'] = args.model_name
    if args.device:
        overrides['device'] = args.device

    ex_f32 = build_lane(args.feature_type, 'float32', overrides, tmp_root)
    ex_int8 = build_lane(args.feature_type, 'int8', overrides, tmp_root)

    # the table is derived from the FP32 transplanted layout — exactly
    # what quantize_flat would compute at build (ops/quant._channel_axis
    # decides eligibility in both places)
    import jax
    flat = {k: np.asarray(v) for k, v in
            _flatten(jax.tree_util.tree_map(np.asarray,
                                            ex_f32.params)).items()}
    scales = derive_scales(flat)

    drifts = measure(ex_f32, ex_int8, args.videos, args.n_synthetic)
    worst = max(d['rel_l2'] for d in drifts)
    bound = INT8_REL_L2_BOUNDS[args.feature_type]

    table_path = None
    if args.out or args.checkpoint_path:
        table_path = args.out or scale_table_path(args.checkpoint_path)
        save_scale_table(table_path, scales, meta={
            'feature_type': args.feature_type,
            'measured_rel_l2': f'{worst:.6e}',
            'n_inputs': str(len(drifts)),
            'corpus': ';'.join(args.videos) if args.videos else 'synthetic',
        })

    print(json.dumps({
        'feature_type': args.feature_type,
        'n_scale_tensors': len(scales),
        'scale_table': table_path,
        'drifts': drifts,
        'worst_rel_l2': worst,
        'bound': bound,
        'under_bound': bool(worst <= bound),
    }))
    return 0 if worst <= bound else 1


if __name__ == '__main__':
    raise SystemExit(main())
