#!/usr/bin/env python3
"""Precision ladder across model families: drift + in-graph rate.

Every family with a dense
device step (r21d, s3d, resnet50, clip ViT-B/32, vggish): for each
matmul precision it runs the PRODUCTION extractor step (transforms +
network, the exact jit'd fn the extractor calls) on identical inputs +
seeded weights and prints one JSON line per (family, precision): feature
rel L2 vs the 'highest' baseline and an in-graph rate (lax.scan over
distinct batches inside one jit, value fetch: for ranking the rungs of
one run, not a benchmark number — PERF.md has those). Inputs match each step's production range as well as geometry
(0-255 frames for the vision families, log-mel-scaled values for
vggish — bf16 drift depends on activation magnitude).

Stack families (r21d, s3d) report clips (stacks) per second; frame-wise
families (resnet, clip) report frames per second; vggish reports 0.96 s
log-mel examples per second. `BENCH_STACK` overrides
the stack length and `R21D_ARCH` the r21d variant.

    python tools/family_precision_study.py [families...]
    BENCH_PLATFORM=cpu python tools/family_precision_study.py s3d  # smoke
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
from functools import partial
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

LADDER = ('highest', 'high', 'default')


def _family_specs(on_accel: bool):
    """{name: (init_fn, step_fn, batch_shape, unit, input_map,
    count_per_batch)} — step fns are the extractors' own; input geometry
    AND value range mirror what each step receives in production
    (decode-geometry 0-255 stacks for the in-graph-resizing stack
    families, host-cropped 0-255 frames for the frame-wise ones,
    log-mel-range examples for vggish — input_map rescales the shared
    random tensor host-side). count_per_batch is the work-unit count one
    step produces (None → batch_shape[0]; raft's B+1 frames make B
    flows)."""
    from video_features_tpu.extract.clip import ExtractCLIP
    from video_features_tpu.extract.r21d import ExtractR21D
    from video_features_tpu.extract.raft import ExtractRAFT
    from video_features_tpu.extract.resnet import ExtractResNet
    from video_features_tpu.extract.s3d import ExtractS3D
    from video_features_tpu.models import clip as clip_model
    from video_features_tpu.models import r21d as r21d_model
    from video_features_tpu.models import raft as raft_model
    from video_features_tpu.models import resnet as resnet_model
    from video_features_tpu.models import s3d as s3d_model
    from video_features_tpu.models import vggish as vggish_model

    h, w = (256, 340) if on_accel else (64, 86)
    stack = int(os.environ.get('BENCH_STACK', 16))
    r21d_arch = os.environ.get('R21D_ARCH', 'r2plus1d_18')
    b_stack = 16 if on_accel else 1
    b_frame = 64 if on_accel else 2
    px = 224 if on_accel else 64
    # CLIP's positional embedding fixes its input at 224, and s3d's
    # in-graph center_crop is fixed at 224 (a smaller smoke frame would
    # exercise a clamped crop production never sees) — shrink the batch,
    # not the geometry, for smoke runs
    clip_px, clip_b = 224, (b_frame if on_accel else 1)
    s3d_h, s3d_w = (h, w) if on_accel else (256, 340)
    s3d_scale = 224 / min(s3d_h, s3d_w)
    s3d_hw = (math.floor(s3d_h * s3d_scale), math.floor(s3d_w * s3d_scale))
    # the VGG step consumes log-mel values log(mel + 0.01) ≈ [-4.6, 5]
    # directly (no in-graph normalization) — map the shared 0-255 tensor
    # into that range so drift is measured at production magnitude
    def log_mel_range(x):
        return x / 255.0 * 9.6 - 4.6

    # raft-as-feature-type (flow fields out, reference models/raft/
    # extract_raft.py:12-29): native-resolution geometry — the sample's
    # 256x340 short-side-256 frame padded to /8 (256x344), B+1 frames in
    # one extractor step -> B flows via forward_consecutive
    raft_h, raft_w = (256, 344) if on_accel else (64, 88)
    raft_b = (16 if on_accel else 2) + 1

    return {
        'r21d': (
            partial(r21d_model.init_state_dict, arch=r21d_arch),
            partial(ExtractR21D._forward_batch, arch=r21d_arch),
            (b_stack, stack, h, w, 3), 'clips/sec', None, None),
        's3d': (
            s3d_model.init_state_dict,
            partial(ExtractS3D._forward, resize_hw=s3d_hw,
                    resize_scale=s3d_scale),
            (b_stack, stack, s3d_h, s3d_w, 3), 'clips/sec', None, None),
        'resnet': (
            partial(resnet_model.init_state_dict, arch='resnet50'),
            partial(ExtractResNet._forward, arch='resnet50'),
            (b_frame, px, px, 3), 'frames/sec', None, None),
        'clip': (
            partial(clip_model.init_state_dict, model_name='ViT-B/32'),
            partial(ExtractCLIP._forward, arch='ViT-B/32'),
            (clip_b, clip_px, clip_px, 3), 'frames/sec', None, None),
        'vggish': (
            vggish_model.init_state_dict,
            vggish_model.forward,
            (b_frame, 96, 64, 1), 'examples/sec', log_mel_range, None),
        'raft': (
            raft_model.init_state_dict,
            partial(ExtractRAFT._flow_batch, iters=raft_model.ITERS),
            (raft_b, raft_h, raft_w, 3), 'flows/sec', None, raft_b - 1),
    }


def run_family(name: str, init_fn, step_fn, batch_shape, unit,
               input_map, count_per_batch, iters: int) -> None:
    import jax
    from jax import lax

    from video_features_tpu.transplant.torch2jax import transplant
    from video_features_tpu.utils.device import jax_device

    platform = jax.devices()[0].platform
    device = jax_device(platform)
    params = jax.device_put(transplant(init_fn()), device)
    rng = np.random.RandomState(0)
    raw = rng.randint(0, 255,
                      size=(iters,) + batch_shape).astype(np.float32)
    if input_map is not None:     # host-side: production value range
        raw = input_map(raw).astype(np.float32)
    frames = jax.device_put(raw, device)

    def run(precision):
        def chained(p, xs):
            def body(_, batch):
                with jax.default_matmul_precision(precision):
                    return None, step_fn(p, batch)
            _, feats = lax.scan(body, None, xs)
            return feats
        jitted = jax.jit(chained)
        feats = np.asarray(jitted(params, frames))       # compile + warm
        assert np.isfinite(feats).all()
        t0 = time.perf_counter()
        feats = np.asarray(jitted(params, frames))
        elapsed = time.perf_counter() - t0
        count = (count_per_batch if count_per_batch is not None
                 else batch_shape[0])
        return feats, count * iters / elapsed

    base, _ = run('highest')
    for precision in LADDER:
        feats, rate = run(precision)
        drift = float(np.linalg.norm(feats - base) / np.linalg.norm(base))
        print(json.dumps({
            'family': name, 'precision': precision, 'platform': platform,
            'batch_shape': list(batch_shape),
            'feature_rel_l2_vs_highest': float(f'{drift:.3e}'),
            'rate': round(rate, 2), 'unit': unit,
        }), flush=True)


def main() -> None:
    import jax

    if os.environ.get('BENCH_PLATFORM'):
        jax.config.update('jax_platforms', os.environ['BENCH_PLATFORM'])
    from video_features_tpu.utils.device import enable_compilation_cache

    platform = jax.devices()[0].platform
    on_accel = platform != 'cpu'
    enable_compilation_cache('auto', platform)
    iters = int(os.environ.get('BENCH_ITERS', 8 if on_accel else 2))

    specs = _family_specs(on_accel)
    picks = sys.argv[1:] or list(specs)
    for name in picks:
        run_family(name, *specs[name], iters)


if __name__ == '__main__':
    main()
