#!/usr/bin/env python3
"""Measure drift vs speed for mixed-precision policies on the fused path.

The parity bar (rel L2 ≤ 1e-3 vs the reference) pins global matmul
precision to 'highest' — bf16 MXU passes drift 1.3e-2 end-to-end because
the flow uint8 quantization cliff amplifies flow error. This tool sweeps
per-sub-graph policies (ops/precision.py pins) on real hardware and prints
one JSON line per policy: drift vs the all-highest baseline (same inputs,
same weights) and in-graph clips/sec — the data behind the 'mixed'
precision mode's pin set (ops/precision.py:MIXED_PINS).

On TPU, matmul precision maps to bf16 pass counts: default=1 pass,
high=3 (error ~2^-21), highest=6 (~fp32). The rate it prints times an
in-graph lax.scan + value fetch: good for ranking policies within one run,
not a benchmark number (PERF.md has those).

    python tools/precision_study.py            # sweep on the default device
    BENCH_PLATFORM=cpu python tools/precision_study.py  # smoke (no drift)
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# (name, ambient, pins) — pins may both up-pin (sensitive subgraphs to
# highest) and down-pin (tolerant subgraphs to fast passes).
#
# Round-1 sweep (v5e, batch 8, stack 16, 224px, vs all_highest):
#   all_highest       flow 0        rgb 0
#   all_high          flow 8.4e-04  rgb 1.3e-04
#   all_default       flow 1.24e-02 rgb 4.1e-03
#   enc_default       flow 1.04e-02 rgb 0        (ambient highest)
#   enc_corr_default  flow 1.03e-02 rgb 0
#   enc_corr_high     flow 6.6e-04  rgb 0
#   mixed(enc dflt)   flow 1.03e-02 rgb 0
# ⇒ the fnet/cnet encoders dominate the drift (1-pass bf16 there is 1e-2 on
#   its own); corr tolerates 1-pass; iter+i3d at 1-pass add ~7e-3. So every
#   matmul-heavy subgraph except corr/upsample needs ≥ 'high' (3-pass).
# Round-2 refinement sweep results (drift deterministic; timings are
# load-noisy):
#   high_corr_default          flow 4.4e-03  (corr needs ≥ high too)
#   high_iter_default          flow 1.3e-02  (iter needs ≥ high)
#   high_i3d_default           flow 3.4e-03 rgb 4.1e-03 (i3d needs ≥ high)
# ⇒ 'mixed' = plain ambient 'high' (8.4e-4), no sub-graph survives 1-pass
#   steady-state. The early-iteration hypothesis (first n refinement
#   iterations at 1-pass, healed by later full-precision ones) was also
#   measured and REJECTED: high_early8_default → flow 1.30e-2 — the GRU
#   hidden state carries the early error through every later iteration.
#   Further parity-precision speed must come from kernels, not precision.
# Round-3 finer-grain sweep (v5e, after the GRU restructure + quantizer
# offset fix; drift deterministic, timings noisy):
#   all_high                   flow 8.50e-04  (mixed drift unchanged)
#   high_motion_default        flow 1.08e-02  ✗
#   high_head_default          flow 7.81e-03  ✗
#   high_gru_default           flow 1.00e-02  ✗
#   high_motion_head_default   flow 1.11e-02  ✗
# ⇒ the 1-pass intolerance holds at PER-CONV granularity inside the
#   refinement iteration: every component's output feeds back through the
#   coords→lookup loop within one iteration, so there is no "cold side" to
#   down-pin. The precision lever is exhausted at every measured
#   granularity (docs/design.md "Numerical parity contract" has the ladder).
POLICIES = [
    ('all_highest', 'highest', None),                       # baseline
    ('all_high', 'high', None),                             # = 'mixed'
    ('high_early8_default', 'high', (('iter_early', 'default:8'),)),
    # Round-3 finer-grain sweep: per-component pins INSIDE the refinement
    # iteration (models/raft.py nests iter_motion/iter_gru/iter_head in
    # 'iter'), probing whether part of the per-iteration conv stack
    # tolerates 1-pass while the GRU feedback path stays 3-pass.
    ('high_motion_default', 'high', (('iter_motion', 'default'),)),
    ('high_head_default', 'high', (('iter_head', 'default'),)),
    ('high_gru_default', 'high', (('iter_gru', 'default'),)),
    ('high_motion_head_default', 'high',
     (('iter_motion', 'default'), ('iter_head', 'default'))),
]


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    if os.environ.get('BENCH_PLATFORM'):
        jax.config.update('jax_platforms', os.environ['BENCH_PLATFORM'])

    from video_features_tpu.extract.i3d import fused_two_stream_step
    from video_features_tpu.models import i3d as i3d_model
    from video_features_tpu.models import raft as raft_model
    from video_features_tpu.transplant.torch2jax import transplant
    from video_features_tpu.utils.device import (
        enable_compilation_cache, jax_device,
    )

    platform = jax.devices()[0].platform
    on_accel = platform != 'cpu'
    stack = int(os.environ.get('BENCH_STACK', 16))
    size = int(os.environ.get('BENCH_SIZE', 224 if on_accel else 64))
    batch = int(os.environ.get('BENCH_BATCH', 8 if on_accel else 1))
    iters = int(os.environ.get('BENCH_ITERS', 4 if on_accel else 1))
    enable_compilation_cache('auto', platform)

    device = jax_device(platform)
    params = jax.device_put({
        'rgb': transplant(i3d_model.init_state_dict(modality='rgb')),
        'flow': transplant(i3d_model.init_state_dict(modality='flow')),
        'raft': transplant(raft_model.init_state_dict()),
    }, device)
    rng = np.random.RandomState(0)
    # smooth-ish frames (video-like): white noise makes flow meaningless and
    # understates the quantization-cliff amplification
    base = rng.rand(batch, 1, size // 4, size // 4, 3) * 255
    drift_field = rng.rand(batch, stack + 1, size // 4, size // 4, 3) * 40
    frames = np.clip(base + drift_field, 0, 255).astype(np.float32)
    frames = np.kron(frames, np.ones((1, 1, 4, 4, 1), np.float32))  # upsample
    stacks = jax.device_put(frames, device)
    kwargs = dict(pads=(0, 0, 0, 0), streams=('rgb', 'flow'),
                  crop_size=min(224, size), platform=platform)

    def build(ambient, pins):
        def feats(p, x):
            with jax.default_matmul_precision(ambient):
                return fused_two_stream_step(p, x, pins=pins, **kwargs)

        def timed(p, x):
            def body(carry, _):
                o = feats(p, x)
                return {k: carry[k] + o[k].sum() for k in carry}, None
            acc, _ = lax.scan(
                body, {k: jnp.float32(0) for k in kwargs['streams']},
                None, length=iters)
            return acc
        return jax.jit(feats), jax.jit(timed)

    # CPU executes everything in fp32 regardless of the requested matmul
    # precision — drift is identically 0 and the sweep is meaningless, so
    # smoke-run only the baseline + one pinned policy for plumbing coverage.
    policies = POLICIES if on_accel else [POLICIES[0], POLICIES[-2]]

    results = {}
    for name, ambient, pins in policies:
        # retry each policy once and keep going — drift numbers are
        # deterministic, a lost policy can rerun later
        for attempt in (1, 2):
            try:
                feats_fn, timed_fn = build(ambient, pins)
                out = jax.tree_util.tree_map(np.asarray,
                                             feats_fn(params, stacks))
                timed_fn(params, stacks)  # compile + warm
                t0 = time.perf_counter()
                acc = jax.tree_util.tree_map(float, timed_fn(params, stacks))
                dt = time.perf_counter() - t0
                break
            except Exception as e:
                print(json.dumps({'policy': name, 'attempt': attempt,
                                  'error': f'{type(e).__name__}: {e}'}),
                      flush=True)
                if attempt == 2 and name == 'all_highest':
                    raise  # no baseline → no drift numbers at all
        else:
            continue
        assert all(np.isfinite(v) for v in acc.values()), (name, acc)
        clips = batch * iters / dt
        if name == 'all_highest':
            results['baseline'] = out
        ref = results['baseline']
        rel = {
            s: float(np.linalg.norm(out[s] - ref[s])
                     / max(np.linalg.norm(ref[s]), 1e-12))
            for s in out
        }
        print(json.dumps({
            'policy': name, 'ambient': ambient,
            'pins': list(map(list, pins)) if pins else [],
            'rel_l2_vs_highest': rel,
            'clips_per_sec': round(clips, 2),
        }), flush=True)


if __name__ == '__main__':
    main()
