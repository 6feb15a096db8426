#!/usr/bin/env python3
"""Validate the TPU corr-lookup kernel at FULL production depth.

tests/test_corr_lookup.py compares the lanes kernel against the gather
oracle at reduced GRU iterations (fp-noise amplifies under random
weights — see ops/pallas_corr.py); this tool runs the three lookups
(dense, lanes, gather) through the complete 20-iteration RAFT forward at
CLI geometry (256×344) on real hardware and reports their mutual drift.

Automated coverage of the same property lives in
tests/test_corr_lookup.py::test_lanes_full_depth_* — an interpret-mode
reduced-geometry variant in the slow lane plus a `-m tpu` real-hardware
variant that calls :func:`measure_drift` exactly like this CLI does, and
in chip_smoke.py's ``kernel`` phase.

Measured on v5e (2026-07-31, precision=highest, seeded weights):
    lanes  vs dense: rel L2 3.2e-05
    gather vs dense: rel L2 3.0e-05
i.e. the lane-packed production kernel sits at the same fp-noise floor as
the XLA gather oracle — the 20-iteration behavior is validated directly,
not just transitively through few-iteration tests.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Dict, Sequence

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def measure_drift(h: int = 256, w: int = 344,
                  impls: Sequence[str] = ('dense', 'lanes', 'gather'),
                  iters: int = 20, precision: str = 'highest',
                  platform: str = None) -> Dict[str, float]:
    """Full-depth RAFT forward under each lookup impl → rel L2 vs the
    first impl. Frames are a smooth pattern with a second frame shifted by
    noise, 4× upsampled so bilinear lookups exercise fractional coords."""
    import jax

    from video_features_tpu.models import raft as raft_model
    from video_features_tpu.transplant.torch2jax import transplant
    from video_features_tpu.utils.device import jax_device

    platform = platform or jax.devices()[0].platform
    dev = jax_device(platform)
    params = jax.device_put(transplant(raft_model.init_state_dict()), dev)
    rng = np.random.RandomState(0)
    assert h % 4 == 0 and w % 4 == 0, (h, w)
    base = rng.rand(1, h // 4, w // 4, 3) * 255
    up = np.ones((1, 4, 4, 1))
    f1 = np.kron(np.clip(base, 0, 255), up).astype(np.float32)
    f2 = np.kron(np.clip(base + rng.rand(1, h // 4, w // 4, 3) * 25, 0, 255),
                 up).astype(np.float32)
    f1, f2 = jax.device_put(f1, dev), jax.device_put(f2, dev)

    outs = {}
    saved = os.environ.get('VFT_RAFT_LOOKUP')
    try:
        with jax.default_matmul_precision(precision):
            for impl in impls:
                os.environ['VFT_RAFT_LOOKUP'] = impl
                fn = jax.jit(lambda p, a, b: raft_model.forward(
                    p, a, b, iters=iters, platform=platform))
                outs[impl] = np.asarray(fn(params, f1, f2))
    finally:
        if saved is None:
            os.environ.pop('VFT_RAFT_LOOKUP', None)
        else:
            os.environ['VFT_RAFT_LOOKUP'] = saved
    ref = outs[impls[0]]
    return {impl: float(np.linalg.norm(outs[impl] - ref)
                        / np.linalg.norm(ref))
            for impl in impls[1:]}


def main() -> int:
    import jax

    from video_features_tpu.utils.device import enable_compilation_cache
    enable_compilation_cache('auto', jax.devices()[0].platform)
    rels = measure_drift()
    ok = True
    for impl, rel in rels.items():
        print(f'{impl} vs dense @20 iters, highest, 256x344: '
              f'rel L2 = {rel:.3e}')
        ok &= rel < 1e-3
    return 0 if ok else 1


if __name__ == '__main__':
    raise SystemExit(main())
