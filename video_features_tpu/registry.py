"""Extractor registry with lazy imports (reference main.py:20-38 dispatch)."""
from __future__ import annotations

import importlib
from typing import TYPE_CHECKING, Dict, Tuple

if TYPE_CHECKING:
    from video_features_tpu.config import Config
    from video_features_tpu.extract.base import BaseExtractor

# feature_type -> (module, class). Imports are deferred so a missing optional
# dependency for one family never breaks the others.
EXTRACTORS: Dict[str, Tuple[str, str]] = {
    'i3d': ('video_features_tpu.extract.i3d', 'ExtractI3D'),
    'r21d': ('video_features_tpu.extract.r21d', 'ExtractR21D'),
    's3d': ('video_features_tpu.extract.s3d', 'ExtractS3D'),
    'vggish': ('video_features_tpu.extract.vggish', 'ExtractVGGish'),
    'resnet': ('video_features_tpu.extract.resnet', 'ExtractResNet'),
    'raft': ('video_features_tpu.extract.raft', 'ExtractRAFT'),
    'clip': ('video_features_tpu.extract.clip', 'ExtractCLIP'),
    'timm': ('video_features_tpu.extract.timm', 'ExtractTIMM'),
    'lm': ('video_features_tpu.extract.lm', 'ExtractLM'),
}

# feature types whose extractor implements in-graph data parallelism
# (data_parallel=true). The single authoritative set — sanity_check
# consults it; deliberately an explicit literal (NOT frozenset(EXTRACTORS))
# so a future extractor without DP support trips the warn-and-disable path
# instead of silently claiming capability.
DATA_PARALLEL_FEATURES = frozenset(
    {'i3d', 'r21d', 's3d', 'vggish', 'resnet', 'raft', 'clip', 'timm'})

# feature types whose extractor implements the packed corpus mode
# (pack_across_videos=true — batch-major scheduling across videos,
# parallel/packing.py). Same deliberate-literal policy as above: a new
# extractor must opt in here AND set supports_packing, or sanity_check
# degrades the knob to the per-video loop with a warning.
PACKED_FEATURES = frozenset(
    {'i3d', 'r21d', 's3d', 'resnet', 'clip', 'timm', 'lm'})

# feature types whose extractor accepts the bf16 fast lane
# (compute_dtype=bfloat16 — params cast bf16 at transplant, bf16
# activations with fp32 accumulation islands, ops/precision.py). Same
# deliberate-literal policy: a family joins ONLY once its rel-L2 error
# vs the float32 lane is measured and pinned (ops/precision.py
# BF16_REL_L2_BOUNDS, asserted by tests/test_precision.py) — an
# unmeasured family refuses the knob with a
# structured build-time error (ops/precision.check_compute_dtype)
# instead of shipping drift nobody bounded. i3d and raft stay OUT by
# measurement, not omission: the flow uint8-quantization cliff / 20-step
# GRU error compounding put them over the parity bar under bf16
# (ops/precision.BF16_REFUSALS names the numbers).
BF16_FEATURES = frozenset(
    {'r21d', 's3d', 'resnet', 'clip', 'timm', 'vggish'})

# feature types whose extractor accepts the int8 weight lane
# (compute_dtype=int8 — conv/linear weights quantized per-output-channel
# symmetric int8 at transplant time, dequantized in-graph at use, fp32
# activations; ops/quant.py). Same deliberate-literal policy as
# BF16_FEATURES: a family joins ONLY once its rel-L2 drift vs the fp32
# lane is measured and pinned (ops/precision.INT8_REL_L2_BOUNDS,
# asserted by tests/test_precision.py). The set is the bandwidth-bound
# framewise backbones the lane exists for; i3d/raft refuse by
# measurement (ops/precision.INT8_REFUSALS — the same error amplifiers
# that disqualify bf16), the video families (r21d/s3d/vggish) refuse by
# the generic no-measured-bound rule until someone pins them.
INT8_FEATURES = frozenset({'resnet', 'clip', 'timm'})

# feature types whose extractor can consume a LIVE session (ingress/):
# raw network frames windowed to the family's packed geometry
# (BaseExtractor.live_window_spec). Same deliberate-literal policy: a
# family must opt in here AND return a spec, or the ingress rejects the
# session up front with a clear error instead of failing mid-stream.
LIVE_FEATURES = frozenset(
    {'i3d', 'r21d', 's3d', 'resnet', 'clip', 'timm'})


def create_extractor(args: 'Config') -> 'BaseExtractor':
    feature_type = args['feature_type']
    try:
        module_name, class_name = EXTRACTORS[feature_type]
    except KeyError:
        raise NotImplementedError(f'Extractor {feature_type!r} is not implemented. '
                                  f'Known: {", ".join(EXTRACTORS)}')
    if hasattr(args, 'get'):
        from video_features_tpu.utils.device import enable_compilation_cache
        enable_compilation_cache(args.get('compilation_cache_dir'),
                                 str(args.get('device') or 'cpu'))
    module = importlib.import_module(module_name)
    extractor = getattr(module, class_name)(args)
    if hasattr(args, 'get'):
        # run fingerprint (config-aware resume) + content-addressed
        # feature cache; duck-typed arg objects without .get stay legacy
        extractor.configure_cache(args)
        # persistent executable store (aot/): programs load from disk
        # instead of compiling when a previous process published them.
        # Attach-only — warming is lazy (aot_call, at the ACTUAL batch
        # geometry) except on the serve boot path, which calls
        # aot_warm() after device placement.
        extractor.configure_aot(args)
        # flight recorder (obs/): trace_out / manifest_out knobs
        extractor.configure_obs(args)
        # decode farm (farm/): decode_workers / decode_farm_ring_mb
        extractor.configure_farm(args)
        # mesh-sharded packed execution (parallel/mesh.py): mesh_devices
        # resolves against this host's local devices at build time
        extractor.configure_mesh(args)
    return extractor
