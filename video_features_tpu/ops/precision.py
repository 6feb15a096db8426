"""Targeted matmul-precision pinning for mixed-precision graphs.

The parity bar (feature rel L2 ≤ 1e-3 vs the reference, BASELINE.json)
forces ``precision=highest`` when applied globally — bf16 MXU passes drift
1.3e-2 through the fused RAFT→quantize→I3D path because the flow uint8
quantization cliff amplifies small flow errors. But the drift is not
uniform across the graph: a few numerically sensitive sub-graphs (the
correlation volume, the per-iteration refinement whose error compounds over
20 GRU steps, the I3D towers reading the quantized flow) dominate it, while
the one-shot encoders tolerate fast passes.

``pins`` name sub-graphs to run at a DIFFERENT matmul precision than the
ambient one: a tuple of (component, precision) pairs — hashable so it can
ride jit static args and participate in the compile cache key. Components
wired up:

  * raft: 'encoder' (fnet/cnet), 'corr' (pyramid build + lookup),
    'iter' (motion encoder + GRU + flow/mask heads), 'upsample';
  * the fused I3D step: 'i3d' (both towers).

``precision='mixed'`` in an extraction config = ambient 'default' (fast
MXU passes) + the measured-safe pins (MIXED_PINS below, tuned on TPU by
tools/precision_study.py).
"""
from __future__ import annotations

from contextlib import nullcontext
from typing import Dict, Optional, Tuple, Union

import numpy as np

Pins = Tuple[Tuple[str, str], ...]

# The 'mixed' policy, tuned by tools/precision_study.py on v5e (fused
# two-stream path, drift = feature rel L2 vs all-float32 on identical
# inputs/weights): ambient 'high' (3-pass bf16 ≈ fp32 to ~2^-21 per
# matmul) measures 8.4e-4 flow / 1.3e-4 rgb — under the ≤1e-3 parity bar
# (its rate against float32 on the chip: not measured, every benchmark
# cell runs 'mixed'). No
# sub-graph survives 1-pass: encoder-at-default alone is 1.04e-2, and
# corr-at-default under ambient high is 4.4e-3 (the flow-quantization
# cliff amplifies both). So 'mixed' is ambient 'high' with no down-pins;
# the pins machinery stays for study sweeps and future per-op tuning.
MIXED_AMBIENT = 'high'
MIXED_PINS: Pins = ()


def normalize_pins(pins: Union[None, Pins, Dict[str, str]]) -> Optional[Pins]:
    """dict/tuple → canonical sorted tuple (None stays None)."""
    if pins is None:
        return None
    items = pins.items() if isinstance(pins, dict) else pins
    return tuple(sorted((str(k), str(v)) for k, v in items))


def pin_scope(pins: Optional[Pins], component: str):
    """Trace-time context: matmul precision override for one sub-graph.

    Returns a null context when the component is not pinned, so call sites
    cost nothing in the common (unpinned) case.
    """
    if pins:
        for name, prec in pins:
            if name == component:
                import jax
                return jax.default_matmul_precision(prec)
    return nullcontext()


# -- the compute_dtype fast lanes (the precision ladder) ---------------------
#
# ``compute_dtype=`` is ORTHOGONAL to the matmul ``precision=`` knob above:
# ``precision`` selects how many bf16 passes each fp32 matmul executes on
# the MXU (the *arithmetic* of an fp32-resident graph), while
# ``compute_dtype`` changes what is *stored*. The ladder:
#
#   * ``bfloat16`` — params cast bf16 once at transplant time (half the
#     HBM residency and H2D bytes) and activations flow bf16 through the
#     whole step, with fp32 accumulation islands where parity demands it
#     (softmax / LayerNorm / BatchNorm statistics, global pooling —
#     ops/nn.py, the model layer_norm homes).
#   * ``int8`` — conv/linear weights quantized per-output-channel
#     symmetric int8 at transplant time (ops/quant.py; a QUARTER of the
#     fp32 param bytes) and dequantized in-graph at use; activations stay
#     float32, so the drift is pure weight rounding.
#
# Feature outputs are cast back to float32 at the step epilogue, so the
# on-disk contract is unchanged; the *values* differ from the fp32 lane
# within the per-family bounds below.

COMPUTE_DTYPES = ('float32', 'bfloat16', 'int8')

# Per-family parity bounds for the bf16 lane: feature rel-L2 error vs the
# float32 lane on identical inputs/weights — the same metric the repo's
# reference-parity bar uses (BASELINE.json), PARITY.md-style pinned.
# Measured by tests/test_precision.py (CPU XLA bf16, random weights, the
# REAL jitted steps) and re-asserted there on every run. Bounds carry ~3x
# headroom over the measured drift (max-abs error is recorded alongside
# for absolute context, but scales with feature magnitude — rel-L2 is
# the stable pin across weights/geometry).
#
# NOTE the lane's honest trade: ~0.5-2e-2 rel-L2 is an order past the
# <=1e-3 reference-parity bar — the bf16 lane is for throughput-bound
# embedding consumers (retrieval, dedup, clustering), not for
# reference-parity reproduction; precision=mixed remains the
# parity-grade fast mode.
BF16_REL_L2_BOUNDS: Dict[str, float] = {
    'r21d': 1.5e-2,    # measured 4.9e-3 (stack 10, 64x86, CPU XLA bf16)
    's3d': 2e-2,       # measured 5.9e-3 (in-graph scale-resize rides bf16)
    'resnet': 2e-2,    # measured 5.8e-3 (resnet18; BN-fold islands fp32)
    'clip': 3e-2,      # measured 1.0e-2 (ViT-B/32; LN/softmax islands)
    'timm': 5e-2,      # measured 1.8e-2 (vit_base_patch16_224)
    'vggish': 2.5e-2,  # measured 7.2e-3 (plain conv/relu VGG)
}

# Families that REFUSE the knob, with the measured drift that disqualifies
# them (tools/family_precision_study.py): the fused i3d flow path
# amplifies flow error through the uint8 quantization cliff, and raft's
# raw flow output compounds bf16 error over 20 GRU refinement iterations —
# neither meets its parity bound under bf16 storage, so the knob fails the
# BUILD with a structured error instead of shipping out-of-bound features.
# The int8 weight lane's parity bounds (compute_dtype=int8): post-training
# per-output-channel symmetric weight quantization (ops/quant.py) with
# fp32 activations — so the drift is pure weight rounding, not compounding
# activation error, and stays in the same order as bf16 for the framewise
# backbones the lane exists for (quarter-size params). Same measurement protocol and ~3x headroom as
# BF16_REL_L2_BOUNDS above (tests/test_precision.py, CPU XLA, random
# weights, the REAL jitted steps); tools/calibrate_int8.py re-measures
# against real checkpoints and pins the per-tensor scale tables.
INT8_REL_L2_BOUNDS: Dict[str, float] = {
    'resnet': 5e-2,   # measured 1.5e-2 (resnet18; BN params stay fp32)
    'clip': 3.5e-2,   # measured 1.1e-2 (ViT-B/32; LN/proj/embeds fp32)
    'timm': 7.5e-2,   # measured 2.5e-2 (vit_base_patch16_224)
}

# Families that REFUSE compute_dtype=int8, with the reason (same contract
# as BF16_REFUSALS: the knob fails the BUILD with a structured error).
# i3d/raft fail for a STRICTER version of their bf16 reasons — weight
# rounding feeds the same error amplifiers (the flow uint8-quantization
# cliff, 20 GRU refinement iterations) that already disqualify bf16's
# smaller perturbation. The video families (r21d/s3d/vggish) are not
# bandwidth-bound at their geometries, so nobody has measured them a
# bound — they fall through to the generic no-measured-bound refusal.
INT8_REFUSALS: Dict[str, str] = {
    'i3d': ('the fused RAFT->quantize->I3D flow path already measures '
            '1.24e-2 drift under bf16 (docs/design.md precision '
            'ladder) vs the <=1e-3 parity bound, and int8 weight '
            'rounding is a coarser perturbation through the same flow '
            'uint8-quantization cliff; use precision=mixed (8.5e-4) '
            "for i3d's fast lane instead"),
    'raft': ('raw flow output compounds weight-rounding error across 20 '
             'GRU refinement iterations (the corr/iter sub-graphs '
             'measure >=4.4e-3 under fast passes, docs/design.md) '
             'vs the <=1e-3 parity bound; use precision=mixed for raft '
             'instead'),
}

BF16_REFUSALS: Dict[str, str] = {
    'i3d': ('the fused RAFT->quantize->I3D flow path measures 1.24e-2 '
            'feature drift under 1-pass bf16 (docs/design.md '
            'precision ladder) vs the <=1e-3 parity bound — the flow '
            'uint8-quantization cliff amplifies bf16 error; use '
            "precision=mixed (3-pass bf16 matmuls, 8.5e-4) for i3d's "
            'fast lane instead'),
    'raft': ('raw flow output compounds bf16 error across 20 GRU '
             'refinement iterations (corr/iter sub-graphs measure '
             '>=4.4e-3 under fast passes, docs/design.md) vs the '
             '<=1e-3 parity bound; use precision=mixed for raft '
             'instead'),
}


class ComputeDtypeError(ValueError):
    """A family refused (or doesn't know) the requested compute_dtype."""


def check_compute_dtype(feature_type: Optional[str],
                        compute_dtype: str) -> str:
    """Validate the knob at BUILD time (config.sanity_check): the value
    must be known, and a fast-lane ask (bfloat16 / int8) against a family
    outside the lane's opt-in registry set raises a structured error
    naming the parity bound it would break — a serve submit then fails
    its build with this message instead of a worker shipping drifted
    features. The refusal message echoes the REQUESTED dtype (not a
    hardcoded lane name — tests/test_precision.py pins this for both
    fast lanes)."""
    if compute_dtype in ('float8', 'fp8', 'float8_e4m3fn', 'float8_e5m2'):
        # the rung below int8 is not a measurement gap, it is a backend
        # gap: structured not-yet so the remediation is honest
        raise ComputeDtypeError(
            f'compute_dtype={compute_dtype} is not supported yet: fp8 '
            f'param storage is gated on XLA backend support for fp8 '
            f'convert/dot lowering on the deployed runtimes — the '
            f'precision ladder currently ends at int8 weight '
            f'quantization (compute_dtype=int8, ops/quant.py)')
    if compute_dtype not in COMPUTE_DTYPES:
        raise ComputeDtypeError(
            f'compute_dtype must be one of {COMPUTE_DTYPES}; '
            f'got {compute_dtype!r}')
    if compute_dtype != 'float32' and feature_type is not None:
        if compute_dtype == 'bfloat16':
            from video_features_tpu.registry import BF16_FEATURES
            accepted, refusals, registry_name = (
                BF16_FEATURES, BF16_REFUSALS, 'registry.BF16_FEATURES')
        else:
            from video_features_tpu.registry import INT8_FEATURES
            accepted, refusals, registry_name = (
                INT8_FEATURES, INT8_REFUSALS, 'registry.INT8_FEATURES')
        if feature_type not in accepted:
            why = refusals.get(
                feature_type,
                f'{feature_type} has no measured {compute_dtype} parity '
                f'bound (tests/test_precision.py) — a family must opt in '
                f'via {registry_name} with a pinned bound before the '
                f'fast lane is allowed to serve its features')
            raise ComputeDtypeError(
                f'compute_dtype={compute_dtype} is refused for '
                f'feature_type={feature_type}: {why}')
    return compute_dtype


def param_np_dtype(compute_dtype: str) -> np.dtype:
    """The numpy dtype params are STORED in for this lane — what the
    transplant layer casts checkpoints to, so bf16 params are bf16 in
    HBM from the first ``device_put``, not cast per-step. For the int8
    lane this is the STORAGE dtype of the quantized weight payload: the
    transplant layer treats it as "quantize eligible weights, float32
    for the rest" (ops/quant.quantize_flat), not a blanket astype.
    Dispatch is exhaustive over COMPUTE_DTYPES — an unrecognized lane
    raises instead of silently storing float32 (the pre-int8 fall-through
    shipped full-size params under a lane nobody validated)."""
    if compute_dtype == 'float32':
        return np.dtype(np.float32)
    if compute_dtype == 'bfloat16':
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    if compute_dtype == 'int8':
        return np.dtype(np.int8)
    raise ComputeDtypeError(
        f'param_np_dtype: unknown compute_dtype {compute_dtype!r} '
        f'(known: {COMPUTE_DTYPES})')


def rel_l2(reference: np.ndarray, candidate: np.ndarray) -> float:
    """||candidate - reference||2 / ||reference||2 — the ONE definition
    of the parity metric the bounds above pin, shared by the tests and
    the study tools so no two consumers can disagree about what "under
    the bound" means."""
    a = np.asarray(reference, np.float64).ravel()
    b = np.asarray(candidate, np.float64).ravel()
    denom = float(np.linalg.norm(a))
    return float(np.linalg.norm(b - a)) / max(denom, 1e-30)


def features_to_f32(x):
    """Step-epilogue cast: feature outputs always leave the device as
    float32, whatever lane computed them (the on-disk .npy contract and
    every consumer's dtype expectation stay lane-independent). A no-op —
    emitting NO convert into the lowered program, so the float32 lane's
    StableHLO stays byte-identical to the pre-knob programs — when the
    input is already float32."""
    import jax.numpy as jnp
    if x.dtype == jnp.float32:
        return x
    return x.astype(jnp.float32)
