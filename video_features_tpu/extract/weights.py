"""Checkpoint resolution with a loud failure when weights are missing.

The reference always runs real weights — every extractor self-provisions
them (reference models/i3d/extract_i3d.py:180-183 loads bundled .pt files,
models/resnet/extract_resnet.py:38-40 uses torchvision's pretrained enums,
models/r21d/extract_r21d.py:109-118 torch.hub). This framework reads local
checkpoint files instead (TPU hosts are often torch-free and air-gapped), so
a *missing* path must be a hard error: silently falling back to random
weights would hand the user plausible-looking garbage features.

Escape hatches for tests/benches that intentionally run random weights:
  * config: ``allow_random_weights=true``
  * env:    ``VFT_ALLOW_RANDOM_WEIGHTS=1`` (set by the test suite's conftest)

``tools/fetch_checkpoints.py`` provisions real weights from the same sources
the reference downloads from.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Callable, Dict, Optional

ENV_FLAG = 'VFT_ALLOW_RANDOM_WEIGHTS'

# Families tools/fetch_checkpoints.py can provision (its SOURCES keys;
# test_fetch_checkpoints.test_registry_covers_every_family keeps the two in
# sync). Families outside this set (timm) get their weights elsewhere, so
# the missing-checkpoint remediation text must not point at the tool.
FETCHABLE_FAMILIES = frozenset(
    {'clip', 'resnet', 'r21d', 'vggish', 'i3d', 'raft', 's3d'})


class MissingCheckpointError(ValueError):
    """No checkpoint configured and random weights were not explicitly allowed."""


def _get(args: Any, key: str, default: Any = None) -> Any:
    if hasattr(args, 'get'):
        return args.get(key, default)
    return getattr(args, key, default)


def random_weights_allowed(args: Any) -> bool:
    if _get(args, 'allow_random_weights'):
        return True
    return os.environ.get(ENV_FLAG, '').lower() not in ('', '0', 'false')


def require_checkpoint(args: Any, key: str, *, feature_type: str,
                       what: Optional[str] = None) -> Optional[str]:
    """Return ``args[key]``; raise if absent unless random weights are allowed.

    Returns None ONLY when the caller may proceed with random init (the
    explicit escape hatch was set). ``what`` names the weights in messages
    (defaults to the feature type).
    """
    ckpt = _get(args, key)
    if ckpt:
        return str(ckpt)
    what = what or feature_type
    if not random_weights_allowed(args):
        if feature_type in FETCHABLE_FAMILIES:
            provision = (f'Provision real weights with `python '
                         f'tools/fetch_checkpoints.py {feature_type}` '
                         f'(see docs/checkpoints.md).')
        elif feature_type == 'lm':
            provision = ('`lm` reads a flat `.npz` of the trunk\'s share '
                         '(dotted checkpoint names, matrices laid out (in, '
                         f'out)); pass it via `{key}` (see '
                         'docs/models/lm.md).')
        else:
            # timm (and any future bridge-fed family): weights come from
            # pip-timm via the bridge or a user-supplied converted file,
            # not from the fetch tool
            provision = (f'`{feature_type}` weights are not served by '
                         f'tools/fetch_checkpoints.py — export them from a '
                         f'host with pip timm installed, or convert a '
                         f'HuggingFace checkpoint for the native families '
                         f'(`python tools/convert_checkpoint.py '
                         f'--hf-family ...`), then pass the converted .npz '
                         f'via `{key}` (see docs/checkpoints.md).')
        raise MissingCheckpointError(
            f'No checkpoint configured for {what}: set `{key}=<path to a '
            f'.pt/.pth/.npz checkpoint>` (feature_type={feature_type}). '
            f'{provision} To intentionally run RANDOM weights '
            f'(tests/benchmarks only — features will be meaningless), set '
            f'`allow_random_weights=true`.')
    # stderr: diagnostics must never pollute machine-read stdout (the CLI
    # print path and the benchmark's result line)
    print(f'WARNING: {what}: no `{key}` configured — running RANDOM weights '
          f'(allow_random_weights is set). Extracted features are '
          f'meaningless for downstream use.', file=sys.stderr)
    return None


def load_or_init(args: Any, key: str, init_fn: Callable[[], Dict[str, Any]],
                 *, feature_type: str, what: Optional[str] = None,
                 load: Optional[Callable[[str], Dict[str, Any]]] = None,
                 dtype: Any = None,
                 ) -> Dict[str, Any]:
    """Transplanted params from ``args[key]``, or gated random init.

    ``load`` overrides the default :func:`load_torch_checkpoint` for
    families with special checkpoint handling. ``dtype`` is the STORAGE
    dtype floating params are cast to at transplant time (the fast
    lanes' seam — ``compute_dtype=bfloat16`` extractors pass
    ``ml_dtypes.bfloat16`` here so params are bf16 in HBM from the first
    ``device_put``, never cast per-step; ``compute_dtype=int8``
    extractors pass ``np.int8``, which the transplant layer treats as
    "quantize eligible conv/linear weights per-output-channel, float32
    for the rest" — ops/quant.py — consuming any pinned
    ``<ckpt>.int8-scales.npz`` calibration table automatically); None
    keeps the historical float32 default.
    """
    from video_features_tpu.transplant.torch2jax import (
        load_torch_checkpoint, transplant,
    )
    ckpt = require_checkpoint(args, key, feature_type=feature_type, what=what)
    if ckpt:
        if load is not None:
            return load(ckpt)
        return (load_torch_checkpoint(ckpt) if dtype is None
                else load_torch_checkpoint(ckpt, dtype=dtype))
    return transplant(init_fn(), dtype=dtype)


def load_npz_to_device(path: str, shapes: Dict[str, tuple], device,
                       ) -> Dict[str, Any]:
    """``{name: device array}`` for every name of ``shapes``, read from a
    flat ``.npz`` (dotted names, matrices as (in, out)) one array at a
    time and put on ``device`` as it is read: the host holds two arrays at
    most (the one being read and the one in flight), never a second copy of
    the checkpoint. For families whose parameters are a large share of the
    device's memory (``extract/lm.py``).

    A layer's held experts are one stacked array
    (``….mlp.experts.gate_proj.weight``: (held, in, out)); a checkpoint
    that keeps them one by one (``….mlp.experts.<j>.gate_proj.weight``,
    the published layout) is stacked here, expert 0 of the archive first.
    Missing names and wrong shapes are errors that name them."""
    import jax
    import numpy as np
    if not str(path).endswith('.npz'):
        raise ValueError(
            f'{path}: this family reads a flat .npz archive (dotted '
            f'checkpoint names, matrices laid out (in, out)); convert '
            f'other formats first (docs/checkpoints.md)')
    out: Dict[str, Any] = {}
    in_flight = None
    with np.load(path) as data:
        have = set(data.files)

        def read(name: str, shape: tuple):
            if name in have:
                return data[name]
            head, sep, tail = name.partition('.experts.')
            one = [f'{head}.experts.{j}.{tail}' for j in range(shape[0])]
            if sep and all(k in have for k in one):
                stacked = np.empty(shape, np.float32)
                for j, k in enumerate(one):
                    stacked[j] = data[k]
                return stacked
            raise KeyError(f'{path} has no parameter {name!r}')

        for name, shape in shapes.items():
            arr = read(name, tuple(shape))
            if tuple(arr.shape) != tuple(shape):
                raise ValueError(f'{path}: {name} has shape '
                                 f'{tuple(arr.shape)}, the config needs '
                                 f'{tuple(shape)}')
            if arr.dtype != np.float32:
                arr = arr.astype(np.float32)
            placed = jax.device_put(arr, device)
            if in_flight is not None:
                in_flight.block_until_ready()
            out[name] = in_flight = placed
    return out
