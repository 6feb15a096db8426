"""R(2+1)D extractor (reference models/r21d/extract_r21d.py behavior).

TPU-first data path: frames stream off the decoder into stack windows
(extract.streaming — bounded memory, decode overlapped with compute via a
prefetch thread), and the jit-compiled step transforms + runs a FIXED-shape
batch of stacks per call (ragged tails padded and masked) so XLA compiles
exactly once per video geometry. The reference instead loads the ENTIRE
video into RAM (extract_r21d.py:72-74) and loops python-side one stack at a
time (extract_r21d.py:81-85).
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from video_features_tpu.extract.base import (
    BaseExtractor, StackPackingMixin, named_step,
)
from video_features_tpu.models import r21d as r21d_model
from video_features_tpu.ops.transforms import (
    center_crop, normalize, resize_bilinear, to_float_zero_one,
)
from video_features_tpu.utils.device import jax_device

# model_name -> (arch, native stack, native step, pred dataset)
MODEL_CFGS = {
    'r2plus1d_18_16_kinetics': dict(arch='r2plus1d_18', stack_size=16,
                                    step_size=16, dataset='kinetics'),
    'r2plus1d_34_32_ig65m_ft_kinetics': dict(arch='r2plus1d_34', stack_size=32,
                                             step_size=32, dataset='kinetics'),
    'r2plus1d_34_8_ig65m_ft_kinetics': dict(arch='r2plus1d_34', stack_size=8,
                                            step_size=8, dataset='kinetics'),
}

# stacks per device step; tails are padded to this and masked out
STACK_BATCH = 4


class ExtractR21D(StackPackingMixin, BaseExtractor):

    def __init__(self, args) -> None:
        super().__init__(
            feature_type=args.feature_type,
            on_extraction=args.on_extraction,
            tmp_path=args.tmp_path,
            output_path=args.output_path,
            keep_tmp_files=args.keep_tmp_files,
            device=args.device,
            profile=args.get('profile', False),
            precision=args.get('precision', 'highest'),
            inflight=args.get('inflight', 2),
            compute_dtype=args.get('compute_dtype', 'float32'),
        )
        self.model_name = args.model_name
        self.model_def = MODEL_CFGS[self.model_name]
        self.extraction_fps = args.extraction_fps
        self.stack_size = args.stack_size or self.model_def['stack_size']
        self.step_size = args.step_size or self.model_def['step_size']
        self.show_pred = args.show_pred
        self.output_feat_keys = [self.feature_type]
        # stacks per device step (the reference runs one at a time,
        # extract_r21d.py:81-85); with data_parallel this is the global batch
        self.stack_batch = args.get('batch_size') or STACK_BATCH
        # data_parallel=true shards stack batches over all local devices
        # (params replicated, batch data-sharded — same scheme as framewise)
        self.decode_backend = args.get('decode_backend', 'auto')
        self.data_parallel = args.get('data_parallel', False)
        self._device = jax_device(self.device)
        self.params = jax.device_put(self.load_params(args), self._device)
        # dtype rides the partial as a trace-time constant: the float32
        # lane's jitted program is byte-identical to the pre-knob graph
        self._step = jax.jit(named_step(
            partial(self._forward_batch, arch=self.model_def['arch'],
                    dtype=self.compute_jnp_dtype), self.step_name))

    # -- model --------------------------------------------------------------

    def load_params(self, args):
        """Transplanted torch checkpoint; missing path is a hard error unless
        random weights are explicitly allowed (extract.weights)."""
        from video_features_tpu.extract.weights import load_or_init
        return load_or_init(
            args, 'checkpoint_path',
            partial(r21d_model.init_state_dict, arch=self.model_def['arch']),
            feature_type='r21d', dtype=self.param_dtype)

    @staticmethod
    def _forward_batch(params, stacks, arch, dtype=None):
        """(B, stack, H, W, 3) uint8 → (B, 512) features.

        Transform chain parity (reference extract_r21d.py:102-107):
        ToFloatTensorInZeroOne → Resize(128, 171) → Normalize → CenterCrop(112).
        ``dtype`` is the bf16 fast lane's activation dtype (trace-time
        constant; None ≡ float32, the byte-identical default graph) —
        features always leave as float32.
        """
        from video_features_tpu.ops.precision import features_to_f32
        x = to_float_zero_one(stacks, dtype)
        x = resize_bilinear(x, (128, 171))
        x = normalize(x, r21d_model.MEAN, r21d_model.STD)
        x = center_crop(x, (112, 112))
        return features_to_f32(
            r21d_model.forward(params, x, arch=arch, features=True))

    # -- packed corpus mode: hooks from StackPackingMixin -------------------

    packed_feat_dim = 512

    def program_specs(self, mesh=None):
        """vft-programs abstract step spec: raw uint8 decode-geometry
        stacks into the one jitted step (in-graph resize/normalize/crop
        + the R(2+1)D forward)."""
        from video_features_tpu.analysis.programs import ProgramSpec
        h, w = self.PROGRAM_DECODE_HW
        batch = self._abstract_batch(
            (self._program_batch_slots(mesh), self.stack_size, h, w, 3),
            np.uint8, mesh)
        return [ProgramSpec('step', self._step,
                            (self._abstract_params(mesh), batch))]

    def packed_step(self, stacks):
        # dispatch only (device array out); the scheduler's deferred
        # fetch_outputs owns the D2H readback. aot_call routes through a
        # resident/store-loaded executable when the aot store is on
        # (byte-identical either way), else it IS the jit call.
        return {self.feature_type:
                self.aot_call('step', self._step, self.params, stacks)}

    # -- extraction ---------------------------------------------------------

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        from video_features_tpu.extract.streaming import stream_windows

        if self.data_parallel:
            self._ensure_mesh('stack_batch')
        loader = self._make_loader(video_path)
        windows = stream_windows(loader, self.stack_size, self.step_size,
                                 self.tracer, 'decode')

        from video_features_tpu.extract.streaming import (
            iter_batched_windows, overlap_fetch, transfer_batches,
        )

        feats: list = []
        depth = 1 if self.show_pred else self.inflight

        def dispatched():
            # decode thread assembles + transfers stack batch k+1 while
            # the device runs k (see streaming.transfer_batches); 'model'
            # is dispatch only, the deferred readback is the 'd2h' stage
            for stacks, _, valid, window_idx in transfer_batches(
                    iter_batched_windows(windows, self.stack_batch,
                                         self.tracer),
                    self.put_input, tracer=self.tracer):
                with self.tracer.stage(
                        'model', **self.step_attrs(valid, self.stack_batch)):
                    dev = self.aot_call('step', self._step,
                                        self.params, stacks)
                self.tracer.add_occupancy('model', valid, self.stack_batch)
                yield dev, valid, window_idx

        with self.precision_scope():
            for out, valid, window_idx in overlap_fetch(
                    dispatched(), self.fetch_outputs, depth, self.tracer,
                    self.last_step):
                out = out[:valid]
                feats.append(out)
                if self.show_pred:
                    for k in range(valid):
                        start = (window_idx + k) * self.step_size
                        self.maybe_show_pred(out[k:k + 1], start,
                                             start + self.stack_size)

        feats = (np.concatenate(feats, axis=0) if feats
                 else np.zeros((0, 512), np.float32))
        return {self.feature_type: feats}

    def maybe_show_pred(self, visual_feats: np.ndarray, start_idx: int, end_idx: int):
        if self.show_pred:
            from video_features_tpu.ops.nn import linear
            from video_features_tpu.utils.preds import show_predictions_on_dataset
            logits = np.asarray(linear(jnp.asarray(visual_feats), self.params['fc']))
            # vft-lint: ok=stdout-purity — show_pred narration surface
            print(f'At frames ({start_idx}, {end_idx})')
            show_predictions_on_dataset(logits, self.model_def['dataset'])
