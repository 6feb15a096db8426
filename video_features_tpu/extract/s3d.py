"""S3D extractor (reference models/s3d/extract_s3d.py behavior).

Transform parity (reference extract_s3d.py:30-35 — kylemin/S3D convention,
deliberately NO normalization): ToFloatTensorInZeroOne → Resize(224,
short side, torch bilinear) → CenterCrop(224). Default extraction_fps=25,
stack/step 64 (configs/s3d.yml). Partial final stacks are dropped.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import numpy as np

from video_features_tpu.extract.base import (
    BaseExtractor, StackPackingMixin, named_step,
)
from video_features_tpu.models import s3d as s3d_model
from video_features_tpu.ops.transforms import (
    center_crop, resize_bilinear_scale, to_float_zero_one,
)
from video_features_tpu.utils.device import jax_device

STACK_BATCH = 1  # 64-frame stacks are large; one per device step


class ExtractS3D(StackPackingMixin, BaseExtractor):

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
        self.stack_size = args.stack_size
        self.step_size = args.step_size
        self.extraction_fps = args.extraction_fps
        self.show_pred = args.show_pred
        self.output_feat_keys = [self.feature_type]
        # stacks per device step; 64-frame stacks are large, so default 1
        self.stack_batch = args.get('batch_size') or STACK_BATCH
        self.decode_backend = args.get('decode_backend', 'auto')
        self.data_parallel = args.get('data_parallel', False)
        self._device = jax_device(self.device)
        self.params = jax.device_put(self.load_params(args), self._device)
        # the jit step is static per decode geometry (the short-side-224
        # resize scale); cache one executable per (h, w) so a corpus of
        # same-geometry videos compiles exactly once
        self._geom_steps: dict = {}

    def load_params(self, args):
        from video_features_tpu.extract.weights import load_or_init
        return load_or_init(args, 'checkpoint_path', s3d_model.init_state_dict,
                            feature_type='s3d', dtype=self.param_dtype)

    @staticmethod
    def _forward(params, stacks, resize_hw, resize_scale, dtype=None):
        from video_features_tpu.ops.precision import features_to_f32
        x = to_float_zero_one(stacks, dtype)
        # the reference's short-side Resize(224) interpolates at the GIVEN
        # scale 224/min(h, w), not out/in (reference models/transforms.py:
        # 76-96, scale_factor + recompute_scale_factor=False)
        x = resize_bilinear_scale(x, resize_hw, resize_scale)
        x = center_crop(x, (224, 224))
        return features_to_f32(s3d_model.forward(params, x, features=True))

    def _geometry_step(self, h: int, w: int):
        """(jitted step, resize_hw, scale) for decode geometry (h, w).

        Short-side 224 at the GIVEN scale 224/min(h, w): BOTH the output
        sizes and the sampling grid follow torch's
        F.interpolate(scale_factor=s, recompute_scale_factor=False) —
        sizes are floor(dim * s) with the exact float s (e.g.
        floor(480 * (224/336)) = 319, and a 107px short side floors to
        223, not 224 — the subsequent CenterCrop then behaves exactly
        like the reference's). Cached per (h, w) so a whole corpus of
        same-geometry videos compiles once.
        """
        cached = self._geom_steps.get((h, w))
        if cached is None:
            import math
            # bound the executable cache: each entry retains a compiled
            # XLA program + buffers, and a long heterogeneous corpus must
            # not accumulate them without limit (FIFO eviction trades a
            # recompile for bounded memory; real corpora cluster into a
            # handful of aspect ratios, so evictions are rare)
            if len(self._geom_steps) >= 16:
                self._geom_steps.pop(next(iter(self._geom_steps)))
                # the evicted geometry's resident AOT executable must
                # retire with its jitted step (the cap bounds live
                # executables, and the aot table is per-geometry too)
                self._aot_invalidate()
            scale = 224.0 / min(h, w)
            resize_hw = (math.floor(h * scale), math.floor(w * scale))
            step = jax.jit(named_step(
                partial(self._forward, resize_hw=resize_hw,
                        resize_scale=scale, dtype=self.compute_jnp_dtype),
                self.step_name))
            cached = self._geom_steps[(h, w)] = (step, resize_hw, scale)
        return cached

    # -- packed corpus mode: hooks from StackPackingMixin -------------------

    packed_feat_dim = s3d_model.FEAT_DIM

    def program_specs(self, mesh=None):
        """vft-programs abstract step spec: the per-geometry jitted step
        at the canonical lock geometry (one executable per (h, w) — the
        lock pins the count at ONE geometry; the per-shape cache is the
        family's own executable-growth bound)."""
        from video_features_tpu.analysis.programs import ProgramSpec
        h, w = self.PROGRAM_DECODE_HW
        step, _, _ = self._geometry_step(h, w)
        batch = self._abstract_batch(
            (self._program_batch_slots(mesh), self.stack_size, h, w, 3),
            np.uint8, mesh)
        return [ProgramSpec('step', step,
                            (self._abstract_params(mesh), batch))]

    def packed_step(self, stacks):
        # dispatch only (device array out); the scheduler's deferred
        # fetch_outputs owns the D2H readback. aot_call's dispatch key
        # includes the batch geometry, so each per-(h, w) jitted step
        # resolves to its own resident/store-loaded executable.
        step, _, _ = self._geometry_step(*stacks.shape[2:4])
        return {self.feature_type:
                self.aot_call('step', step, self.params, stacks)}

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
            # the device runs k; the host batch rides along for show_pred
            # (see streaming.transfer_batches). 'model' is dispatch only;
            # the deferred readback is the 'd2h' stage in overlap_fetch.
            for stacks, host_stacks, valid, window_idx in transfer_batches(
                    iter_batched_windows(windows, self.stack_batch,
                                         self.tracer),
                    self.put_input, keep_host=self.show_pred,
                    tracer=self.tracer):
                step, resize_hw, scale = \
                    self._geometry_step(*stacks.shape[2:4])
                with self.tracer.stage(
                        'model', **self.step_attrs(valid, self.stack_batch)):
                    dev = self.aot_call('step', step, self.params, stacks)
                self.tracer.add_occupancy('model', valid, self.stack_batch)
                yield dev, host_stacks, valid, window_idx, resize_hw, scale

        with self.precision_scope():
            for out, host_stacks, valid, window_idx, resize_hw, scale in \
                    overlap_fetch(dispatched(), self.fetch_outputs, depth,
                                  self.tracer, self.last_step):
                out = out[:valid]
                feats.append(out)
                if self.show_pred:
                    for k in range(valid):
                        start = (window_idx + k) * self.step_size
                        self.maybe_show_pred(host_stacks[k:k + 1], start,
                                             start + self.stack_size,
                                             resize_hw, scale)

        feats = (np.concatenate(feats, axis=0) if feats
                 else np.zeros((0, s3d_model.FEAT_DIM), np.float32))
        return {self.feature_type: feats}

    def maybe_show_pred(self, stacks, start_idx, end_idx, resize_hw, scale):
        import jax.numpy as jnp
        from video_features_tpu.utils.preds import show_predictions_on_dataset
        x = to_float_zero_one(jnp.asarray(stacks))
        x = resize_bilinear_scale(x, resize_hw, scale)
        x = center_crop(x, (224, 224))
        logits = np.asarray(s3d_model.forward(self.params, x, features=False))
        # vft-lint: ok=stdout-purity — show_pred narration surface
        print(f'At frames ({start_idx}, {end_idx})')
        show_predictions_on_dataset(logits, 'kinetics')
