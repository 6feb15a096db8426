"""Frame-wise extractor base (ResNet / CLIP / timm-style backbones).

Re-design of reference models/_base/base_framewise_extractor.py (90 LoC):
the host prepares fixed-size uint8 frames (PIL short-side resize + center
crop), batches are padded to the compiled batch size and masked, and one
jit-compiled step does float conversion + normalization + the backbone
forward — so every batch reuses a single XLA executable per video geometry.

Returns {feature_type: (T, D), 'fps': scalar, 'timestamps_ms': (T,)} exactly
like the reference (:75-79).
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np

from video_features_tpu.extract.base import BaseExtractor
from video_features_tpu.extract.streaming import (
    overlap_fetch, transfer_batches,
)
from video_features_tpu.io.video import VideoLoader


class BaseFrameWiseExtractor(BaseExtractor):

    def __init__(self, args, feat_dim: int) -> None:
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
        self.batch_size = args.batch_size
        self.decode_workers = args.get('decode_workers')    # None: unset
        self.decode_backend = args.get('decode_backend', 'auto')
        # data_parallel=true shards frame batches over ALL local devices:
        # params are re-placed replicated and batches arrive with a
        # data-axis sharding, so the subclass's jitted step compiles into
        # one pjit program with XLA-inserted collectives (reference
        # scale-out is one process per GPU, README.md:70-84)
        self.data_parallel = args.get('data_parallel', False)
        self.extraction_fps = args.get('extraction_fps')
        self.extraction_total = args.get('extraction_total')
        self.show_pred = args.show_pred
        self.feat_dim = feat_dim
        self.output_feat_keys = [self.feature_type, 'fps', 'timestamps_ms']

    # subclasses provide:
    def host_transform(self, frame: np.ndarray) -> np.ndarray:
        """HWC uint8 RGB frame → fixed-size HWC uint8 (resize + crop)."""
        raise NotImplementedError

    def device_step(self, batch: np.ndarray) -> jax.Array:
        """(B, H, W, 3) uint8 → (B, D) features. Must be jit-compiled."""
        raise NotImplementedError

    def maybe_show_pred(self, feats: np.ndarray) -> None:
        pass

    def _make_loader(self, video_path: str) -> VideoLoader:
        return VideoLoader(
            video_path,
            batch_size=self.batch_size,
            fps=self.extraction_fps,
            total=self.extraction_total,
            tmp_path=self.tmp_path,
            keep_tmp=self.keep_tmp_files,
            transform=self.host_transform,
            transform_workers=self.decode_workers or 1,
            backend=self.decode_backend,
        )

    # -- packed corpus mode (see extract.base / parallel.packing) -----------
    #
    # One packed "window" is a single host-transformed frame; the packer
    # fills frame batches across video boundaries — at corpus scale the
    # per-video tail batch (up to batch_size - 1 padded slots, paid per
    # video today) collapses into one tail batch per corpus.

    supports_packing = True

    def _packed_setup(self) -> None:
        super()._packed_setup()
        if self.data_parallel:
            self._ensure_mesh('batch_size')

    def packed_windows(self, task):
        from video_features_tpu.extract.streaming import (
            framewise_segment_windows, segment_frame_range,
        )
        loader = self._make_loader(task.path)
        task.info['fps'] = loader.fps
        # deterministic close (segment early-stop abandons the loader
        # mid-decode; GC-timed release would strand codec contexts and
        # re-encode temps in a long-lived serve worker)
        try:
            yield from framewise_segment_windows(
                loader, segment_frame_range(task.segment, loader.fps))
        finally:
            loader.close()

    def live_window_spec(self):
        # one window = one host-transformed frame; meta is a timestamp
        # (the live layer synthesizes it from the session's declared fps)
        return (1, 1, self.host_transform, True)

    def host_transform_spec(self):
        """Named-spec form of :meth:`host_transform` (``farm/recipes.py``
        vocabulary), or None when the transform can't be specced — which
        disables the decode farm for this extractor (in-process decode
        keeps working). Subclasses whose ``host_transform`` is the
        standard edge-resize + center-crop pair override this."""
        return None

    def farm_recipe(self):
        spec = self.host_transform_spec()
        if spec is None:
            return None
        from video_features_tpu.farm.recipes import FramewiseRecipe
        return FramewiseRecipe(
            batch_size=self.batch_size, fps=self.extraction_fps,
            total=self.extraction_total, tmp_path=self.tmp_path,
            keep_tmp=self.keep_tmp_files, backend=self.decode_backend,
            transform=spec)

    def fused_decode_signature(self):
        """Frame-wise families fuse when everything upstream of the
        per-frame transform matches: same retiming (fps/total) and same
        decode backend produce the same raw frame stream, and the
        per-family transform is a pure per-frame call over it
        (``io.video.VideoLoader``) — so one shared decode branched into
        N spec transforms is byte-identical to N separate decodes. A
        family whose transform can't be specced can't branch off a
        shared raw stream, so it stays unfused (None)."""
        if self.host_transform_spec() is None:
            return None
        return ('framewise', self.extraction_fps, self.extraction_total,
                self.decode_backend)

    def packed_step(self, batch) -> Dict:
        # dispatch only (device array out); the scheduler's deferred
        # fetch_outputs owns the D2H readback
        return {self.feature_type: self.device_step(batch)}

    def program_specs(self, mesh=None):
        """vft-programs abstract step spec, shared by every frame-wise
        family (resnet/clip/timm): the REAL ``host_transform`` discovers
        the compiled input geometry (run once on a zero frame at the
        canonical decode shape), so the spec can never drift from the
        preprocessing that actually feeds the step."""
        import numpy as np

        from video_features_tpu.analysis.programs import ProgramSpec
        h, w = self.PROGRAM_DECODE_HW
        ch, cw = self.host_transform(
            np.zeros((h, w, 3), np.uint8)).shape[:2]
        batch = self._abstract_batch(
            (self._program_batch_slots(mesh), ch, cw, 3), np.uint8, mesh)
        return [ProgramSpec('step', self._step,
                            (self._abstract_params(mesh), batch))]

    def packed_result(self, task) -> Dict[str, np.ndarray]:
        rows = task.rows.get(self.feature_type, [])
        return {
            self.feature_type: (np.stack(rows) if rows
                                else np.zeros((0, self.feat_dim),
                                              np.float32)),
            'fps': np.array(task.info.get('fps', 0.0)),
            'timestamps_ms': np.array(task.meta_rows),
        }

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        if self.data_parallel:
            self._ensure_mesh('batch_size')
        loader = self._make_loader(video_path)
        feats, timestamps = [], []

        def assembled():
            # pad tails to the compiled batch shape on the producer thread
            for batch, times, _ in self.tracer.wrap_iter(
                    'decode+preprocess', loader):
                batch = np.stack(batch)
                valid = batch.shape[0]
                if valid < self.batch_size:
                    pad = np.repeat(batch[-1:], self.batch_size - valid,
                                    axis=0)
                    batch = np.concatenate([batch, pad], axis=0)
                yield batch, valid, times

        depth = 1 if self.show_pred else self.inflight

        def dispatched():
            # transfer of batch k+1 overlaps the device running batch k
            # (see streaming.transfer_batches); 'model' is dispatch only,
            # the deferred readback is the 'd2h' stage in overlap_fetch
            for batch, _, valid, times in transfer_batches(
                    assembled(), self.put_input, tracer=self.tracer):
                with self.tracer.stage(
                        'model', **self.step_attrs(valid, self.batch_size)):
                    dev = self.device_step(batch)
                self.tracer.add_occupancy('model', valid, self.batch_size)
                yield dev, valid, times

        with self.precision_scope():
            for out, valid, times in overlap_fetch(
                    dispatched(), self.fetch_outputs, depth, self.tracer,
                    self.last_step):
                out = out[:valid]
                feats.append(out)
                timestamps.extend(times)
                if self.show_pred:
                    self.maybe_show_pred(out)

        features = (np.concatenate(feats, axis=0) if feats
                    else np.zeros((0, self.feat_dim), np.float32))
        return {
            self.feature_type: features,
            'fps': np.array(loader.fps),
            'timestamps_ms': np.array(timestamps),
        }
