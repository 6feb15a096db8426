"""RAFT flow extractor (reference models/raft/extract_raft.py +
models/_base/base_flow_extractor.py behavior).

Contract parity:
  * consecutive-pair batching: the loader yields ``batch_size + 1`` frames
    with overlap 1, producing ``batch_size`` flows per step (reference
    base_flow_extractor.py:76-84);
  * optional host-side PIL edge resize (``side_size`` /
    ``resize_to_smaller_edge``), else raw float frames (:50-58);
  * pad to /8 (sintel replicate padding), flow computed on padded frames,
    unpadded before collection (:104-115);
  * outputs {'raft': (T-1, 2, H, W), 'fps', 'timestamps_ms'} where
    timestamps keep every decoded frame (first batch whole, later batches
    minus the overlapped head) (:92-101) — note the reference stores flow
    channels-first; we keep that on-disk layout for drop-in compatibility.

TPU-first: one jit step per video geometry — the padded (B+1, H, W, 3)
batch maps to B frame pairs computed in a single compiled RAFT call; ragged
tails are padded to the compiled shape and masked.
"""
from __future__ import annotations

from functools import partial
from pathlib import Path
from typing import Dict

import jax
import numpy as np

from video_features_tpu.extract.base import BaseExtractor, named_step
from video_features_tpu.extract.streaming import transfer_batches
from video_features_tpu.io.video import VideoLoader
from video_features_tpu.models import raft as raft_model
from video_features_tpu.ops.transforms import resize_pil
from video_features_tpu.utils.device import jax_device

FINETUNED_CKPTS = ('sintel', 'kitti')


class ExtractRAFT(BaseExtractor):

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
        )
        self.batch_size = args.batch_size
        self.decode_workers = args.get('decode_workers')    # None: unset
        self.decode_backend = args.get('decode_backend', 'auto')
        self.side_size = args.get('side_size')
        self.resize_to_smaller_edge = args.get('resize_to_smaller_edge', True)
        self.extraction_fps = args.get('extraction_fps')
        self.extraction_total = args.get('extraction_total')
        self.finetuned_on = args.get('finetuned_on', 'sintel')
        assert self.finetuned_on in FINETUNED_CKPTS, \
            f'finetuned_on must be one of {FINETUNED_CKPTS}'
        # Shapes are static per jit: every distinct padded geometry is a
        # fresh multi-minute compile (docs/design.md "one jit step per
        # video geometry"). bucket_multiple > 8 rounds the replicate-pad
        # up to coarser buckets so a heterogeneous corpus shares
        # executables (e.g. 64 → 256×342 and 256×344 both run 256×384).
        # Opt-in because wider replicate pads ARE visible to the flow
        # numerics near borders (the padding participates in correlation
        # and context) — measured in tests/test_raft_extractor.py.
        self.bucket_multiple = int(args.get('bucket_multiple', 8))
        assert self.bucket_multiple % 8 == 0 and self.bucket_multiple > 0, \
            'bucket_multiple must be a positive multiple of 8'
        self.show_pred = args.show_pred
        self.output_feat_keys = [self.feature_type, 'fps', 'timestamps_ms']
        # data_parallel=true spreads the B consecutive-pair flows over all
        # local devices: the host hands each device its own run of k+1
        # frames (k = B / n_devices; the one-frame halo at shard boundaries
        # is duplicated host-side), and a shard_map'd forward_consecutive
        # encodes each device's frames ONCE — interior frames share their
        # fnet encoding between their two pairs exactly like the
        # single-device path, and no in-graph halo exchange is needed.
        self.data_parallel = args.get('data_parallel', False)
        # refinement-depth knob; 20 = the fork's pin = full parity
        self.raft_iters = raft_model.resolve_iters(args.get('raft_iters'))
        self._device = jax_device(self.device)
        self.params = jax.device_put(self.load_params(args), self._device)
        # thread the resolved device's platform so the corr-lookup dispatch
        # matches where the operands actually live, not the process default
        self._step = jax.jit(named_step(
            partial(self._flow_batch, platform=self._device.platform,
                    pins=self.precision_pins, iters=self.raft_iters),
            self.step_name))

    def load_params(self, args):
        # RAFT checkpoints were saved from nn.DataParallel — prefixes are
        # stripped by the transplant layer
        from video_features_tpu.extract.weights import load_or_init
        return load_or_init(args, 'checkpoint_path', raft_model.init_state_dict,
                            feature_type='raft')

    @staticmethod
    def _flow_batch(params, frames, platform=None, pins=None,
                    iters=raft_model.ITERS):
        """(B+1, Hp, Wp, 3) padded frames → (B, Hp, Wp, 2) flows; interior
        frames are fnet-encoded once (forward_consecutive), not twice."""
        return raft_model.forward_consecutive(params, frames, iters=iters,
                                              platform=platform, pins=pins)

    def _build_dp_step(self):
        """shard_map'd per-device forward_consecutive over the data axis.

        Input is the host-assembled halo layout (n·(k+1), Hp, Wp, 3):
        device d's shard holds frames [d·k, d·k + k] inclusive, so its k
        flows concatenate to the global (B, Hp, Wp, 2) result in order.
        """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        return jax.jit(named_step(shard_map(
            partial(raft_model.forward_consecutive,
                    iters=self.raft_iters,
                    platform=self._device.platform,
                    pins=self.precision_pins),
            mesh=self._mesh, in_specs=(P(), P('data')),
            out_specs=P('data')), self.step_name))

    def _halo_shards(self, padded: np.ndarray) -> np.ndarray:
        """(B+1, ...) frames → (n·(k+1), ...) per-device runs with the
        boundary frame duplicated; fnet cost is B + n frame encodes instead
        of the pair form's 2·B."""
        n = self._mesh.shape['data']
        k = (padded.shape[0] - 1) // n
        halo = np.stack([padded[d * k: d * k + k + 1] for d in range(n)])
        return halo.reshape((n * (k + 1),) + padded.shape[1:])

    def program_specs(self, mesh=None):
        """vft-programs abstract step specs. Single-device: the
        consecutive-pair flow step over (B+1, Hp, Wp, 3) padded frames.
        Mesh variant: the family's REAL data-parallel program is the
        shard_map'd halo layout (each device gets its own k+1 frame run,
        boundary frame duplicated host-side) — n·(k+1) rows, evenly
        shardable by construction, unlike the B+1 pair form."""
        from video_features_tpu.analysis.programs import ProgramSpec
        h, w = self.PROGRAM_DECODE_HW           # already /8-aligned
        if mesh is None:
            batch = self._abstract_batch(
                (self.batch_size + 1, h, w, 3), np.uint8)
            return [ProgramSpec('flow_step', self._step,
                                (self._abstract_params(), batch))]
        prev_mesh = self._mesh
        self._mesh = mesh
        try:
            dp_step = self._build_dp_step()
        finally:
            self._mesh = prev_mesh
        n = mesh.shape['data']
        k = max(int(self.batch_size), 1)
        batch = self._abstract_batch((n * (k + 1), h, w, 3), np.uint8,
                                     mesh)
        return [ProgramSpec('flow_step_dp', dp_step,
                            (self._abstract_params(mesh), batch))]

    def host_transform(self, frame: np.ndarray) -> np.ndarray:
        # uint8 until on-device (RAFT normalizes in-graph): the values are
        # exact integers either way and the H2D transfer is 4x smaller
        if self.side_size is not None:
            frame = resize_pil(frame, self.side_size, self.resize_to_smaller_edge)
        return frame

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        if self.data_parallel and self._mesh is None:
            self._ensure_mesh('batch_size')
            self._dp_step = self._build_dp_step()
        self._viz_stem, self._viz_count = Path(video_path).stem, 0
        loader = VideoLoader(
            video_path,
            batch_size=self.batch_size + 1,
            fps=self.extraction_fps,
            total=self.extraction_total,
            tmp_path=self.tmp_path,
            keep_tmp=self.keep_tmp_files,
            transform=self.host_transform,
            transform_workers=self.decode_workers or 1,
            backend=self.decode_backend,
            overlap=1,
        )
        flows, timestamps = [], []

        def assembled():
            # stack + tail-pad + /8-pad on the producer thread; 'model'
            # stage stays pure device time
            first = True
            for batch, times, _ in self.tracer.wrap_iter(
                    'decode+preprocess', loader):
                batch = np.stack(batch)                      # (n, H, W, 3)
                ts = times if first else times[1:]
                first = False
                if batch.shape[0] < 2:
                    yield None, None, 0, ts   # timestamps only, no pairs
                    continue
                valid = batch.shape[0] - 1
                if batch.shape[0] < self.batch_size + 1:
                    pad = np.repeat(
                        batch[-1:], self.batch_size + 1 - batch.shape[0],
                        axis=0)
                    batch = np.concatenate([batch, pad], axis=0)
                padded, pads = raft_model.pad_to_multiple(
                    batch, mode=self.finetuned_on,
                    multiple=self.bucket_multiple)
                self.say_kernels('raft', raft_model.lookup_note(
                    padded.shape[1] // 8, padded.shape[2] // 8,
                    self._device.platform))
                yield padded, pads, valid, ts

        def put(padded):
            if padded is None:
                return None
            if self._mesh is not None:
                # dp feeds per-device frame runs (host-duplicated one-frame
                # halo) so each device fnet-encodes its frames once
                return self._put_batch(self._halo_shards(padded))
            return self.put_input(padded)

        with self.precision_scope():
            # transfer of batch k+1 overlaps the device running batch k
            for dev, _, pads, valid, ts in transfer_batches(
                    assembled(), put, tracer=self.tracer):
                timestamps.extend(ts)
                if dev is None:
                    continue
                with self.tracer.stage(
                        'model', **self.step_attrs(valid, self.batch_size)):
                    # aot_call on the single-device path only: the dp
                    # shard_map program keeps its direct jit dispatch
                    flow = (self._dp_step(self.params, dev)
                            if self._mesh is not None
                            else self.aot_call('flow_step', self._step,
                                               self.params, dev))
                    flow = np.asarray(raft_model.unpad(flow, pads))[:valid]
                flows.append(flow)
                if self.show_pred:
                    self.maybe_show_pred(flow)

        if flows:
            features = np.concatenate(flows, axis=0).transpose(0, 3, 1, 2)
        else:
            # Empty fallback must match the geometry normal outputs would
            # have — i.e. AFTER the host resize, not the raw video dims.
            h, w = self.host_transform(
                np.zeros((loader.height, loader.width, 3), np.uint8)).shape[:2]
            features = np.zeros((0, 2, h, w), np.float32)
        return {
            self.feature_type: features,
            'fps': np.array(loader.fps),
            'timestamps_ms': np.array(timestamps),
        }

    def maybe_show_pred(self, flows: np.ndarray) -> None:
        """Render flow frames via the Middlebury wheel (headless-safe).

        The reference opens cv2 windows per frame (reference
        base_flow_extractor.py:134-149); TPU hosts are headless, so the
        rendered image is preserved as a PNG artifact under
        ``<output_path>/flow_debug/`` instead (one per device batch).
        """
        from video_features_tpu.utils.flow_viz import flow_to_image
        for flow in flows[:1]:
            img = flow_to_image(flow)
            # vft-lint: ok=stdout-purity — show_pred narration surface
            print(f'[flow viz] frame rendered: shape={img.shape}, '
                  f'mean_mag={np.linalg.norm(flow, axis=-1).mean():.3f}')
            try:
                import cv2
                out_dir = Path(self.output_path) / 'flow_debug'
                out_dir.mkdir(parents=True, exist_ok=True)
                path = out_dir / f'{self._viz_stem}_{self._viz_count:06d}.png'
                cv2.imwrite(str(path), img[..., ::-1])  # RGB → BGR on disk
                self._viz_count += 1
            except Exception:  # debug surface: never fail extraction
                import logging as _logging

                from video_features_tpu.obs.events import event
                event(_logging.WARNING, 'flow viz PNG write skipped',
                      exc_info=True, subsystem='raft')
