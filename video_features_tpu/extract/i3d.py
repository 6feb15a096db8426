"""I3D two-stream extractor — the flagship fused RAFT→I3D pipeline.

Behavior parity with reference models/i3d/extract_i3d.py:
  * frames host-resized to short side 256 (PIL, ResizeImproved numerics,
    :43-48) and accumulated into stacks of ``stack_size + 1`` frames — B+1
    frames give B flow pairs, and the rgb stream uses the first B frames so
    both streams have equal length (:115-123, :150-160);
  * flow stream: RAFT on /8-padded consecutive pairs; the center crop is
    taken from the PADDED flow exactly like the reference (which never
    unpads before TensorCenterCrop, :156-164);
  * transforms: rgb = crop224 → 2x/255-1; flow = crop224 → clamp(±20) →
    uint8 quantize → 2x/255-1 (:49-62);
  * ``step_size`` < ``stack_size`` overlaps windows; partial final stacks
    are dropped (:126-129); streams configurable ('rgb'/'flow'/both).

TPU-first: the whole stack→flow→transform→two-I3D graph is ONE jit-compiled
function; stacks are gathered with a vectorized index array and batched
``batch_size`` windows per device step (padded + masked at the tail). The
reference instead runs a python frame loop with per-stack device round trips.
"""
from __future__ import annotations

from functools import partial
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from video_features_tpu.extract.base import BaseExtractor, named_step
from video_features_tpu.io.video import VideoLoader
from video_features_tpu.models import i3d as i3d_model
from video_features_tpu.models import raft as raft_model
from video_features_tpu.ops.transforms import (
    center_crop, flow_to_uint8_levels, resize_pil, scale_to_pm1,
)
from video_features_tpu.utils.device import jax_device
from video_features_tpu.utils.tracing import NULL_TRACER

MIN_SIDE_SIZE = 256
CROP_SIZE = 224


def rgb_stream_input(stacks, crop_size):
    """(B, S+1, H, W, 3) frames → rgb I3D input: first S frames, center
    crop, 2x/255-1 rescale (reference extract_i3d.py:49-55)."""
    return scale_to_pm1(center_crop(stacks[:, :-1], crop_size))


def flow_stream_input(raft_params, stacks, pads, crop_size,
                      constrain_pairs=None, platform=None, pins=None,
                      raft_iters=raft_model.ITERS):
    """(B, S+1, H, W, 3) frames → quantized flow I3D input (B, S, c, c, 2).

    RAFT on /8-padded consecutive pairs (each interior frame's fnet
    encoding shared between its two pairs — raft.forward_stack_pairs), then
    the kinetics-i3d flow recipe: crop the PADDED flow (the reference never
    unpads before TensorCenterCrop, extract_i3d.py:156-164) → clamp ±20 →
    uint8 levels → ±1 rescale. ``raft_iters`` trades refinement quality for
    speed (the reference's own RAFT default was 12 before the fork pinned
    20, raft_src/raft.py:117-118).
    """
    t, b, l, r = pads
    padded = jnp.pad(stacks, [(0, 0), (0, 0), (t, b), (l, r), (0, 0)],
                     mode='edge')
    flow = raft_model.forward_stack_pairs(raft_params, padded,
                                          iters=raft_iters,
                                          constrain=constrain_pairs,
                                          platform=platform, pins=pins)
    with jax.named_scope('flow_quantise'):
        flow = center_crop(flow, crop_size)
        return scale_to_pm1(flow_to_uint8_levels(flow, 20.0))


def _pil_short_side_geometry(h, w, size):
    """PIL's short-side resize target for (h, w), or None when resize_pil
    would no-op — delegates to the one home of the arithmetic
    (ops.transforms.pil_edge_resize_geometry)."""
    from video_features_tpu.ops.transforms import pil_edge_resize_geometry
    return pil_edge_resize_geometry(h, w, size)


def _device_resize_stacks(stacks, resize_to):
    """(B, S, H, W, 3) → (B, S, H', W', 3) BIT-EXACT Pillow bilinear
    resize in-graph (ops.transforms.pil_resize_bilinear_device) — the
    ONE in-graph resize both the fused step and the show_pred debug path
    apply. Because it reproduces PIL's fixed-point arithmetic exactly,
    device_resize=true yields the IDENTICAL pixels the host resize_pil
    path produces — zero feature drift, so the host decode wall can be
    escaped at full parity (VERDICT r4 task 1)."""
    from video_features_tpu.ops.transforms import pil_resize_bilinear_device
    return jnp.asarray(
        pil_resize_bilinear_device(stacks, tuple(resize_to)), stacks.dtype)


def fused_two_stream_step(params, stacks, pads, streams, constrain_pairs=None,
                          crop_size=CROP_SIZE, platform=None, pins=None,
                          raft_iters=raft_model.ITERS, resize_to=None):
    """(B, stack+1, H, W, 3) float frames → {stream: (B, 1024)}.

    The full two-stream graph — RAFT flow, quantization, both I3D towers —
    compiles into a single XLA executable. ``constrain_pairs`` optionally
    applies a sharding constraint to the leading-flattened tensors feeding
    RAFT's heavy sub-graphs (unique frames, fmap pairs, cnet input) so they
    spread over a (data, time) mesh (sequence parallelism over temporal
    pairs — see parallel.mesh). ``pins`` selects per-sub-graph matmul
    precision (ops/precision.py: 'encoder'/'corr'/'iter'/'upsample' inside
    RAFT, 'i3d' for both towers) — the precision='mixed' fast-parity mode.

    ``resize_to=(H', W')`` moves the short-side resize into the graph
    (``device_resize=true``): raw decode-geometry frames in, BIT-EXACT
    Pillow bilinear resample on device (ops.transforms.
    pil_resize_bilinear_device) — identical pixels to the host resize_pil
    path, zero feature cost (tests/test_device_resize.py asserts it).
    """
    from video_features_tpu.ops.precision import pin_scope
    if resize_to is not None:
        stacks = _device_resize_stacks(stacks, resize_to)
    out = {}
    if 'rgb' in streams:
        rgb = rgb_stream_input(stacks, crop_size)
        with pin_scope(pins, 'i3d'), jax.named_scope('i3d_towers'):
            out['rgb'] = i3d_model.forward(params['rgb'], rgb, features=True)
    if 'flow' in streams:
        flow = flow_stream_input(params['raft'], stacks, pads, crop_size,
                                 constrain_pairs, platform=platform,
                                 pins=pins, raft_iters=raft_iters)
        with pin_scope(pins, 'i3d'), jax.named_scope('i3d_towers'):
            out['flow'] = i3d_model.forward(params['flow'], flow,
                                            features=True)
    return out


@partial(jax.jit, static_argnames=('stream', 'pads', 'crop_size', 'platform'))
def _pred_logits(params, stacks, stream, pads, crop_size, platform=None):
    """Classifier logits for one stream — the show_pred debug surface,
    compiled so it doesn't pay eager dispatch per displayed batch."""
    if stream == 'rgb':
        x = rgb_stream_input(stacks, crop_size)
    else:
        x = flow_stream_input(params['raft'], stacks, pads, crop_size,
                              platform=platform)
    return i3d_model.forward(params[stream], x, features=False)[1]


@partial(jax.jit, static_argnames=('pads', 'crop_size', 'platform'))
def _debug_flow(raft_params, stacks, pads, crop_size, platform=None):
    """Cropped un-quantized flow of the FIRST pair of the first stack —
    the frame the reference renders in its cv2 window
    (base_flow_extractor.py:134-149). Debug surface only."""
    t, b, l, r = pads
    pair = jnp.pad(stacks[:1, :2], [(0, 0), (0, 0), (t, b), (l, r), (0, 0)],
                   mode='edge')
    flow = raft_model.forward_stack_pairs(raft_params, pair,
                                          platform=platform)
    return center_crop(flow, crop_size)[0, 0]


class ExtractI3D(BaseExtractor):

    def __init__(self, args) -> None:
        super().__init__(
            feature_type=args.feature_type,
            on_extraction=args.on_extraction,
            tmp_path=args.tmp_path,
            output_path=args.output_path,
            keep_tmp_files=args.keep_tmp_files,
            device=args.device,
            concat_rgb_flow=args.get('concat_rgb_flow', False),
            profile=args.get('profile', False),
            precision=args.get('precision', 'highest'),
            inflight=args.get('inflight', 2),
        )
        self.streams: List[str] = (['rgb', 'flow'] if args.streams is None
                                   else [args.streams])
        for s in self.streams:
            assert s in ('rgb', 'flow'), f'unknown stream {s}'
        if args.flow_type != 'raft':
            raise NotImplementedError('only flow_type=raft is supported')
        self.stack_size = 64 if args.stack_size is None else args.stack_size
        self.step_size = 64 if args.step_size is None else args.step_size
        # refinement-depth knob; 20 = the fork's pin = full parity
        self.raft_iters = raft_model.resolve_iters(args.get('raft_iters'))
        self.extraction_fps = args.extraction_fps
        self.batch_size = args.get('batch_size', 1)
        self.decode_workers = args.get('decode_workers')    # None: unset
        self.decode_backend = args.get('decode_backend', 'auto')
        # device_resize=true ships RAW decode-geometry uint8 frames and
        # runs the short-side-256 resize inside the fused graph — lifting
        # the host's per-frame PIL work onto the MXU. The in-graph resample is
        # bit-exact Pillow arithmetic, so the features are identical to
        # the host path's (tests/test_device_resize.py)
        self.device_resize = bool(args.get('device_resize', False))
        self.show_pred = args.show_pred
        self.output_feat_keys = list(self.streams)
        # decode-geometry (H, W) -> (pads, resize_to): shared by the
        # per-video and packed paths so a corpus of same-geometry videos
        # derives its RAFT padding / device-resize target exactly once
        self._geom_cache: Dict[tuple, tuple] = {}
        self._device = jax_device(self.device)
        # data_parallel=true shards stack batches over ALL local devices with
        # one pjit program (params replicated, RAFT pairs spread over the
        # time axis) — the reference's only scale-out is launching one
        # process per GPU (reference README.md:70-84)
        self.data_parallel = args.get('data_parallel', False)
        if self.data_parallel:
            from video_features_tpu.parallel import (
                build_sharded_two_stream_step, make_mesh, put_batch,
                put_replicated, round_batch_to_data_axis,
            )
            from video_features_tpu.utils.device import jax_devices_all
            # self._mesh keeps the one-flag-per-extractor invariant from
            # BaseExtractor; self.mesh stays the public name
            self.mesh = self._mesh = make_mesh(
                devices=jax_devices_all(self.device))
            # batch_size is the global batch; round up to fill the data axis
            self.batch_size = round_batch_to_data_axis(self.batch_size,
                                                       self.mesh)
            self.params = put_replicated(self.mesh, self.load_params(args))
            self._put_batch = partial(put_batch, self.mesh)
            sharded = build_sharded_two_stream_step(
                self.mesh, streams=tuple(self.streams),
                pins=self.precision_pins, raft_iters=self.raft_iters)

            def _step(params, stacks, pads, streams, resize_to=None):
                return sharded(params, stacks, pads,
                               resize_to=tuple(resize_to)
                               if resize_to is not None else None)

            self._step = _step
        else:
            self.params = jax.device_put(self.load_params(args), self._device)
            # pads/streams are static so one executable serves each geometry;
            # the resolved device's platform drives the RAFT corr-lookup
            # dispatch (not the process default backend)
            self._step = jax.jit(
                named_step(
                    partial(self._stack_batch,
                            platform=self._device.platform,
                            pins=self.precision_pins,
                            raft_iters=self.raft_iters), self.step_name),
                static_argnames=('pads', 'streams', 'resize_to'))

    def load_params(self, args):
        """{'rgb': i3d params, 'flow': i3d params, 'raft': raft params}.

        Missing checkpoint paths are a hard error unless random weights are
        explicitly allowed (extract.weights; the reference always loads real
        weights, extract_i3d.py:180-183).
        """
        from video_features_tpu.extract.weights import load_or_init
        params = {}
        if 'rgb' in self.streams:
            params['rgb'] = load_or_init(
                args, 'i3d_rgb_checkpoint_path',
                partial(i3d_model.init_state_dict, modality='rgb'),
                feature_type='i3d', what='i3d rgb stream')
        if 'flow' in self.streams:
            params['flow'] = load_or_init(
                args, 'i3d_flow_checkpoint_path',
                partial(i3d_model.init_state_dict, modality='flow'),
                feature_type='i3d', what='i3d flow stream')
            params['raft'] = load_or_init(
                args, 'raft_checkpoint_path', raft_model.init_state_dict,
                feature_type='i3d', what='i3d flow stream (raft)')
        return params

    # -- the fused device step ----------------------------------------------

    _stack_batch = staticmethod(fused_two_stream_step)
    step_name = 'i3d_two_stream_step'

    # -- extraction ---------------------------------------------------------

    def _stream_windows(self, loader, tracer=None, frame_range=None):
        """(stack_size+1)-frame windows (B+1 frames → B flow pairs) streamed
        off the decoder; see extract.streaming for the semantics."""
        from video_features_tpu.extract.streaming import stream_windows
        tracer = self.tracer if tracer is None else tracer
        return stream_windows(loader, self.stack_size + 1, self.step_size,
                              tracer, 'decode+preprocess',
                              frame_range=frame_range)

    def _make_loader(self, video_path: str) -> VideoLoader:
        # frames stay uint8 until they are on the device: values are exact
        # integers either way, and a (B, S+1, 256, W, 3) float32 stack batch
        # is 4x the host->device bytes of the uint8 one — H2D bandwidth is
        # the CLI's bottleneck ahead of the fused compute.
        # device_resize lifts the PIL resize into the fused graph: raw
        # decode frames ship as-is and the jitted step resizes them
        # (resize_to computed per geometry with PIL's own edge rule).
        return VideoLoader(
            video_path, batch_size=64,
            fps=self.extraction_fps, tmp_path=self.tmp_path,
            keep_tmp=self.keep_tmp_files,
            transform=(None if self.device_resize
                       else lambda f: resize_pil(f, MIN_SIDE_SIZE)),
            transform_workers=self.decode_workers or 1,
            backend=self.decode_backend)

    def _geometry(self, h: int, w: int) -> tuple:
        """(pads, resize_to) for decode geometry (h, w), cached per shape."""
        geom = self._geom_cache.get((h, w))
        if geom is None:
            # every distinct geometry also specializes the jitted step
            # (static pads/resize_to); bound that executable growth on
            # long heterogeneous corpora by dropping ALL specializations
            # past 16 geometries (coarser than s3d's per-entry FIFO —
            # jit's internal cache is all-or-nothing — but real corpora
            # cluster into a handful of aspect ratios, so this never
            # fires in practice; the data_parallel wrapper has no
            # clear_cache and keeps jit's unbounded default)
            if len(self._geom_cache) >= 16:
                getattr(self._step, 'clear_cache', lambda: None)()
                self._geom_cache.clear()
                # resident AOT executables are per-geometry too: the
                # bound exists to cap live executables, so drop both
                self._aot_invalidate()
            resize_to = None
            gh, gw = h, w
            if self.device_resize:
                resize_to = _pil_short_side_geometry(gh, gw, MIN_SIDE_SIZE)
                if resize_to is not None:
                    gh, gw = resize_to
            pads = tuple(raft_model.pad_to_multiple(
                np.zeros((1, gh, gw, 1), np.float32))[1])
            geom = self._geom_cache[(h, w)] = (pads, resize_to)
            if 'flow' in self.streams:
                t, b, l, r = pads
                self.say_kernels('raft', raft_model.lookup_note(
                    (gh + t + b) // 8, (gw + l + r) // 8,
                    self._device.platform))
        return geom

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        from video_features_tpu.extract.streaming import (
            iter_batched_windows, overlap_fetch, transfer_batches,
        )

        loader = self._make_loader(video_path)
        feats: Dict[str, list] = {s: [] for s in self.streams}
        # show_pred narrates windows as they compute (and needs the input
        # batch alive at fetch time) — keep the debug surface synchronous
        depth = 1 if self.show_pred else self.inflight

        def dispatched():
            # decode thread assembles + transfers batch k+1 while the
            # device runs batch k (see streaming.transfer_batches); the
            # 'model' stage is DISPATCH only — the deferred readback is
            # its own 'd2h' stage inside overlap_fetch
            for stacks, _, valid, window_idx in transfer_batches(
                    iter_batched_windows(self._stream_windows(loader),
                                         self.batch_size, self.tracer),
                    self.put_input, tracer=self.tracer):
                pads, resize_to = self._geometry(*stacks.shape[2:4])
                with self.tracer.stage(
                        'model', **self.step_attrs(valid, self.batch_size)):
                    out = self.aot_call('step', self._step,
                                        self.params, stacks, pads=pads,
                                        streams=tuple(self.streams),
                                        resize_to=resize_to)
                self.tracer.add_occupancy('model', valid, self.batch_size)
                # carry the input batch only for show_pred — holding it
                # across the in-flight window would pin input HBM
                yield (out, stacks if self.show_pred else None,
                       valid, window_idx, pads, resize_to)

        with self.precision_scope():
            for out, stacks, valid, window_idx, pads, resize_to in \
                    overlap_fetch(dispatched(), self.fetch_outputs, depth,
                                  self.tracer, self.last_step):
                for s in self.streams:
                    feats[s].append(out[s][:valid])
                if self.show_pred:
                    self.maybe_show_pred(stacks[:valid], pads, window_idx,
                                         resize_to)

        return {
            s: (np.concatenate(v, axis=0) if v
                else np.zeros((0, i3d_model.FEAT_DIM), np.float32))
            for s, v in feats.items()
        }

    # -- packed corpus mode (see extract.base / parallel.packing) -----------

    supports_packing = True

    def packed_windows(self, task):
        from video_features_tpu.extract.streaming import segment_frame_range
        loader = self._make_loader(task.path)
        # deterministic close (segment early-stop abandons the stream
        # mid-decode; GC-timed release would strand codec contexts and
        # re-encode temps in a long-lived serve worker)
        try:
            for window in self._stream_windows(
                    loader, tracer=NULL_TRACER,
                    frame_range=segment_frame_range(task.segment,
                                                    loader.fps)):
                yield window, None
        finally:
            loader.close()

    def live_window_spec(self):
        # B+1 raw frames → B flow pairs; the host short-side resize
        # applies per frame unless device_resize lifted it in-graph
        return (self.stack_size + 1, self.step_size,
                (None if self.device_resize
                 else lambda f: resize_pil(f, MIN_SIDE_SIZE)), False)

    def program_specs(self, mesh=None):
        """vft-programs abstract step spec: the fused two-stream program
        (RAFT flow + quantization + both I3D towers in ONE executable)
        at the canonical decode geometry — post-host-resize unless
        ``device_resize`` lifted the resize in-graph, exactly what the
        hot path feeds ``_step``."""
        from video_features_tpu.analysis.programs import ProgramSpec
        h, w = self.PROGRAM_DECODE_HW
        if not self.device_resize:
            geom = _pil_short_side_geometry(h, w, MIN_SIDE_SIZE)
            if geom is not None:
                h, w = geom
        pads, resize_to = self._geometry(h, w)
        batch = self._abstract_batch(
            (self._program_batch_slots(mesh), self.stack_size + 1, h, w,
             3), np.uint8, mesh)
        return [ProgramSpec(
            'step', self._step, (self._abstract_params(mesh), batch),
            kwargs=dict(pads=pads, streams=tuple(self.streams),
                        resize_to=resize_to))]

    def packed_step(self, stacks):
        # device arrays out — dispatch only; the scheduler materializes
        # results k batches later (fetch_outputs), overlapping D2H +
        # scatter + save with device compute
        pads, resize_to = self._geometry(*stacks.shape[2:4])
        # aot_call keys on the static kwargs too: each (pads, resize_to)
        # specialization resolves to its own resident executable
        out = self.aot_call('step', self._step, self.params, stacks,
                            pads=pads, streams=tuple(self.streams),
                            resize_to=resize_to)
        return {s: out[s] for s in self.streams}

    def packed_result(self, task):
        return {
            s: (np.stack(task.rows[s]) if task.rows.get(s)
                else np.zeros((0, i3d_model.FEAT_DIM), np.float32))
            for s in self.streams
        }

    def farm_recipe(self):
        # one extra frame per window (B+1 frames → B flow pairs); the
        # host short-side resize rides as a spec unless device_resize
        # lifted it into the fused graph (raw frames ship then)
        from video_features_tpu.farm.recipes import StackRecipe
        return StackRecipe(
            win=self.stack_size + 1, step=self.step_size, batch_size=64,
            fps=self.extraction_fps, total=None, tmp_path=self.tmp_path,
            keep_tmp=self.keep_tmp_files, backend=self.decode_backend,
            transform=(None if self.device_resize
                       else ('edge_resize', MIN_SIDE_SIZE, 'bilinear')))

    def maybe_show_pred(self, stacks, pads, stack_counter, resize_to=None):
        """Kinetics top-5 per STREAM, like the reference (extract_i3d.py:
        212-216 runs the classifier head on each stream's transformed
        slice). Debug surface only — the flow recompute happens outside the
        fused hot path. Under device_resize the raw stacks are resized
        here first (same graph-side resize the fused step applies)."""
        from video_features_tpu.utils.preds import show_predictions_on_dataset
        if resize_to is not None:
            stacks = np.asarray(_device_resize_stacks(
                jnp.asarray(stacks, jnp.float32), resize_to))
        crop = min(CROP_SIZE, stacks.shape[2], stacks.shape[3])
        for stream in self.streams:
            logits = _pred_logits(self.params, jnp.asarray(stacks),
                                  stream=stream, pads=tuple(pads),
                                  crop_size=crop,
                                  platform=self._device.platform)
            # vft-lint: ok=stdout-purity — show_pred narration surface
            print(f'At stack {stack_counter} ({stream} stream)')
            show_predictions_on_dataset(np.asarray(logits), 'kinetics')
        if 'flow' in self.streams:
            # headless counterpart of the reference's cv2 flow window:
            # write the Middlebury-rendered first flow frame as a PNG
            try:
                import cv2

                from video_features_tpu.utils.flow_viz import flow_to_image
                flow = np.asarray(_debug_flow(
                    self.params['raft'], jnp.asarray(stacks),
                    pads=tuple(pads), crop_size=crop,
                    platform=self._device.platform))
                out_dir = Path(self.output_path) / 'flow_debug'
                out_dir.mkdir(parents=True, exist_ok=True)
                path = out_dir / f'stack_{stack_counter:06d}.png'
                cv2.imwrite(str(path), flow_to_image(flow)[..., ::-1])
            except Exception:  # debug surface: never fail extraction
                import logging as _logging

                from video_features_tpu.obs.events import event
                event(_logging.WARNING, 'flow viz PNG write skipped',
                      exc_info=True, subsystem='i3d')
