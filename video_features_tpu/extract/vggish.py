"""VGGish audio extractor (reference models/vggish/extract_vggish.py).

Behavior parity: .mp4 input is demuxed mp4 → aac → wav with ffmpeg (tmp
files removed unless ``keep_tmp_files``); .wav input is used directly;
anything else raises. Output is {'vggish': (Ta, 128)}, Ta = duration/0.96
(reference extract_vggish.py:31-62, docs/models/vggish.md:9).

TPU-first: the log-mel DSP runs on the host (float64 numpy, microseconds),
and ALL 0.96 s examples go through the jitted VGG in fixed-size padded
batches so one executable serves any clip length.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

import jax
import numpy as np

from video_features_tpu.extract.base import BaseExtractor, named_step
from video_features_tpu.models import vggish as vggish_model
from video_features_tpu.ops.audio import waveform_to_examples
from video_features_tpu.utils.device import jax_device

BATCH = 32  # compiled example-batch size (a 30 s clip is ~31 examples)


class ExtractVGGish(BaseExtractor):

    # the PCA postprocess matrices are committed to the build device;
    # serve placement (place_on) must migrate them with the params or a
    # placed entry would feed the jitted postprocess operands committed
    # to two different chips
    _device_buffer_attrs = ('_pca_eig', '_pca_means')

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
            compute_dtype=args.get('compute_dtype', 'float32'),
        )
        if args.show_pred:
            raise NotImplementedError('vggish has no show_pred (reference '
                                      'extract_vggish.py:25-26)')
        self.output_feat_keys = [self.feature_type]
        # 0.96 s examples per device step; global batch under data_parallel
        self.example_batch = args.get('batch_size') or BATCH
        self.data_parallel = args.get('data_parallel', False)
        # mp4 audio backend: 'ffmpeg' = the reference's mp4→aac→wav
        # subprocess chain (exact parity, needs an ffmpeg binary); 'native'
        # = in-process libav demux+decode+resample straight to mono 16 kHz
        # float (no temp files, no binary); 'auto' = ffmpeg when present.
        self.audio_backend = args.get('audio_backend', 'auto')
        assert self.audio_backend in ('auto', 'ffmpeg', 'native'), \
            self.audio_backend
        # AudioSet-compatible PCA-whiten + uint8 quantization: off by default
        # (the reference's forward(post_process=False) bypasses its vendored
        # Postprocessor, vggish_slim.py:150-156) but available for users who
        # need YouTube-8M/AudioSet-format embeddings. Validate before the
        # (expensive) checkpoint load so misconfiguration fails fast.
        self.post_process = args.get('post_process', False)
        pca_path = args.get('pca_params_path')
        if self.post_process and not pca_path:
            raise ValueError(
                'post_process=true needs pca_params_path=<vggish_pca_params.npz>')
        self._device = jax_device(self.device)
        self.params = jax.device_put(self.load_params(args), self._device)
        if self.compute_dtype == 'bfloat16':
            # bf16 fast lane: examples ship bf16 (half the H2D bytes —
            # _run_batched casts at the device edge), the VGG runs bf16,
            # features leave as float32 like every lane's contract
            from video_features_tpu.ops.precision import features_to_f32

            def _bf16_forward(params, x):
                return features_to_f32(vggish_model.forward(params, x))

            self._step = jax.jit(named_step(_bf16_forward, self.step_name))
        else:
            self._step = jax.jit(named_step(vggish_model.forward,
                                            self.step_name))
        if self.post_process:
            pca = np.load(pca_path)
            self._pca_eig = jax.device_put(
                pca['pca_eigen_vectors'].astype(np.float32), self._device)
            self._pca_means = jax.device_put(
                pca['pca_means'].astype(np.float32).reshape(-1), self._device)

    def load_params(self, args):
        from video_features_tpu.extract.weights import load_or_init
        return load_or_init(args, 'checkpoint_path',
                            vggish_model.init_state_dict,
                            feature_type='vggish', dtype=self.param_dtype)

    def program_specs(self, mesh=None):
        """vft-programs abstract step spec: one fixed-size batch of
        0.96 s log-mel examples into the jitted VGG. The batch dtype is
        float32 BY CONTRACT — the host DSP runs float64 for reference
        parity and :meth:`extract` pins the narrowing cast at the device
        boundary (the no-f64 rule holds the program side of that line).
        Under the bf16 fast lane the batch ships bf16 (``_run_batched``
        narrows at the device edge — half the H2D bytes), which the lock
        variant's batch dtype records."""
        from video_features_tpu.analysis.programs import ProgramSpec
        if mesh is None:
            b = self.example_batch
        else:
            # vggish has no packed path: its real multi-device program
            # is in-graph data_parallel, whose global batch is
            # example_batch ROUNDED UP to the data axis (_ensure_mesh →
            # round_batch_to_data_axis) — not the packed families'
            # capacity × ndev plan. Pin the program production compiles.
            from video_features_tpu.parallel.mesh import (
                round_batch_to_data_axis,
            )
            b = round_batch_to_data_axis(self.example_batch, mesh)
        batch = self._abstract_batch((b, 96, 64, 1), self.param_dtype, mesh)
        return [ProgramSpec('step', self._step,
                            (self._abstract_params(mesh), batch))]

    def _read_audio(self, video_path: str):
        """(waveform, sr, tmp_files_to_clean) for any supported input."""
        from video_features_tpu.io.audio import extract_wav_from_mp4, read_wav
        from video_features_tpu.io.video import which_ffmpeg

        ext = Path(video_path).suffix
        if ext == '.wav':
            data, sr = read_wav(video_path)
            return data, sr, ()
        if ext != '.mp4':
            raise NotImplementedError(f'unsupported extension {ext}')

        backend = self.audio_backend
        if backend == 'auto':
            if which_ffmpeg():
                backend = 'ffmpeg'
            else:
                from video_features_tpu.io import native
                if not native.available():
                    raise RuntimeError(
                        'no mp4 audio backend available: install an ffmpeg '
                        'binary (audio_backend=ffmpeg) or a C++ toolchain + '
                        'libav dev packages for the in-process decoder '
                        '(audio_backend=native)')
                backend = 'native'
        if backend == 'native':
            from video_features_tpu.io.native import read_audio_native
            from video_features_tpu.ops.audio import SAMPLE_RATE
            data, sr = read_audio_native(video_path, SAMPLE_RATE)
            return data.astype(np.float64), sr, ()
        wav_path, aac_path = extract_wav_from_mp4(video_path, self.tmp_path)
        try:
            data, sr = read_wav(wav_path)
        except Exception:
            # the temp files are bound here, not yet at the caller: clean up
            # so a malformed wav can't leak them
            if not self.keep_tmp_files:
                for p in (wav_path, aac_path):
                    if p and os.path.exists(p):
                        os.remove(p)
            raise
        return data, sr, (wav_path, aac_path)

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        tmp_files = ()
        try:
            with self.tracer.stage('audio_dsp'):
                data, sr, tmp_files = self._read_audio(video_path)
                examples = waveform_to_examples(data, sr)  # (N, 96, 64)
            # The DSP above is float64 BY DESIGN (reference-parity host
            # math); the device program is float32 BY CONTRACT
            # (PROGRAMS.lock.json pins the batch dtype — the no-f64
            # rule). Narrow HERE, explicitly: jax used to apply the same
            # double→float cast silently at device_put (x64 disabled),
            # which is exactly the invisible promotion seam the rule
            # exists to keep pinned. Byte-identical to the implicit
            # path — tests/test_programs.py holds the parity.
            feats = self._run_batched(
                examples.astype(np.float32)[..., None])  # NHWC
            if self.post_process:
                feats = np.asarray(vggish_model.postprocess(
                    self._pca_eig, self._pca_means, feats)).astype(np.uint8)
        finally:
            if not self.keep_tmp_files:
                for p in tmp_files:
                    if p and os.path.exists(p):
                        os.remove(p)
        return {self.feature_type: feats}

    def _run_batched(self, examples: np.ndarray) -> np.ndarray:
        if self.data_parallel:
            self._ensure_mesh('example_batch')
        n = examples.shape[0]
        if n == 0:
            return np.zeros((0, vggish_model.FEAT_DIM), np.float32)
        if self.compute_dtype == 'bfloat16':
            # the device edge of the bf16 fast lane: examples narrow to
            # bf16 HERE (host-side, before device_put) so the H2D
            # transfer ships half the bytes — the step's graph then runs
            # bf16 end to end with the ops/nn.py fp32 islands
            examples = examples.astype(self.param_dtype)
        B = self.example_batch
        out = []
        with self.precision_scope():
            for start in range(0, n, B):
                chunk = examples[start:start + B]
                valid = chunk.shape[0]
                if valid < B:
                    pad = np.repeat(chunk[-1:], B - valid, axis=0)
                    chunk = np.concatenate([chunk, pad], axis=0)
                if self._mesh is not None:
                    chunk = self._put_batch(chunk)
                # aot_call: resident/store-loaded executable when the
                # aot store is on (byte-identical), else the jit call.
                # One 'model' span a chunk (dispatch AND readback: this
                # loop is synchronous), so each carries a step ordinal
                with self.tracer.stage('model',
                                       **self.step_attrs(valid, B)):
                    out.append(np.asarray(self.aot_call(
                        'step', self._step, self.params, chunk))[:valid])
        return np.concatenate(out, axis=0)
