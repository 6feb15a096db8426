"""Token-trunk extractor: a language trunk run over ids cut from decoded
frames (``feature_type=lm``).

The ninth family, and the first whose device step takes integers: a window
is ``stack_size`` consecutive decoded frames (step ``step_size``, a partial
tail dropped as the stack families drop it), the host preprocess is a
tokeniser, and the step runs a trunk over
``(batch, stack_size · patch_grid²) int32`` → one ``(hidden_size,)`` row a
window. Everything around the step is the stack families' path: decode
lanes → windows → host preprocess → pack → H2D → step → D2H → scatter →
save (``StackPackingMixin``, ``parallel/packing.py::run_packed``).

**The trunk is chosen by ``model_type``**, the published ``config.json``'s
key (``TRUNKS``): ``joyai_llm_flash`` — latent attention + sparse experts,
``models/latent_moe.py``, what ``configs/lm.yml`` ships —, ``brumby`` —
gated power retention, dense, ``models/retention_trunk.py`` —,
``lfm2_moe`` — gated short convolutions among grouped-query attention
layers, sparse experts, ``models/hybrid_trunk.py`` — or ``afmoe`` — sliding-
window and full grouped-query attention layers mixed, gated, over sparse
experts with a shared one, the same module's second dialect —, or
``dots3_note`` — latent attention under a learned selection of keys and
under a window, gated, over sparse experts with a shared one,
``models/latent_moe.py``'s second dialect —, or ``granitemoehybrid`` —
Mamba-2 state-space mixers among grouped-query attention layers with no
positional code, dense, ``models/hybrid_trunk.py``'s third dialect. Every
one runs the one decoder of
``models/token_trunk.py``, under its dialect's row; a trunk module says what
this file needs of it (``models/token_trunk.py`` lists the names): its
config from the args, its parameters, its step's second output, what it
notes in the manifest and which counters it fills. An unknown
``model_type`` is refused by name.

**The ids are traffic, not model.** No tokeniser ships with a trunk's
``config.json``, so the ids are cut from the pixels by a fixed rule: of each
RGB frame the centred region of ``g·(H div g)`` rows × ``g·(W div g)``
columns is cut into a ``g × g`` grid of patches (``g = patch_grid``), and a
patch's id is ``((sum of its bytes) · 2654435761 mod 2³²) mod vocab_size``,
patches row-major, frames in order — integer arithmetic only, so any
decoder that is bit-exact gives the same ids.

**What fits.** The build refuses, with the sizes, a trunk whose parameters
exceed the device's memory, and says how that trunk is held in part: fewer
layers (further pipeline stages) for all, and for the expert trunks a share
of each layer's experts (the shipped yml is the whole published model, 48 B
parameters).

Telemetry: the ``tokenise`` span; the ``kernels`` note of the run manifest
(which causal attention path, or which form of the retention mixer and its
chunk, the step compiled); and the trunk's counters on the stage table,
filled from the step's second output at each readback — ``moe_route`` /
``moe_held`` / ``moe_walk`` (the expert trunks), ``retention_scan`` /
``retention_kernel`` (retention trunk), ``ssd_scan`` / ``ssd_kernel``
(granitemoehybrid's Mamba-2 mixers), ``index_kernel`` / ``topk_ties``
(dots3_note's lightning indexer).
"""
from __future__ import annotations

import importlib
from functools import partial
from typing import Dict

import jax
import numpy as np

from video_features_tpu.extract.base import (
    BaseExtractor, StackPackingMixin, named_step,
)
from video_features_tpu.models import token_trunk
from video_features_tpu.utils.device import jax_device

TOKEN_HASH = 2654435761
# published model_type → the module of its trunk (models/token_trunk.py
# lists what such a module offers)
TRUNKS = {
    'joyai_llm_flash': 'video_features_tpu.models.latent_moe',
    'brumby': 'video_features_tpu.models.retention_trunk',
    'lfm2_moe': 'video_features_tpu.models.hybrid_trunk',
    'afmoe': 'video_features_tpu.models.hybrid_trunk',
    'dots3_note': 'video_features_tpu.models.latent_moe',
    'granitemoehybrid': 'video_features_tpu.models.hybrid_trunk',
}


def load_trunk(model_type: str):
    if model_type not in TRUNKS:
        raise ValueError(f'feature_type=lm has no trunk for model_type='
                         f'{model_type!r}; known: {", ".join(sorted(TRUNKS))}')
    return importlib.import_module(TRUNKS[model_type])


def step_counter(cfg):
    """(name, count) of the step's second output: the dialect's own where
    it has one, else its trunk module's ``COUNTER`` and ``count``."""
    trunk = load_trunk(cfg.model_type)
    return cfg.dialect.counter or (trunk.COUNTER, trunk.count)


def tokenise_frames(frames: np.ndarray, grid: int,
                    vocab_size: int) -> np.ndarray:
    """(n, H, W, 3) uint8 frames → (n · grid²,) int32 ids (module doc)."""
    n, h, w, c = frames.shape
    ph, pw = h // grid, w // grid
    if not ph or not pw:
        raise ValueError(f'a {w}x{h} frame is smaller than the '
                         f'{grid}x{grid} patch grid')
    top, left = (h - grid * ph) // 2, (w - grid * pw) // 2
    region = frames[:, top:top + grid * ph, left:left + grid * pw]
    sums = region.reshape(n, grid, ph, grid, pw, c).sum(
        axis=(2, 4, 5), dtype=np.uint64)
    ids = (sums * np.uint64(TOKEN_HASH) % np.uint64(1 << 32)
           % np.uint64(vocab_size))
    return ids.reshape(-1).astype(np.int32)


def check_params_fit(need_bytes: int, limit_bytes, what: str,
                     advice: str = '') -> None:
    """Refuse at build what would fail in the first step: parameters larger
    than the device's memory (``limit_bytes`` None or 0: not known, e.g. the
    CPU backend — nothing to check). ``advice`` says how the trunk named by
    ``what`` is held in part."""
    if limit_bytes and need_bytes > limit_bytes:
        raise ValueError(
            f'{what}: {need_bytes / 1e9:.2f} GB of float32 parameters do '
            f'not fit the device\'s {limit_bytes / 1e9:.2f} GB. '
            f'{advice}'.rstrip())


class ExtractLM(StackPackingMixin, BaseExtractor):

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
        self.stack_size = int(args.stack_size)
        self.step_size = int(args.step_size)
        self.patch_grid = int(args.patch_grid)
        self.extraction_fps = args.extraction_fps
        self.output_feat_keys = [self.feature_type]
        self.stack_batch = int(args.get('batch_size') or 1)
        self.decode_backend = args.get('decode_backend', 'auto')
        self.data_parallel = False       # not in DATA_PARALLEL_FEATURES
        self.trunk = load_trunk(args.get('model_type'))
        self.cfg = self.trunk.TrunkConfig.from_args(args)
        self.window_ids = self.stack_size * self.patch_grid ** 2
        self.packed_feat_dim = self.cfg.hidden_size
        self._device = jax_device(self.device)
        check_params_fit(
            self.trunk.param_count(self.cfg) * 4,
            (self._device.memory_stats() or {}).get('bytes_limit'),
            f'feature_type=lm model_type={self.cfg.model_type} with '
            f'{self.trunk.describe(self.cfg)}', self.trunk.SHARE_ADVICE)
        self.params = self.load_params(args)
        self._step = jax.jit(named_step(
            partial(self._forward, cfg=self.cfg,
                    platform=self._device.platform), self.step_name))
        self.kernel_notes = self._say_kernels()

    def load_params(self, args):
        """The flat ``{checkpoint name: device array}`` dict: read from the
        ``.npz`` one array at a time straight onto the device (a share of
        several GB must not stand on the host twice), or seeded random."""
        from video_features_tpu.extract.weights import (
            load_npz_to_device, require_checkpoint,
        )
        shapes = self.trunk.param_shapes(self.cfg)
        ckpt = require_checkpoint(args, 'checkpoint_path', feature_type='lm')
        if ckpt:
            return load_npz_to_device(ckpt, shapes, self._device)
        return jax.device_put(self.trunk.init_params(self.cfg), self._device)

    def _say_kernels(self) -> Dict[str, object]:
        """Which path the step compiles here (``trunk.kernels``: from the
        device's platform, the window's shapes and this run's matmul
        precision) — said once on stderr and kept for the run manifest's
        ``kernels`` section."""
        import logging

        from video_features_tpu.obs.events import event
        with self.precision_scope():
            notes = self.trunk.kernels(
                self.cfg, self._device.platform, self.window_ids,
                jax.config.jax_default_matmul_precision)
        event(logging.INFO, 'lm: the step\'s paths', subsystem='lm',
              model_type=self.cfg.model_type, **notes,
              platform=self._device.platform, precision=self.precision)
        return notes

    def configure_obs(self, args) -> None:
        super().configure_obs(args)
        if self.manifest is not None:
            self.manifest.note_kernels(self.kernel_notes)

    @staticmethod
    def _forward(params, ids, cfg, platform=None):
        feats, counter = token_trunk.forward(params, ids, cfg,
                                             platform=platform)
        return {'lm': feats, step_counter(cfg)[0]: counter}

    # -- the host preprocess: frames → ids ----------------------------------

    def _tokenise(self, window: np.ndarray) -> np.ndarray:
        with self.tracer.stage('tokenise'):
            return tokenise_frames(window, self.patch_grid,
                                   self.cfg.vocab_size)

    def packed_windows(self, task):
        for window, meta in super().packed_windows(task):
            yield self._tokenise(window), meta

    def live_window_spec(self):
        return None      # raw network frames would skip the tokeniser

    def farm_recipe(self):
        return None      # the tokeniser is no named transform spec

    # -- the device step ------------------------------------------------------

    def program_specs(self, mesh=None):
        from video_features_tpu.analysis.programs import ProgramSpec
        batch = self._abstract_batch(
            (self._program_batch_slots(mesh), self.window_ids), np.int32,
            mesh)
        return [ProgramSpec('step', self._step,
                            (self._abstract_params(mesh), batch))]

    def packed_step(self, ids):
        return self.aot_call('step', self._step, self.params, ids)

    def fetch_outputs(self, out):
        # the step's second output is the trunk's: taken off here, turned
        # into its stage-table counters, never scattered to a video
        out = dict(super().fetch_outputs(out))
        name, count = step_counter(self.cfg)
        counter = out.pop(name, None)
        if counter is not None and self.tracer.enabled:
            count(self.tracer, counter, self.cfg,
                  self.stack_batch * self.window_ids)
        return out

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        from video_features_tpu.extract.streaming import (
            iter_batched_windows, overlap_fetch, stream_windows,
            transfer_batches,
        )
        loader = self._make_loader(video_path)
        ids = (self._tokenise(w) for w in stream_windows(
            loader, self.stack_size, self.step_size, self.tracer, 'decode'))

        def dispatched():
            for batch, _, valid, _ in transfer_batches(
                    iter_batched_windows(ids, self.stack_batch, self.tracer),
                    self.put_input, tracer=self.tracer):
                with self.tracer.stage(
                        'model', **self.step_attrs(valid, self.stack_batch)):
                    dev = self.packed_step(batch)
                self.tracer.add_occupancy('model', valid, self.stack_batch)
                yield dev, valid

        feats = []
        with self.precision_scope():
            for out, valid in overlap_fetch(dispatched(), self.fetch_outputs,
                                            self.inflight, self.tracer,
                                            self.last_step):
                feats.append(out[self.feature_type][:valid])
        feats = (np.concatenate(feats, axis=0) if feats
                 else np.zeros((0, self.packed_feat_dim), np.float32))
        return {self.feature_type: feats}
