"""CLIP frame-wise extractor (reference models/clip/extract_clip.py).

Transform parity with the reference's `_transform` (reference
clip_src/clip.py: Resize(n_px, BICUBIC) → CenterCrop(n_px) → ToTensor →
Normalize(CLIP mean/std)): the resize+crop runs on the host (PIL bicubic),
scale+normalize are fused into the jitted encode_image step.

``show_pred`` is zero-shot classification: cosine-similarity logits against
Kinetics-400 ``"a photo of {label}"`` prompts or user ``pred_texts``
(reference extract_clip.py:32-40,86-108). Text features are encoded once
per run and cached.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional

import jax
import numpy as np

from video_features_tpu.extract.base import named_step
from video_features_tpu.extract.framewise import BaseFrameWiseExtractor
from video_features_tpu.models import clip as clip_model
from video_features_tpu.ops.transforms import (
    center_crop_host, normalize, resize_pil, to_float_zero_one,
)
from video_features_tpu.utils.device import jax_device


class ExtractCLIP(BaseFrameWiseExtractor):

    def __init__(self, args) -> None:
        self.model_name = args.model_name
        if (self.model_name != 'custom'
                and self.model_name not in clip_model.VISUAL_CFGS):
            raise NotImplementedError(
                f'model_name {self.model_name!r}; known: '
                f'{", ".join(clip_model.VISUAL_CFGS)} or "custom"')
        state_dict, params = self._load_state_dict(args)
        if self.model_name != 'custom':
            self.arch = self.model_name
        elif params is not None:  # pre-transplanted .npz: infer from pytree
            self.arch = clip_model.infer_model_name_from_params(params)
        else:
            self.arch = clip_model.infer_model_name(state_dict)
        cfg = clip_model.VISUAL_CFGS[self.arch]
        super().__init__(args, feat_dim=cfg['embed_dim'])
        self.input_resolution = cfg['input_resolution']
        self.pred_texts: Optional[List[str]] = (
            list(args.pred_texts) if args.get('pred_texts') else None)
        self._device = jax_device(self.device)
        if params is None:
            from video_features_tpu.transplant.torch2jax import transplant
            # param_dtype: float32 upcast of the fp16 OpenAI checkpoints
            # by default; the bf16 fast lane stores bf16 in HBM instead
            params = transplant(state_dict,
                                no_transpose=set(clip_model.NO_TRANSPOSE),
                                dtype=self.param_dtype)
        self.params = jax.device_put(params, self._device)
        self._step = jax.jit(named_step(
            partial(self._forward, arch=self.arch,
                    dtype=self.compute_jnp_dtype), self.step_name))
        self._text_feats: Optional[np.ndarray] = None

    def _load_state_dict(self, args):
        """Checkpoint sources → (torch_state_dict, transplanted_params);
        exactly one is non-None. Sources: explicit path (a torch .pt/.pth,
        or a pre-transplanted .npz for torch-free hosts — see
        docs/checkpoints.md), or 'custom' → CLIP-custom.pth (reference
        extract_clip.py:55-61). OpenAI URL download needs network — a local
        path must be provided in this environment."""
        ckpt = args.get('checkpoint_path')
        if self.model_name == 'custom' and not ckpt:
            ckpt = './checkpoints/CLIP-custom.pth'
        if not ckpt:
            # hard error unless random weights are explicitly allowed —
            # the reference always downloads real CLIP weights
            # (clip_src/clip.py:32-74)
            from video_features_tpu.extract.weights import require_checkpoint
            require_checkpoint(args, 'checkpoint_path', feature_type='clip',
                               what=f'clip ({self.model_name})')
        if ckpt and str(ckpt).endswith('.npz'):
            # via load_torch_checkpoint for the same float32 upcast the
            # .pt path (and every other extractor) applies — or the bf16
            # storage cast / int8 weight quantization when a fast lane is
            # on. args because this runs before super().__init__ sets
            # self.compute_dtype.
            from video_features_tpu.ops.precision import param_np_dtype
            from video_features_tpu.transplant.torch2jax import (
                load_torch_checkpoint,
            )
            return None, load_torch_checkpoint(
                ckpt, dtype=param_np_dtype(
                    args.get('compute_dtype', 'float32')))
        if ckpt:
            import torch
            sd = torch.load(ckpt, map_location='cpu', weights_only=False)
            if hasattr(sd, 'state_dict'):  # jit-archived OpenAI models
                sd = sd.state_dict()
            if isinstance(sd, dict) and 'state_dict' in sd:
                sd = sd['state_dict']
            return sd, None
        return clip_model.init_state_dict(model_name=args.model_name), None

    @staticmethod
    def _forward(params, batch, arch, dtype=None):
        from video_features_tpu.ops.precision import features_to_f32
        from video_features_tpu.ops.quant import dequantize_tree
        # int8 lane: expand QuantizedTensor weights in-graph; structural
        # identity (same StableHLO) on the fp32/bf16 lanes' plain trees
        params = dequantize_tree(params, dtype)
        x = to_float_zero_one(batch, dtype)
        x = normalize(x, clip_model.MEAN, clip_model.STD)
        return features_to_f32(clip_model.encode_image(params, x, arch))

    def host_transform(self, frame: np.ndarray) -> np.ndarray:
        n_px = self.input_resolution
        frame = resize_pil(frame, n_px, interpolation='bicubic')
        return center_crop_host(frame, n_px)

    def host_transform_spec(self):
        n_px = self.input_resolution
        return ('edge_resize_crop', n_px, n_px, 'bicubic')

    def device_step(self, batch: np.ndarray) -> jax.Array:
        # aot_call: resident/store-loaded executable when the aot store
        # is on (byte-identical), else exactly the jit call
        return self.aot_call('step', self._step, self.params, batch)

    # -- zero-shot show_pred -------------------------------------------------

    def _get_text_feats(self):
        if getattr(self, '_text_feats_resolved', False):
            return self._text_feats, self._classes
        self._text_feats_resolved = True
        from video_features_tpu.utils.clip_tokenizer import tokenize
        from video_features_tpu.utils.preds import load_label_map
        if self.pred_texts is not None:
            self._classes = self.pred_texts
        else:
            labels = load_label_map('kinetics')
            if labels is None:
                # vft-lint: ok=stdout-purity — show_pred narration surface
                print('show_pred: no Kinetics label map available — skipping')
                self._classes = None
                return None, None
            self._classes = [f'a photo of {label}' for label in labels]
        tokens = tokenize(self._classes)
        # one-shot narration path: dequantize eagerly for the int8 lane
        # (identity otherwise) — the text tower reads raw weight arrays
        from video_features_tpu.ops.quant import dequantize_tree
        feats = jax.jit(partial(clip_model.encode_text, model_name=self.arch))(
            dequantize_tree(self.params), tokens)
        self._text_feats = feats
        return self._text_feats, self._classes

    def maybe_show_pred(self, feats: np.ndarray) -> None:
        from video_features_tpu.utils.preds import show_predictions_on_dataset
        try:
            text_feats, classes = self._get_text_feats()
        except FileNotFoundError as e:
            # vft-lint: ok=stdout-purity — show_pred narration surface
            print(f'show_pred unavailable: {e}')
            return
        if text_feats is None:
            return
        logits = clip_model.zero_shot_logits(
            self.params, jax.numpy.asarray(feats), text_feats)
        show_predictions_on_dataset(np.asarray(logits), classes)
