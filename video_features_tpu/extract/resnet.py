"""ResNet frame-wise extractor (reference models/resnet/extract_resnet.py).

Transform parity with torchvision's IMAGENET1K_V1 preset (the reference takes
transforms straight from the weights object, extract_resnet.py:41-44):
short-side resize 256 (host, PIL bilinear/antialiased) → center crop 224 →
scale to [0,1] → normalize — the latter two fused into the jitted step.
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np

from video_features_tpu.extract.base import named_step
from video_features_tpu.extract.framewise import BaseFrameWiseExtractor
from video_features_tpu.models import resnet as resnet_model
from video_features_tpu.ops.transforms import (
    center_crop_host, normalize, short_side_resize_pil, to_float_zero_one,
)
from video_features_tpu.utils.device import jax_device

RESIZE_SIZE = 256
CROP_SIZE = 224
# Per-arch IMAGENET1K_V1 preset deviations (the reference takes transforms
# straight from the torchvision weights object, extract_resnet.py:41-44;
# resnext101_64x4d's V1 recipe is resize_size=232 — every other family
# member's is 256)
RESIZE_OVERRIDES = {'resnext101_64x4d': 232}


class ExtractResNet(BaseFrameWiseExtractor):

    def __init__(self, args) -> None:
        self.model_name = args.model_name
        cfg = resnet_model.ARCHS[self.model_name]
        super().__init__(args, feat_dim=cfg['feat_dim'])
        self._device = jax_device(self.device)
        self.params = jax.device_put(self.load_params(args), self._device)
        # dtype rides the partial as a trace-time constant: the float32
        # lane's jitted program is byte-identical to the pre-knob graph
        self._step = jax.jit(named_step(
            partial(self._forward, arch=self.model_name,
                    dtype=self.compute_jnp_dtype), self.step_name))

    def load_params(self, args):
        from video_features_tpu.extract.weights import load_or_init
        return load_or_init(
            args, 'checkpoint_path',
            partial(resnet_model.init_state_dict, arch=self.model_name),
            feature_type='resnet', what=f'resnet ({self.model_name})',
            dtype=self.param_dtype)

    @staticmethod
    def _forward(params, batch, arch, dtype=None):
        from video_features_tpu.ops.precision import features_to_f32
        from video_features_tpu.ops.quant import dequantize_tree
        # int8 lane: expand QuantizedTensor weights in-graph (one
        # convert+multiply each); structural identity — zero ops, same
        # StableHLO — on the fp32/bf16 lanes' plain trees
        params = dequantize_tree(params, dtype)
        x = to_float_zero_one(batch, dtype)
        x = normalize(x, resnet_model.MEAN, resnet_model.STD)
        return features_to_f32(
            resnet_model.forward(params, x, arch=arch, features=True))

    def host_transform(self, frame: np.ndarray) -> np.ndarray:
        frame = short_side_resize_pil(
            frame, RESIZE_OVERRIDES.get(self.model_name, RESIZE_SIZE))
        return center_crop_host(frame, CROP_SIZE)

    def host_transform_spec(self):
        return ('edge_resize_crop',
                RESIZE_OVERRIDES.get(self.model_name, RESIZE_SIZE),
                CROP_SIZE, 'bilinear')

    def device_step(self, batch: np.ndarray) -> jax.Array:
        # aot_call: resident/store-loaded executable when the aot store
        # is on (byte-identical), else exactly the jit call
        return self.aot_call('step', self._step, self.params, batch)

    def maybe_show_pred(self, feats: np.ndarray) -> None:
        from video_features_tpu.ops.nn import linear
        from video_features_tpu.ops.quant import dequantize_tree
        from video_features_tpu.utils.preds import show_predictions_on_dataset
        import jax.numpy as jnp
        logits = np.asarray(linear(jnp.asarray(feats),
                                   dequantize_tree(self.params['fc'])))
        show_predictions_on_dataset(logits, 'imagenet1k')
