"""timm-style pluggable image-backbone extractor (reference models/timm/).

The reference creates any pip-timm model, resolves its data config, and
strips the classifier (reference models/timm/extract_timm.py:48-60). Here
the backbone registry is native-JAX — the ViT family (models/vit.py) and the
ResNet family (models/resnet.py) cover the curated model space — and a real
``timm`` install (optional) extends it: if timm is importable and
``pretrained=true``, the torch model's state_dict and resolved data config
are transplanted mechanically.

Output parity: {feature_type: (T, D), 'fps', 'timestamps_ms'} and
``show_pred`` top-5 against the ImageNet-1k label map when a classifier head
exists (reference extract_timm.py:63-91 infers the dataset from the hf tag;
our native registry is in1k-headed).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import numpy as np

from video_features_tpu.extract.base import named_step
from video_features_tpu.extract.framewise import BaseFrameWiseExtractor
from video_features_tpu.models import beit as beit_model
from video_features_tpu.models import convnext as convnext_model
from video_features_tpu.models import efficientnet as efficientnet_model
from video_features_tpu.models import mixer as mixer_model
from video_features_tpu.models import mobilenetv3 as mobilenetv3_model
from video_features_tpu.models import regnet as regnet_model
from video_features_tpu.models import resnet as resnet_model
from video_features_tpu.models import swin as swin_model
from video_features_tpu.models import vit as vit_model
from video_features_tpu.ops.transforms import (
    center_crop_host, normalize, resize_pil, to_float_zero_one,
)
from video_features_tpu.utils.device import jax_device


def _data_cfg(family: str, arch: str = '') -> Dict[str, Any]:
    """timm resolve_data_config equivalents for the native families:
    resize = floor(input_size / crop_pct), family-default interpolation."""
    if family == 'efficientnet':
        # per-arch input sizes (timm efficientnet default_cfgs)
        _, _, size, crop_pct = efficientnet_model.ARCHS[arch]
        return dict(resize=int(size / crop_pct), crop=size,
                    interpolation='bicubic',
                    mean=efficientnet_model.MEAN, std=efficientnet_model.STD)
    if family == 'vit':
        # timm vit: crop_pct 0.9, bicubic, 0.5 "inception" stats
        return dict(resize=248, crop=224, interpolation='bicubic',
                    mean=vit_model.MEAN, std=vit_model.STD)
    if family == 'beit':
        # timm beit: same recipe as vit (crop_pct 0.9, bicubic, 0.5 stats)
        return dict(resize=248, crop=224, interpolation='bicubic',
                    mean=beit_model.MEAN, std=beit_model.STD)
    if family == 'mixer':
        # timm mixer _cfg: crop_pct 0.875, bicubic, 0.5 stats
        return dict(resize=256, crop=224, interpolation='bicubic',
                    mean=mixer_model.MEAN, std=mixer_model.STD)
    if family == 'deit':
        # timm deit _cfg: crop_pct 0.9, bicubic, ImageNet stats
        return dict(resize=248, crop=224, interpolation='bicubic',
                    mean=convnext_model.MEAN, std=convnext_model.STD)
    if family == 'convnext':
        # timm convnext default_cfg: crop_pct 0.875, bicubic, ImageNet stats
        return dict(resize=256, crop=224, interpolation='bicubic',
                    mean=convnext_model.MEAN, std=convnext_model.STD)
    if family == 'swin':
        # timm swin default_cfg: crop_pct 0.9, bicubic, ImageNet stats
        return dict(resize=248, crop=224, interpolation='bicubic',
                    mean=swin_model.MEAN, std=swin_model.STD)
    if family == 'regnet':
        # timm regnet _cfg: crop_pct 0.875, bicubic, ImageNet stats
        return dict(resize=256, crop=224, interpolation='bicubic',
                    mean=regnet_model.MEAN, std=regnet_model.STD)
    # resnet and mobilenetv3 share the timm default recipe: crop_pct
    # 0.875, bilinear, ImageNet stats
    return dict(resize=256, crop=224, interpolation='bilinear',
                mean=resnet_model.MEAN, std=resnet_model.STD)


def _registry() -> Dict[str, Dict[str, Any]]:
    reg = {}
    for name, cfg in vit_model.ARCHS.items():
        reg[name] = dict(family='vit', arch=name, feat_dim=cfg['width'])
    # non-distilled DeiT IS timm's VisionTransformer (same module tree and
    # state_dict; only the data config differs) — alias onto the vit archs;
    # distilled variants add dist_token/head_dist (models/vit.py dispatches
    # on the checkpoint's dist_token, so the graph follows the weights)
    for deit, vit_arch in [
        ('deit_tiny_patch16_224', 'vit_tiny_patch16_224'),
        ('deit_small_patch16_224', 'vit_small_patch16_224'),
        ('deit_base_patch16_224', 'vit_base_patch16_224'),
    ]:
        reg[deit] = dict(family='deit', arch=vit_arch,
                         feat_dim=vit_model.ARCHS[vit_arch]['width'])
        dist = deit.replace('_patch', '_distilled_patch')
        reg[dist] = dict(family='deit', arch=vit_arch,
                         feat_dim=vit_model.ARCHS[vit_arch]['width'],
                         init=dict(distilled=True))
    for name, cfg in resnet_model.ARCHS.items():
        reg[name] = dict(family='resnet', arch=name, feat_dim=cfg['feat_dim'])
    for name, cfg in convnext_model.ARCHS.items():
        reg[name] = dict(family='convnext', arch=name,
                         feat_dim=cfg['dims'][-1])
    for name in swin_model.ARCHS:
        reg[name] = dict(family='swin', arch=name,
                         feat_dim=swin_model.feat_dim(name))
    for name in efficientnet_model.ARCHS:
        reg[name] = dict(family='efficientnet', arch=name,
                         feat_dim=efficientnet_model.feat_dim(name))
    for name in regnet_model.ARCHS:
        reg[name] = dict(family='regnet', arch=name,
                         feat_dim=regnet_model.feat_dim(name))
    for name in mobilenetv3_model.ARCHS:
        reg[name] = dict(family='mobilenetv3', arch=name,
                         feat_dim=mobilenetv3_model.feat_dim(name))
    for name in beit_model.ARCHS:
        reg[name] = dict(family='beit', arch=name,
                         feat_dim=beit_model.feat_dim(name))
    for name in mixer_model.ARCHS:
        reg[name] = dict(family='mixer', arch=name,
                         feat_dim=mixer_model.feat_dim(name))
    return reg


REGISTRY = _registry()

# family → native model module (deit shares the vit graph; only the data
# config differs — see _data_cfg)
_MODEL_MODULES = {'vit': vit_model, 'deit': vit_model,
                  'resnet': resnet_model, 'convnext': convnext_model,
                  'swin': swin_model, 'efficientnet': efficientnet_model,
                  'regnet': regnet_model, 'mobilenetv3': mobilenetv3_model,
                  'beit': beit_model, 'mixer': mixer_model}


class ExtractTIMM(BaseFrameWiseExtractor):

    def __init__(self, args) -> None:
        self.model_name = args.model_name
        # hf-hub ids (reference tests/timm/test_timm.py:24) resolve by tail:
        # 'hf_hub:timm/vit_base_patch16_224.augreg_in21k' → vit_base_patch16_224
        name = self.model_name.split(':')[-1].split('/')[-1].split('.')[0]
        if name not in REGISTRY:
            raise NotImplementedError(
                f'model_name {self.model_name!r} is not in the native '
                f'backbone registry: {", ".join(sorted(REGISTRY))}. '
                f'(With pip timm installed, timm checkpoints for these '
                f'architectures transplant via checkpoint_path.)')
        spec = REGISTRY[name]
        self.family, self.arch = spec['family'], spec['arch']
        if self.family in ('beit', 'mixer') and args.get('image_size'):
            # checked before any checkpoint loads: nothing loaded changes it
            raise NotImplementedError(
                f'image_size override is not supported for '
                f'{self.family}: its weights are tied to the checkpoint '
                f'resolution (224) — BEiT via the relative-position-bias '
                f'tables, Mixer via the token-mix MLP width. Use a '
                f'ViT/DeiT model for high-resolution inputs.')
        self._init_kwargs = spec.get('init', {})
        super().__init__(args, feat_dim=spec['feat_dim'])
        if args.get('sequence_parallel') and self.compute_dtype != 'float32':
            # refused BEFORE _load_params: every other compute_dtype
            # refusal fires pre-weights (config time), and this one must
            # not transplant a potentially-GBs checkpoint first
            raise NotImplementedError(
                'sequence_parallel + compute_dtype=bfloat16 is not '
                'supported: the ring-attention kernel\'s online-'
                'softmax accumulators are tuned fp32 end to end '
                '(ops/attention.py) and have no measured bf16 parity '
                'bound — run the fast lane on the standard path, or '
                'sequence-parallel at float32')
        self.data_cfg = _data_cfg(self.family, self.arch)
        self._device = jax_device(self.device)
        # _load_params may refine data_cfg from pip-timm's resolved config,
        # so the image_size override must come AFTER it
        self.params = jax.device_put(self._load_params(args), self._device)
        # image_size overrides the checkpoint's native resolution: the crop
        # becomes image_size and the resize scales to keep the family's
        # crop_pct. For ViT this resamples the pos embed to the larger patch
        # grid (models/vit.py:interpolate_pos_embed); past ~736px the token
        # count crosses BLOCKWISE_THRESHOLD and attention runs blockwise —
        # the high-resolution / long-token production path.
        image_size = args.get('image_size')
        if image_size:
            image_size = int(image_size)
            if self.family in ('vit', 'deit'):
                patch = vit_model.ARCHS[self.arch]['patch']
                if image_size % patch:
                    raise ValueError(
                        f'image_size={image_size} must be a multiple of the '
                        f'patch size ({patch}) for {self.arch}')
            factor = image_size / self.data_cfg['crop']
            self.data_cfg['resize'] = int(round(
                self.data_cfg['resize'] * factor))
            self.data_cfg['crop'] = image_size
        # sequence_parallel=true (ViT/DeiT only): the TOKEN axis of every
        # frame shards over ALL local devices and attention runs as a KV
        # ring over ICI (ops/attention.ring_attention) — the multi-chip
        # long-token path for resolutions whose token count exceeds one
        # chip (pairs with image_size; single-chip long-token inputs use
        # blockwise attention automatically).
        # (sequence_parallel + bfloat16 was already refused above,
        # before the checkpoint loaded)
        self.sequence_parallel = args.get('sequence_parallel', False)
        if self.sequence_parallel:
            if self.family not in ('vit', 'deit'):
                raise NotImplementedError(
                    'sequence_parallel is implemented for the ViT/DeiT '
                    f'families (attention over tokens); {self.family} has '
                    'no token axis to shard')
            if self.data_parallel:
                raise NotImplementedError(
                    'sequence_parallel claims every local device for the '
                    'token axis; combine with data parallelism across '
                    'hosts (multihost=true), not data_parallel=true')
            from video_features_tpu.parallel import (
                make_mesh, put_batch, put_replicated,
            )
            from video_features_tpu.utils.device import jax_devices_all
            devices = jax_devices_all(self.device)
            self._mesh = make_mesh(devices=devices,
                                   time_parallel=len(devices))
            # data axis is 1: put_input replicates each frame batch
            self._put_batch = partial(put_batch, self._mesh)
            mesh, arch = self._mesh, self.arch
            mean, std = self.data_cfg['mean'], self.data_cfg['std']

            def _sp_forward(params, batch):
                x = to_float_zero_one(batch)
                x = normalize(x, mean, std)
                return vit_model.forward_sequence_parallel(
                    params, x, mesh, arch=arch)

            self.params = put_replicated(mesh, self.params)
            self._step = jax.jit(named_step(_sp_forward, self.step_name))
            return
        self._step = jax.jit(named_step(partial(
            self._forward, family=self.family, arch=self.arch,
            mean=self.data_cfg['mean'], std=self.data_cfg['std'],
            dtype=self.compute_jnp_dtype), self.step_name))

    def _load_params(self, args):
        from video_features_tpu.transplant.torch2jax import (
            load_torch_checkpoint, transplant,
        )
        ckpt = args.get('checkpoint_path')
        if ckpt:
            return load_torch_checkpoint(ckpt, dtype=self.param_dtype)
        if args.get('pretrained', True):  # opt-out for offline runs
            try:  # optional pip timm: pull pretrained weights + data config
                import timm
            except ImportError:
                timm = None
        else:
            timm = None
        if timm is not None:
            # failures past the import (missing checkpoint dep, bad hf id)
            # must propagate — silently falling back to random weights would
            # masquerade as a successful pretrained load
            model = timm.create_model(self.model_name, pretrained=True)
            data = timm.data.resolve_data_config({}, model=model)
            self.data_cfg.update(
                resize=data['input_size'][-1] if data.get('crop_pct') is None
                else int(data['input_size'][-1] / data['crop_pct']),
                crop=data['input_size'][-1],
                interpolation=data.get('interpolation', 'bilinear'),
                mean=tuple(data['mean']), std=tuple(data['std']))
            return transplant(model.state_dict(), dtype=self.param_dtype)
        # no checkpoint and no pip-timm: hard error unless random weights
        # are explicitly allowed (the reference's timm path always loads
        # pretrained weights, extract_timm.py:48)
        from video_features_tpu.extract.weights import require_checkpoint
        require_checkpoint(args, 'checkpoint_path', feature_type='timm',
                           what=f'timm ({self.model_name})')
        init = _MODEL_MODULES[self.family]
        return transplant(init.init_state_dict(arch=self.arch,
                                               **self._init_kwargs),
                          dtype=self.param_dtype)

    @staticmethod
    def _forward(params, batch, family, arch, mean, std, dtype=None):
        from video_features_tpu.ops.precision import features_to_f32
        from video_features_tpu.ops.quant import dequantize_tree
        # int8 lane: expand QuantizedTensor weights in-graph; structural
        # identity (same StableHLO) on the fp32/bf16 lanes' plain trees
        params = dequantize_tree(params, dtype)
        x = to_float_zero_one(batch, dtype)
        x = normalize(x, mean, std)
        return features_to_f32(
            _MODEL_MODULES[family].forward(params, x, arch=arch,
                                           features=True))

    def host_transform(self, frame: np.ndarray) -> np.ndarray:
        frame = resize_pil(frame, self.data_cfg['resize'],
                           interpolation=self.data_cfg['interpolation'])
        return center_crop_host(frame, self.data_cfg['crop'])

    def host_transform_spec(self):
        return ('edge_resize_crop', self.data_cfg['resize'],
                self.data_cfg['crop'], self.data_cfg['interpolation'])

    def device_step(self, batch: np.ndarray) -> jax.Array:
        # aot_call: resident/store-loaded executable when the aot store
        # is on (byte-identical), else exactly the jit call
        return self.aot_call('step', self._step, self.params, batch)

    def maybe_show_pred(self, feats: np.ndarray) -> None:
        if self.family in ('vit', 'deit', 'beit', 'mixer'):
            if 'dist_token' in self.params:
                # timm's distilled inference scores the cls and dist tokens
                # with SEPARATE heads ((head(cls)+head_dist(dist))/2); the
                # pooled features here can't reconstruct the two tokens, so
                # any logits printed from them would misrepresent the model
                # vft-lint: ok=stdout-purity — show_pred narration surface
                print('show_pred: distilled DeiT logits need the separate '
                      'cls/dist tokens (timm deit.py); skipping the top-5 '
                      'table for pooled features')
                return
            head = self.params.get('head')
        elif self.family in ('convnext', 'swin', 'regnet'):
            head = (self.params.get('head') or {}).get('fc')
        elif self.family in ('efficientnet', 'mobilenetv3'):
            head = self.params.get('classifier')
        else:
            head = self.params.get('fc')
        if not head:
            return
        import jax.numpy as jnp
        from video_features_tpu.ops.nn import linear
        from video_features_tpu.ops.quant import dequantize_tree
        from video_features_tpu.utils.preds import show_predictions_on_dataset
        logits = np.asarray(linear(jnp.asarray(feats),
                                   dequantize_tree(head)))
        show_predictions_on_dataset(logits, 'imagenet1k')
