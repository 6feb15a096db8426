"""Vision Transformer image backbones (timm `vit_*` state_dict layout).

The reference's timm extractor accepts any pip-timm model
(reference models/timm/extract_timm.py:48 `timm.create_model`). timm is an
optional dependency here; this module natively implements the ViT family —
the workhorse of that model space — against the exact timm
``VisionTransformer`` state_dict naming (``cls_token``, ``pos_embed``,
``patch_embed.proj``, ``blocks.N.{norm1,attn.qkv,attn.proj,norm2,mlp}``,
``norm``) so real timm checkpoints transplant mechanically, and parity can
be tested against a torch mirror without timm installed.

Feature semantics match `reset_classifier(0)` + `forward(x)`
(reference models/timm/extract_timm.py:59-60): class-token pooling after the
final norm, no head.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]

# timm default_cfg constants for the supported family: inputs are 224px,
# bicubic, crop_pct 0.9 → resize short side 248; "inception" 0.5 mean/std.
MEAN = (0.5, 0.5, 0.5)
STD = (0.5, 0.5, 0.5)

ARCHS = {
    'vit_tiny_patch16_224': dict(width=192, layers=12, heads=3, patch=16),
    'vit_small_patch16_224': dict(width=384, layers=12, heads=6, patch=16),
    'vit_small_patch32_224': dict(width=384, layers=12, heads=6, patch=32),
    'vit_base_patch16_224': dict(width=768, layers=12, heads=12, patch=16),
    'vit_base_patch32_224': dict(width=768, layers=12, heads=12, patch=32),
    'vit_large_patch16_224': dict(width=1024, layers=24, heads=16, patch=16),
}
INPUT_RESOLUTION = 224


def layer_norm(x: jax.Array, p: Params, eps: float = 1e-6) -> jax.Array:
    if x.dtype == jnp.bfloat16:
        # fp32 accumulation island (bf16 fast lane, ops/nn.py contract):
        # LayerNorm statistics in fp32, result cast back
        return layer_norm(x.astype(jnp.float32), p, eps).astype(x.dtype)
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p['weight'] + p['bias']


# Above this token count, attention switches to the blockwise online-softmax
# path (O(N·block) score memory instead of O(N²)) — irrelevant for 224px
# frames (~197 tokens) but load-bearing when a long video's temporal tokens
# are attended as one sequence.
BLOCKWISE_THRESHOLD = 2048
_BLOCK = 512


def _attention(p: Params, x: jax.Array, num_heads: int,
               attn_impl=None) -> jax.Array:
    """timm `Attention`: fused qkv linear, per-head scaled dot product.

    ``attn_impl`` overrides the core attention op (``(q, k, v) → out`` on
    (B, N, H, hd) tensors) — the sequence-parallel path injects a ring
    kernel here; default picks dense or blockwise by token count.
    """
    from video_features_tpu.ops.attention import (
        blockwise_attention, dense_attention,
    )
    B, N, D = x.shape
    head_dim = D // num_heads
    qkv = x @ p['qkv']['weight'] + p['qkv']['bias']          # (B, N, 3D)
    qkv = qkv.reshape(B, N, 3, num_heads, head_dim)
    q, k, v = jnp.moveaxis(qkv, 2, 0)                        # (B, N, H, hd)
    if attn_impl is not None:
        out = attn_impl(q, k, v)
    elif N >= BLOCKWISE_THRESHOLD:
        out = blockwise_attention(q, k, v, block_size=_BLOCK)
    else:
        out = dense_attention(q, k, v)
    out = out.reshape(B, N, D)
    return out @ p['proj']['weight'] + p['proj']['bias']


def _block(p: Params, x: jax.Array, num_heads: int,
           attn_impl=None) -> jax.Array:
    """Pre-norm transformer block with exact-erf GELU (torch nn.GELU)."""
    x = x + _attention(p['attn'], layer_norm(x, p['norm1']), num_heads,
                       attn_impl)
    h = layer_norm(x, p['norm2'])
    h = h @ p['mlp']['fc1']['weight'] + p['mlp']['fc1']['bias']
    h = jax.nn.gelu(h, approximate=False)
    h = h @ p['mlp']['fc2']['weight'] + p['mlp']['fc2']['bias']
    return x + h


def interpolate_pos_embed(pos_embed: jax.Array,
                          grid: "tuple[int, int]",
                          n_prefix: int = 1) -> jax.Array:
    """Resample a (1, n_prefix+g², D) pos embed to a new (gh, gw) grid.

    The standard timm recipe for non-native input resolutions
    (`resample_abs_pos_embed`): keep the ``n_prefix`` prefix positions
    (cls, plus dist for distilled DeiT), bicubically resize the 2-D grid
    positions. Lets 224-trained checkpoints run at higher resolutions
    (more tokens — the blockwise-attention regime).
    """
    n = pos_embed.shape[1] - n_prefix
    side = int(round(n ** 0.5))
    if (side, side) == grid:
        return pos_embed
    cls_pos = pos_embed[:, :n_prefix]
    grid_pos = pos_embed[:, n_prefix:]
    d = pos_embed.shape[-1]
    grid_pos = grid_pos.reshape(1, side, side, d)
    grid_pos = jax.image.resize(grid_pos, (1, grid[0], grid[1], d),
                                method='bicubic')
    return jnp.concatenate(
        [cls_pos, grid_pos.reshape(1, grid[0] * grid[1], d)], axis=1)


def embed(params: Params, x: jax.Array,
          arch: str = 'vit_base_patch16_224') -> jax.Array:
    """(B, H, W, 3) → (B, 1+grid², width) embedded tokens (patch conv +
    cls + resampled pos embed)."""
    cfg = ARCHS[arch]
    width, patch = cfg['width'], cfg['patch']
    B = x.shape[0]
    # patch embed: conv stride=patch, then row-major flatten (timm flattens
    # NCHW as (B, D, H', W') → (B, H'·W', D); NHWC flatten matches directly)
    k = params['patch_embed']['proj']
    x = jax.lax.conv_general_dilated(
        x, k['weight'], window_strides=(patch, patch), padding='VALID',
        dimension_numbers=('NHWC', 'HWIO', 'NHWC')) + k['bias']
    grid = (x.shape[1], x.shape[2])
    x = x.reshape(B, -1, width)
    prefix = [jnp.broadcast_to(params['cls_token'], (B, 1, width))]
    if 'dist_token' in params:      # distilled DeiT (timm deit.py)
        prefix.append(jnp.broadcast_to(params['dist_token'], (B, 1, width)))
    return jnp.concatenate(prefix + [x], axis=1) + interpolate_pos_embed(
        params['pos_embed'], grid, n_prefix=len(prefix))


def trunk(params: Params, tokens: jax.Array, arch: str,
          attn_impl=None) -> jax.Array:
    """All transformer blocks over (B, N, width) tokens (no final norm).

    Every op except attention is token-local, so under ``shard_map`` with
    the token axis sharded this runs unmodified — only ``attn_impl`` needs
    to be a sequence-parallel kernel (see forward_sequence_parallel).
    """
    cfg = ARCHS[arch]
    for i in range(cfg['layers']):
        tokens = _block(params['blocks'][str(i)], tokens, cfg['heads'],
                        attn_impl)
    return tokens


def forward(params: Params, x: jax.Array, arch: str = 'vit_base_patch16_224',
            features: bool = True) -> jax.Array:
    """(B, H, W, 3) float in model space → (B, width) cls-token features.

    With ``features=False`` and a transplanted ``head``, returns (B, 1000)
    logits (the reference's show_pred path, extract_timm.py:63-91).
    Inputs need not be the checkpoint's native 224px — the pos embed is
    bicubically resampled to the actual patch grid (timm's high-res recipe),
    and past BLOCKWISE_THRESHOLD tokens attention switches to the
    O(N·block) blockwise path.
    """
    x = trunk(params, embed(params, x, arch), arch)
    x = layer_norm(x, params['norm'])
    if 'dist_token' in params:
        # distilled DeiT inference (timm deit.py VisionTransformerDistilled):
        # features = mean of cls and dist tokens; logits = mean of the two
        # heads' outputs
        if features:
            return (x[:, 0] + x[:, 1]) / 2
        cls_logits = x[:, 0] @ params['head']['weight'] + params['head']['bias']
        dist_logits = (x[:, 1] @ params['head_dist']['weight']
                       + params['head_dist']['bias'])
        return (cls_logits + dist_logits) / 2
    feats = x[:, 0]
    if features:
        return feats
    return feats @ params['head']['weight'] + params['head']['bias']


def forward_sequence_parallel(params: Params, x: jax.Array, mesh,
                              arch: str = 'vit_base_patch16_224',
                              axis: str = 'time',
                              features: bool = True) -> jax.Array:
    """ViT forward with the TOKEN axis sharded over a mesh axis.

    The sequence-parallel production path for inputs whose token count
    exceeds one chip's memory (very high resolution / long token videos):
    tokens are zero-padded to a multiple of the axis size with a validity
    mask, every token-local op (LN, MLP, patch projection output) runs
    unchanged inside ``shard_map``, and attention is
    :func:`ops.attention.ring_attention` — KV shards rotate over ICI
    neighbor hops while each device accumulates its queries' online
    softmax; padded keys are masked out of every softmax and the mask
    rotates with its shard.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from video_features_tpu.ops.attention import ring_attention

    tokens = embed(params, x, arch)
    B, N, width = tokens.shape
    n = mesh.shape[axis]
    pad = (-N) % n
    if pad:
        tokens = jnp.pad(tokens, [(0, 0), (0, pad), (0, 0)])
    valid = jnp.arange(N + pad) < N

    def shard_fn(p, tok, val):
        def attn(q, k, v):
            return ring_attention(q, k, v, axis_name=axis, kv_valid=val)
        return trunk(p, tok, arch, attn_impl=attn)

    out = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(None, axis, None), P(axis)),
        out_specs=P(None, axis, None),
    )(params, tokens, valid)
    x = layer_norm(out[:, :N], params['norm'])
    # same head dispatch as forward() — a distilled checkpoint must yield
    # identical features on the single-chip and sequence-parallel paths
    if 'dist_token' in params:
        if features:
            return (x[:, 0] + x[:, 1]) / 2
        cls_logits = x[:, 0] @ params['head']['weight'] + params['head']['bias']
        dist_logits = (x[:, 1] @ params['head_dist']['weight']
                       + params['head_dist']['bias'])
        return (cls_logits + dist_logits) / 2
    feats = x[:, 0]
    if features:
        return feats
    return feats @ params['head']['weight'] + params['head']['bias']


def init_state_dict(seed: int = 0, arch: str = 'vit_base_patch16_224',
                    num_classes: int = 1000,
                    distilled: bool = False) -> Dict[str, np.ndarray]:
    """Random torch-layout state_dict (keys/shapes as timm saves them);
    ``distilled`` adds DeiT's dist_token / head_dist / extra pos slot."""
    cfg = ARCHS[arch]
    width, patch, layers = cfg['width'], cfg['patch'], cfg['layers']
    n_tokens = (2 if distilled else 1) + (INPUT_RESOLUTION // patch) ** 2
    rng = np.random.RandomState(seed)

    def f32(*shape, scale=0.02):
        return (rng.randn(*shape) * scale).astype(np.float32)

    sd = {
        'cls_token': f32(1, 1, width),
        'pos_embed': f32(1, n_tokens, width),
        'patch_embed.proj.weight': f32(width, 3, patch, patch),
        'patch_embed.proj.bias': f32(width),
        'norm.weight': np.ones(width, np.float32),
        'norm.bias': np.zeros(width, np.float32),
        'head.weight': f32(num_classes, width),
        'head.bias': np.zeros(num_classes, np.float32),
    }
    if distilled:
        sd['dist_token'] = f32(1, 1, width)
        sd['head_dist.weight'] = f32(num_classes, width)
        sd['head_dist.bias'] = np.zeros(num_classes, np.float32)
    for i in range(layers):
        b = f'blocks.{i}.'
        sd[b + 'norm1.weight'] = np.ones(width, np.float32)
        sd[b + 'norm1.bias'] = np.zeros(width, np.float32)
        sd[b + 'attn.qkv.weight'] = f32(3 * width, width)
        sd[b + 'attn.qkv.bias'] = np.zeros(3 * width, np.float32)
        sd[b + 'attn.proj.weight'] = f32(width, width)
        sd[b + 'attn.proj.bias'] = np.zeros(width, np.float32)
        sd[b + 'norm2.weight'] = np.ones(width, np.float32)
        sd[b + 'norm2.bias'] = np.zeros(width, np.float32)
        sd[b + 'mlp.fc1.weight'] = f32(4 * width, width)
        sd[b + 'mlp.fc1.bias'] = np.zeros(4 * width, np.float32)
        sd[b + 'mlp.fc2.weight'] = f32(width, 4 * width)
        sd[b + 'mlp.fc2.bias'] = np.zeros(width, np.float32)
    return sd
