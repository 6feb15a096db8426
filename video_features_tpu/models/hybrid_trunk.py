"""A hybrid token trunk: gated short convolutions among grouped-query
attention layers, over sparse experts (``model_type: lfm2_moe``).

The decoder trunk of Liquid AI's LFM2-8B-A1B as a feature extractor: token
ids in, one hidden-state row a window out. Its layers differ in kind, twice
over, and which kind a layer is is static — read from the published
``layer_types`` and ``num_dense_layers``:

    h = x + op_i(RMSNorm(x))          op_i by layer_types[i]
    x = h + ffn_i(RMSNorm(h))         ffn_i dense for i < num_dense_layers

* ``conv`` — the gated short convolution (``ops/short_conv.py``):
  ``[B ‖ C ‖ h] = x W_in``, a depthwise causal convolution of
  ``conv_L_cache`` taps over ``B ⊙ h``, gated by ``C``, then ``W_out``; no
  bias, no activation. It runs over the whole batch at once and never
  reads across a window's start.
* ``full_attention`` — grouped-query softmax attention: ``num_attention_heads``
  query heads reading ``num_key_value_heads`` key-value heads (query head j
  reads key-value head ``j div group``) of ``hidden_size /
  num_attention_heads`` dims, an RMSNorm with a gain of its own over each
  head of q and k before the half-split rotary code, causal, scale
  ``head_dim^-½``. On a TPU under ``precision=mixed`` / ``default`` through
  the fused kernel's grouped-query lane (``ops/pallas_attention.py``: one
  key-value head and its query heads a grid step), elsewhere through
  ``ops.attention.blockwise_attention(causal=True)``, the XLA tiles;
  ``ops.attention.resolve_causal`` decides from the shapes (``kernels``).
* the feed-forward — a dense SwiGLU of ``intermediate_size`` in the first
  ``num_dense_layers`` layers (in row blocks); after them ``num_experts``
  SwiGLU experts of ``moe_intermediate_size``, ``num_experts_per_tok`` a
  token, no shared expert: sigmoid scores, the largest of ``score +
  expert_bias`` chosen, the chosen raw scores over their sum + 1e-6, ×
  ``routed_scaling_factor`` (``ops/moe.py``, shared with
  ``models/latent_moe.py``).
* the share — ``n_experts_held`` experts from ``first_expert`` on are held
  here (all of them when None), as in ``models/latent_moe.py``.
* output — ``embedding_norm``, mean over the window's positions. The output
  head (tied to the embedding in the published model) is neither held nor
  run.

The equations are ``transformers``' ``models/lfm2/modeling_lfm2.py``
(``Lfm2ShortConv``, ``Lfm2Attention``, ``Lfm2DecoderLayer``) and, for the
expert layer, its ``lfm2_moe`` sibling's. Prefill only: a window starts
from nothing and keeps nothing.

Parameters are a flat ``{dotted name: array}`` dict under the checkpoint's
own names (``model.layers.3.conv.in_proj.weight``,
``model.layers.2.self_attn.q_layernorm.weight``,
``model.layers.5.feed_forward.experts.w1.weight`` …), matrices as (in, out);
a layer's held experts are stacked, (held, in, out); the convolution's
``conv.conv.weight`` is **(taps, hidden)** — the checkpoint's (hidden, 1,
taps) transposed, tap 0 the oldest position — so that a tap is one
lane-dense row.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from video_features_tpu.models import token_trunk
from video_features_tpu.models.token_trunk import (
    Params, embed, final_norm, mean_features, mlp_rows, rms_norm, swiglu,
)
from video_features_tpu.ops import moe
from video_features_tpu.ops.attention import (
    KERNEL_PASSES, blockwise_attention, resolve_causal, rotary_half,
)
from video_features_tpu.ops.short_conv import gated_short_conv

MODEL_TYPE = 'lfm2_moe'
# the step's second output: (expert layers, held) assignment counts of the
# batch
COUNTER = 'moe_counts'
SHARE_ADVICE = ('Run fewer layers here (num_hidden_layers and as many '
                'entries of layer_types: the rest are further pipeline '
                'stages) or hold a share of each layer\'s experts '
                '(n_experts_held, first_expert).')
LAYER_TYPES = ('conv', 'full_attention')
# LFM2 names a SwiGLU's matrices w1 (gate), w3 (up), w2 (down)
FFN_NAMES = ('w1', 'w3', 'w2')
# the routing weights' normaliser: the chosen scores over their sum + this
# (the lfm2_moe modelling code's constant; the config has no key for it)
ROUTE_EPS = 1e-6

# the config keys the trunk is built from, under the published names
CONFIG_KEYS = (
    'vocab_size', 'hidden_size', 'num_hidden_layers', 'layer_types',
    'conv_L_cache', 'num_dense_layers', 'intermediate_size',
    'moe_intermediate_size', 'num_experts', 'num_experts_per_tok',
    'routed_scaling_factor', 'norm_topk_prob', 'use_expert_bias',
    'num_attention_heads', 'num_key_value_heads', 'rope_theta', 'norm_eps',
    'n_experts_held', 'first_expert',
)


@dataclass(frozen=True)
class TrunkConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    conv_L_cache: int
    num_dense_layers: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    use_expert_bias: bool
    num_attention_heads: int
    num_key_value_heads: int
    rope_theta: float
    norm_eps: float
    n_experts_held: Optional[int] = None     # None: all of them
    first_expert: int = 0

    model_type = MODEL_TYPE

    def __post_init__(self):
        object.__setattr__(self, 'layer_types', tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f'layer_types names {len(self.layer_types)} layers, '
                f'num_hidden_layers={self.num_hidden_layers}: give one entry '
                f'a layer run here')
        for i, kind in enumerate(self.layer_types):
            if kind not in LAYER_TYPES:
                raise ValueError(
                    f'layer_types[{i}]={kind!r} is no operator of the '
                    f'model_type={MODEL_TYPE} trunk; known: '
                    f'{", ".join(LAYER_TYPES)}')
        object.__setattr__(self, 'n_experts_held', token_trunk.held_experts(
            self.n_experts_held, self.first_expert, self.num_experts))
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError('num_experts_per_tok exceeds num_experts')
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f'num_attention_heads={self.num_attention_heads} is no '
                f'whole number of groups of num_key_value_heads='
                f'{self.num_key_value_heads}')
        if self.hidden_size % (2 * self.num_attention_heads):
            raise ValueError(
                f'hidden_size={self.hidden_size} over num_attention_heads='
                f'{self.num_attention_heads} is no even head width (rotary '
                f'pairs)')

    @classmethod
    def from_args(cls, args) -> 'TrunkConfig':
        values = {k: args.get(k) for k in CONFIG_KEYS}
        values['first_expert'] = values['first_expert'] or 0
        missing = [k for k, v in values.items()
                   if v is None and k != 'n_experts_held']
        if missing:
            raise ValueError(f'the lm trunk model_type={MODEL_TYPE} needs '
                             f'config keys {missing}')
        return cls(**values)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def is_dense(self, layer: int) -> bool:
        return layer < self.num_dense_layers

    def operators(self) -> Dict[str, int]:
        """{operator kind: layers of it run here}, in ``LAYER_TYPES``' order."""
        return {kind: self.layer_types.count(kind) for kind in LAYER_TYPES}


def param_shapes(cfg: TrunkConfig) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of every parameter held, in checkpoint order."""
    d, h, g, hd = (cfg.hidden_size, cfg.num_attention_heads,
                   cfg.num_key_value_heads, cfg.head_dim)
    shapes: Dict[str, Tuple[int, ...]] = {
        'model.embed_tokens.weight': (cfg.vocab_size, d)}
    for i, kind in enumerate(cfg.layer_types):
        p = f'model.layers.{i}'
        shapes[f'{p}.operator_norm.weight'] = (d,)
        if kind == 'conv':
            shapes.update({
                f'{p}.conv.in_proj.weight': (d, 3 * d),
                f'{p}.conv.conv.weight': (cfg.conv_L_cache, d),
                f'{p}.conv.out_proj.weight': (d, d)})
        else:
            a = f'{p}.self_attn'
            shapes.update({
                f'{a}.q_proj.weight': (d, h * hd),
                f'{a}.k_proj.weight': (d, g * hd),
                f'{a}.v_proj.weight': (d, g * hd),
                f'{a}.q_layernorm.weight': (hd,),
                f'{a}.k_layernorm.weight': (hd,),
                f'{a}.out_proj.weight': (h * hd, d)})
        shapes[f'{p}.ffn_norm.weight'] = (d,)
        m = f'{p}.feed_forward'
        if cfg.is_dense(i):
            f = cfg.intermediate_size
            shapes.update({f'{m}.w1.weight': (d, f),
                           f'{m}.w3.weight': (d, f),
                           f'{m}.w2.weight': (f, d)})
            continue
        f, e = cfg.moe_intermediate_size, cfg.n_experts_held
        shapes[f'{m}.gate.weight'] = (d, cfg.num_experts)
        if cfg.use_expert_bias:
            shapes[f'{m}.expert_bias'] = (cfg.num_experts,)
        shapes.update({f'{m}.experts.w1.weight': (e, d, f),
                       f'{m}.experts.w3.weight': (e, d, f),
                       f'{m}.experts.w2.weight': (e, f, d)})
    shapes['model.embedding_norm.weight'] = (d,)
    return shapes


def param_count(cfg: TrunkConfig) -> int:
    return token_trunk.param_count(param_shapes(cfg))


def init_params(cfg: TrunkConfig, seed: int = 0) -> Dict[str, np.ndarray]:
    """Seeded random parameters (``token_trunk.draw_params``: the taps come
    out N(0, 1/taps)), and a small router bias."""
    def router_bias(name, shape, rng):
        if name.endswith('expert_bias'):
            return 0.05 * rng.standard_normal(shape, dtype=np.float32)
        return None
    return token_trunk.draw_params(param_shapes(cfg), seed, router_bias)


def describe(cfg: TrunkConfig) -> str:
    ops = ' + '.join(f'{n} {kind}' for kind, n in cfg.operators().items())
    return (f'{cfg.num_hidden_layers} layers ({ops}) and '
            f'{cfg.n_experts_held} of {cfg.num_experts} experts in each of '
            f'the {max(cfg.num_hidden_layers - cfg.num_dense_layers, 0)} '
            f'expert layers')


def _causal_path(cfg: TrunkConfig, platform: str, s: int,
                 precision: Optional[str]) -> str:
    """``resolve_causal``'s answer for a window of ``s`` positions at this
    trunk's head width and head counts."""
    return resolve_causal(platform, s, cfg.head_dim, cfg.head_dim, precision,
                          cfg.num_attention_heads, cfg.num_key_value_heads)


def kernels(cfg: TrunkConfig, platform: str, window_ids: int,
            precision: Optional[str]) -> Dict[str, object]:
    """What the step compiles: the causal attention's path ('kernel' or
    'xla': ``ops.attention.resolve_causal``, from the platform, the window's
    shapes, the head counts and the matmul precision; all or nothing per
    program: it is the kernel's engagement counter) and the operator kinds
    run here."""
    return {'causal_attention': _causal_path(cfg, platform, window_ids,
                                             precision),
            'operators': ', '.join(f'{kind} {n}'
                                   for kind, n in cfg.operators().items())}


def count(tracer, counts: np.ndarray, cfg: TrunkConfig, tokens: int) -> None:
    """The step's per-expert counts → ``moe_route``, ``moe_held`` and
    ``moe_walk`` (``token_trunk.count_experts``)."""
    token_trunk.count_experts(tracer, counts, cfg.num_experts_per_tok, tokens,
                              moe.BLOCK)


# -- blocks -------------------------------------------------------------------

def conv_block(p: Params, prefix: str, x: jax.Array) -> jax.Array:
    """The short-convolution operator over (B, S, D) normed windows."""
    with jax.named_scope('short_conv'):
        return gated_short_conv(x, p[f'{prefix}.in_proj.weight'],
                                p[f'{prefix}.conv.weight'],
                                p[f'{prefix}.out_proj.weight'])


def attention_block(p: Params, prefix: str, x: jax.Array, cfg: TrunkConfig,
                    attn_block: int = 1024,
                    platform: Optional[str] = None) -> jax.Array:
    """Grouped-query attention over one window: (S, D) normed input →
    (S, D), causal, positions 0…S−1. ``platform`` is where the graph will
    run (None: the default backend); with the shapes and the ambient matmul
    precision it decides the causal path (``ops.attention.resolve_causal``):
    the fused kernel where it applies — q as it stands, its heads' columns
    side by side, k and v with their own fewer heads: nothing is repeated or
    folded — the XLA tiles of ``blockwise_attention`` elsewhere."""
    with jax.named_scope('attention'):
        s = x.shape[0]
        h, g, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        positions = jnp.arange(s)
        q = jnp.dot(x, p[f'{prefix}.q_proj.weight']).reshape(s, h, d)
        k = jnp.dot(x, p[f'{prefix}.k_proj.weight']).reshape(s, g, d)
        v = jnp.dot(x, p[f'{prefix}.v_proj.weight']).reshape(s, g, d)
        q = rotary_half(rms_norm(q, p[f'{prefix}.q_layernorm.weight'],
                                 cfg.norm_eps), positions, cfg.rope_theta)
        k = rotary_half(rms_norm(k, p[f'{prefix}.k_layernorm.weight'],
                                 cfg.norm_eps), positions, cfg.rope_theta)
        precision = jax.config.jax_default_matmul_precision
        if _causal_path(cfg, platform or jax.default_backend(), s,
                        precision) == 'kernel':
            from video_features_tpu.ops.pallas_attention import (
                causal_attention,
            )
            out = causal_attention(q[None], k[None], v[None], d ** -0.5,
                                   KERNEL_PASSES[precision])[0]
        else:
            out = blockwise_attention(q[None], k[None], v[None],
                                      block_size=min(attn_block, s),
                                      causal=True)[0]
        return jnp.dot(out.reshape(s, h * d), p[f'{prefix}.out_proj.weight'])


def expert_block(p: Params, prefix: str, x: jax.Array, cfg: TrunkConfig,
                 moe_block: int = moe.BLOCK) -> Tuple[jax.Array, jax.Array]:
    """The expert layer's feed-forward over (T, D) tokens: the held
    experts' share of the routed sum (``ops.moe.routed_experts``, under this
    checkpoint's names). Returns the output and the (held,) counts."""
    with jax.named_scope('moe'):
        bias = (p[f'{prefix}.expert_bias'] if cfg.use_expert_bias
                else jnp.zeros((cfg.num_experts,), jnp.float32))
        return moe.routed_experts(
            x, p[f'{prefix}.gate.weight'], bias,
            p[f'{prefix}.experts.w1.weight'],
            p[f'{prefix}.experts.w3.weight'],
            p[f'{prefix}.experts.w2.weight'],
            top_k=cfg.num_experts_per_tok,
            scaling=cfg.routed_scaling_factor,
            normalise=cfg.norm_topk_prob, eps=ROUTE_EPS,
            first=cfg.first_expert, block=moe_block)


def hidden_states(params: Params, ids: jax.Array, cfg: TrunkConfig,
                  attn_block: int = 1024, moe_block: int = moe.BLOCK,
                  platform: Optional[str] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """(B, S) int32 ids → ``(embedding_norm's hidden states (B, S, D),
    counts)``; ``counts`` is (expert layers, held) int32, the batch's
    assignments on each held expert (zero rows when no layer has experts).
    Attention runs a window at a time (its tiles are the memory that
    matters); the convolution takes the batch whole, each window shifted
    within itself; the feed-forward takes all B·S tokens at once."""
    b, s = ids.shape
    d = cfg.hidden_size
    eps = cfg.norm_eps
    x = embed(params, ids)                                  # (B, S, D)
    counts = []
    for i, kind in enumerate(cfg.layer_types):
        p = f'model.layers.{i}'
        normed = rms_norm(x, params[f'{p}.operator_norm.weight'], eps)
        if kind == 'conv':
            x = x + conv_block(params, f'{p}.conv', normed)
        else:
            x = x + jax.lax.map(
                lambda w: attention_block(params, f'{p}.self_attn', w, cfg,
                                          attn_block, platform),
                normed)
        normed = rms_norm(x, params[f'{p}.ffn_norm.weight'], eps
                          ).reshape(b * s, d)
        if cfg.is_dense(i):
            with jax.named_scope('dense_mlp'):
                y = swiglu(normed, params, f'{p}.feed_forward',
                           row_block=mlp_rows(b * s), names=FFN_NAMES)
        else:
            y, c = expert_block(params, f'{p}.feed_forward', normed, cfg,
                                moe_block)
            counts.append(c)
        x = x + y.reshape(b, s, d)
    counts = (jnp.stack(counts) if counts
              else jnp.zeros((0, cfg.n_experts_held), jnp.int32))
    return (final_norm(x, params, eps, 'model.embedding_norm.weight'),
            counts)


def forward(params: Params, ids: jax.Array, cfg: TrunkConfig,
            attn_block: int = 1024, moe_block: int = moe.BLOCK,
            platform: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """(B, S) int32 ids → ``(features (B, D) float32, counts)``: the mean
    of the window's final hidden states (:func:`hidden_states`)."""
    x, counts = hidden_states(params, ids, cfg, attn_block, moe_block,
                              platform)
    return mean_features(x), counts
