"""A hybrid token trunk: layers that differ in kind by ``layer_types`` —
gated short convolutions, full and sliding-window grouped-query attention —
over sparse experts. Two published model types run through it, each a
*dialect* of the same blocks (``DIALECTS``): ``lfm2_moe`` and ``afmoe``.

Token ids in, one hidden-state row a window out. Which kind a layer is is
static, read from the published ``layer_types`` and ``num_dense_layers``:

    lfm2_moe   h = x + op_i(RMSNorm(x))
               x = h + ffn_i(RMSNorm(h))
    afmoe      h = x + RMSNorm(op_i(RMSNorm(x)))        four norms a layer,
               x = h + RMSNorm(ffn_i(RMSNorm(h)))       x0 = E[ids] · √hidden

with ``op_i`` by ``layer_types[i]`` and ``ffn_i`` dense for ``i <
num_dense_layers``.

* ``conv`` (lfm2_moe) — the gated short convolution (``ops/short_conv.py``):
  ``[B ‖ C ‖ h] = x W_in``, a depthwise causal convolution of
  ``conv_L_cache`` taps over ``B ⊙ h``, gated by ``C``, then ``W_out``; no
  bias, no activation. It runs over the whole batch at once and never
  reads across a window's start.
* ``full_attention`` (both) — grouped-query softmax attention:
  ``num_attention_heads`` query heads reading ``num_key_value_heads``
  key-value heads (query head j reads key-value head ``j div group``) of
  ``head_dim`` dims (``hidden_size / num_attention_heads`` where no key
  gives it), an RMSNorm with a gain of its own over each head of q and k,
  causal, scale ``head_dim^-½``. lfm2_moe turns q and k by the half-split
  rotary code; afmoe's full layers carry **no positional code at all**.
* ``sliding_attention`` (afmoe) — the same under a window: query i sees
  keys i − ``sliding_window`` + 1 … i, its own among them, with the
  half-split rotary code. Neither causal path computes or fetches a key
  tile outside that band (``ops.attention._causal_blockwise(window=)``,
  ``ops/pallas_attention.py``'s windowed lane).
* afmoe gates the heads' output, ``(o ⊙ σ(x W_gate)) W_o``, ``W_gate`` a
  ``hidden → heads · head_dim`` matrix over the layer's normed input.
* On a TPU under ``precision=mixed`` / ``default`` attention runs the fused
  kernel's grouped-query lane (``ops/pallas_attention.py``: one key-value
  head and its query heads a grid step; named ``causal_attention`` in
  traces, ``window_attention`` under a window), elsewhere
  ``ops.attention.blockwise_attention(causal=True)``, the XLA tiles;
  ``ops.attention.resolve_causal`` decides per layer kind from the shapes
  and the window (``kernels``).
* the feed-forward — a dense SwiGLU of ``intermediate_size`` in the first
  ``num_dense_layers`` layers (in row blocks); after them ``num_experts``
  SwiGLU experts of ``moe_intermediate_size``, ``num_experts_per_tok`` a
  token: sigmoid scores, the largest of ``score + expert_bias`` chosen (the
  bias moves the choice, not the weight), the chosen raw scores over their
  sum + the dialect's constant (1e-6; afmoe 1e-20), × the scaling factor
  (``ops/moe.py``, shared with ``models/latent_moe.py``); afmoe adds
  ``num_shared_experts`` shared experts every token takes.
* the share — ``n_experts_held`` experts from ``first_expert`` on are held
  here (all of them when None), as in ``models/latent_moe.py``; a shared
  expert is every chip's alike.
* output — the final norm, mean over the window's positions. The output
  head is neither held nor run.
* scopes — ``short_conv``; the attention mixer under ``attention``
  (lfm2_moe) or the layer's kind, ``sliding_attention`` / ``full_attention``
  (afmoe), opened before its projections; ``moe`` (router, walk and shared
  expert); ``dense_mlp``.

The equations are ``transformers``' ``models/lfm2/modeling_lfm2.py``
(``Lfm2ShortConv``, ``Lfm2Attention``, ``Lfm2DecoderLayer``) with its
``lfm2_moe`` sibling's expert layer, and ``models/afmoe/modeling_afmoe.py``.
Prefill only: a window starts from nothing and keeps nothing.

Parameters are a flat ``{dotted name: array}`` dict under each checkpoint's
own names (lfm2_moe: ``model.layers.3.conv.in_proj.weight``,
``model.layers.2.self_attn.q_layernorm.weight``,
``model.layers.5.feed_forward.experts.w1.weight``; afmoe:
``model.layers.3.self_attn.gate_proj.weight``,
``model.layers.3.post_attention_layernorm.weight``,
``model.layers.5.mlp.router.gate.weight``,
``model.layers.5.mlp.shared_experts.up_proj.weight`` …), matrices as (in,
out); a layer's held experts are stacked, (held, in, out); the convolution's
``conv.conv.weight`` is **(taps, hidden)** — the checkpoint's (hidden, 1,
taps) transposed, tap 0 the oldest position — so that a tap is one
lane-dense row.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from video_features_tpu.models import token_trunk
from video_features_tpu.models.token_trunk import (
    SWIGLU_NAMES, Params, embed, final_norm, mean_features, mlp_rows,
    rms_norm, swiglu,
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
# the routing weights' normaliser: the chosen scores over their sum + this
# (the lfm2_moe modelling code's constant; the config has no key for it)
ROUTE_EPS = 1e-6

# the config keys the lfm2_moe trunk is built from, under the published names
CONFIG_KEYS = (
    'vocab_size', 'hidden_size', 'num_hidden_layers', 'layer_types',
    'conv_L_cache', 'num_dense_layers', 'intermediate_size',
    'moe_intermediate_size', 'num_experts', 'num_experts_per_tok',
    'routed_scaling_factor', 'norm_topk_prob', 'use_expert_bias',
    'num_attention_heads', 'num_key_value_heads', 'rope_theta', 'norm_eps',
    'n_experts_held', 'first_expert',
)
# and the afmoe trunk's, under its own: route_scale, route_norm and
# rms_norm_eps are what lfm2_moe calls routed_scaling_factor, norm_topk_prob
# and norm_eps; mup_enabled multiplies the embedding by sqrt(hidden_size)
AFMOE_CONFIG_KEYS = (
    'vocab_size', 'hidden_size', 'num_hidden_layers', 'layer_types',
    'sliding_window', 'head_dim', 'num_dense_layers', 'intermediate_size',
    'moe_intermediate_size', 'num_experts', 'num_experts_per_tok',
    'num_shared_experts', 'route_scale', 'route_norm', 'score_func',
    'mup_enabled', 'num_attention_heads', 'num_key_value_heads',
    'rope_theta', 'rms_norm_eps', 'n_experts_held', 'first_expert',
)


@dataclass(frozen=True)
class Dialect:
    """What a ``model_type`` fixes beside its sizes: which operator kinds its
    layers may be and which of them carry the rotary code, whether the
    attention output is gated, the names of the norms over the two
    sub-layers' outputs (none: no such norms), the normaliser's constant,
    whether its attention mixer's scope is the layer's kind ('attention'
    where not), the published config keys and the checkpoint's names."""
    layer_types: Tuple[str, ...]
    rotary: Tuple[str, ...]
    gated: bool
    post_norms: Tuple[str, ...]
    route_eps: float
    scope_by_kind: bool
    config_keys: Tuple[str, ...]
    renamed: Tuple[Tuple[str, str], ...]      # (field, published key)
    operator_norm: str
    ffn_norm: str
    qk_norms: Tuple[str, str]
    out_proj: str
    ffn: str
    ffn_names: Tuple[str, str, str]
    router: str
    final_norm: str


DIALECTS = {
    'lfm2_moe': Dialect(
        layer_types=('conv', 'full_attention'), rotary=('full_attention',),
        gated=False, post_norms=(), route_eps=ROUTE_EPS,
        scope_by_kind=False, config_keys=CONFIG_KEYS, renamed=(),
        operator_norm='operator_norm', ffn_norm='ffn_norm',
        qk_norms=('q_layernorm', 'k_layernorm'), out_proj='out_proj',
        # LFM2 names a SwiGLU's matrices w1 (gate), w3 (up), w2 (down)
        ffn='feed_forward', ffn_names=('w1', 'w3', 'w2'), router='gate',
        final_norm='model.embedding_norm.weight'),
    'afmoe': Dialect(
        layer_types=('sliding_attention', 'full_attention'),
        rotary=('sliding_attention',), gated=True,
        post_norms=('post_attention_layernorm', 'post_mlp_layernorm'),
        route_eps=1e-20, scope_by_kind=True, config_keys=AFMOE_CONFIG_KEYS,
        renamed=(('routed_scaling_factor', 'route_scale'),
                 ('norm_topk_prob', 'route_norm'),
                 ('norm_eps', 'rms_norm_eps'),
                 ('embed_scale', 'mup_enabled')),
        operator_norm='input_layernorm', ffn_norm='pre_mlp_layernorm',
        qk_norms=('q_norm', 'k_norm'), out_proj='o_proj', ffn='mlp',
        ffn_names=SWIGLU_NAMES, router='router.gate',
        final_norm='model.norm.weight'),
}


@dataclass(frozen=True)
class TrunkConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    num_dense_layers: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    num_attention_heads: int
    num_key_value_heads: int
    rope_theta: float
    norm_eps: float
    use_expert_bias: bool = True
    conv_L_cache: int = 0                    # 'conv' layers' taps
    head_dim: Optional[int] = None           # None: hidden / heads
    sliding_window: Optional[int] = None     # 'sliding_attention' layers'
    num_shared_experts: int = 0
    embed_scale: bool = False                # embedding · sqrt(hidden)
    n_experts_held: Optional[int] = None     # None: all of them
    first_expert: int = 0
    model_type: str = MODEL_TYPE

    def __post_init__(self):
        object.__setattr__(self, 'layer_types', tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f'layer_types names {len(self.layer_types)} layers, '
                f'num_hidden_layers={self.num_hidden_layers}: give one entry '
                f'a layer run here')
        known = self.dialect.layer_types
        for i, kind in enumerate(self.layer_types):
            if kind not in known:
                raise ValueError(
                    f'layer_types[{i}]={kind!r} is no operator of the '
                    f'model_type={self.model_type} trunk; known: '
                    f'{", ".join(known)}')
        object.__setattr__(self, 'n_experts_held', token_trunk.held_experts(
            self.n_experts_held, self.first_expert, self.num_experts))
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError('num_experts_per_tok exceeds num_experts')
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f'num_attention_heads={self.num_attention_heads} is no '
                f'whole number of groups of num_key_value_heads='
                f'{self.num_key_value_heads}')
        if self.head_dim is None:
            if self.hidden_size % (2 * self.num_attention_heads):
                raise ValueError(
                    f'hidden_size={self.hidden_size} over '
                    f'num_attention_heads={self.num_attention_heads} is no '
                    f'even head width (rotary pairs)')
            object.__setattr__(self, 'head_dim',
                               self.hidden_size // self.num_attention_heads)
        elif self.head_dim % 2:
            raise ValueError(f'head_dim={self.head_dim} is no even head '
                             f'width (rotary pairs)')
        if 'sliding_attention' in self.layer_types and not (
                self.sliding_window and self.sliding_window > 0):
            raise ValueError(
                f'sliding_attention layers need sliding_window, the keys a '
                f'query sees; got {self.sliding_window!r}')

    @classmethod
    def from_args(cls, args) -> 'TrunkConfig':
        model_type = args.get('model_type')
        if model_type not in DIALECTS:
            model_type = MODEL_TYPE
        dialect = DIALECTS[model_type]
        values = {k: args.get(k) for k in dialect.config_keys}
        values['first_expert'] = values['first_expert'] or 0
        missing = [k for k, v in values.items()
                   if v is None and k != 'n_experts_held']
        if missing:
            raise ValueError(f'the lm trunk model_type={model_type} needs '
                             f'config keys {missing}')
        score_func = values.pop('score_func', 'sigmoid')
        if score_func != 'sigmoid':
            raise ValueError(
                f'score_func={score_func!r}: the model_type={model_type} '
                f'trunk routes on sigmoid scores (ops.moe.route) and has '
                f'no other')
        for field, key in dialect.renamed:
            values[field] = values.pop(key)
        return cls(**values, model_type=model_type)

    @property
    def dialect(self) -> Dialect:
        return DIALECTS[self.model_type]

    def is_dense(self, layer: int) -> bool:
        return layer < self.num_dense_layers

    def window_of(self, kind: str) -> Optional[int]:
        """The keys a query of a layer of ``kind`` sees: ``sliding_window``
        in a sliding layer, None (all before it) in the others."""
        return self.sliding_window if kind == 'sliding_attention' else None

    def operators(self) -> Dict[str, int]:
        """{operator kind: layers of it run here}, in the dialect's order."""
        return {kind: self.layer_types.count(kind)
                for kind in self.dialect.layer_types}


def param_shapes(cfg: TrunkConfig) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of every parameter held, in checkpoint order."""
    d, h, g, hd = (cfg.hidden_size, cfg.num_attention_heads,
                   cfg.num_key_value_heads, cfg.head_dim)
    names = cfg.dialect
    post_op, post_ffn = names.post_norms or (None, None)
    gate_name, up_name, down_name = names.ffn_names
    shapes: Dict[str, Tuple[int, ...]] = {
        'model.embed_tokens.weight': (cfg.vocab_size, d)}
    for i, kind in enumerate(cfg.layer_types):
        p = f'model.layers.{i}'
        shapes[f'{p}.{names.operator_norm}.weight'] = (d,)
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
                f'{a}.v_proj.weight': (d, g * hd)})
            if names.gated:
                shapes[f'{a}.gate_proj.weight'] = (d, h * hd)
            shapes.update({
                f'{a}.{names.qk_norms[0]}.weight': (hd,),
                f'{a}.{names.qk_norms[1]}.weight': (hd,),
                f'{a}.{names.out_proj}.weight': (h * hd, d)})
        if post_op:
            shapes[f'{p}.{post_op}.weight'] = (d,)
        shapes[f'{p}.{names.ffn_norm}.weight'] = (d,)
        m = f'{p}.{names.ffn}'
        if cfg.is_dense(i):
            f = cfg.intermediate_size
            shapes.update({f'{m}.{gate_name}.weight': (d, f),
                           f'{m}.{up_name}.weight': (d, f),
                           f'{m}.{down_name}.weight': (f, d)})
        else:
            f, e = cfg.moe_intermediate_size, cfg.n_experts_held
            shapes[f'{m}.{names.router}.weight'] = (d, cfg.num_experts)
            if cfg.use_expert_bias:
                shapes[f'{m}.expert_bias'] = (cfg.num_experts,)
            shapes.update({f'{m}.experts.{gate_name}.weight': (e, d, f),
                           f'{m}.experts.{up_name}.weight': (e, d, f),
                           f'{m}.experts.{down_name}.weight': (e, f, d)})
            if cfg.num_shared_experts:
                fs = f * cfg.num_shared_experts
                shapes.update({
                    f'{m}.shared_experts.{gate_name}.weight': (d, fs),
                    f'{m}.shared_experts.{up_name}.weight': (d, fs),
                    f'{m}.shared_experts.{down_name}.weight': (fs, d)})
        if post_ffn:
            shapes[f'{p}.{post_ffn}.weight'] = (d,)
    shapes[names.final_norm] = (d,)
    return shapes


def param_count(cfg: TrunkConfig) -> int:
    return token_trunk.param_count(param_shapes(cfg))


def init_params(cfg: TrunkConfig, seed: int = 0) -> Dict[str, np.ndarray]:
    """Seeded random parameters (``token_trunk.draw_params``: the taps come
    out N(0, 1/taps)), a small router bias, and where the embedding is
    multiplied by sqrt(hidden) one drawn that much smaller."""
    def special(name, shape, rng):
        if name.endswith('expert_bias'):
            return 0.05 * rng.standard_normal(shape, dtype=np.float32)
        if cfg.embed_scale and name == 'model.embed_tokens.weight':
            return (rng.standard_normal(shape, dtype=np.float32)
                    * np.float32(cfg.hidden_size ** -0.5))
        return None
    return token_trunk.draw_params(param_shapes(cfg), seed, special)


def describe(cfg: TrunkConfig) -> str:
    ops = ' + '.join(f'{n} {kind}' for kind, n in cfg.operators().items())
    return (f'{cfg.num_hidden_layers} layers ({ops}) and '
            f'{cfg.n_experts_held} of {cfg.num_experts} experts in each of '
            f'the {max(cfg.num_hidden_layers - cfg.num_dense_layers, 0)} '
            f'expert layers')


def _causal_path(cfg: TrunkConfig, platform: str, s: int,
                 precision: Optional[str],
                 window: Optional[int] = None) -> str:
    """``resolve_causal``'s answer for a window of ``s`` positions at this
    trunk's head width and head counts, a layer seeing ``window`` keys back
    (None: all)."""
    return resolve_causal(platform, s, cfg.head_dim, cfg.head_dim, precision,
                          cfg.num_attention_heads, cfg.num_key_value_heads,
                          window)


def band_note(cfg: TrunkConfig, path: str, s: int, attn_block: int) -> str:
    """The sliding layers' tiles in words: the key tiles a query tile
    visits under the window against those of the whole triangle, at the
    tiles the path takes (the kernel's, or the XLA tiles' ``attn_block``)."""
    from video_features_tpu.ops import pallas_attention as kernel
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    w = min(cfg.sliding_window, s)
    block_q, block_k = (kernel.tiles(s, group, w) if path == 'kernel'
                        else (min(attn_block, s),) * 2)
    visited = triangle = 0
    for q0 in range(0, s - s % block_q, block_q):
        last = (q0 + block_q - 1) // block_k
        visited += last - max(q0 - w + 1, 0) // block_k + 1
        triangle += last + 1
    return (f'{visited} of the triangle\'s {triangle} (query, key) tiles of '
            f'{block_q} x {block_k}')


def kernels(cfg: TrunkConfig, platform: str, window_ids: int,
            precision: Optional[str], attn_block: int = 1024
            ) -> Dict[str, object]:
    """What the step compiles: the causal attention's path ('kernel' or
    'xla': ``ops.attention.resolve_causal``, from the platform, the window's
    shapes, the head counts and the matmul precision; all or nothing per
    layer kind: it is the kernel's engagement counter) and the operator
    kinds run here. A trunk with sliding layers says it per kind, with the
    window and what it saves in tiles (:func:`band_note`)."""
    notes: Dict[str, object] = {}
    if cfg.sliding_window is None:
        notes['causal_attention'] = _causal_path(cfg, platform, window_ids,
                                                 precision)
    else:
        for kind in cfg.operators():
            notes[kind] = _causal_path(cfg, platform, window_ids, precision,
                                       cfg.window_of(kind))
        notes['sliding_window'] = cfg.sliding_window
        notes['window_tiles'] = band_note(cfg, notes['sliding_attention'],
                                          window_ids, attn_block)
    notes['operators'] = ', '.join(f'{kind} {n}'
                                   for kind, n in cfg.operators().items())
    return notes


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


def mixer_scope(cfg: TrunkConfig, kind: str):
    """The scope an attention mixer opens: ``attention``, or where the
    dialect has mixers of two kinds the layer's. Each a literal:
    ``obs/scopes.py`` pins the vocabulary."""
    if not cfg.dialect.scope_by_kind:
        return jax.named_scope('attention')
    if kind == 'sliding_attention':
        return jax.named_scope('sliding_attention')
    return jax.named_scope('full_attention')


def attention_block(p: Params, prefix: str, x: jax.Array, cfg: TrunkConfig,
                    attn_block: int = 1024,
                    platform: Optional[str] = None,
                    kind: str = 'full_attention') -> jax.Array:
    """Grouped-query attention over one window: (S, D) normed input →
    (S, D), causal, positions 0…S−1; a layer of ``kind``
    'sliding_attention' sees ``sliding_window`` keys back, its own among
    them. The dialect says which kinds carry the rotary code and whether
    the heads' output is gated by ``σ(x W_gate)`` before the output
    projection. ``platform`` is where the graph will run (None: the default
    backend); with the shapes, the window and the ambient matmul precision
    it decides the causal path (``ops.attention.resolve_causal``): the
    fused kernel where it applies — q as it stands, its heads' columns side
    by side, k and v with their own fewer heads: nothing is repeated or
    folded — the XLA tiles of ``blockwise_attention`` elsewhere."""
    names = cfg.dialect
    with mixer_scope(cfg, kind):
        s = x.shape[0]
        h, g, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        window = cfg.window_of(kind)
        positions = jnp.arange(s) if kind in names.rotary else None

        def head_norm(t, name):
            """The per-head RMSNorm, then the rotary code where this kind
            of layer carries one."""
            t = rms_norm(t, p[f'{prefix}.{name}.weight'], cfg.norm_eps)
            if positions is None:
                return t
            return rotary_half(t, positions, cfg.rope_theta)

        q = jnp.dot(x, p[f'{prefix}.q_proj.weight']).reshape(s, h, d)
        k = jnp.dot(x, p[f'{prefix}.k_proj.weight']).reshape(s, g, d)
        v = jnp.dot(x, p[f'{prefix}.v_proj.weight']).reshape(s, g, d)
        q = head_norm(q, names.qk_norms[0])
        k = head_norm(k, names.qk_norms[1])
        precision = jax.config.jax_default_matmul_precision
        if _causal_path(cfg, platform or jax.default_backend(), s,
                        precision, window) == 'kernel':
            from video_features_tpu.ops.pallas_attention import (
                causal_attention,
            )
            out = causal_attention(q[None], k[None], v[None], d ** -0.5,
                                   KERNEL_PASSES[precision], window=window)[0]
        else:
            out = blockwise_attention(q[None], k[None], v[None],
                                      block_size=min(attn_block, s),
                                      causal=True, window=window)[0]
        out = out.reshape(s, h * d)
        if names.gated:
            out = out * jax.nn.sigmoid(
                jnp.dot(x, p[f'{prefix}.gate_proj.weight']))
        return jnp.dot(out, p[f'{prefix}.{names.out_proj}.weight'])


def expert_block(p: Params, prefix: str, x: jax.Array, cfg: TrunkConfig,
                 moe_block: int = moe.BLOCK) -> Tuple[jax.Array, jax.Array]:
    """The expert layer's feed-forward over (T, D) tokens: the held
    experts' share of the routed sum (``ops.moe.routed_experts``, under this
    checkpoint's names) plus, where the model has them, the shared experts
    every token takes. Returns the output and the (held,) counts."""
    names = cfg.dialect
    gate_name, up_name, down_name = names.ffn_names
    with jax.named_scope('moe'):
        bias = (p[f'{prefix}.expert_bias'] if cfg.use_expert_bias
                else jnp.zeros((cfg.num_experts,), jnp.float32))
        y, counts = moe.routed_experts(
            x, p[f'{prefix}.{names.router}.weight'], bias,
            p[f'{prefix}.experts.{gate_name}.weight'],
            p[f'{prefix}.experts.{up_name}.weight'],
            p[f'{prefix}.experts.{down_name}.weight'],
            top_k=cfg.num_experts_per_tok,
            scaling=cfg.routed_scaling_factor,
            normalise=cfg.norm_topk_prob, eps=names.route_eps,
            first=cfg.first_expert, block=moe_block)
        if cfg.num_shared_experts:
            y = y + swiglu(x, p, f'{prefix}.shared_experts',
                           names=names.ffn_names)
        return y, counts


def hidden_states(params: Params, ids: jax.Array, cfg: TrunkConfig,
                  attn_block: int = 1024, moe_block: int = moe.BLOCK,
                  platform: Optional[str] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """(B, S) int32 ids → ``(the final norm's hidden states (B, S, D),
    counts)``; ``counts`` is (expert layers, held) int32, the batch's
    assignments on each held expert (zero rows when no layer has experts).
    Attention runs a window at a time (its tiles are the memory that
    matters); the convolution takes the batch whole, each window shifted
    within itself; the feed-forward takes all B·S tokens at once. Where the
    dialect has post-norms, each sub-layer's output is normed before it
    joins the stream."""
    b, s = ids.shape
    d = cfg.hidden_size
    eps = cfg.norm_eps
    names = cfg.dialect
    post_op, post_ffn = names.post_norms or (None, None)
    x = embed(params, ids)                                  # (B, S, D)
    if cfg.embed_scale:
        x = x * math.sqrt(d)
    counts = []
    for i, kind in enumerate(cfg.layer_types):
        p = f'model.layers.{i}'
        normed = rms_norm(x, params[f'{p}.{names.operator_norm}.weight'], eps)
        if kind == 'conv':
            y = conv_block(params, f'{p}.conv', normed)
        else:
            y = jax.lax.map(
                lambda w: attention_block(params, f'{p}.self_attn', w, cfg,
                                          attn_block, platform, kind),
                normed)
        if post_op:
            y = rms_norm(y, params[f'{p}.{post_op}.weight'], eps)
        x = x + y
        normed = rms_norm(x, params[f'{p}.{names.ffn_norm}.weight'], eps
                          ).reshape(b * s, d)
        m = f'{p}.{names.ffn}'
        if cfg.is_dense(i):
            with jax.named_scope('dense_mlp'):
                y = swiglu(normed, params, m, row_block=mlp_rows(b * s),
                           names=names.ffn_names)
        else:
            y, c = expert_block(params, m, normed, cfg, moe_block)
            counts.append(c)
        if post_ffn:
            y = rms_norm(y, params[f'{p}.{post_ffn}.weight'], eps)
        x = x + y.reshape(b, s, d)
    counts = (jnp.stack(counts) if counts
              else jnp.zeros((0, cfg.n_experts_held), jnp.int32))
    return final_norm(x, params, eps, names.final_norm), counts


def forward(params: Params, ids: jax.Array, cfg: TrunkConfig,
            attn_block: int = 1024, moe_block: int = moe.BLOCK,
            platform: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """(B, S) int32 ids → ``(features (B, D) float32, counts)``: the mean
    of the window's final hidden states (:func:`hidden_states`)."""
    x, counts = hidden_states(params, ids, cfg, attn_block, moe_block,
                              platform)
    return mean_features(x), counts
