"""A hybrid token trunk: layers that differ in kind by ``layer_types`` —
gated short convolutions, full and sliding-window grouped-query attention —
over sparse experts. Two published model types run through it, each a row
of ``DIALECTS`` over the one decoder of ``models/token_trunk.py``:
``lfm2_moe`` and ``afmoe``. What is this module's own is the two mixers
(the short convolution; grouped-query attention, full or under a window),
the config's published fields, and the notes.

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

from dataclasses import dataclass
from operator import attrgetter
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from video_features_tpu.models import token_trunk
# param_shapes and param_count are this trunk's too: the build and the
# benchmark read them here
from video_features_tpu.models.token_trunk import (
    FULL, SLIDING, BaseConfig, Dialect, Mixer, Params, param_count,
    param_shapes, rms_norm,
)
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


# -- the mixers -----------------------------------------------------------------

def conv_shapes(cfg: TrunkConfig, a: str, kind: str
                ) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of one layer's short convolution under prefix ``a``."""
    d = cfg.hidden_size
    return {f'{a}.in_proj.weight': (d, 3 * d),
            f'{a}.conv.weight': (cfg.conv_L_cache, d),
            f'{a}.out_proj.weight': (d, d)}


def conv_block(p: Params, prefix: str, x: jax.Array, *_) -> jax.Array:
    """The short-convolution operator over (B, S, D) normed windows (it has
    one form: the loop's other arguments are not read)."""
    with jax.named_scope('short_conv'):
        return gated_short_conv(x, p[f'{prefix}.in_proj.weight'],
                                p[f'{prefix}.conv.weight'],
                                p[f'{prefix}.out_proj.weight'])


def attention_shapes(cfg: TrunkConfig, a: str, kind: str
                     ) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of one layer's grouped-query attention under prefix
    ``a``, under the dialect's names."""
    d, h, g, hd = (cfg.hidden_size, cfg.num_attention_heads,
                   cfg.num_key_value_heads, cfg.head_dim)
    names = cfg.dialect
    shapes = {f'{a}.q_proj.weight': (d, h * hd),
              f'{a}.k_proj.weight': (d, g * hd),
              f'{a}.v_proj.weight': (d, g * hd)}
    if names.gated:
        shapes[f'{a}.gate_proj.weight'] = (d, h * hd)
    shapes.update({f'{a}.{names.qk_norms[0]}.weight': (hd,),
                   f'{a}.{names.qk_norms[1]}.weight': (hd,),
                   f'{a}.{names.out_proj}.weight': (h * hd, d)})
    return shapes


def _causal_path(cfg: TrunkConfig, platform: str, s: int,
                 precision: Optional[str],
                 window: Optional[int] = None) -> str:
    """``resolve_causal``'s answer for a window of ``s`` positions at this
    trunk's head width and head counts, a layer seeing ``window`` keys back
    (None: all)."""
    return resolve_causal(platform, s, cfg.head_dim, cfg.head_dim, precision,
                          cfg.num_attention_heads, cfg.num_key_value_heads,
                          window)


def mixer_scope(cfg: TrunkConfig, kind: str):
    """The scope an attention mixer opens: ``attention``, or where the
    dialect has mixers of two kinds the layer's. Each a literal:
    ``obs/scopes.py`` pins the vocabulary."""
    if not cfg.dialect.scope_by_kind:
        return jax.named_scope('attention')
    if kind == SLIDING:
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


# -- the dialects -----------------------------------------------------------------

CONV = Mixer(conv_block, conv_shapes, prefix='conv', per_window=False)
ATTENTION = Mixer(attention_block, attention_shapes)
DIALECTS = {
    'lfm2_moe': Dialect(
        mixers={'conv': CONV, FULL: ATTENTION}, config_keys=CONFIG_KEYS,
        # the normaliser's constant: the lfm2_moe modelling code's (the
        # config has no key for it)
        route_eps=1e-6,
        operator_norm='operator_norm', ffn_norm='ffn_norm',
        # LFM2 names a SwiGLU's matrices w1 (gate), w3 (up), w2 (down)
        ffn='feed_forward', ffn_names=('w1', 'w3', 'w2'),
        expert_bias='expert_bias', final_norm='model.embedding_norm.weight',
        rotary=(FULL,), qk_norms=('q_layernorm', 'k_layernorm'),
        out_proj='out_proj'),
    'afmoe': Dialect(
        mixers={SLIDING: ATTENTION, FULL: ATTENTION},
        config_keys=AFMOE_CONFIG_KEYS,
        renamed=(('routed_scaling_factor', 'route_scale'),
                 ('norm_topk_prob', 'route_norm'),
                 ('norm_eps', 'rms_norm_eps'),
                 ('embed_scale', 'mup_enabled')),
        ffn_norm='pre_mlp_layernorm',
        post_norms=('post_attention_layernorm', 'post_mlp_layernorm'),
        router='router.gate', expert_bias='expert_bias',
        rotary=(SLIDING,), gated=True, scope_by_kind=True),
}


@dataclass(frozen=True)
class TrunkConfig(BaseConfig):
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
    score_func: str = 'sigmoid'              # afmoe's key: no other runs
    n_experts_held: Optional[int] = None     # None: all of them
    first_expert: int = 0
    model_type: str = MODEL_TYPE

    dialects = DIALECTS
    window_key = 'sliding_window'
    eps = property(attrgetter('norm_eps'))
    routed_experts = property(attrgetter('num_experts'))
    shared_experts = property(attrgetter('num_shared_experts'))

    def __post_init__(self):
        self.check_layers()
        if self.score_func != 'sigmoid':
            raise ValueError(
                f'score_func={self.score_func!r}: the '
                f'model_type={self.model_type} trunk routes on sigmoid '
                f'scores (ops.moe.route) and has no other')
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

    def is_dense(self, layer: int) -> bool:
        return layer < self.num_dense_layers

    def window_of(self, kind: str) -> Optional[int]:
        """The keys a query of a layer of ``kind`` sees: ``sliding_window``
        in a sliding layer, None (all before it) in the others."""
        return self.sliding_window if kind == SLIDING else None


# -- what the build says and counts ---------------------------------------------

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
    ops = ' + '.join(f'{n} {kind}' for kind, n in cfg.kinds().items())
    return (f'{cfg.num_hidden_layers} layers ({ops}) and '
            f'{cfg.n_experts_held} of {cfg.num_experts} experts in each of '
            f'the {max(cfg.num_hidden_layers - cfg.num_dense_layers, 0)} '
            f'expert layers')


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
        for kind in cfg.kinds():
            notes[kind] = _causal_path(cfg, platform, window_ids, precision,
                                       cfg.window_of(kind))
        notes['sliding_window'] = cfg.sliding_window
        notes['window_tiles'] = band_note(cfg, notes['sliding_attention'],
                                          window_ids, attn_block)
    notes['operators'] = ', '.join(f'{kind} {n}'
                                   for kind, n in cfg.kinds().items())
    return notes


# the step's per-expert counts → moe_route, moe_held and moe_walk
count = token_trunk.count_experts
