"""A hybrid token trunk: layers that differ in kind by ``layer_types`` —
gated short convolutions, Mamba-2 state-space mixers, full and
sliding-window grouped-query attention — over sparse experts or a dense
feed-forward. Three published model types run through it, each a row of
``DIALECTS`` over the one decoder of ``models/token_trunk.py``:
``lfm2_moe``, ``afmoe`` and ``granitemoehybrid``. What is this module's own
is the three mixers (the short convolution; the Mamba-2 mixer;
grouped-query attention, full or under a window), the config's published
fields, and the notes.

Token ids in, one hidden-state row a window out. Which kind a layer is is
static, read from the published ``layer_types`` and ``num_dense_layers``:

    lfm2_moe   h = x + op_i(RMSNorm(x))
               x = h + ffn_i(RMSNorm(h))
    afmoe      h = x + RMSNorm(op_i(RMSNorm(x)))        four norms a layer,
               x = h + RMSNorm(ffn_i(RMSNorm(h)))       x0 = E[ids] · √hidden
    granite…   h = x + m · op_i(RMSNorm(x))             m residual_multiplier,
               x = h + m · ffn(RMSNorm(h))              x0 = E[ids] · embedding_multiplier

with ``op_i`` by ``layer_types[i]`` and ``ffn_i`` dense for ``i <
num_dense_layers`` (granitemoehybrid: every layer's, ``shared_mlp``).

* ``conv`` (lfm2_moe) — the gated short convolution (``ops/short_conv.py``):
  ``[B ‖ C ‖ h] = x W_in``, a depthwise causal convolution of
  ``conv_L_cache`` taps over ``B ⊙ h``, gated by ``C``, then ``W_out``; no
  bias, no activation. It runs over the whole batch at once and never
  reads across a window's start.
* ``mamba`` (granitemoehybrid) — the Mamba-2 mixer (:func:`mamba_block`):
  ``[z ‖ xBC ‖ dt] = x W_in``; a depthwise causal convolution of
  ``mamba_d_conv`` taps with a bias over ``xBC``, then SiLU (never reading
  across a window's start); ``[x ‖ B ‖ C] = xBC``, x ``mamba_n_heads``
  heads of ``mamba_d_head``, B and C ``mamba_d_state`` wide and shared by
  every head (one group); ``Δ = softplus(dt + dt_bias)``, ``A =
  −exp(A_log)``; the selective scan from a zero state with its ``D`` skip
  (``ops/ssd.py``: chunks of ``mamba_chunk_size``; on a TPU under
  ``precision=mixed`` / ``default`` the Mosaic kernel ``ssd_scan`` of
  ``ops/pallas_ssd.py``, ``ops.ssd.resolve_ssd``); then ``RMSNorm(y ⊙
  silu(z))`` over all ``mamba_n_heads · mamba_d_head`` channels in float32
  with its own gain, and ``W_out``. A window starts from a zero state and
  its final state and convolution rows are dropped.
* ``full_attention`` (lfm2_moe, afmoe), ``attention`` (granitemoehybrid's
  spelling) — grouped-query softmax attention: ``num_attention_heads``
  query heads reading ``num_key_value_heads`` key-value heads (query head j
  reads key-value head ``j div group``) of ``head_dim`` dims
  (``hidden_size / num_attention_heads`` where no key gives it), causal.
  lfm2_moe and afmoe put an RMSNorm with a gain of its own over each head
  of q and k and scale by ``head_dim^-½``; granitemoehybrid has no head
  norms and scales by ``attention_multiplier``. lfm2_moe turns q and k by
  the half-split rotary code; afmoe's full layers and granitemoehybrid's
  (``position_embedding_type: nope``) carry **no positional code at all**.
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
  ``num_dense_layers`` layers (in row blocks; granitemoehybrid's
  ``shared_mlp`` of ``shared_intermediate_size`` in every layer, its gate
  and up one matrix, ``input_linear``); after them ``num_experts``
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
* scopes — ``short_conv``; ``mamba``, and inside it ``ssd`` (the step
  sizes, the decays, the scan and the ``D`` skip); the attention mixer
  under ``attention`` (lfm2_moe, granitemoehybrid) or the layer's kind,
  ``sliding_attention`` / ``full_attention`` (afmoe), opened before its
  projections; ``moe`` (router, walk and shared expert); ``dense_mlp``.

The equations are ``transformers``' ``models/lfm2/modeling_lfm2.py``
(``Lfm2ShortConv``, ``Lfm2Attention``, ``Lfm2DecoderLayer``) with its
``lfm2_moe`` sibling's expert layer, ``models/afmoe/modeling_afmoe.py`` and
``models/granitemoehybrid/modeling_granitemoehybrid.py`` (its Mamba layer
is Mamba-2's, arXiv:2405.21060). Prefill only: a window starts from nothing
and keeps nothing.

Parameters are a flat ``{dotted name: array}`` dict under each checkpoint's
own names (lfm2_moe: ``model.layers.3.conv.in_proj.weight``,
``model.layers.2.self_attn.q_layernorm.weight``,
``model.layers.5.feed_forward.experts.w1.weight``; afmoe:
``model.layers.3.self_attn.gate_proj.weight``,
``model.layers.3.post_attention_layernorm.weight``,
``model.layers.5.mlp.router.gate.weight``,
``model.layers.5.mlp.shared_experts.up_proj.weight`` …; granitemoehybrid:
``model.layers.0.mamba.in_proj.weight``, ``….mamba.A_log``,
``model.layers.5.self_attn.q_proj.weight``,
``model.layers.5.shared_mlp.input_linear.weight`` …), matrices as (in,
out); a layer's held experts are stacked, (held, in, out); a convolution's
taps, ``conv.conv.weight`` and ``mamba.conv1d.weight``, are **(taps,
channels)** — the checkpoint's (channels, 1, taps) transposed, tap 0 the
oldest position — so that a tap is one lane-dense row.
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
from video_features_tpu.ops.short_conv import causal_taps, gated_short_conv
from video_features_tpu.ops.ssd import resolve_ssd, ssd_chunked

MODEL_TYPE = 'lfm2_moe'
GRANITE = 'granitemoehybrid'
# the step's second output: (expert layers, held) assignment counts of the
# batch; granitemoehybrid's, SSD_COUNTER: (mamba layers, 3) positions the
# scan covered, chunks it scanned and chunks of those through the kernel
COUNTER = 'moe_counts'
SSD_COUNTER = 'ssd_scanned'
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
# and the granitemoehybrid trunk's: shared_intermediate_size, rms_norm_eps
# and num_local_experts are what lfm2_moe calls intermediate_size, norm_eps
# and num_experts
GRANITE_CONFIG_KEYS = (
    'vocab_size', 'hidden_size', 'num_hidden_layers', 'layer_types',
    'shared_intermediate_size', 'num_attention_heads', 'num_key_value_heads',
    'attention_multiplier', 'embedding_multiplier', 'residual_multiplier',
    'logits_scaling', 'position_embedding_type', 'rms_norm_eps',
    'num_local_experts', 'mamba_n_heads', 'mamba_d_head', 'mamba_d_state',
    'mamba_n_groups', 'mamba_d_conv', 'mamba_expand', 'mamba_chunk_size',
    'mamba_conv_bias', 'mamba_proj_bias',
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


def mamba_shapes(cfg: TrunkConfig, a: str, kind: str
                 ) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of one layer's Mamba-2 mixer under prefix ``a``."""
    d, h, inner = cfg.hidden_size, cfg.mamba_n_heads, cfg.mamba_inner
    conv = cfg.mamba_conv_dim
    return {f'{a}.in_proj.weight': (d, inner + conv + h),
            f'{a}.conv1d.weight': (cfg.mamba_d_conv, conv),
            f'{a}.conv1d.bias': (conv,),
            f'{a}.dt_bias': (h,), f'{a}.A_log': (h,), f'{a}.D': (h,),
            f'{a}.norm.weight': (inner,),
            f'{a}.out_proj.weight': (inner, d)}


def _ssd_path(cfg: TrunkConfig, platform: str, s: int,
              precision: Optional[str]) -> str:
    """``resolve_ssd``'s answer for a window of ``s`` positions at this
    trunk's widths."""
    return resolve_ssd(platform, s, cfg.mamba_n_heads, cfg.mamba_d_head,
                       cfg.mamba_d_state, cfg.mamba_chunk_size, precision)


def mamba_block(p: Params, prefix: str, x: jax.Array, cfg: TrunkConfig,
                attn_block: Optional[int] = None,
                platform: Optional[str] = None, kind: str = 'mamba'
                ) -> Tuple[jax.Array, jax.Array]:
    """The Mamba-2 mixer over one window: (S, D) normed input → (S, D), from
    a zero state; and a (3,) count: the positions the scan covered, the
    chunks it scanned and how many of those through the kernel (what
    ``ssd_scan`` and ``ssd_kernel`` count). ``platform`` is where the graph
    will run (None: the default backend); with the widths, the chunk and
    the ambient matmul precision it decides the scan's form
    (``ops.ssd.resolve_ssd``). ``attn_block`` and ``kind`` are the loop's,
    not read."""
    with jax.named_scope('mamba'):
        s = x.shape[0]
        h, inner, n = cfg.mamba_n_heads, cfg.mamba_inner, cfg.mamba_d_state
        zxbcdt = jnp.dot(x, p[f'{prefix}.in_proj.weight'])
        z = zxbcdt[:, :inner]
        xbc = zxbcdt[:, inner:inner + cfg.mamba_conv_dim]
        dt = zxbcdt[:, inner + cfg.mamba_conv_dim:]
        xbc = jax.nn.silu(
            causal_taps(xbc[None], p[f'{prefix}.conv1d.weight'])[0]
            + p[f'{prefix}.conv1d.bias'])
        xs, b, c = xbc[:, :inner], xbc[:, inner:inner + n], xbc[:, inner + n:]
        precision = jax.config.jax_default_matmul_precision
        kernel = _ssd_path(cfg, platform or jax.default_backend(), s,
                           precision) == 'kernel'
        with jax.named_scope('ssd'):
            f32 = jnp.float32
            dt = jax.nn.softplus(dt.astype(f32) + p[f'{prefix}.dt_bias'])
            a = -jnp.exp(p[f'{prefix}.A_log'].astype(f32))
            y = ssd_chunked(xs, dt, a, b, c, p[f'{prefix}.D'],
                            cfg.mamba_chunk_size,
                            KERNEL_PASSES[precision] if kernel else None)
        # the gated norm: y ⊙ silu(z), then RMSNorm over every channel
        gated = y.astype(f32) * jax.nn.silu(z.astype(f32))
        y = rms_norm(gated, p[f'{prefix}.norm.weight'], cfg.norm_eps
                     ).astype(x.dtype)
        out = jnp.dot(y, p[f'{prefix}.out_proj.weight'])
        chunks = -(-s // min(cfg.mamba_chunk_size, s))
        return out, jnp.array([s, chunks, chunks if kernel else 0],
                              jnp.int32)


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
    for norm in names.qk_norms or ():
        shapes[f'{a}.{norm}.weight'] = (hd,)
    shapes[f'{a}.{names.out_proj}.weight'] = (h * hd, d)
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
    them. The dialect says which kinds carry the rotary code, whether q and
    k have per-head norms and whether the heads' output is gated by
    ``σ(x W_gate)`` before the output projection; the softmax scale is the
    config's ``attention_multiplier``, or ``head_dim^-½`` where it has
    none. ``platform`` is where the graph will run (None: the default
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
        scale = (d ** -0.5 if cfg.attention_multiplier is None
                 else cfg.attention_multiplier)

        def head_norm(t, name):
            """The per-head RMSNorm where the dialect has one, then the
            rotary code where this kind of layer carries one."""
            if name is not None:
                t = rms_norm(t, p[f'{prefix}.{name}.weight'], cfg.norm_eps)
            if positions is None:
                return t
            return rotary_half(t, positions, cfg.rope_theta)

        q = jnp.dot(x, p[f'{prefix}.q_proj.weight']).reshape(s, h, d)
        k = jnp.dot(x, p[f'{prefix}.k_proj.weight']).reshape(s, g, d)
        v = jnp.dot(x, p[f'{prefix}.v_proj.weight']).reshape(s, g, d)
        q_norm, k_norm = names.qk_norms or (None, None)
        q = head_norm(q, q_norm)
        k = head_norm(k, k_norm)
        precision = jax.config.jax_default_matmul_precision
        if _causal_path(cfg, platform or jax.default_backend(), s,
                        precision, window) == 'kernel':
            from video_features_tpu.ops.pallas_attention import (
                causal_attention,
            )
            out = causal_attention(q[None], k[None], v[None], scale,
                                   KERNEL_PASSES[precision], window=window)[0]
        else:
            out = blockwise_attention(q[None], k[None], v[None],
                                      block_size=min(attn_block, s),
                                      scale=scale, causal=True,
                                      window=window)[0]
        out = out.reshape(s, h * d)
        if names.gated:
            out = out * jax.nn.sigmoid(
                jnp.dot(x, p[f'{prefix}.gate_proj.weight']))
        return jnp.dot(out, p[f'{prefix}.{names.out_proj}.weight'])


def count_ssd(tracer, counter: np.ndarray, cfg: TrunkConfig,
              tokens: int) -> None:
    """One fetched step's ``(mamba layers, 3)`` counter → the stage table:
    ``ssd_scan``, positions × layers the scan covered ÷ the step's
    positions × mamba layers; ``ssd_kernel``, chunks scanned through the
    kernel ÷ chunks scanned."""
    covered, chunks, through_kernel = (int(col.sum()) for col in
                                       np.asarray(counter).reshape(-1, 3).T)
    tracer.add_occupancy('ssd_scan', covered,
                         int(tokens) * cfg.kinds()['mamba'])
    tracer.add_occupancy('ssd_kernel', through_kernel, chunks)


# -- the dialects -----------------------------------------------------------------

CONV = Mixer(conv_block, conv_shapes, prefix='conv', per_window=False)
MAMBA = Mixer(mamba_block, mamba_shapes, prefix='mamba', counted=True)
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
    GRANITE: Dialect(
        mixers={'mamba': MAMBA, 'attention': ATTENTION},
        config_keys=GRANITE_CONFIG_KEYS,
        renamed=(('intermediate_size', 'shared_intermediate_size'),
                 ('norm_eps', 'rms_norm_eps')),
        optional=(),
        # gate and up side by side in input_linear, the gate's half first
        ffn='shared_mlp', ffn_names=('input_linear', None, 'output_linear'),
        qk_norms=None, counter=(SSD_COUNTER, count_ssd)),
}


@dataclass(frozen=True)
class TrunkConfig(BaseConfig):
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    norm_eps: float
    num_dense_layers: Optional[int] = None   # None: every layer (no experts)
    moe_intermediate_size: int = 0
    num_experts: int = 0                     # 0: a dense stage
    num_experts_per_tok: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rope_theta: Optional[float] = None       # where a kind carries rotary
    use_expert_bias: bool = True
    conv_L_cache: int = 0                    # 'conv' layers' taps
    head_dim: Optional[int] = None           # None: hidden / heads
    sliding_window: Optional[int] = None     # 'sliding_attention' layers'
    num_shared_experts: int = 0
    embed_scale: bool = False                # embedding · sqrt(hidden)
    score_func: str = 'sigmoid'              # afmoe's key: no other runs
    n_experts_held: Optional[int] = None     # None: all of them
    first_expert: int = 0
    # granitemoehybrid's
    attention_multiplier: Optional[float] = None  # None: head_dim^-½
    embedding_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None   # the head's: read, not run
    position_embedding_type: Optional[str] = None
    num_local_experts: int = 0               # block_sparse_moe: not run here
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 0
    mamba_expand: int = 0
    mamba_chunk_size: int = 0
    mamba_conv_bias: bool = False
    mamba_proj_bias: bool = False
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
        if self.position_embedding_type not in (None, 'nope'):
            raise ValueError(
                f'position_embedding_type={self.position_embedding_type!r}: '
                f'the model_type={self.model_type} trunk runs its attention '
                f'layers with no positional code (\'nope\') and has no other')
        if self.num_local_experts:
            raise ValueError(
                f'num_local_experts={self.num_local_experts}: the '
                f'model_type={self.model_type} trunk runs the dense stage '
                f'(shared_mlp) and no block_sparse_moe')
        if 'mamba' in self.dialect.mixers:
            self.check_mamba()
        if not self.num_experts:
            # a dense stage: every layer's feed-forward is the dense one
            object.__setattr__(self, 'num_dense_layers',
                               self.num_hidden_layers)
            object.__setattr__(self, 'n_experts_held', 0)
        else:
            object.__setattr__(self, 'n_experts_held',
                               token_trunk.held_experts(
                                   self.n_experts_held, self.first_expert,
                                   self.num_experts))
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

    def check_mamba(self) -> None:
        """What the Mamba-2 mixer can run: one group of B and C, heads that
        are the expanded width, a bias on the convolution and none on the
        projections."""
        if not self.mamba_conv_bias or self.mamba_proj_bias:
            raise ValueError(
                f'mamba_conv_bias={self.mamba_conv_bias}, mamba_proj_bias='
                f'{self.mamba_proj_bias}: the mamba mixer has a bias on its '
                f'convolution and none on its projections')
        if self.mamba_n_groups != 1:
            raise ValueError(
                f'mamba_n_groups={self.mamba_n_groups}: the mamba mixer '
                f'shares one B and C over every head and has no groups')
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_inner:
            raise ValueError(
                f'mamba_n_heads={self.mamba_n_heads} x mamba_d_head='
                f'{self.mamba_d_head} is not mamba_expand='
                f'{self.mamba_expand} x hidden_size={self.hidden_size}')

    @property
    def mamba_inner(self) -> int:
        """The mixer's expanded width: x, z and the gated norm's."""
        return self.mamba_expand * self.hidden_size

    @property
    def mamba_conv_dim(self) -> int:
        """The convolution's channels: x, B and C side by side."""
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

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
    multiplied (by sqrt(hidden), or by ``embedding_multiplier``) one drawn
    that much smaller. A Mamba-2 mixer's as its paper initialises them:
    ``A = −U(1, 16)``, ``Δ = softplus(dt_bias)`` log-uniform in [1e-3,
    0.1], ``D = 1``, the convolution's bias zero."""
    embed_factor = (cfg.hidden_size ** -0.5 if cfg.embed_scale
                    else 1.0 / (cfg.embedding_multiplier or 1.0))

    def special(name, shape, rng):
        if name.endswith('expert_bias'):
            return 0.05 * rng.standard_normal(shape, dtype=np.float32)
        if name == 'model.embed_tokens.weight' and embed_factor != 1.0:
            return (rng.standard_normal(shape, dtype=np.float32)
                    * np.float32(embed_factor))
        if name.endswith('.A_log'):
            return np.log(rng.uniform(1.0, 16.0, shape)).astype(np.float32)
        if name.endswith('.dt_bias'):
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), shape))
            return np.log(np.expm1(dt)).astype(np.float32)   # softplus⁻¹
        if name.endswith('.D'):
            return np.ones(shape, np.float32)
        if name.endswith('.conv1d.bias'):
            return np.zeros(shape, np.float32)
        return None
    return token_trunk.draw_params(param_shapes(cfg), seed, special)


def describe(cfg: TrunkConfig) -> str:
    ops = ' + '.join(f'{n} {kind}' for kind, n in cfg.kinds().items())
    if not cfg.num_experts:
        return (f'{cfg.num_hidden_layers} layers ({ops}) and a dense SwiGLU '
                f'of {cfg.intermediate_size}')
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
    window and what it saves in tiles (:func:`band_note`); one with Mamba-2
    mixers the scan's form ('kernel' or 'xla': ``ops.ssd.resolve_ssd``) and
    its chunk."""
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
    if cfg.kinds().get('mamba'):
        notes['ssd'] = _ssd_path(cfg, platform, window_ids, precision)
        notes['ssd_chunk'] = min(cfg.mamba_chunk_size, window_ids)
    notes['operators'] = ', '.join(f'{kind} {n}'
                                   for kind, n in cfg.kinds().items())
    return notes


# the step's per-expert counts → moe_route, moe_held and moe_walk
# (granitemoehybrid's counter is its dialect's: count_ssd)
count = token_trunk.count_experts
