"""A sparse-expert, latent-attention token trunk (DeepSeek-V3 lineage). Two
published model types run through it, each a row of ``DIALECTS`` over the
one decoder of ``models/token_trunk.py``: ``joyai_llm_flash`` and
``dots3_note``. What is this module's own is the latent attention mixer
(widths by layer kind, the indexer, the gate, the rescale), the config's
published fields, and the notes.

The decoder trunk of such language models as a feature extractor: token ids
in, one hidden-state row a window out. Pre-norm residual blocks, RMSNorm,
no biases; layer ``i`` of kind ``layer_types[i]`` (joyai's layers are all
``full_attention``):

    h = x + Mixer_kind(RMSNorm(x))
    x = h + FFN_i(RMSNorm(h))

* **latent attention (MLA)** — queries through a low-rank bottleneck
  (``q_lora_rank``), keys and values expanded from one shared latent
  (``kv_lora_rank``) plus one rotary key head shared by all heads; heads of
  ``qk_nope_head_dim + qk_rope_head_dim`` for q/k and ``v_head_dim`` for v;
  rotary on the rope dims only, interleaved pairs; causal. Prefill only —
  no cache, so the expanded form::

      c_q = RMSNorm(x W_qa) · ρ_q        q = c_q W_qb → (S, H, d_n + d_r)
      [c_kv ‖ k_r] = x W_kva             c_kv = RMSNorm(c_kv) · ρ_kv
      [k_n ‖ v] = c_kv W_kvb             s_h[t, u] = q_h[t] · [k_n,h ‖ k_r][u]
                                                     · (d_n + d_r)^-½
      o_h[t] = Σ_{u visible to t} softmax_u(s_h[t, u]) v_h[u]
      out = concat_h(g_h · o_h) W_o

  ``ρ`` is 1, or under ``apply_mla_qkv_lora_rescale`` √(hidden / rank) of
  each latent (it scales k_n and v, not the rotary key); ``g`` is 1, or
  under a ``headwise`` gate ``σ(x W_g)``, one sigmoid a head over the
  layer's normed input.
* **dots3_note's two kinds** — ``full_attention``: its own widths (128
  heads of 128 + 64 / 128, kv rank 512, θ 8e7), ``u ≤ t`` and ``u`` among
  the ``index_topk`` keys the lightning indexer scores highest for ``t``
  (``ops/sparse_index.py``: learned sparse attention); ``sliding_attention``:
  the ``swa_*`` widths (64 heads of 192 + 64 / 128, kv rank 1,024, θ 5e4)
  and ``t − window + 1 ≤ u ≤ t``, ``window = sliding_window_size``.
* **the causal path** — on a TPU under ``precision=mixed`` / ``default``
  the fused kernel (``ops/pallas_attention.py``: its column-group lane,
  named ``causal_attention`` in traces; under the selection its keep lane,
  ``sparse_attention``; under a window its windowed lane,
  ``window_attention``), elsewhere ``ops.attention.blockwise_attention``'s
  XLA tiles with the same mask; ``ops.attention.resolve_causal`` decides
  per kind (``kernels``).
* **the feed-forward** — a dense SwiGLU in the first
  ``first_k_dense_replace`` layers; after them a mixture of
  ``n_routed_experts`` SwiGLU experts, ``num_experts_per_tok`` a token
  (``ops/moe.py``), plus ``n_shared_experts`` shared ones every token takes.
* **the share** — ``n_experts_held`` experts from ``first_expert`` on are
  held here (all of them when None); the router keeps its full width, and
  what the absent experts would add is left out.
* **output** — final RMSNorm, mean over the window's positions. The output
  head and the multi-token-prediction module are not part of a feature
  extractor and are neither held nor run.
* **scopes** — ``mla`` (joyai), ``sparse_mla`` with ``mla_indexer`` inside
  it and ``window_mla`` (dots3_note's two kinds), each opened before the
  mixer's projections; ``moe``; ``dense_mlp``.

Parameters are a flat ``{dotted name: array}`` dict under the checkpoint's
own names (``model.layers.3.self_attn.q_b_proj.weight``;
dots3_note's ``self_attn.gate_proj`` (hidden → heads) and
``self_attn.indexer.{wq_b, wk, k_norm, weights_proj}``, DeepSeek-V3.2's
names …), matrices as (in, out); a layer's held experts are stacked:
``mlp.experts.gate_proj.weight`` is (held, hidden, moe_intermediate).
"""
from __future__ import annotations

import math
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
    KERNEL_PASSES, blockwise_attention, resolve_causal, rotary_interleaved,
)
from video_features_tpu.ops.sparse_index import (
    BLOCK, resolve_index, resolve_threshold, scored_blocks, select_keys,
)

MODEL_TYPE = 'joyai_llm_flash'
# the step's second output: (expert layers, held) assignment counts of the
# batch
COUNTER = 'moe_counts'
# dots3_note's: those, and (full layers, 4) counts of the indexer's query
# blocks and rows (:func:`count_selection`)
SELECTION_COUNTER = 'moe_index_counts'
SHARE_ADVICE = ('Hold a share (n_experts_held, first_expert: the experts of '
                'a layer divided over chips) and run fewer layers here '
                '(num_hidden_layers: the rest are further pipeline stages).')

# the config keys a trunk is built from, under the names the published
# config.json uses (configs/lm.yml ships JoyAI-LLM-Flash's values)
CONFIG_KEYS = (
    'vocab_size', 'hidden_size', 'num_hidden_layers', 'first_k_dense_replace',
    'intermediate_size', 'moe_intermediate_size', 'n_routed_experts',
    'n_shared_experts', 'num_experts_per_tok', 'routed_scaling_factor',
    'norm_topk_prob', 'num_attention_heads', 'q_lora_rank', 'kv_lora_rank',
    'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim', 'rope_theta',
    'rms_norm_eps', 'n_experts_held', 'first_expert',
)
# and dots3_note's besides: the layer kinds, the sliding layers' own widths
# (swa_*), the indexer (index_*), the gates and the latent rescale
DOTS3_CONFIG_KEYS = CONFIG_KEYS + (
    'layer_types', 'sliding_window_size', 'swa_num_attention_heads',
    'swa_q_lora_rank', 'swa_kv_lora_rank', 'swa_qk_nope_head_dim',
    'swa_qk_rope_head_dim', 'swa_v_head_dim', 'swa_rope_theta',
    'index_n_heads', 'index_head_dim', 'index_topk', 'attention_gate_type',
    'swa_attention_gate_type', 'apply_mla_qkv_lora_rescale',
)
# the gate types a mixer may carry: none, or one sigmoid a head
GATES = (None, 'headwise')


@dataclass(frozen=True)
class Latent:
    """One layer kind's latent attention: its widths and rotary θ, the keys
    a query sees (``window``: the last ``window`` positions, its own among
    them; ``index_topk``: the indexer's selection; neither: all before
    it), the head gate and the latent rescale."""
    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    window: Optional[int] = None
    gated: bool = False
    rescale: bool = False
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0


# -- the mixer ----------------------------------------------------------------

def mla_shapes(cfg: TrunkConfig, a: str, kind: str
               ) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of one layer's latent attention under prefix ``a``."""
    m, d = cfg.latent(kind), cfg.hidden_size
    shapes = {
        f'{a}.q_a_proj.weight': (d, m.q_lora_rank),
        f'{a}.q_a_layernorm.weight': (m.q_lora_rank,),
        f'{a}.q_b_proj.weight': (m.q_lora_rank, m.heads * (m.nope + m.rope)),
        f'{a}.kv_a_proj_with_mqa.weight': (d, m.kv_lora_rank + m.rope),
        f'{a}.kv_a_layernorm.weight': (m.kv_lora_rank,),
        f'{a}.kv_b_proj.weight': (m.kv_lora_rank, m.heads * (m.nope + m.v)),
        f'{a}.o_proj.weight': (m.heads * m.v, d),
    }
    if m.gated:
        shapes[f'{a}.gate_proj.weight'] = (d, m.heads)
    if m.index_topk:
        i = f'{a}.indexer'
        shapes.update({
            f'{i}.wq_b.weight': (m.q_lora_rank, m.index_heads * m.index_dim),
            f'{i}.wk.weight': (d, m.index_dim),
            f'{i}.k_norm.weight': (m.index_dim,),
            f'{i}.k_norm.bias': (m.index_dim,),
            f'{i}.weights_proj.weight': (d, m.index_heads),
        })
    return shapes


def _causal_path(cfg: TrunkConfig, kind: str, platform: str, s: int,
                 precision: Optional[str]) -> str:
    """``resolve_causal``'s answer for a layer of ``kind`` over ``s``
    positions: its head widths, its window, whether it takes a selection."""
    m = cfg.latent(kind)
    return resolve_causal(platform, s, m.nope + m.rope, m.v, precision, 1, 1,
                          m.window, bool(m.index_topk))


def _index_path(cfg: TrunkConfig, kind: str, platform: str, s: int,
                precision: Optional[str]) -> str:
    """``resolve_index``'s answer for the indexer of a layer of ``kind``
    over ``s`` positions."""
    m = cfg.latent(kind)
    return resolve_index(platform, s, m.index_heads, m.index_dim, BLOCK,
                         precision)


def _threshold_path(cfg: TrunkConfig, kind: str, platform: str, s: int,
                    precision: Optional[str]) -> str:
    """Which form finds the thresholds of that indexer: the kernel reads
    ``index_scores``' table, so 'kernel' only beside it and where
    ``resolve_threshold`` says so."""
    if _index_path(cfg, kind, platform, s, precision) != 'kernel':
        return 'xla'
    return resolve_threshold(platform, s, BLOCK)


def _head_columns(w: jax.Array, h: int, lo: int, hi: int) -> jax.Array:
    """Columns ``lo:hi`` of every head of a (in, h·d) projection, as
    (in, h·(hi − lo)): the product of an activation with it writes that
    column group of all heads and nothing else."""
    return w.reshape(w.shape[0], h, -1)[:, :, lo:hi].reshape(w.shape[0], -1)


def mixer_scope(cfg: TrunkConfig, kind: str):
    """The scope a latent attention mixer opens: ``mla`` (joyai), or
    dots3_note's by kind. Each a literal: ``obs/scopes.py`` pins the
    vocabulary."""
    if not cfg.dialect.scope_by_kind:
        return jax.named_scope('mla')
    if kind == SLIDING:
        return jax.named_scope('window_mla')
    return jax.named_scope('sparse_mla')


def mla_block(p: Params, prefix: str, x: jax.Array, cfg: TrunkConfig,
              attn_block: int = 1024,
              platform: Optional[str] = None,
              kind: str = FULL) -> jax.Array:
    """Latent attention over one window: (S, D) → (S, D), causal, positions
    0…S-1, a layer of ``kind`` (its widths, window, selection, gate and
    rescale: :meth:`TrunkConfig.latent`). ``platform`` is where the graph
    will run (None: the default backend); with the shapes and the ambient
    matmul precision it decides the causal path
    (``ops.attention.resolve_causal``): the fused kernel where it applies,
    the XLA tiles of ``blockwise_attention`` elsewhere; and so the
    indexer's scores (``ops.sparse_index.resolve_index``) and thresholds
    (``resolve_threshold``)."""
    return _mla(p, prefix, x, cfg, attn_block, platform, kind)[0]


def _mla(p: Params, prefix: str, x: jax.Array, cfg: TrunkConfig,
         attn_block: int, platform: Optional[str], kind: str
         ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """:func:`mla_block`'s output, and where the layer's indexer selects
    the rows whose threshold the tie rule settled (None without one)."""
    m = cfg.latent(kind)
    platform = platform or jax.default_backend()
    precision = jax.config.jax_default_matmul_precision
    with mixer_scope(cfg, kind):
        s = x.shape[0]
        h, dn, dr, dv = m.heads, m.nope, m.rope, m.v
        eps = cfg.rms_norm_eps
        c_q = rms_norm(jnp.dot(x, p[f'{prefix}.q_a_proj.weight']),
                       p[f'{prefix}.q_a_layernorm.weight'], eps)
        keep = tied = None
        if m.index_topk:
            kernel = _index_path(cfg, kind, platform, s,
                                 precision) == 'kernel'
            with jax.named_scope('mla_indexer'):
                i = f'{prefix}.indexer'
                keep, tied = select_keys(
                    x, c_q, p[f'{i}.wq_b.weight'], p[f'{i}.wk.weight'],
                    p[f'{i}.k_norm.weight'], p[f'{i}.k_norm.bias'],
                    p[f'{i}.weights_proj.weight'], heads=m.index_heads,
                    dim=m.index_dim, rope=dr, topk=m.index_topk,
                    theta=m.theta,
                    kernel_passes=KERNEL_PASSES[precision] if kernel
                    else None,
                    threshold_kernel=_threshold_path(
                        cfg, kind, platform, s, precision) == 'kernel')
                keep = keep[None]
        if m.rescale:
            c_q = c_q * math.sqrt(cfg.hidden_size / m.q_lora_rank)
        if _causal_path(cfg, kind, platform, s, precision) == 'kernel':
            out = _mla_kernel_path(p, prefix, x, c_q, cfg, m, precision,
                                   keep)
        else:
            out = _mla_xla_path(p, prefix, x, c_q, cfg, m, attn_block, keep)
        out = out.reshape(s, h * dv)
        if m.gated:
            gate = jax.nn.sigmoid(jnp.dot(x, p[f'{prefix}.gate_proj.weight']))
            out = (out.reshape(s, h, dv) * gate[..., None]).reshape(s, h * dv)
        return jnp.dot(out, p[f'{prefix}.o_proj.weight']), tied


def sparse_mla_block(p: Params, prefix: str, x: jax.Array, cfg: TrunkConfig,
                     attn_block: int = 1024,
                     platform: Optional[str] = None,
                     kind: str = FULL) -> Tuple[jax.Array, jax.Array]:
    """:func:`mla_block` of a layer the indexer selects for, and a (4,)
    count: the window's query blocks the indexer scored and how many of
    them through the kernel (what ``index_kernel`` counts); the scored rows
    whose threshold the tie rule settled and the scored rows (what
    ``topk_ties`` counts)."""
    out, tied = _mla(p, prefix, x, cfg, attn_block, platform, kind)
    m, s = cfg.latent(kind), x.shape[0]
    scored = len(scored_blocks(s, m.index_topk)) if m.index_topk else 0
    kernel = scored and _index_path(
        cfg, kind, platform or jax.default_backend(), s,
        jax.config.jax_default_matmul_precision) == 'kernel'
    count = jnp.array([scored, scored if kernel else 0, 0,
                       scored * min(BLOCK, s)], jnp.int32)
    return out, count if tied is None else count.at[2].set(tied)


def count_selection(tracer, counter, cfg: TrunkConfig, tokens: int) -> None:
    """One fetched ``dots3_note`` step's counter — (the expert layers'
    counts, the full layers' ``(layers, 4)`` indexer counts) → the stage
    table: the expert rows (``token_trunk.count_experts``),
    ``index_kernel``, query blocks scored through the kernel ÷ query blocks
    scored, and ``topk_ties``, scored rows whose threshold the tie rule
    settled ÷ scored rows."""
    experts, index = counter
    token_trunk.count_experts(tracer, experts, cfg, tokens)
    scored, through, tied, rows = (
        int(c) for c in np.asarray(index).reshape(-1, 4).sum(axis=0))
    tracer.add_occupancy('index_kernel', through, scored)
    tracer.add_occupancy('topk_ties', tied, rows)


def _latent_kv(p: Params, prefix: str, x: jax.Array, cfg: TrunkConfig,
               m: Latent) -> Tuple[jax.Array, jax.Array]:
    """(the normed — and under the rescale, rescaled — kv latent, the
    rotary key's columns before rotation)."""
    kv_a = jnp.dot(x, p[f'{prefix}.kv_a_proj_with_mqa.weight'])
    c_kv = rms_norm(kv_a[:, :m.kv_lora_rank],
                    p[f'{prefix}.kv_a_layernorm.weight'], cfg.rms_norm_eps)
    if m.rescale:
        c_kv = c_kv * math.sqrt(cfg.hidden_size / m.kv_lora_rank)
    return c_kv, kv_a[:, m.kv_lora_rank:]


def _mla_xla_path(p: Params, prefix: str, x: jax.Array, c_q: jax.Array,
                  cfg: TrunkConfig, m: Latent, attn_block: int,
                  keep: Optional[jax.Array]) -> jax.Array:
    """The heads' output (S, H, d_v) through the XLA tiles."""
    s = x.shape[0]
    h, dn, dr, dv = m.heads, m.nope, m.rope, m.v
    q = jnp.dot(c_q, p[f'{prefix}.q_b_proj.weight']).reshape(s, h, dn + dr)
    c_kv, k_rope = _latent_kv(p, prefix, x, cfg, m)
    k_rope = k_rope.reshape(s, 1, dr)
    kv = jnp.dot(c_kv, p[f'{prefix}.kv_b_proj.weight']).reshape(s, h, dn + dv)
    positions = jnp.arange(s)
    q_rope = rotary_interleaved(q[..., dn:], positions, m.theta)
    k_rope = rotary_interleaved(k_rope, positions, m.theta)
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (s, h, dr))], axis=-1)
    return blockwise_attention(q[None], k[None], kv[None, ..., dn:],
                               block_size=min(attn_block, s), causal=True,
                               window=m.window, keep=keep)[0]


def _mla_kernel_path(p: Params, prefix: str, x: jax.Array, c_q: jax.Array,
                     cfg: TrunkConfig, m: Latent, precision: Optional[str],
                     keep: Optional[jax.Array]) -> jax.Array:
    """The same attention through ``ops/pallas_attention.py``. The kernel
    reads a head's columns as (tile, width) slabs, heads-major, so each
    column group it takes — q's and k's nope and rope parts, v — is written
    by a product of its own (``q_b``'s and ``kv_b``'s columns regrouped, a
    pass over the weights, not over 200 MB of activations), 128-wide groups
    straight into that layout; the nope ‖ rope concatenation and the rotary
    key's copy for every head are never written: the kernel takes the parts,
    and one rotary key for all heads."""
    from video_features_tpu.ops.pallas_attention import causal_attention
    s = x.shape[0]
    h, dn, dr, dv = m.heads, m.nope, m.rope, m.v
    w_q, w_kv = p[f'{prefix}.q_b_proj.weight'], p[f'{prefix}.kv_b_proj.weight']
    c_kv, k_rope = _latent_kv(p, prefix, x, cfg, m)
    positions = jnp.arange(s)
    q_nope = jnp.dot(c_q, _head_columns(w_q, h, 0, dn)).reshape(s, h, dn)
    q_rope = rotary_interleaved(
        jnp.dot(c_q, _head_columns(w_q, h, dn, dn + dr)).reshape(s, h, dr),
        positions, m.theta)
    k_nope = jnp.dot(c_kv, _head_columns(w_kv, h, 0, dn)).reshape(s, h, dn)
    k_rope = rotary_interleaved(k_rope.reshape(s, 1, dr), positions, m.theta)
    v = jnp.dot(c_kv, _head_columns(w_kv, h, dn, dn + dv)).reshape(s, h, dv)
    return causal_attention((q_nope[None], q_rope[None]),
                            (k_nope[None], k_rope[None]), v[None],
                            (dn + dr) ** -0.5, KERNEL_PASSES[precision],
                            window=m.window, keep=keep)[0]



# -- the dialects -------------------------------------------------------------

MLA = Mixer(mla_block, mla_shapes)
SPARSE_MLA = Mixer(sparse_mla_block, mla_shapes, counted=True)
DIALECTS = {
    'joyai_llm_flash': Dialect(mixers={FULL: MLA}, config_keys=CONFIG_KEYS,
                               row_blocked_mlp=False),
    'dots3_note': Dialect(
        mixers={FULL: SPARSE_MLA, SLIDING: MLA},
        config_keys=DOTS3_CONFIG_KEYS,
        optional=('n_experts_held', 'first_expert', 'attention_gate_type',
                  'swa_attention_gate_type'),
        scope_by_kind=True,
        counter=(SELECTION_COUNTER, count_selection)),
}


@dataclass(frozen=True)
class TrunkConfig(BaseConfig):
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    n_experts_held: Optional[int] = None     # None: all of them
    first_expert: int = 0
    # dots3_note's (None: a joyai trunk, every layer full, no indexer)
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window_size: Optional[int] = None
    swa_num_attention_heads: Optional[int] = None
    swa_q_lora_rank: Optional[int] = None
    swa_kv_lora_rank: Optional[int] = None
    swa_qk_nope_head_dim: Optional[int] = None
    swa_qk_rope_head_dim: Optional[int] = None
    swa_v_head_dim: Optional[int] = None
    swa_rope_theta: Optional[float] = None
    index_n_heads: Optional[int] = None
    index_head_dim: Optional[int] = None
    index_topk: Optional[int] = None
    attention_gate_type: Optional[str] = None
    swa_attention_gate_type: Optional[str] = None
    apply_mla_qkv_lora_rescale: bool = False
    model_type: str = MODEL_TYPE

    dialects = DIALECTS
    window_key = 'sliding_window_size'
    eps = property(attrgetter('rms_norm_eps'))
    routed_experts = property(attrgetter('n_routed_experts'))
    shared_experts = property(attrgetter('n_shared_experts'))

    def __post_init__(self):
        object.__setattr__(self, 'n_experts_held', token_trunk.held_experts(
            self.n_experts_held, self.first_expert, self.n_routed_experts))
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError('num_experts_per_tok exceeds n_routed_experts')
        self.check_layers()
        for key in ('attention_gate_type', 'swa_attention_gate_type'):
            if getattr(self, key) not in GATES:
                raise ValueError(
                    f'{key}={getattr(self, key)!r}: the trunk gates a '
                    f'mixer\'s heads headwise or not at all')

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def latent(self, kind: str = FULL) -> Latent:
        """The latent attention of a layer of ``kind``."""
        rescale = bool(self.apply_mla_qkv_lora_rescale)
        if kind == SLIDING:
            return Latent(
                self.swa_num_attention_heads, self.swa_q_lora_rank,
                self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                self.swa_rope_theta, window=self.sliding_window_size,
                gated=self.swa_attention_gate_type == 'headwise',
                rescale=rescale)
        return Latent(
            self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta, gated=self.attention_gate_type == 'headwise',
            rescale=rescale, index_heads=self.index_n_heads or 0,
            index_dim=self.index_head_dim or 0,
            index_topk=self.index_topk or 0)


# -- what the build says and counts --------------------------------------------

def init_params(cfg: TrunkConfig, seed: int = 0) -> Dict[str, np.ndarray]:
    """Seeded random parameters (``token_trunk.draw_params``), and a small
    router bias and indexer key-norm bias."""
    def small_bias(name, shape, rng):
        if name.endswith(('e_score_correction_bias', 'k_norm.bias')):
            return 0.05 * rng.standard_normal(shape, dtype=np.float32)
        return None
    return token_trunk.draw_params(param_shapes(cfg), seed, small_bias)


def describe(cfg: TrunkConfig) -> str:
    kinds = ''
    if cfg.model_type != MODEL_TYPE:
        kinds = ' (' + ' + '.join(f'{n} {kind}'
                                  for kind, n in cfg.kinds().items()) + ')'
    return (f'{cfg.num_hidden_layers} layers{kinds} and {cfg.n_experts_held} '
            f'of {cfg.n_routed_experts} experts a layer')


def kernels(cfg: TrunkConfig, platform: str, window_ids: int,
            precision: Optional[str]) -> Dict[str, object]:
    """Which causal path the step compiles ('kernel' or 'xla':
    ``ops.attention.resolve_causal``, from the platform, the window's
    shapes and the matmul precision). All or nothing per program, or where
    the dialect has two layer kinds per kind, named by the kernel's lane
    (``sparse_attention`` for the selected full layers,
    ``window_attention`` for the sliding ones): it is the kernel's
    engagement counter. Beside them the indexer's two forms, where the
    full layers select: ``index_scores`` and ``topk_threshold``."""
    if cfg.model_type == MODEL_TYPE:
        return {'causal_attention': _causal_path(cfg, FULL, platform,
                                                 window_ids, precision)}
    notes: Dict[str, object] = {}
    for kind, n in cfg.kinds().items():
        if n:
            lane = 'window_attention' if kind == SLIDING else \
                'sparse_attention'
            notes[lane] = _causal_path(cfg, kind, platform, window_ids,
                                       precision)
    if cfg.kinds().get(FULL) and cfg.latent(FULL).index_topk:
        notes['index_scores'] = _index_path(cfg, FULL, platform, window_ids,
                                            precision)
        notes['topk_threshold'] = _threshold_path(cfg, FULL, platform,
                                                  window_ids, precision)
    notes['layers'] = ', '.join(f'{kind} {n}'
                                for kind, n in cfg.kinds().items())
    return notes


# the step's per-expert counts → moe_route, moe_held and moe_walk
count = token_trunk.count_experts
