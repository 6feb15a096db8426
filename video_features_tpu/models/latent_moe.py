"""A sparse-expert, latent-attention token trunk (DeepSeek-V3 lineage).

The decoder trunk of ``joyai_llm_flash`` / ``deepseek_v3``-style language
models as a feature extractor: token ids in, one hidden-state row a window
out. Pre-norm residual blocks, RMSNorm, no biases:

* **latent attention (MLA)** — queries through a low-rank bottleneck
  (``q_lora_rank``), keys and values expanded from one shared latent
  (``kv_lora_rank``) plus one rotary key head shared by all heads; heads of
  ``qk_nope_head_dim + qk_rope_head_dim`` for q/k and ``v_head_dim`` for v;
  rotary on the rope dims only, interleaved pairs; causal. Prefill only —
  no cache, so the expanded form (``ops.attention.blockwise_attention``).
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

Parameters are a flat ``{dotted name: array}`` dict under the checkpoint's
own names (``model.layers.3.self_attn.q_b_proj.weight`` …), matrices as
(in, out); a layer's held experts are stacked: ``mlp.experts.gate_proj.weight``
is (held, hidden, moe_intermediate).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from video_features_tpu.models import token_trunk
from video_features_tpu.models.token_trunk import (
    Params, embed, final_norm, mean_features, rms_norm, swiglu,
)
from video_features_tpu.ops import moe
from video_features_tpu.ops.attention import (
    KERNEL_PASSES, blockwise_attention, resolve_causal, rotary_interleaved,
)

MODEL_TYPE = 'joyai_llm_flash'
# the step's second output: (expert layers, held) assignment counts of the
# batch
COUNTER = 'moe_counts'
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


@dataclass(frozen=True)
class TrunkConfig:
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

    model_type = MODEL_TYPE

    def __post_init__(self):
        object.__setattr__(self, 'n_experts_held', token_trunk.held_experts(
            self.n_experts_held, self.first_expert, self.n_routed_experts))
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError('num_experts_per_tok exceeds n_routed_experts')

    @classmethod
    def from_args(cls, args) -> 'TrunkConfig':
        values = {k: args.get(k) for k in CONFIG_KEYS}
        values['first_expert'] = values['first_expert'] or 0
        missing = [k for k, v in values.items()
                   if v is None and k != 'n_experts_held']
        if missing:
            raise ValueError(f'the lm trunk needs config keys {missing}')
        return cls(**values)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace


def param_shapes(cfg: TrunkConfig) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of every parameter held, in checkpoint order."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    shapes: Dict[str, Tuple[int, ...]] = {
        'model.embed_tokens.weight': (cfg.vocab_size, d)}
    for i in range(cfg.num_hidden_layers):
        p = f'model.layers.{i}'
        a = f'{p}.self_attn'
        shapes.update({
            f'{p}.input_layernorm.weight': (d,),
            f'{a}.q_a_proj.weight': (d, cfg.q_lora_rank),
            f'{a}.q_a_layernorm.weight': (cfg.q_lora_rank,),
            f'{a}.q_b_proj.weight': (cfg.q_lora_rank, h * cfg.qk_head_dim),
            f'{a}.kv_a_proj_with_mqa.weight':
                (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            f'{a}.kv_a_layernorm.weight': (cfg.kv_lora_rank,),
            f'{a}.kv_b_proj.weight':
                (cfg.kv_lora_rank,
                 h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            f'{a}.o_proj.weight': (h * cfg.v_head_dim, d),
            f'{p}.post_attention_layernorm.weight': (d,),
        })
        m = f'{p}.mlp'
        if cfg.is_dense(i):
            f = cfg.intermediate_size
            shapes.update({f'{m}.gate_proj.weight': (d, f),
                           f'{m}.up_proj.weight': (d, f),
                           f'{m}.down_proj.weight': (f, d)})
            continue
        f, e = cfg.moe_intermediate_size, cfg.n_experts_held
        shapes.update({
            f'{m}.gate.weight': (d, cfg.n_routed_experts),
            f'{m}.gate.e_score_correction_bias': (cfg.n_routed_experts,),
            f'{m}.experts.gate_proj.weight': (e, d, f),
            f'{m}.experts.up_proj.weight': (e, d, f),
            f'{m}.experts.down_proj.weight': (e, f, d),
        })
        if cfg.n_shared_experts:
            fs = f * cfg.n_shared_experts
            shapes.update({f'{m}.shared_experts.gate_proj.weight': (d, fs),
                           f'{m}.shared_experts.up_proj.weight': (d, fs),
                           f'{m}.shared_experts.down_proj.weight': (fs, d)})
    shapes['model.norm.weight'] = (d,)
    return shapes


def param_count(cfg: TrunkConfig) -> int:
    return token_trunk.param_count(param_shapes(cfg))


def init_params(cfg: TrunkConfig, seed: int = 0) -> Dict[str, np.ndarray]:
    """Seeded random parameters (``token_trunk.draw_params``), and a small
    router bias."""
    def router_bias(name, shape, rng):
        if name.endswith('e_score_correction_bias'):
            return 0.05 * rng.standard_normal(shape, dtype=np.float32)
        return None
    return token_trunk.draw_params(param_shapes(cfg), seed, router_bias)


def describe(cfg: TrunkConfig) -> str:
    return (f'{cfg.num_hidden_layers} layers and {cfg.n_experts_held} of '
            f'{cfg.n_routed_experts} experts a layer')


def kernels(cfg: TrunkConfig, platform: str, window_ids: int,
            precision: Optional[str]) -> Dict[str, object]:
    """Which causal path the step compiles ('kernel' or 'xla':
    ``ops.attention.resolve_causal``, from the platform, the window's
    shapes and the matmul precision). All or nothing per program: it is
    the kernel's engagement counter."""
    return {'causal_attention': resolve_causal(
        platform, window_ids, cfg.qk_head_dim, cfg.v_head_dim, precision)}


def count(tracer, counts: np.ndarray, cfg: TrunkConfig, tokens: int) -> None:
    """The step's per-expert counts → ``moe_route``, ``moe_held`` and
    ``moe_walk`` (``token_trunk.count_experts``)."""
    token_trunk.count_experts(tracer, counts, cfg.num_experts_per_tok, tokens,
                              moe.BLOCK)


# -- blocks -------------------------------------------------------------------

def _head_columns(w: jax.Array, h: int, lo: int, hi: int) -> jax.Array:
    """Columns ``lo:hi`` of every head of a (in, h·d) projection, as
    (in, h·(hi − lo)): the product of an activation with it writes that
    column group of all heads and nothing else."""
    return w.reshape(w.shape[0], h, -1)[:, :, lo:hi].reshape(w.shape[0], -1)


def mla_block(p: Params, prefix: str, x: jax.Array, cfg: TrunkConfig,
              attn_block: int = 1024,
              platform: Optional[str] = None) -> jax.Array:
    """Latent attention over one window: (S, D) → (S, D), causal, positions
    0…S-1. ``platform`` is where the graph will run (None: the default
    backend); with the shapes and the ambient matmul precision it decides
    the causal path (``ops.attention.resolve_causal``): the fused kernel
    where it applies, the XLA tiles of ``blockwise_attention`` elsewhere."""
    with jax.named_scope('mla'):
        s = x.shape[0]
        h, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        eps = cfg.rms_norm_eps
        c_q = rms_norm(jnp.dot(x, p[f'{prefix}.q_a_proj.weight']),
                       p[f'{prefix}.q_a_layernorm.weight'], eps)
        precision = jax.config.jax_default_matmul_precision
        if resolve_causal(platform or jax.default_backend(), s, dn + dr, dv,
                          precision) == 'kernel':
            return _mla_kernel_path(p, prefix, x, c_q, cfg, precision)
        q = jnp.dot(c_q, p[f'{prefix}.q_b_proj.weight']).reshape(s, h, dn + dr)
        kv_a = jnp.dot(x, p[f'{prefix}.kv_a_proj_with_mqa.weight'])
        c_kv = rms_norm(kv_a[:, :cfg.kv_lora_rank],
                        p[f'{prefix}.kv_a_layernorm.weight'], eps)
        k_rope = kv_a[:, cfg.kv_lora_rank:].reshape(s, 1, dr)
        kv = jnp.dot(c_kv, p[f'{prefix}.kv_b_proj.weight']
                     ).reshape(s, h, dn + dv)
        positions = jnp.arange(s)
        q_rope = rotary_interleaved(q[..., dn:], positions, cfg.rope_theta)
        k_rope = rotary_interleaved(k_rope, positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (s, h, dr))], axis=-1)
        out = blockwise_attention(q[None], k[None], kv[None, ..., dn:],
                                  block_size=min(attn_block, s),
                                  causal=True)[0]
        return jnp.dot(out.reshape(s, h * dv), p[f'{prefix}.o_proj.weight'])


def _mla_kernel_path(p: Params, prefix: str, x: jax.Array, c_q: jax.Array,
                     cfg: TrunkConfig, precision: Optional[str]) -> jax.Array:
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
    h, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    w_q, w_kv = p[f'{prefix}.q_b_proj.weight'], p[f'{prefix}.kv_b_proj.weight']
    kv_a = jnp.dot(x, p[f'{prefix}.kv_a_proj_with_mqa.weight'])
    c_kv = rms_norm(kv_a[:, :cfg.kv_lora_rank],
                    p[f'{prefix}.kv_a_layernorm.weight'], cfg.rms_norm_eps)
    positions = jnp.arange(s)
    q_nope = jnp.dot(c_q, _head_columns(w_q, h, 0, dn)).reshape(s, h, dn)
    q_rope = rotary_interleaved(
        jnp.dot(c_q, _head_columns(w_q, h, dn, dn + dr)).reshape(s, h, dr),
        positions, cfg.rope_theta)
    k_nope = jnp.dot(c_kv, _head_columns(w_kv, h, 0, dn)).reshape(s, h, dn)
    k_rope = rotary_interleaved(kv_a[:, cfg.kv_lora_rank:].reshape(s, 1, dr),
                                positions, cfg.rope_theta)
    v = jnp.dot(c_kv, _head_columns(w_kv, h, dn, dn + dv)).reshape(s, h, dv)
    out = causal_attention((q_nope[None], q_rope[None]),
                           (k_nope[None], k_rope[None]), v[None],
                           (dn + dr) ** -0.5, KERNEL_PASSES[precision])[0]
    return jnp.dot(out.reshape(s, h * dv), p[f'{prefix}.o_proj.weight'])


def expert_block(p: Params, prefix: str, x: jax.Array, cfg: TrunkConfig,
                 moe_block: int = moe.BLOCK) -> Tuple[jax.Array, jax.Array]:
    """The expert layer's feed-forward over (T, D) tokens: the held
    experts' share of the routed sum (``ops.moe.routed_experts``, under this
    checkpoint's names) plus the shared expert. Returns the output and the
    (held,) assignment counts."""
    with jax.named_scope('moe'):
        y, counts = moe.routed_experts(
            x, p[f'{prefix}.gate.weight'],
            p[f'{prefix}.gate.e_score_correction_bias'],
            p[f'{prefix}.experts.gate_proj.weight'],
            p[f'{prefix}.experts.up_proj.weight'],
            p[f'{prefix}.experts.down_proj.weight'],
            top_k=cfg.num_experts_per_tok,
            scaling=cfg.routed_scaling_factor,
            normalise=cfg.norm_topk_prob, eps=1e-20,
            first=cfg.first_expert, block=moe_block)
        if cfg.n_shared_experts:
            y = y + swiglu(x, p, f'{prefix}.shared_experts')
        return y, counts


def hidden_states(params: Params, ids: jax.Array, cfg: TrunkConfig,
                  attn_block: int = 1024, moe_block: int = moe.BLOCK,
                  platform: Optional[str] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """(B, S) int32 ids → ``(final-norm hidden states (B, S, D), counts)``.

    ``counts`` is (expert layers, held) int32: the batch's assignments on
    each held expert, layer by layer (zero rows when no layer has experts).
    Attention runs a window at a time (its tiles are the memory that
    matters); the feed-forward takes all B·S tokens at once, so an expert
    sees the whole batch's assignments in one grouped product."""
    b, s = ids.shape
    d = cfg.hidden_size
    eps = cfg.rms_norm_eps
    x = embed(params, ids)                                  # (B, S, D)
    counts = []
    for i in range(cfg.num_hidden_layers):
        p = f'model.layers.{i}'
        normed = rms_norm(x, params[f'{p}.input_layernorm.weight'], eps)
        x = x + lax.map(
            lambda w: mla_block(params, f'{p}.self_attn', w, cfg, attn_block,
                                platform),
            normed)
        normed = rms_norm(x, params[f'{p}.post_attention_layernorm.weight'],
                          eps).reshape(b * s, d)
        if cfg.is_dense(i):
            with jax.named_scope('dense_mlp'):
                y = swiglu(normed, params, f'{p}.mlp')
        else:
            y, c = expert_block(params, f'{p}.mlp', normed, cfg, moe_block)
            counts.append(c)
        x = x + y.reshape(b, s, d)
    counts = (jnp.stack(counts) if counts
              else jnp.zeros((0, cfg.n_experts_held), jnp.int32))
    return final_norm(x, params, eps), counts


def forward(params: Params, ids: jax.Array, cfg: TrunkConfig,
            attn_block: int = 1024, moe_block: int = moe.BLOCK,
            platform: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """(B, S) int32 ids → ``(features (B, D) float32, counts)``: the mean
    of the window's final-norm hidden states (:func:`hidden_states`)."""
    x, counts = hidden_states(params, ids, cfg, attn_block, moe_block,
                              platform)
    return mean_features(x), counts
