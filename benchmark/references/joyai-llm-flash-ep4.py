"""Plain reference: the JoyAI-LLM-Flash trunk, one chip's share of it.

Written from the published ``config.json`` (``model_type: joyai_llm_flash``,
https://huggingface.co/jdopensource/JoyAI-LLM-Flash) and the DeepSeek-V3
modelling code its keys come from. Pre-norm residual blocks, RMSNorm
(eps 1e-6), no biases, float32 through ``Ops('highest')``:

* latent attention: ``c_q = rms(x W_qa)``; ``q = c_q W_qb`` → heads of
  (nope ‖ rope); ``[c_kv ‖ k_r] = x W_kva``; ``c_kv = rms(c_kv)``;
  ``[k_nope ‖ v] = c_kv W_kvb``; ``k_r`` is one head shared by all. Rotary
  on the rope dims, interleaved pairs, theta 32e6, positions 0…S-1;
  ``softmax_causal(q·k / sqrt(192)) v`` → ``W_o``. A query block at a time
  against the keys up to its end, so that the scores fit.
* layer 0: a dense SwiGLU. Later layers: ``s = sigmoid(x W_g)`` in float32
  at highest whatever the mode (the product decides a discrete choice);
  the 8 largest of ``s + b``; weights = the chosen ``s`` over their sum,
  × 2.5; every *held* expert computed densely over all tokens, one after
  another, and weighted by its column of the gate; plus the shared expert.
* the share: of the router's 256 experts this chip holds ``n_routed_experts``
  (64: experts 0–63, four chips share a layer). What the others would add
  is left out, and that partial result goes on.
* output: final RMSNorm, mean over the window's positions.

Departures from the published model: the output head and the multi-token
prediction module are not run (a feature extractor saves hidden states);
depth is 1 dense + 4 expert layers. The ids are traffic, cut from the
decoded frames (no tokeniser ships with the config): of each RGB frame the
centred region of ``16·(H div 16)`` × ``16·(W div 16)`` pixels in a 16 × 16
grid of patches, ``id = ((sum of the patch's bytes) · 2654435761 mod 2^32)
mod vocab``, patches row-major, 32 frames a window → 8,192 ids.

``CFG`` holds the sizes; a test at a tiny size replaces it.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from _video import read_frames

# published widths; ``layers``, ``n_routed_experts`` (held here) and the
# window are the cut (benchmark/configs/joyai-llm-flash-ep4.json)
CFG = {
    'vocab_size': 129280, 'hidden_size': 2048, 'layers': 5,
    'first_k_dense_replace': 1, 'intermediate_size': 7168,
    'moe_intermediate_size': 768, 'router_experts': 256,
    'n_routed_experts': 64, 'first_expert': 0, 'n_shared_experts': 1,
    'num_experts_per_tok': 8, 'routed_scaling_factor': 2.5,
    'num_attention_heads': 32, 'q_lora_rank': 1536, 'kv_lora_rank': 512,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'v_head_dim': 128,
    'rope_theta': 32000000.0, 'rms_norm_eps': 1e-6,
    'frames': 32, 'patch_grid': 16, 'query_block': 1024,
}
HASH = 2654435761
UNIT = 'window'
FEATURE_DIM = CFG['hidden_size']


def _c(cfg):
    return CFG if cfg is None else cfg


def window_ids(cfg=None) -> int:
    c = _c(cfg)
    return c['frames'] * c['patch_grid'] ** 2


# -- parameters ---------------------------------------------------------------

def param_specs(cfg=None):
    """{checkpoint key of the program's config: parameter list}. A matrix
    is N(0, 1/fan_in) over its contracted axis (``linear`` draws
    sqrt(2 / prod(shape[:-1])) × scale, so the scale undoes the 2 and, for
    the stacked experts and the embedding, the leading axis); the embedding
    N(0, 1); norm gains in [0.8, 1.2]; the router's bias N(0, 0.05)."""
    c = _c(cfg)
    d, h = c['hidden_size'], c['num_attention_heads']
    dqk = c['qk_nope_head_dim'] + c['qk_rope_head_dim']
    lin = math.sqrt(0.5)
    specs = [('model.embed_tokens.weight', 'linear', (c['vocab_size'], d),
              math.sqrt(c['vocab_size'] / 2.0))]
    for i in range(c['layers']):
        p, a, m = (f'model.layers.{i}', f'model.layers.{i}.self_attn',
                   f'model.layers.{i}.mlp')
        specs += [
            (f'{p}.input_layernorm.weight', 'bn_weight', (d,), 1.0),
            (f'{a}.q_a_proj.weight', 'linear', (d, c['q_lora_rank']), lin),
            (f'{a}.q_a_layernorm.weight', 'bn_weight',
             (c['q_lora_rank'],), 1.0),
            (f'{a}.q_b_proj.weight', 'linear',
             (c['q_lora_rank'], h * dqk), lin),
            (f'{a}.kv_a_proj_with_mqa.weight', 'linear',
             (d, c['kv_lora_rank'] + c['qk_rope_head_dim']), lin),
            (f'{a}.kv_a_layernorm.weight', 'bn_weight',
             (c['kv_lora_rank'],), 1.0),
            (f'{a}.kv_b_proj.weight', 'linear',
             (c['kv_lora_rank'],
              h * (c['qk_nope_head_dim'] + c['v_head_dim'])), lin),
            (f'{a}.o_proj.weight', 'linear', (h * c['v_head_dim'], d), lin),
            (f'{p}.post_attention_layernorm.weight', 'bn_weight', (d,), 1.0),
        ]
        if i < c['first_k_dense_replace']:
            f = c['intermediate_size']
            specs += [(f'{m}.gate_proj.weight', 'linear', (d, f), lin),
                      (f'{m}.up_proj.weight', 'linear', (d, f), lin),
                      (f'{m}.down_proj.weight', 'linear', (f, d), lin)]
            continue
        f, e = c['moe_intermediate_size'], c['n_routed_experts']
        stacked = math.sqrt(e / 2.0)
        specs += [
            (f'{m}.gate.weight', 'linear', (d, c['router_experts']), lin),
            (f'{m}.gate.e_score_correction_bias', 'bias',
             (c['router_experts'],), 0.5),
            (f'{m}.experts.gate_proj.weight', 'linear', (e, d, f), stacked),
            (f'{m}.experts.up_proj.weight', 'linear', (e, d, f), stacked),
            (f'{m}.experts.down_proj.weight', 'linear', (e, f, d), stacked),
        ]
        fs = f * c['n_shared_experts']
        if fs:
            specs += [
                (f'{m}.shared_experts.gate_proj.weight', 'linear', (d, fs),
                 lin),
                (f'{m}.shared_experts.up_proj.weight', 'linear', (d, fs),
                 lin),
                (f'{m}.shared_experts.down_proj.weight', 'linear', (fs, d),
                 lin)]
    specs.append(('model.norm.weight', 'bn_weight', (d,), 1.0))
    return {'checkpoint_path': specs}


# -- from a video file to ids ---------------------------------------------------

def rows_of(n_frames: int, cfg=None) -> int:
    """One row per whole window of ``frames`` frames; a tail is dropped."""
    return int(n_frames) // _c(cfg)['frames']


def tokenise(frames: np.ndarray, cfg=None) -> np.ndarray:
    """(n, H, W, 3) uint8 RGB frames → (n · grid²,) int32 ids."""
    c = _c(cfg)
    g = c['patch_grid']
    n, h, w, _ = frames.shape
    ph, pw = h // g, w // g
    top, left = (h - g * ph) // 2, (w - g * pw) // 2
    region = frames[:, top:top + g * ph, left:left + g * pw]
    sums = region.reshape(n, g, ph, g, pw, 3).sum(axis=(2, 4, 5),
                                                  dtype=np.uint64)
    ids = ((sums * np.uint64(HASH)) % np.uint64(2 ** 32)) \
        % np.uint64(c['vocab_size'])
    return ids.reshape(-1).astype(np.int32)


def load_units(video_path: str, rows, cfg=None) -> np.ndarray:
    """The model inputs of the given rows: (n, window ids) int32."""
    t = _c(cfg)['frames']
    rows = list(rows)
    frames = read_frames(video_path, upto=(max(rows) + 1) * t)
    return np.stack([tokenise(frames[r * t:(r + 1) * t], cfg) for r in rows])


def unit_shape(cfg=None):
    return (window_ids(cfg),), np.int32


# -- the model ----------------------------------------------------------------

def _rms(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rotary(x, theta):
    """(n, S, H, d): pair (x[2i], x[2i+1]) turned by position · theta^(-2i/d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attention(ops, p, a, x, c):
    n, s, _ = x.shape
    h, dn, dr, dv = (c['num_attention_heads'], c['qk_nope_head_dim'],
                     c['qk_rope_head_dim'], c['v_head_dim'])
    eps = c['rms_norm_eps']
    c_q = _rms(ops.einsum('nsd,dr->nsr', x, p[f'{a}.q_a_proj.weight']),
               p[f'{a}.q_a_layernorm.weight'], eps)
    q = ops.einsum('nsr,rf->nsf', c_q, p[f'{a}.q_b_proj.weight']
                   ).reshape(n, s, h, dn + dr)
    kv_a = ops.einsum('nsd,dr->nsr', x, p[f'{a}.kv_a_proj_with_mqa.weight'])
    c_kv = _rms(kv_a[..., :c['kv_lora_rank']],
                p[f'{a}.kv_a_layernorm.weight'], eps)
    k_r = _rotary(kv_a[..., c['kv_lora_rank']:].reshape(n, s, 1, dr),
                  c['rope_theta'])
    kv = ops.einsum('nsr,rf->nsf', c_kv, p[f'{a}.kv_b_proj.weight']
                    ).reshape(n, s, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], c['rope_theta'])],
                        axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (n, s, h, dr))],
                        axis=-1)
    v = kv[..., dn:]
    blk = min(c['query_block'], s)
    outs = []
    for q0 in range(0, s, blk):
        q1 = min(q0 + blk, s)
        scores = ops.einsum('nqhd,nkhd->nhqk', q[:, q0:q1], k[:, :q1]) \
            / math.sqrt(dn + dr)
        visible = (jnp.arange(q0, q1)[:, None] >= jnp.arange(q1)[None, :])
        scores = jnp.where(visible, scores, -jnp.inf)
        outs.append(ops.einsum('nhqk,nkhd->nqhd',
                               jax.nn.softmax(scores, axis=-1), v[:, :q1]))
    out = jnp.concatenate(outs, axis=1).reshape(n, s, h * dv)
    return ops.einsum('nsf,fd->nsd', out, p[f'{a}.o_proj.weight'])


def _swiglu(ops, x, w_gate, w_up, w_down):
    return ops.einsum('nsf,fd->nsd',
                      jax.nn.silu(ops.einsum('nsd,df->nsf', x, w_gate))
                      * ops.einsum('nsd,df->nsf', x, w_up), w_down)


def _gate(ops, p, m, x, c):
    """(n, S, router) float32: a token's weight on each expert, 0 where it
    did not choose it. float32 at highest in every mode."""
    logits = jnp.einsum('nsd,de->nse', x, p[f'{m}.gate.weight'],
                        precision=lax.Precision.HIGHEST)
    ops._count(math.prod(logits.shape) * x.shape[-1])
    s = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(s + p[f'{m}.gate.e_score_correction_bias'],
                          c['num_experts_per_tok'])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + 1e-20) \
        * c['routed_scaling_factor']
    onehot = jax.nn.one_hot(chosen, c['router_experts'], dtype=w.dtype)
    return (onehot * w[..., None]).sum(axis=-2)


def _experts(ops, p, m, x, c):
    held = c['n_routed_experts']
    gate = _gate(ops, p, m, x, c)
    gate = gate[..., c['first_expert']:c['first_expert'] + held]

    def one(y, e):
        w_gate, w_up, w_down, g = e
        return y + g[..., None] * _swiglu(ops, x, w_gate, w_up, w_down), None

    with ops.repeat(held):
        y, _ = lax.scan(one, jnp.zeros_like(x), (
            p[f'{m}.experts.gate_proj.weight'],
            p[f'{m}.experts.up_proj.weight'],
            p[f'{m}.experts.down_proj.weight'],
            jnp.moveaxis(gate, -1, 0)))
    if c['n_shared_experts']:
        y = y + _swiglu(ops, x, p[f'{m}.shared_experts.gate_proj.weight'],
                        p[f'{m}.shared_experts.up_proj.weight'],
                        p[f'{m}.shared_experts.down_proj.weight'])
    return y


def forward(ops, params, units, cfg=None):
    """(n, window ids) int32 → (n, hidden) float32."""
    c = _c(cfg)
    p = params['checkpoint_path']
    eps = c['rms_norm_eps']
    x = p['model.embed_tokens.weight'][units]
    for i in range(c['layers']):
        b = f'model.layers.{i}'
        x = x + _attention(ops, p, f'{b}.self_attn',
                           _rms(x, p[f'{b}.input_layernorm.weight'], eps), c)
        normed = _rms(x, p[f'{b}.post_attention_layernorm.weight'], eps)
        if i < c['first_k_dense_replace']:
            x = x + _swiglu(ops, normed, p[f'{b}.mlp.gate_proj.weight'],
                            p[f'{b}.mlp.up_proj.weight'],
                            p[f'{b}.mlp.down_proj.weight'])
        else:
            x = x + _experts(ops, p, f'{b}.mlp', normed, c)
    return _rms(x, p['model.norm.weight'], eps).mean(axis=1)


# -- the model's work, for step_mfu ---------------------------------------------

def _work_terms(c):
    """(window ids, multiply-adds a visible query-key pair over all heads,
    multiply-adds of one expert for one token, expert layers)."""
    per_pair = c['num_attention_heads'] * (
        c['qk_nope_head_dim'] + c['qk_rope_head_dim'] + c['v_head_dim'])
    expert = 3 * c['hidden_size'] * c['moe_intermediate_size']
    return (window_ids(c), per_pair, expert,
            c['layers'] - c['first_k_dense_replace'])


def reference_waste_macs(cfg=None):
    """(attention, routed) multiply-adds :func:`forward` makes for one
    window — more than the model needs: whole key blocks under the mask,
    and every held expert over every token."""
    c = _c(cfg)
    s, per_pair, expert, n_moe = _work_terms(c)
    blk = min(c['query_block'], s)
    pairs = sum((min(q0 + blk, s) - q0) * min(q0 + blk, s)
                for q0 in range(0, s, blk))
    return (pairs * per_pair * c['layers'],
            s * c['n_routed_experts'] * expert * n_moe)


def model_macs(counted: int, cfg=None) -> int:
    """The model's multiply-adds for one window at even routing, from the
    reference's own count ``counted`` (``Ops.macs`` after tracing one
    window): every contraction outside the routed experts and the attention
    scores as counted; the two attention contractions over the S(S+1)/2
    visible pairs; the routed experts as S · per-token · held/router
    assignments of one expert each."""
    c = _c(cfg)
    s, per_pair, expert, n_moe = _work_terms(c)
    waste_attn, waste_routed = reference_waste_macs(c)
    routed = (s * c['num_experts_per_tok'] * c['n_routed_experts']
              * expert * n_moe) // c['router_experts']
    attn = s * (s + 1) // 2 * per_pair * c['layers']
    return counted - waste_attn - waste_routed + attn + routed
