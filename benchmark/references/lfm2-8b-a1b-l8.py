"""Plain reference: the LFM2-8B-A1B trunk, eight of its twenty-four layers.

Written from the published ``config.json`` (``model_type: lfm2_moe``,
https://huggingface.co/LiquidAI/LFM2-8B-A1B) and ``transformers``'
``models/lfm2/modeling_lfm2.py`` (``Lfm2ShortConv``, ``Lfm2Attention``,
``Lfm2DecoderLayer``, ``Lfm2RMSNorm``, ``apply_rotary_pos_emb``); the
expert layer from that file's ``lfm2_moe`` sibling as the config's keys
spell it. Pre-norm residual blocks, RMSNorm (eps 1e-5), no biases, float32
through ``Ops('highest')``. Layer ``i``:

    h = x + operator_i(rms(x, operator_norm))
    x = h + feed_forward_i(rms(h, ffn_norm))

* ``layer_types[i] == 'conv'`` — the gated short convolution:
  ``[B ‖ C ‖ z] = x W_in`` (three chunks of 2,048); ``u = B ⊙ z``;
  ``c_t = w_0 ⊙ u_{t-2} + w_1 ⊙ u_{t-1} + w_2 ⊙ u_t`` (``nn.Conv1d`` with
  ``groups = hidden``, ``padding = 2``, cut to the first S outputs: zeros
  before position 0), as three explicit shifted terms; ``y = (C ⊙ c) W_out``.
* ``layer_types[i] == 'full_attention'`` — ``q = x W_q`` → 32 heads of 64,
  ``k = x W_k``, ``v = x W_v`` → 8 heads of 64; RMSNorm over a head's 64
  dims with a gain of its own on q and on k; rope in the half-split form
  (``rotate_half``: the pair is ``(x[i], x[i + 32])``), theta 1e6,
  positions 0…S−1; keys and values repeated four times (``repeat_kv``:
  query head j reads key-value head j div 4); ``softmax_causal(q·k / 8) v``
  → ``W_out``. A query block at a time against the keys up to its end, so
  that the scores fit.
* layers 0–1: a dense SwiGLU ``W_2(silu(W_1 x) ⊙ W_3 x)`` of 7,168. Later
  layers: ``s = sigmoid(x W_g)`` in float32 at highest whatever the mode
  (the product decides a discrete choice); the 4 largest of ``s +
  expert_bias``; weights = the chosen ``s`` over (their sum + 1e-6), × 1.0;
  every held expert computed densely over all tokens, one after another,
  and weighted by its column of the gate. No shared expert.
* output: ``embedding_norm``, mean over the window's positions.

Departures from the published model: the output head (tied to the
embedding) is not run — a feature extractor saves hidden states; depth is
layers 0–7 of 24. The ids are traffic, cut from the decoded frames (no
tokeniser ships with the config): of each RGB frame the centred region of
``16·(H div 16)`` × ``16·(W div 16)`` pixels in a 16 × 16 grid of patches,
``id = ((sum of the patch's bytes) · 2654435761 mod 2^32) mod vocab``,
patches row-major, 32 frames a window → 8,192 ids.

``CFG`` holds the sizes; a test at a tiny size replaces it.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from _video import read_frames

# published widths; ``layers`` (and with it ``layer_types``' first eight
# entries) and the window are the cut (benchmark/configs/lfm2-8b-a1b-l8.json).
# ``n_routed_experts`` of the router's ``router_experts`` are held here, from
# ``first_expert`` on: all of them in the cell; a test holds shares
CFG = {
    'vocab_size': 65536, 'hidden_size': 2048, 'layers': 8,
    'layer_types': ('conv', 'conv', 'full_attention', 'conv', 'conv', 'conv',
                    'full_attention', 'conv'),
    'conv_L_cache': 3, 'num_dense_layers': 2, 'intermediate_size': 7168,
    'moe_intermediate_size': 1792, 'router_experts': 32,
    'n_routed_experts': 32, 'first_expert': 0, 'num_experts_per_tok': 4,
    'routed_scaling_factor': 1.0, 'route_eps': 1e-6,
    'num_attention_heads': 32, 'num_key_value_heads': 8,
    'rope_theta': 1000000.0, 'norm_eps': 1e-5,
    'frames': 32, 'patch_grid': 16, 'query_block': 512,
}
HASH = 2654435761
UNIT = 'window'
FEATURE_DIM = CFG['hidden_size']


def _c(cfg):
    return CFG if cfg is None else cfg


def window_ids(cfg=None) -> int:
    c = _c(cfg)
    return c['frames'] * c['patch_grid'] ** 2


def head_dim(cfg=None) -> int:
    c = _c(cfg)
    return c['hidden_size'] // c['num_attention_heads']


# -- parameters ---------------------------------------------------------------

def param_specs(cfg=None):
    """{checkpoint key of the program's config: parameter list}, under the
    checkpoint's names, matrices (in, out), the convolution's taps as
    (taps, hidden): ``conv.conv.weight[j]`` weighs the position ``2 − j``
    back. A matrix is N(0, 1/fan_in) over its contracted axis (``linear``
    draws sqrt(2 / prod(shape[:-1])) × scale, so the scale undoes the 2 and,
    for the stacked experts and the embedding, the leading axis); the taps
    N(0, 1/3); the embedding N(0, 1); norm gains in [0.8, 1.2]; the router's
    bias N(0, 0.05). Two choices make every mechanism count in what is
    compared: the per-head gains of q and k are × 1.5, so that a score has a
    deviation of some 2.25 and a query reads tens of keys, not thousands
    (as a trained head does), and attention's ``out_proj`` is × 4, so that
    its output (a mean over those keys) stands at the stream's size beside
    the convolutions'; the experts' ``w2`` is × 2, so that a token's four
    experts at a weight of a quarter each add what a dense SwiGLU adds."""
    c = _c(cfg)
    d, h, g, hd = (c['hidden_size'], c['num_attention_heads'],
                   c['num_key_value_heads'], head_dim(c))
    lin = math.sqrt(0.5)
    specs = [('model.embed_tokens.weight', 'linear', (c['vocab_size'], d),
              math.sqrt(c['vocab_size'] / 2.0))]
    for i in range(c['layers']):
        p = f'model.layers.{i}'
        specs.append((f'{p}.operator_norm.weight', 'bn_weight', (d,), 1.0))
        if c['layer_types'][i] == 'conv':
            specs += [
                (f'{p}.conv.in_proj.weight', 'linear', (d, 3 * d), lin),
                (f'{p}.conv.conv.weight', 'linear', (c['conv_L_cache'], d),
                 lin),
                (f'{p}.conv.out_proj.weight', 'linear', (d, d), lin)]
        else:
            a = f'{p}.self_attn'
            specs += [
                (f'{a}.q_proj.weight', 'linear', (d, h * hd), lin),
                (f'{a}.k_proj.weight', 'linear', (d, g * hd), lin),
                (f'{a}.v_proj.weight', 'linear', (d, g * hd), lin),
                (f'{a}.q_layernorm.weight', 'bn_weight', (hd,), 1.5),
                (f'{a}.k_layernorm.weight', 'bn_weight', (hd,), 1.5),
                (f'{a}.out_proj.weight', 'linear', (h * hd, d), 4.0 * lin)]
        specs.append((f'{p}.ffn_norm.weight', 'bn_weight', (d,), 1.0))
        m = f'{p}.feed_forward'
        if i < c['num_dense_layers']:
            f = c['intermediate_size']
            specs += [(f'{m}.w1.weight', 'linear', (d, f), lin),
                      (f'{m}.w3.weight', 'linear', (d, f), lin),
                      (f'{m}.w2.weight', 'linear', (f, d), lin)]
            continue
        f, e = c['moe_intermediate_size'], c['n_routed_experts']
        stacked = math.sqrt(e / 2.0)
        specs += [
            (f'{m}.gate.weight', 'linear', (d, c['router_experts']), lin),
            (f'{m}.expert_bias', 'bias', (c['router_experts'],), 0.5),
            (f'{m}.experts.w1.weight', 'linear', (e, d, f), stacked),
            (f'{m}.experts.w3.weight', 'linear', (e, d, f), stacked),
            (f'{m}.experts.w2.weight', 'linear', (e, f, d), 2.0 * stacked)]
    specs.append(('model.embedding_norm.weight', 'bn_weight', (d,), 1.0))
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
    """(n, S, H, d): ``x · cos + rotate_half(x) · sin``, the pair
    (x[i], x[i + d/2]) turned by position · theta^(-2i/d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None]  # (1, S, 1, d)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def _short_conv(ops, p, a, x, c):
    """``Lfm2ShortConv.slow_forward`` without a cache: (n, S, D) → (n, S, D)."""
    n, s, d = x.shape
    bcx = ops.einsum('nsd,df->nsf', x, p[f'{a}.in_proj.weight'])
    gate_b, gate_c, z = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    u = gate_b * z
    taps = c['conv_L_cache']
    w = p[f'{a}.conv.weight']                               # (taps, D)
    # zeros before position 0, then the three shifted terms, spelled out
    padded = jnp.concatenate([jnp.zeros((n, taps - 1, d), u.dtype), u],
                             axis=1)
    conv = sum(w[j] * padded[:, j:j + s] for j in range(taps))
    return ops.einsum('nsd,df->nsf', gate_c * conv,
                      p[f'{a}.out_proj.weight'])


def _attention(ops, p, a, x, c):
    n, s, _ = x.shape
    h, g, hd = (c['num_attention_heads'], c['num_key_value_heads'],
                head_dim(c))
    eps = c['norm_eps']
    q = ops.einsum('nsd,df->nsf', x, p[f'{a}.q_proj.weight']
                   ).reshape(n, s, h, hd)
    k = ops.einsum('nsd,df->nsf', x, p[f'{a}.k_proj.weight']
                   ).reshape(n, s, g, hd)
    v = ops.einsum('nsd,df->nsf', x, p[f'{a}.v_proj.weight']
                   ).reshape(n, s, g, hd)
    q = _rotary(_rms(q, p[f'{a}.q_layernorm.weight'], eps), c['rope_theta'])
    k = _rotary(_rms(k, p[f'{a}.k_layernorm.weight'], eps), c['rope_theta'])
    k = jnp.repeat(k, h // g, axis=2)                       # repeat_kv
    v = jnp.repeat(v, h // g, axis=2)
    blk = min(c['query_block'], s)
    outs = []
    for q0 in range(0, s, blk):
        q1 = min(q0 + blk, s)
        scores = ops.einsum('nqhd,nkhd->nhqk', q[:, q0:q1], k[:, :q1]) \
            / math.sqrt(hd)
        visible = (jnp.arange(q0, q1)[:, None] >= jnp.arange(q1)[None, :])
        scores = jnp.where(visible, scores, -jnp.inf)
        outs.append(ops.einsum('nhqk,nkhd->nqhd',
                               jax.nn.softmax(scores, axis=-1), v[:, :q1]))
    out = jnp.concatenate(outs, axis=1).reshape(n, s, h * hd)
    return ops.einsum('nsf,fd->nsd', out, p[f'{a}.out_proj.weight'])


def _swiglu(ops, x, w1, w3, w2):
    return ops.einsum('nsf,fd->nsd',
                      jax.nn.silu(ops.einsum('nsd,df->nsf', x, w1))
                      * ops.einsum('nsd,df->nsf', x, w3), w2)


def _gate(ops, p, m, x, c):
    """(n, S, router) float32: a token's weight on each expert, 0 where it
    did not choose it. float32 at highest in every mode."""
    logits = jnp.einsum('nsd,de->nse', x, p[f'{m}.gate.weight'],
                        precision=lax.Precision.HIGHEST)
    ops._count(math.prod(logits.shape) * x.shape[-1])
    s = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(s + p[f'{m}.expert_bias'],
                          c['num_experts_per_tok'])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + c['route_eps']) \
        * c['routed_scaling_factor']
    onehot = jax.nn.one_hot(chosen, c['router_experts'], dtype=w.dtype)
    return (onehot * w[..., None]).sum(axis=-2)


def _experts(ops, p, m, x, c):
    held = c['n_routed_experts']
    gate = _gate(ops, p, m, x, c)
    gate = gate[..., c['first_expert']:c['first_expert'] + held]

    def one(y, e):
        w1, w3, w2, g = e
        return y + g[..., None] * _swiglu(ops, x, w1, w3, w2), None

    with ops.repeat(held):
        y, _ = lax.scan(one, jnp.zeros_like(x), (
            p[f'{m}.experts.w1.weight'], p[f'{m}.experts.w3.weight'],
            p[f'{m}.experts.w2.weight'], jnp.moveaxis(gate, -1, 0)))
    return y


def forward(ops, params, units, cfg=None):
    """(n, window ids) int32 → (n, hidden) float32."""
    c = _c(cfg)
    p = params['checkpoint_path']
    eps = c['norm_eps']
    x = p['model.embed_tokens.weight'][units]
    for i in range(c['layers']):
        b = f'model.layers.{i}'
        normed = _rms(x, p[f'{b}.operator_norm.weight'], eps)
        kind = c['layer_types'][i]
        if kind == 'conv':
            x = x + _short_conv(ops, p, f'{b}.conv', normed, c)
        elif kind == 'full_attention':
            x = x + _attention(ops, p, f'{b}.self_attn', normed, c)
        else:
            raise ValueError(f'layer_types[{i}] = {kind!r}')
        normed = _rms(x, p[f'{b}.ffn_norm.weight'], eps)
        m = f'{b}.feed_forward'
        if i < c['num_dense_layers']:
            x = x + _swiglu(ops, normed, p[f'{m}.w1.weight'],
                            p[f'{m}.w3.weight'], p[f'{m}.w2.weight'])
        else:
            x = x + _experts(ops, p, m, normed, c)
    return _rms(x, p['model.embedding_norm.weight'], eps).mean(axis=1)


# -- the model's work, for step_mfu ---------------------------------------------

def _work_terms(c):
    """(window ids, multiply-adds a visible query-key pair over all heads,
    multiply-adds of one expert for one token, attention layers, expert
    layers)."""
    kinds = c['layer_types'][:c['layers']]
    per_pair = c['num_attention_heads'] * 2 * head_dim(c)
    expert = 3 * c['hidden_size'] * c['moe_intermediate_size']
    return (window_ids(c), per_pair, expert, kinds.count('full_attention'),
            c['layers'] - c['num_dense_layers'])


def reference_waste_macs(cfg=None):
    """(attention, routed) multiply-adds :func:`forward` makes for one
    window — more than the model needs: whole key blocks under the mask,
    and every held expert over every token."""
    c = _c(cfg)
    s, per_pair, expert, n_attn, n_moe = _work_terms(c)
    blk = min(c['query_block'], s)
    pairs = sum((min(q0 + blk, s) - q0) * min(q0 + blk, s)
                for q0 in range(0, s, blk))
    return (pairs * per_pair * n_attn,
            s * c['n_routed_experts'] * expert * n_moe)


def model_macs(counted: int, cfg=None) -> int:
    """The model's multiply-adds for one window at even routing, from the
    reference's own count ``counted`` (``Ops.macs`` after tracing one
    window): every contraction outside the routed experts and the attention
    scores as counted (the two products of a convolution operator, the
    attention projections, the dense SwiGLUs, the routers; the taps are
    elementwise and count nothing); the two attention contractions over the
    S(S+1)/2 visible pairs; the routed experts as S · per-token ·
    held/router assignments of one expert each."""
    c = _c(cfg)
    s, per_pair, expert, n_attn, n_moe = _work_terms(c)
    waste_attn, waste_routed = reference_waste_macs(c)
    routed = (s * c['num_experts_per_tok'] * c['n_routed_experts']
              * expert * n_moe) // c['router_experts']
    attn = s * (s + 1) // 2 * per_pair * n_attn
    return counted - waste_attn - waste_routed + attn + routed
