"""Plain reference: the Trinity-Mini trunk (``afmoe``), eight of its
thirty-two layers and thirty-two of each layer's 128 routed experts.

Written from the published ``config.json`` (``model_type: afmoe``,
https://huggingface.co/arcee-ai/Trinity-Mini, 26B-A3B) and, for what no key
spells, ``transformers``' ``models/afmoe/modeling_afmoe.py`` as the issue
that brought this file wrote it out (that module is not on this machine).
RMSNorm ``x · rsqrt(mean x² + 1e-5) · γ``, no biases, float32 through
``Ops('highest')``. ``x0 = E[ids] · √hidden`` (``mup_enabled``); layer ``i``:

    h = x + rms(Attn_i(rms(x, input_layernorm)), post_attention_layernorm)
    x = h + rms(FFN_i(rms(h, pre_mlp_layernorm)), post_mlp_layernorm)

* ``Attn_i(u)``: ``q = u W_q`` → 32 heads of 128, ``k = u W_k``, ``v = u W_v``
  → 4 heads of 128, ``g = u W_gate`` → 4,096; RMSNorm over a head's 128 dims
  with a gain of its own on q and on k. ``layer_types[i] ==
  'sliding_attention'``: rope in the half-split form (the pair is ``(x[i],
  x[i + 64])``), theta 10,000, positions 0…S−1, and key j visible to query i
  ⇔ 0 ≤ i − j < 2,048. ``'full_attention'``: no positional code at all, key
  j visible ⇔ j ≤ i. Query head h reads key-value head h div 8;
  ``o = softmax(q·k / √128 over the visible keys) v``;
  ``Attn = (o ⊙ σ(g)) W_o``. A block of query rows at a time against a fixed
  span of keys under the mask, so that 32,768 positions fit.
* layers 0–1: a dense SwiGLU ``W_down(silu(W_gate x) ⊙ W_up x)`` of 6,144.
  Later layers: ``s = σ(u W_r)`` in float32 at highest whatever the mode
  (the product decides a discrete choice); the 8 largest of ``s +
  expert_bias``; weights = 2.826 · the chosen ``s`` over (their sum + 1e-20)
  — the bias moves the choice, not the weight; every held expert (1,024
  wide) computed densely over all tokens, one after another, weighted by its
  column of the gate; plus the shared expert, which every token takes.
* output: ``norm``, mean over the window's positions.

Departures from the published model: the output head is not run — a feature
extractor saves hidden states; depth is layers 0–7 of 32 (``S S S F S S S
F``); experts 0–31 of each layer's 128 are held, the router keeps its 128
outputs and 8 a token, and what experts 32–127 would add is left out. The
ids are traffic, cut from the decoded frames (no tokeniser ships with the
config): of each RGB frame the centred region of ``32·(H div 32)`` ×
``32·(W div 32)`` pixels in a 32 × 32 grid of patches, ``id = ((sum of the
patch's bytes) · 2654435761 mod 2^32) mod vocab``, patches row-major, 32
frames a window → 32,768 ids.

``CFG`` holds the sizes; a test at a tiny size replaces it.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from _video import read_frames

S, F = 'sliding_attention', 'full_attention'
# published widths; ``layers`` (and ``layer_types``' first eight entries),
# the experts held (``n_routed_experts`` of the router's ``router_experts``,
# from ``first_expert`` on) and the window of ids are the cut
# (benchmark/configs/trinity-mini-ep4-l8.json); a test holds other shares
CFG = {
    'vocab_size': 200192, 'hidden_size': 2048, 'layers': 8,
    'layer_types': (S, S, S, F, S, S, S, F), 'sliding_window': 2048,
    'head_dim': 128, 'num_dense_layers': 2, 'intermediate_size': 6144,
    'moe_intermediate_size': 1024, 'router_experts': 128,
    'n_routed_experts': 32, 'first_expert': 0, 'num_experts_per_tok': 8,
    'num_shared_experts': 1, 'route_scale': 2.826, 'route_eps': 1e-20,
    'num_attention_heads': 32, 'num_key_value_heads': 4,
    'rope_theta': 10000.0, 'rms_norm_eps': 1e-5,
    'frames': 32, 'patch_grid': 32, 'query_block': 256,
}
HASH = 2654435761
UNIT = 'window'
FEATURE_DIM = CFG['hidden_size']
SWIGLU = ('gate_proj', 'up_proj', 'down_proj')


def _c(cfg):
    return CFG if cfg is None else cfg


def window_ids(cfg=None) -> int:
    c = _c(cfg)
    return c['frames'] * c['patch_grid'] ** 2


# -- parameters ---------------------------------------------------------------

def param_specs(cfg=None):
    """{checkpoint key of the program's config: parameter list}, under the
    checkpoint's names, matrices (in, out), a layer's held experts stacked.
    A matrix is N(0, 1/fan_in) over its contracted axis (``linear`` draws
    sqrt(2 / prod(shape[:-1])) × scale, so the scale undoes the 2 and, for
    the stacked experts, the leading axis); the **embedding N(0, 1/hidden)**,
    so that ``× √hidden`` leaves a residual stream of size 1 (at N(0, 1) the
    stream would be 45 × every sub-layer's normed output and no fault in
    attention or experts would show in what is compared); every norm's gain
    in [0.8, 1.2], the per-head gains of q and k × 1.5, so that a score has
    a deviation of some 2.25 and a query reads tens to hundreds of keys, not
    thousands; the router's bias N(0, 0.05). Each sub-layer's output passes a
    norm of its own, so no projection needs a scale to stand at the stream's
    size."""
    c = _c(cfg)
    d, h, g, hd = (c['hidden_size'], c['num_attention_heads'],
                   c['num_key_value_heads'], c['head_dim'])
    lin = math.sqrt(0.5)
    gate_name, up_name, down_name = SWIGLU
    specs = [('model.embed_tokens.weight', 'linear', (c['vocab_size'], d),
              math.sqrt(c['vocab_size'] / (2.0 * d)))]
    for i in range(c['layers']):
        p = f'model.layers.{i}'
        a = f'{p}.self_attn'
        specs += [
            (f'{p}.input_layernorm.weight', 'bn_weight', (d,), 1.0),
            (f'{a}.q_proj.weight', 'linear', (d, h * hd), lin),
            (f'{a}.k_proj.weight', 'linear', (d, g * hd), lin),
            (f'{a}.v_proj.weight', 'linear', (d, g * hd), lin),
            (f'{a}.gate_proj.weight', 'linear', (d, h * hd), lin),
            (f'{a}.q_norm.weight', 'bn_weight', (hd,), 1.5),
            (f'{a}.k_norm.weight', 'bn_weight', (hd,), 1.5),
            (f'{a}.o_proj.weight', 'linear', (h * hd, d), lin),
            (f'{p}.post_attention_layernorm.weight', 'bn_weight', (d,), 1.0),
            (f'{p}.pre_mlp_layernorm.weight', 'bn_weight', (d,), 1.0)]
        m = f'{p}.mlp'
        if i < c['num_dense_layers']:
            f = c['intermediate_size']
            specs += [(f'{m}.{gate_name}.weight', 'linear', (d, f), lin),
                      (f'{m}.{up_name}.weight', 'linear', (d, f), lin),
                      (f'{m}.{down_name}.weight', 'linear', (f, d), lin)]
        else:
            f, e = c['moe_intermediate_size'], c['n_routed_experts']
            fs = f * c['num_shared_experts']
            stacked = math.sqrt(e / 2.0)
            specs += [
                (f'{m}.router.gate.weight', 'linear',
                 (d, c['router_experts']), lin),
                (f'{m}.expert_bias', 'bias', (c['router_experts'],), 0.5),
                (f'{m}.experts.{gate_name}.weight', 'linear', (e, d, f),
                 stacked),
                (f'{m}.experts.{up_name}.weight', 'linear', (e, d, f),
                 stacked),
                (f'{m}.experts.{down_name}.weight', 'linear', (e, f, d),
                 stacked),
                (f'{m}.shared_experts.{gate_name}.weight', 'linear', (d, fs),
                 lin),
                (f'{m}.shared_experts.{up_name}.weight', 'linear', (d, fs),
                 lin),
                (f'{m}.shared_experts.{down_name}.weight', 'linear', (fs, d),
                 lin)]
        specs.append((f'{p}.post_mlp_layernorm.weight', 'bn_weight', (d,),
                      1.0))
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
    """(n, S, H, d): ``x · cos + rotate_half(x) · sin``, the pair
    (x[i], x[i + d/2]) turned by position · theta^(-2i/d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None]  # (1, S, 1, d)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def key_span(s: int, window, block: int) -> int:
    """Keys a block of ``block`` query rows is set against: all ``s`` of a
    full layer, under a window its last row's and the ``window − 1`` before
    its first row's."""
    return s if window is None else min(s, window - 1 + block)


def _attend(ops, q, k, v, window, c):
    """softmax(q·k / √d over the visible keys) v: (n, S, h, d) queries over
    (n, S, g, d) keys and values, query head j reading key-value head
    j div (h / g). A block of query rows at a time against a span of keys
    that ends with the block (:func:`key_span`), everything not visible
    masked out: key j for query i ⇔ j ≤ i and, under a window, i − j <
    window."""
    n, s, h, hd = q.shape
    g = k.shape[2]
    blk = min(c['query_block'], s)
    span = key_span(s, window, blk)
    q = q.reshape(n, s, g, h // g, hd)

    def rows(q0):
        k0 = jnp.clip(q0 + blk - span, 0, s - span)
        qb = lax.dynamic_slice_in_dim(q, q0, blk, axis=1)
        kb = lax.dynamic_slice_in_dim(k, k0, span, axis=1)
        vb = lax.dynamic_slice_in_dim(v, k0, span, axis=1)
        scores = ops.einsum('nqgrd,nkgd->ngrqk', qb, kb) / math.sqrt(hd)
        i = q0 + jnp.arange(blk)[:, None]
        j = k0 + jnp.arange(span)[None, :]
        visible = j <= i
        if window is not None:
            visible &= i - j < window
        scores = jnp.where(visible, scores, -jnp.inf)
        return ops.einsum('ngrqk,nkgd->nqgrd',
                          jax.nn.softmax(scores, axis=-1), vb)

    with ops.repeat(s // blk):
        out = lax.map(rows, jnp.arange(0, s, blk))      # (blocks, n, blk, …)
    return jnp.moveaxis(out, 0, 1).reshape(n, s, h * hd)


def _attention(ops, p, a, x, c, kind):
    n, s, _ = x.shape
    h, g, hd = (c['num_attention_heads'], c['num_key_value_heads'],
                c['head_dim'])
    eps = c['rms_norm_eps']
    q = ops.einsum('nsd,df->nsf', x, p[f'{a}.q_proj.weight']
                   ).reshape(n, s, h, hd)
    k = ops.einsum('nsd,df->nsf', x, p[f'{a}.k_proj.weight']
                   ).reshape(n, s, g, hd)
    v = ops.einsum('nsd,df->nsf', x, p[f'{a}.v_proj.weight']
                   ).reshape(n, s, g, hd)
    gate = ops.einsum('nsd,df->nsf', x, p[f'{a}.gate_proj.weight'])
    q = _rms(q, p[f'{a}.q_norm.weight'], eps)
    k = _rms(k, p[f'{a}.k_norm.weight'], eps)
    window = None
    if kind == S:
        q, k = _rotary(q, c['rope_theta']), _rotary(k, c['rope_theta'])
        window = c['sliding_window']
    elif kind != F:
        raise ValueError(f'layer type {kind!r}')
    out = _attend(ops, q, k, v, window, c)
    return ops.einsum('nsf,fd->nsd', out * jax.nn.sigmoid(gate),
                      p[f'{a}.o_proj.weight'])


def _swiglu(ops, x, p, m):
    gate_name, up_name, down_name = SWIGLU
    return ops.einsum(
        'nsf,fd->nsd',
        jax.nn.silu(ops.einsum('nsd,df->nsf', x, p[f'{m}.{gate_name}.weight']))
        * ops.einsum('nsd,df->nsf', x, p[f'{m}.{up_name}.weight']),
        p[f'{m}.{down_name}.weight'])


def _gate(ops, p, m, x, c):
    """(n, S, router) float32: a token's weight on each expert, 0 where it
    did not choose it. float32 at highest in every mode."""
    logits = jnp.einsum('nsd,de->nse', x, p[f'{m}.router.gate.weight'],
                        precision=lax.Precision.HIGHEST)
    ops._count(math.prod(logits.shape) * x.shape[-1])
    s = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(s + p[f'{m}.expert_bias'],
                          c['num_experts_per_tok'])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = c['route_scale'] * w / (w.sum(axis=-1, keepdims=True)
                                + c['route_eps'])
    onehot = jax.nn.one_hot(chosen, c['router_experts'], dtype=w.dtype)
    return (onehot * w[..., None]).sum(axis=-2)


def _routed(ops, p, m, x, c):
    """The held experts' part of the routed sum: every held expert over
    every token, weighted by its column of the gate."""
    held = c['n_routed_experts']
    gate = _gate(ops, p, m, x, c)
    gate = gate[..., c['first_expert']:c['first_expert'] + held]
    gate_name, up_name, down_name = SWIGLU

    def one(y, e):
        w_gate, w_up, w_down, g = e
        weights = {'e.gate_proj.weight': w_gate, 'e.up_proj.weight': w_up,
                   'e.down_proj.weight': w_down}
        return y + g[..., None] * _swiglu(ops, x, weights, 'e'), None

    with ops.repeat(held):
        y, _ = lax.scan(one, jnp.zeros_like(x), (
            p[f'{m}.experts.{gate_name}.weight'],
            p[f'{m}.experts.{up_name}.weight'],
            p[f'{m}.experts.{down_name}.weight'],
            jnp.moveaxis(gate, -1, 0)))
    return y


def _experts(ops, p, m, x, c):
    """An expert layer's feed-forward as this share gives it: the shared
    expert, which every chip computes alike, plus the held routed part."""
    return _swiglu(ops, x, p, f'{m}.shared_experts') + _routed(ops, p, m, x,
                                                               c)


def forward(ops, params, units, cfg=None):
    """(n, window ids) int32 → (n, hidden) float32."""
    c = _c(cfg)
    p = params['checkpoint_path']
    eps = c['rms_norm_eps']
    x = p['model.embed_tokens.weight'][units] * math.sqrt(c['hidden_size'])
    for i in range(c['layers']):
        b = f'model.layers.{i}'
        a = _attention(ops, p, f'{b}.self_attn',
                       _rms(x, p[f'{b}.input_layernorm.weight'], eps), c,
                       c['layer_types'][i])
        x = x + _rms(a, p[f'{b}.post_attention_layernorm.weight'], eps)
        normed = _rms(x, p[f'{b}.pre_mlp_layernorm.weight'], eps)
        if i < c['num_dense_layers']:
            f = _swiglu(ops, normed, p, f'{b}.mlp')
        else:
            f = _experts(ops, p, f'{b}.mlp', normed, c)
        x = x + _rms(f, p[f'{b}.post_mlp_layernorm.weight'], eps)
    return _rms(x, p['model.norm.weight'], eps).mean(axis=1)


# -- the model's work, for step_mfu ---------------------------------------------

def visible_pairs(s: int, window=None) -> int:
    """(query, key) pairs a head sees over ``s`` positions: Σ min(i + 1,
    window)."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def _work_terms(c):
    """(window ids, multiply-adds a visible query-key pair over all heads,
    multiply-adds of one expert for one token, sliding layers, full layers,
    expert layers)."""
    kinds = c['layer_types'][:c['layers']]
    per_pair = c['num_attention_heads'] * 2 * c['head_dim']
    expert = 3 * c['hidden_size'] * c['moe_intermediate_size']
    return (window_ids(c), per_pair, expert, kinds.count(S), kinds.count(F),
            c['layers'] - c['num_dense_layers'])


def reference_waste_macs(cfg=None):
    """(attention, routed) multiply-adds :func:`forward` makes for one
    window — more than the model needs: whole key spans under the mask, and
    every held expert over every token."""
    c = _c(cfg)
    s, per_pair, expert, n_sliding, n_full, n_moe = _work_terms(c)
    blk = min(c['query_block'], s)
    pairs = s * (n_full * key_span(s, None, blk)
                 + n_sliding * key_span(s, c['sliding_window'], blk))
    return pairs * per_pair, s * c['n_routed_experts'] * expert * n_moe


def model_macs(counted: int, cfg=None) -> int:
    """The model's multiply-adds for one window at even routing, from the
    reference's own count ``counted`` (``Ops.macs`` after tracing one
    window): every contraction outside the routed experts and the attention
    scores as counted (the five projections of an attention layer, the
    dense SwiGLUs, the routers, the shared experts); the two attention
    contractions over the visible pairs alone (the triangle of a full
    layer, the band of a sliding one); the routed experts as S · per-token ·
    held/router assignments of one expert each."""
    c = _c(cfg)
    s, per_pair, expert, n_sliding, n_full, n_moe = _work_terms(c)
    waste_attn, waste_routed = reference_waste_macs(c)
    routed = (s * c['num_experts_per_tok'] * c['n_routed_experts']
              * expert * n_moe) // c['router_experts']
    attn = per_pair * (n_full * visible_pairs(s)
                       + n_sliding * visible_pairs(s, c['sliding_window']))
    return counted - waste_attn - waste_routed + attn + routed
