"""Plain reference: the dots3-note-prev language trunk (``dots3_note``), five
of its 46 layers, 8 of each layer's 256 routed experts, an eighth of its
vocabulary.

Written from the published ``config.json`` (``model_type: dots3_note``,
https://huggingface.co/dots-studio/dots3-note-prev, 288B-A17B) and its
described attention ("MLA + DSA indexer (full layers); SWA(513) with its own
low-rank latent attention + headwise gate"): DeepSeek-V3's latent attention
and expert layer, DeepSeek-V3.2's lightning indexer, the headwise gate of
Qiu et al. 2025 (arXiv:2505.06708). RMSNorm ``x · rsqrt(mean x² + 1e-5) ·
γ``, no biases, float32 through ``Ops('highest')``. Layer ``i``:

    h = x + Mixer_kind(rms(x, input_layernorm))
    x = h + FFN_i(rms(h, post_attention_layernorm))

* ``Mixer`` (full: 128 heads, ranks 1,024 / 512, heads 128 + 64 / 128, θ 8e7;
  sliding: 64 heads, ranks 1,024 / 1,024, heads 192 + 64 / 128, θ 5e4):
  ``c_q = rms(x W_qa) · √(5120 / r_q)``; ``q = c_q W_qb`` → heads of
  (nope ‖ rope); ``[c_kv ‖ k_r] = x W_kva``; ``c_kv = rms(c_kv) · √(5120 /
  r_kv)``; ``[k_n ‖ v] = c_kv W_kvb``; ``k_r`` one head shared by all;
  rotary on the rope dims, interleaved pairs, positions 0…S−1;
  ``o = softmax(q·k / √(d_n + d_r) over the visible keys) v``; ``out =
  (o ⊙ σ(x W_g) a head) W_o``. Visible: full — ``u ≤ t`` and ``u`` among
  the indexer's ``Top(t)``; sliding — ``t − 512 ≤ u ≤ t``. A block of query
  rows at a time against the keys it may see, everything else masked out.
* the indexer (full layers): ``q_I = rms(x W_qa) W_qI`` → 64 heads of 128
  (the normed latent before the rescale), ``k_I = LayerNorm(x W_kI)`` (gain,
  bias, eps 1e-6), rotary half-split on dims 0…63 of both (θ 8e7), ``w = x
  W_w / 8``, ``I[t, u] = Σ_j w[t, j] · ReLU(q_I[t, j] · k_I[u] / √128)`` —
  the whole (S, S) matrix, a block of rows at a time — then ``lax.top_k``
  over each row's visible keys: ``Top(t)`` its 2,048 largest (all ``u ≤ t``
  while ``t < 2048``). No Hadamard rotation (orthogonal on both sides: it
  leaves ``q_I · k_I`` as it is), no fp8.
* layer 0: a dense SwiGLU of 13,824. Later layers: ``s = sigmoid(x W_g)`` in
  float32 at highest whatever the mode (the product decides a discrete
  choice); the 8 largest of ``s + e_score_correction_bias``; weights = the
  chosen ``s`` over (their sum + 1e-20) × 1; every *held* expert (1,536
  wide) computed densely over all tokens, one after another, weighted by its
  column of the gate; plus the shared expert.
* output: final RMSNorm, mean over the window's positions.

Departures from the published model: the output head, the multi-token
prediction module, the vision tower and the audio encoder are not run (a
feature extractor of the language trunk saves hidden states); depth is
layers 0–4 of 46 (``F F S S S``: the leading dense layer, then one full and
three sliding expert layers); experts 0–7 of each layer's 256 are held,
the router keeps its 256 outputs and 8 a token, and what experts 8–255
would add is left out; the embedding holds rows 0–19,007 (an eighth of
152,064), and the ids are cut to them. The ids are traffic, cut from the
decoded frames (no tokeniser ships with the config): of each RGB frame the
centred region of ``16·(H div 16)`` × ``16·(W div 16)`` pixels in a 16 × 16
grid of patches, ``id = ((sum of the patch's bytes) · 2654435761 mod 2^32)
mod 19008``, patches row-major, 32 frames a window → 8,192 ids.

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
# published widths; ``layers`` (and ``layer_types``' first five entries),
# the experts held (``n_routed_experts`` of the router's ``router_experts``,
# from ``first_expert`` on), the vocabulary held and the window of ids are
# the cut (benchmark/configs/dots3-note-prev-ep32-l5.json); a test holds
# other sizes
CFG = {
    'vocab_size': 19008, 'hidden_size': 5120, 'layers': 5,
    'layer_types': (F, F, S, S, S), 'first_k_dense_replace': 1,
    'intermediate_size': 13824, 'moe_intermediate_size': 1536,
    'router_experts': 256, 'n_routed_experts': 8, 'first_expert': 0,
    'n_shared_experts': 1, 'num_experts_per_tok': 8,
    'routed_scaling_factor': 1.0,
    'num_attention_heads': 128, 'q_lora_rank': 1024, 'kv_lora_rank': 512,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'v_head_dim': 128,
    'rope_theta': 80000000.0,
    'sliding_window_size': 513, 'swa_num_attention_heads': 64,
    'swa_q_lora_rank': 1024, 'swa_kv_lora_rank': 1024,
    'swa_qk_nope_head_dim': 192, 'swa_qk_rope_head_dim': 64,
    'swa_v_head_dim': 128, 'swa_rope_theta': 50000.0,
    'index_n_heads': 64, 'index_head_dim': 128, 'index_topk': 2048,
    'rms_norm_eps': 1e-5,
    'frames': 32, 'patch_grid': 16, 'query_block': 128, 'index_block': 256,
}
HASH = 2654435761
UNIT = 'window'
FEATURE_DIM = CFG['hidden_size']
LN_EPS = 1e-6


def _c(cfg):
    return CFG if cfg is None else cfg


def window_ids(cfg=None) -> int:
    c = _c(cfg)
    return c['frames'] * c['patch_grid'] ** 2


def widths(kind, cfg=None):
    """(heads, q rank, kv rank, nope, rope, v, theta) of a layer's mixer."""
    c = _c(cfg)
    pre = 'swa_' if kind == S else ''
    return (c[f'{pre}num_attention_heads'], c[f'{pre}q_lora_rank'],
            c['swa_kv_lora_rank' if kind == S else 'kv_lora_rank'],
            c[f'{pre}qk_nope_head_dim'], c[f'{pre}qk_rope_head_dim'],
            c[f'{pre}v_head_dim'], c[f'{pre}rope_theta'])


# -- parameters ---------------------------------------------------------------

def param_specs(cfg=None):
    """{checkpoint key of the program's config: parameter list}, under the
    checkpoint's names (DeepSeek-V3's, V3.2's indexer, ``gate_proj`` for the
    head gate), matrices (in, out), a layer's held experts stacked. A matrix
    is N(0, 1/fan_in) over its contracted axis (``linear`` draws
    sqrt(2 / prod(shape[:-1])) × scale, so the scale undoes the 2 and, for
    the stacked experts and the embedding, the leading axis); the embedding
    N(0, 1), as joyai's; every norm gain in [0.8, 1.2]; the indexer's key
    norm bias and the router's bias N(0, 0.05). No projection is scaled:
    the latent rescale (√5 on c_q, √10 / √5 on c_kv) lifts a query's scores
    to a deviation of some 6, so a query reads a few of its keys, and v to
    some 3, so the gated heads' output reaches the stream's size (≈ 1)
    through ``o_proj`` as drawn."""
    c = _c(cfg)
    d = c['hidden_size']
    lin = math.sqrt(0.5)
    specs = [('model.embed_tokens.weight', 'linear', (c['vocab_size'], d),
              math.sqrt(c['vocab_size'] / 2.0))]
    for i in range(c['layers']):
        p, a, m = (f'model.layers.{i}', f'model.layers.{i}.self_attn',
                   f'model.layers.{i}.mlp')
        kind = c['layer_types'][i]
        h, rq, rkv, dn, dr, dv, _ = widths(kind, c)
        specs += [
            (f'{p}.input_layernorm.weight', 'bn_weight', (d,), 1.0),
            (f'{a}.q_a_proj.weight', 'linear', (d, rq), lin),
            (f'{a}.q_a_layernorm.weight', 'bn_weight', (rq,), 1.0),
            (f'{a}.q_b_proj.weight', 'linear', (rq, h * (dn + dr)), lin),
            (f'{a}.kv_a_proj_with_mqa.weight', 'linear', (d, rkv + dr), lin),
            (f'{a}.kv_a_layernorm.weight', 'bn_weight', (rkv,), 1.0),
            (f'{a}.kv_b_proj.weight', 'linear', (rkv, h * (dn + dv)), lin),
            (f'{a}.o_proj.weight', 'linear', (h * dv, d), lin),
            (f'{a}.gate_proj.weight', 'linear', (d, h), lin),
        ]
        if kind == F:
            hi, di = c['index_n_heads'], c['index_head_dim']
            specs += [
                (f'{a}.indexer.wq_b.weight', 'linear', (rq, hi * di), lin),
                (f'{a}.indexer.wk.weight', 'linear', (d, di), lin),
                (f'{a}.indexer.k_norm.weight', 'bn_weight', (di,), 1.0),
                (f'{a}.indexer.k_norm.bias', 'bias', (di,), 0.5),
                (f'{a}.indexer.weights_proj.weight', 'linear', (d, hi), lin),
            ]
        specs.append((f'{p}.post_attention_layernorm.weight', 'bn_weight',
                      (d,), 1.0))
        if i < c['first_k_dense_replace']:
            f = c['intermediate_size']
            specs += [(f'{m}.gate_proj.weight', 'linear', (d, f), lin),
                      (f'{m}.up_proj.weight', 'linear', (d, f), lin),
                      (f'{m}.down_proj.weight', 'linear', (f, d), lin)]
            continue
        f, e = c['moe_intermediate_size'], c['n_routed_experts']
        stacked = math.sqrt(e / 2.0)
        fs = f * c['n_shared_experts']
        specs += [
            (f'{m}.gate.weight', 'linear', (d, c['router_experts']), lin),
            (f'{m}.gate.e_score_correction_bias', 'bias',
             (c['router_experts'],), 0.5),
            (f'{m}.experts.gate_proj.weight', 'linear', (e, d, f), stacked),
            (f'{m}.experts.up_proj.weight', 'linear', (e, d, f), stacked),
            (f'{m}.experts.down_proj.weight', 'linear', (e, f, d), stacked),
            (f'{m}.shared_experts.gate_proj.weight', 'linear', (d, fs), lin),
            (f'{m}.shared_experts.up_proj.weight', 'linear', (d, fs), lin),
            (f'{m}.shared_experts.down_proj.weight', 'linear', (fs, d), lin),
        ]
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


def _layer_norm(x, gain, bias):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * gain + bias


def _rotary(x, theta):
    """(n, S, H, d): pair (x[2i], x[2i+1]) turned by position · theta^(-2i/d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _rotary_half(x, theta):
    """(n, S, H, d): ``x · cos + rotate_half(x) · sin``, the pair
    (x[i], x[i + d/2]) turned by position · theta^(-2i/d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None]  # (1, S, 1, d)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def _blocks(ops, fn, s, blk):
    """``fn(q0)`` over the row blocks 0, blk, … → stacked on axis 1."""
    with ops.repeat(s // blk):
        out = lax.map(fn, jnp.arange(0, s, blk))        # (blocks, n, blk, …)
    return jnp.moveaxis(out, 0, 1).reshape(out.shape[1], s, *out.shape[3:])


def _indexer(ops, p, a, x, c_q, c):
    """(n, S, S) bool: the keys each query keeps (module doc)."""
    n, s, _ = x.shape
    hi, di, dr = c['index_n_heads'], c['index_head_dim'], c['qk_rope_head_dim']
    topk, theta = c['index_topk'], c['rope_theta']
    i = f'{a}.indexer'
    q = ops.einsum('nsr,rf->nsf', c_q, p[f'{i}.wq_b.weight']
                   ).reshape(n, s, hi, di)
    q = jnp.concatenate([_rotary_half(q[..., :dr], theta), q[..., dr:]], -1)
    k = _layer_norm(ops.einsum('nsd,df->nsf', x, p[f'{i}.wk.weight']),
                    p[f'{i}.k_norm.weight'], p[f'{i}.k_norm.bias'])
    k = jnp.concatenate(
        [_rotary_half(k[:, :, None, :dr], theta)[:, :, 0], k[..., dr:]], -1)
    w = ops.einsum('nsd,dh->nsh', x, p[f'{i}.weights_proj.weight']) \
        / math.sqrt(hi)
    blk = min(c['index_block'], s)

    def rows(q0):
        qb = lax.dynamic_slice_in_dim(q, q0, blk, axis=1)
        wb = lax.dynamic_slice_in_dim(w, q0, blk, axis=1)
        dots = ops.einsum('nthd,nud->nthu', qb, k) / math.sqrt(di)
        return ops.einsum('nthu,nth->ntu', jax.nn.relu(dots), wb)

    scores = _blocks(ops, rows, s, blk)                      # (n, S, S)
    causal = jnp.tril(jnp.ones((s, s), bool))[None]
    if topk >= s:
        return jnp.broadcast_to(causal, (n, s, s))
    _, chosen = lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    keep = jnp.zeros((n, s, s), bool).at[
        jnp.arange(n)[:, None, None], jnp.arange(s)[None, :, None],
        chosen].set(True)
    return keep & causal


def key_span(s: int, window, block: int) -> int:
    """Keys a block of ``block`` query rows is set against: all ``s`` of a
    full layer, under a window its last row's and the ``window − 1`` before
    its first row's."""
    return s if window is None else min(s, window - 1 + block)


def _attend(ops, q, k, v, window, keep, c):
    """softmax(q·k / √d over the visible keys) v, (n, S, h, ·) in and
    (n, S, h · d_v) out: a block of query rows at a time against a span of
    keys that ends with the block (:func:`key_span`), everything not
    visible masked out — under ``keep`` (n, S, S) the selected keys, under a
    window the last ``window`` positions."""
    n, s, h, dqk = q.shape
    blk = min(c['query_block'], s)
    span = key_span(s, window, blk)

    def rows(q0):
        k0 = jnp.clip(q0 + blk - span, 0, s - span)
        qb = lax.dynamic_slice_in_dim(q, q0, blk, axis=1)
        kb = lax.dynamic_slice_in_dim(k, k0, span, axis=1)
        vb = lax.dynamic_slice_in_dim(v, k0, span, axis=1)
        scores = ops.einsum('nqhd,nkhd->nhqk', qb, kb) / math.sqrt(dqk)
        i = q0 + jnp.arange(blk)[:, None]
        j = k0 + jnp.arange(span)[None, :]
        if keep is None:
            visible = ((j <= i) & (i - j < window))[None]
        else:
            visible = lax.dynamic_slice_in_dim(keep, q0, blk, axis=1)
        scores = jnp.where(visible[:, None], scores, -jnp.inf)
        return ops.einsum('nhqk,nkhd->nqhd', jax.nn.softmax(scores, axis=-1),
                          vb)

    return _blocks(ops, rows, s, blk).reshape(n, s, -1)


def _attention(ops, p, a, x, c, kind):
    n, s, d = x.shape
    if kind not in (S, F):
        raise ValueError(f'layer type {kind!r}')
    h, rq, rkv, dn, dr, dv, theta = widths(kind, c)
    eps = c['rms_norm_eps']
    c_q = _rms(ops.einsum('nsd,dr->nsr', x, p[f'{a}.q_a_proj.weight']),
               p[f'{a}.q_a_layernorm.weight'], eps)
    keep = _indexer(ops, p, a, x, c_q, c) if kind == F else None
    q = ops.einsum('nsr,rf->nsf', c_q * math.sqrt(d / rq),
                   p[f'{a}.q_b_proj.weight']).reshape(n, s, h, dn + dr)
    kv_a = ops.einsum('nsd,dr->nsr', x, p[f'{a}.kv_a_proj_with_mqa.weight'])
    c_kv = _rms(kv_a[..., :rkv], p[f'{a}.kv_a_layernorm.weight'], eps) \
        * math.sqrt(d / rkv)
    k_r = _rotary(kv_a[..., rkv:].reshape(n, s, 1, dr), theta)
    kv = ops.einsum('nsr,rf->nsf', c_kv, p[f'{a}.kv_b_proj.weight']
                    ).reshape(n, s, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (n, s, h, dr))],
                        axis=-1)
    out = _attend(ops, q, k, kv[..., dn:],
                  c['sliding_window_size'] if kind == S else None, keep, c)
    gate = jax.nn.sigmoid(ops.einsum('nsd,dh->nsh', x,
                                     p[f'{a}.gate_proj.weight']))
    out = (out.reshape(n, s, h, dv) * gate[..., None]).reshape(n, s, h * dv)
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
    """The held experts' part of the routed sum (every held expert over every
    token, weighted by its column of the gate) plus the shared expert, which
    every chip computes alike."""
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
    return y + _swiglu(ops, x, p[f'{m}.shared_experts.gate_proj.weight'],
                       p[f'{m}.shared_experts.up_proj.weight'],
                       p[f'{m}.shared_experts.down_proj.weight'])


def forward(ops, params, units, cfg=None):
    """(n, window ids) int32 → (n, hidden) float32."""
    c = _c(cfg)
    p = params['checkpoint_path']
    eps = c['rms_norm_eps']
    x = p['model.embed_tokens.weight'][units]
    for i in range(c['layers']):
        b = f'model.layers.{i}'
        x = x + _attention(ops, p, f'{b}.self_attn',
                           _rms(x, p[f'{b}.input_layernorm.weight'], eps), c,
                           c['layer_types'][i])
        normed = _rms(x, p[f'{b}.post_attention_layernorm.weight'], eps)
        if i < c['first_k_dense_replace']:
            x = x + _swiglu(ops, normed, p[f'{b}.mlp.gate_proj.weight'],
                            p[f'{b}.mlp.up_proj.weight'],
                            p[f'{b}.mlp.down_proj.weight'])
        else:
            x = x + _experts(ops, p, f'{b}.mlp', normed, c)
    return _rms(x, p['model.norm.weight'], eps).mean(axis=1)


# -- the model's work, for step_mfu ---------------------------------------------

def visible_pairs(s: int, window=None) -> int:
    """(query, key) pairs a head sees over ``s`` positions: Σ min(t + 1,
    window) — the band, or the indexer's selection of ``window`` keys."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def _kinds(c):
    return c['layer_types'][:c['layers']]


def reference_waste_macs(cfg=None):
    """(attention, indexer, routed) multiply-adds :func:`forward` makes for
    one window — more than the model needs: whole rows of keys under the
    selection, whole spans under the window, the indexer's scores over the
    whole square, and every held expert over every token."""
    c = _c(cfg)
    s = window_ids(c)
    attn = index = 0
    for kind in _kinds(c):
        h, _, _, dn, dr, dv, _ = widths(kind, c)
        window = c['sliding_window_size'] if kind == S else None
        attn += s * key_span(s, window, min(c['query_block'], s)) \
            * h * (dn + dr + dv)
        if kind == F:
            index += s * s * c['index_n_heads'] * (c['index_head_dim'] + 1)
    n_moe = c['layers'] - c['first_k_dense_replace']
    expert = 3 * c['hidden_size'] * c['moe_intermediate_size']
    return attn, index, s * c['n_routed_experts'] * expert * n_moe


def model_macs(counted: int, cfg=None) -> int:
    """The model's multiply-adds for one window at even routing, from the
    reference's own count ``counted`` (``Ops.macs`` after tracing one
    window): every contraction outside the routed experts, the attention
    scores and the indexer's scores as counted (projections, gates, the
    dense and shared SwiGLUs, routers); the two attention contractions over
    the visible pairs alone (a full layer's selected keys, Σ min(t + 1,
    2048), a sliding layer's band, Σ min(t + 1, 513)); the indexer's two
    over the whole triangle (the model scores every visible key to select
    among them); the routed experts as S · per-token · held/router
    assignments of one expert each."""
    c = _c(cfg)
    s = window_ids(c)
    waste = sum(reference_waste_macs(c))
    pairs = 0
    for kind in _kinds(c):
        h, _, _, dn, dr, dv, _ = widths(kind, c)
        window = (c['sliding_window_size'] if kind == S
                  else c['index_topk'])
        pairs += visible_pairs(s, window) * h * (dn + dr + dv)
        if kind == F:
            pairs += visible_pairs(s) * c['index_n_heads'] \
                * (c['index_head_dim'] + 1)
    n_moe = c['layers'] - c['first_k_dense_replace']
    expert = 3 * c['hidden_size'] * c['moe_intermediate_size']
    routed = (s * c['num_experts_per_tok'] * c['n_routed_experts']
              * expert * n_moe) // c['router_experts']
    return counted - waste + pairs + routed
