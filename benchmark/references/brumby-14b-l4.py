"""Plain reference: the Brumby-14B-Base trunk, four of its forty layers.

Written from the published ``config.json`` (``model_type: brumby``,
https://huggingface.co/manifestai/Brumby-14B-Base: Qwen3-14B's widths to the
digit) and from Gelada, Buckman, Zhang, Bach, "Scaling Context Requires
Rethinking Attention" (arXiv:2507.04239) for what the config has no key for.
Pre-norm residual blocks, RMSNorm (eps 1e-6), float32 through
``Ops('highest')``. One layer, ``h = RMSNorm(x)``:

* ``q = rope(rmsnorm_d(h W_q))`` → 40 heads of 128; ``k = rope(rmsnorm_d(h
  W_k))`` and ``v = h W_v`` → 8 heads of 128; ``rmsnorm_d`` over a head's 128
  dims with a gain of its own; rope in the half-split form (the pair is
  ``(x[i], x[i + 64])``), theta 1e6, positions 0…S−1.
* the forget gate ``γ = log σ(h W_g + b_g)``, one a key-value head, ≤ 0.
* **power retention in its attention form**: query head ``i`` reads
  key-value head ``i div 5``;
  ``a_ts = (q_t · k_s)² · exp(γ_{s+1} + … + γ_t)`` for ``s ≤ t``,
  ``y_t = Σ_s a_ts v_s / (Σ_s a_ts + 1e-6)``. A block of queries at a time
  against every key, masked, so that no 32,768 × 32,768 product stands whole.
  The program runs the same function as a chunked scan over a carried state
  (its ``ops/retention.py``); nothing of that algorithm is here.
* ``x ← x + y W_o``; ``x ← x + W_down(silu(h' W_gate) ⊙ h' W_up)``, a block
  of rows at a time.
* output: final RMSNorm, mean over the window's positions.

Departures from the published model: the output head is not run (a feature
extractor saves hidden states); depth is 4 layers. The ids are traffic, cut
from the decoded frames as the joyai reference cuts them, at a 32 × 32 grid:
32 frames a window → 32,768 ids.

``CFG`` holds the sizes; a test at a tiny size replaces it.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from _video import read_frames

# published widths; ``layers`` and the window are the cut
# (benchmark/configs/brumby-14b-l4.json)
CFG = {
    'vocab_size': 151936, 'hidden_size': 5120, 'layers': 4,
    'intermediate_size': 17408, 'num_attention_heads': 40,
    'num_key_value_heads': 8, 'head_dim': 128, 'rope_theta': 1000000.0,
    'rms_norm_eps': 1e-6, 'retention_eps': 1e-6,
    'frames': 32, 'patch_grid': 32, 'query_block': 256, 'row_block': 4096,
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
    the embedding, the leading axis); the embedding N(0, 1); norm gains in
    [0.8, 1.2]. Three choices make the mixer count in what is compared:
    the gate's bias lies in [5.6, 8.4] and its matrix is halved, so a
    position is remembered for some 200 to 6,000 further ones (a trained
    forget gate's range; at a bias near 0 nothing older than ten positions
    would reach a query and the carried state would hold nothing); and
    ``o_proj`` is × 8, because a mean of a thousand random values is small:
    so the mixer's output stands beside the feed-forward's in the residual
    stream (0.3 against 0.4 of the stream's size) and not a tenth of it."""
    c = _c(cfg)
    d, f = c['hidden_size'], c['intermediate_size']
    h, g, hd = (c['num_attention_heads'], c['num_key_value_heads'],
                c['head_dim'])
    lin = math.sqrt(0.5)
    specs = [('model.embed_tokens.weight', 'linear', (c['vocab_size'], d),
              math.sqrt(c['vocab_size'] / 2.0))]
    for i in range(c['layers']):
        p, a, m = (f'model.layers.{i}', f'model.layers.{i}.self_attn',
                   f'model.layers.{i}.mlp')
        specs += [
            (f'{p}.input_layernorm.weight', 'bn_weight', (d,), 1.0),
            (f'{a}.q_proj.weight', 'linear', (d, h * hd), lin),
            (f'{a}.k_proj.weight', 'linear', (d, g * hd), lin),
            (f'{a}.v_proj.weight', 'linear', (d, g * hd), lin),
            (f'{a}.g_proj.weight', 'linear', (d, g), 0.5 * lin),
            (f'{a}.g_proj.bias', 'bn_weight', (g,), 7.0),
            (f'{a}.q_norm.weight', 'bn_weight', (hd,), 1.0),
            (f'{a}.k_norm.weight', 'bn_weight', (hd,), 1.0),
            (f'{a}.o_proj.weight', 'linear', (h * hd, d), 8.0 * lin),
            (f'{p}.post_attention_layernorm.weight', 'bn_weight', (d,), 1.0),
            (f'{m}.gate_proj.weight', 'linear', (d, f), lin),
            (f'{m}.up_proj.weight', 'linear', (d, f), lin),
            (f'{m}.down_proj.weight', 'linear', (f, d), lin),
        ]
    specs.append(('model.norm.weight', 'bn_weight', (d,), 1.0))
    return {'checkpoint_path': specs}


# -- from a video file to ids ---------------------------------------------------

def rows_of(n_frames: int, cfg=None) -> int:
    """One row per whole window of ``frames`` frames; a tail is dropped."""
    return int(n_frames) // _c(cfg)['frames']


def tokenise(frames: np.ndarray, cfg=None) -> np.ndarray:
    """(n, H, W, 3) uint8 RGB frames → (n · grid²,) int32 ids: of each frame
    the centred region of ``g·(H div g)`` × ``g·(W div g)`` pixels in a
    ``g × g`` grid of patches, ``id = ((sum of the patch's bytes) ·
    2654435761 mod 2^32) mod vocab``, patches row-major."""
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
    """(n, S, H, d): the pair (x[i], x[i + d/2]) turned by
    position · theta^(-2i/d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos],
                           axis=-1)


def _retention(ops, p, a, x, c):
    n, s, _ = x.shape
    h, g, d = (c['num_attention_heads'], c['num_key_value_heads'],
               c['head_dim'])
    eps = c['rms_norm_eps']
    q = ops.einsum('nsd,df->nsf', x, p[f'{a}.q_proj.weight']
                   ).reshape(n, s, h, d)
    k = ops.einsum('nsd,df->nsf', x, p[f'{a}.k_proj.weight']
                   ).reshape(n, s, g, d)
    v = ops.einsum('nsd,df->nsf', x, p[f'{a}.v_proj.weight']
                   ).reshape(n, s, g, d)
    q = _rotary(_rms(q, p[f'{a}.q_norm.weight'], eps), c['rope_theta'])
    k = _rotary(_rms(k, p[f'{a}.k_norm.weight'], eps), c['rope_theta'])
    gate = jax.nn.log_sigmoid(
        ops.einsum('nsd,dg->nsg', x, p[f'{a}.g_proj.weight'])
        + p[f'{a}.g_proj.bias'])
    # γ_1 + … + γ_t: the decay between two positions is a difference
    total = jnp.cumsum(gate, axis=1)                        # (n, S, g)
    blk = min(c['query_block'], s)
    if s % blk:
        raise ValueError(f'{s} positions are no whole number of query '
                         f'blocks of {blk}')
    q = q.reshape(n, s // blk, blk, g, h // g, d)
    key_pos = jnp.arange(s)

    def block(i):
        qi = q[:, i]                                        # (n, blk, g, r, d)
        scores = ops.einsum('nqgrd,nkgd->ngrqk', qi, k)
        query_pos = i * blk + jnp.arange(blk)
        seen = query_pos[:, None] >= key_pos[None, :]
        mine = lax.dynamic_slice_in_dim(total, i * blk, blk, axis=1)
        gap = mine.transpose(0, 2, 1)[..., None] \
            - total.transpose(0, 2, 1)[:, :, None, :]       # (n, g, q, k)
        weight = scores * scores * jnp.exp(
            jnp.where(seen, gap, -jnp.inf))[:, :, None]
        num = ops.einsum('ngrqk,nkgd->nqgrd', weight, v)
        den = weight.sum(axis=-1).transpose(0, 3, 1, 2)     # (n, q, g, r)
        return num / (den[..., None] + c['retention_eps'])

    with ops.repeat(s // blk):
        y = lax.map(block, jnp.arange(s // blk))            # (blocks, n, ...)
    y = jnp.moveaxis(y, 0, 1).reshape(n, s, h * d)
    return ops.einsum('nsf,fd->nsd', y, p[f'{a}.o_proj.weight'])


def _swiglu(ops, x, w_gate, w_up, w_down, c):
    """By blocks of rows, so that the two 17,408-wide intermediates of a
    32,768-row window never stand whole."""
    n, s, d = x.shape
    blk = min(c['row_block'], s)
    if s % blk:
        raise ValueError(f'{s} rows are no whole number of blocks of {blk}')

    def rows(xb):
        return ops.einsum('nsf,fd->nsd',
                          jax.nn.silu(ops.einsum('nsd,df->nsf', xb, w_gate))
                          * ops.einsum('nsd,df->nsf', xb, w_up), w_down)

    with ops.repeat(s // blk):
        y = lax.map(rows, jnp.moveaxis(x.reshape(n, s // blk, blk, d), 1, 0))
    return jnp.moveaxis(y, 0, 1).reshape(n, s, d)


def forward(ops, params, units, cfg=None):
    """(n, window ids) int32 → (n, hidden) float32."""
    c = _c(cfg)
    p = params['checkpoint_path']
    eps = c['rms_norm_eps']
    x = p['model.embed_tokens.weight'][units]
    for i in range(c['layers']):
        b = f'model.layers.{i}'
        x = x + _retention(ops, p, f'{b}.self_attn',
                           _rms(x, p[f'{b}.input_layernorm.weight'], eps), c)
        x = x + _swiglu(ops, _rms(x, p[f'{b}.post_attention_layernorm.weight'],
                                  eps),
                        p[f'{b}.mlp.gate_proj.weight'],
                        p[f'{b}.mlp.up_proj.weight'],
                        p[f'{b}.mlp.down_proj.weight'], c)
    return _rms(x, p['model.norm.weight'], eps).mean(axis=1)


# -- the model's work, for step_mfu ---------------------------------------------

def attention_form_macs(cfg=None) -> int:
    """Multiply-adds :func:`forward` makes in the two retention products for
    one window: every query against every key (the mask is applied after),
    40 heads × (128 + 128), each layer."""
    c = _c(cfg)
    s = window_ids(c)
    return (s * s * c['num_attention_heads'] * 2 * c['head_dim']
            * c['layers'])


def recurrent_form_macs(cfg=None) -> int:
    """The same layers in the recurrent form, which no choice of chunk or
    block changes: a position updates a state of d(d+1)/2 × (d + 1) numbers
    a key-value head and reads it once a query head."""
    c = _c(cfg)
    d = c['head_dim']
    state = d * (d + 1) // 2 * (d + 1)
    return (window_ids(c) * state
            * (c['num_key_value_heads'] + c['num_attention_heads'])
            * c['layers'])


def model_macs(counted: int, cfg=None) -> int:
    """The model's multiply-adds for one window, from the reference's own
    count ``counted`` (``Ops.macs`` after tracing one window): every
    projection and the SwiGLU as counted; the retention products in the
    recurrent form instead of the attention form's S² pairs."""
    return counted - attention_form_macs(cfg) + recurrent_form_macs(cfg)
