"""Plain reference: the granite-4.0-h-micro trunk (``granitemoehybrid``),
twenty of its forty layers.

Written from the published ``config.json`` (``model_type:
granitemoehybrid``, https://huggingface.co/ibm-granite/granite-4.0-h-micro,
3B, dense) and, for what no key spells, ``transformers``'
``models/granitemoehybrid/modeling_granitemoehybrid.py`` (whose Mamba layer
is Mamba-2's: Dao & Gu 2024, arXiv:2405.21060) as the issue that brought
this file wrote it out (that module is not on this machine). RMSNorm ``x ·
rsqrt(mean x² + 1e-5) · γ``, float32 through ``Ops('highest')``. ``x0 =
E[ids] · 12`` (``embedding_multiplier``); layer ``i``:

    h = x + 0.22 · mixer_i(rms(x, input_layernorm))
    x = h + 0.22 · W_out2(silu(u_g) ⊙ u_u),  [u_g ‖ u_u] = rms(h, post_attention_layernorm) W_in2

(``residual_multiplier`` 0.22; ``shared_mlp.input_linear`` 2,048 → 2 ·
8,192, the first half the gate; ``output_linear`` 8,192 → 2,048).

* ``layer_types[i] == 'mamba'``: ``[z ‖ xBC ‖ dt] = u W_in`` (4,096 +
  4,352 + 64, no bias); ``xBC = silu(conv(xBC) + b)``, ``conv`` the
  depthwise ``nn.Conv1d`` of 4 taps with ``padding = 3`` cut to the first S
  outputs (zeros before position 0), as four explicit shifted terms; ``[x ‖
  B ‖ C] = xBC``, x 64 heads of 64, B and C 128 wide and shared by every
  head; ``Δ = softplus(dt + dt_bias)``, ``A = −exp(A_log)``; then the
  **recurrence**, one position at a time from a zero state:

      state_t[h] = exp(Δ_t[h] A[h]) · state_{t−1}[h] + Δ_t[h] · B_t ⊗ x_t[h]
      y_t[h] = C_t · state_t[h] + D[h] · x_t[h]

  (state (64, 128, 64)); then ``rms(y ⊙ silu(z), mamba.norm)`` over all
  4,096 channels, and ``W_out``.
* ``layer_types[i] == 'attention'``: ``q = u W_q`` → 32 heads of 64, ``k``,
  ``v`` → 8 heads of 64, no bias, no per-head norms, **no positional code**
  (``position_embedding_type: nope``); query head j reads key-value head j
  div 4; ``softmax(q·k · 1/64 over keys j ≤ i) v`` (the scale is
  ``attention_multiplier``, not 1/√64), then ``o_proj``. A block of query
  rows at a time against all keys under the mask, so that 32,768 positions
  fit.
* output: ``model.norm``, mean over the window's positions.

Departures from the published model: the output head (tied to the
embedding) is not run — a feature extractor saves hidden states — so
``logits_scaling`` (8, a divisor of the logits) is read by nothing; depth is
layers 0–19 of 40 (``M M M M M A M M M M`` twice). The ids are traffic, cut
from the decoded frames (no tokeniser ships with the config): of each RGB
frame the centred region of ``32·(H div 32)`` × ``32·(W div 32)`` pixels in
a 32 × 32 grid of patches, ``id = ((sum of the patch's bytes) · 2654435761
mod 2^32) mod vocab``, patches row-major, 32 frames a window → 32,768 ids.

``CFG`` holds the sizes; a test at a tiny size replaces it.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from _video import read_frames

M, A = 'mamba', 'attention'
# published widths; ``layers`` (and ``layer_types``' first twenty entries)
# and the window of ids are the cut (benchmark/configs/
# granite-4.0-h-micro-l20.json)
CFG = {
    'vocab_size': 100352, 'hidden_size': 2048, 'layers': 20,
    'layer_types': (M, M, M, M, M, A, M, M, M, M) * 2,
    'shared_intermediate_size': 8192, 'num_attention_heads': 32,
    'num_key_value_heads': 8, 'attention_multiplier': 0.015625,
    'embedding_multiplier': 12.0, 'residual_multiplier': 0.22,
    'rms_norm_eps': 1e-5, 'mamba_n_heads': 64, 'mamba_d_head': 64,
    'mamba_d_state': 128, 'mamba_d_conv': 4, 'mamba_expand': 2,
    'frames': 32, 'patch_grid': 32, 'query_block': 256,
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


def inner(cfg=None) -> int:
    """The Mamba mixer's x, z and norm width: ``mamba_expand · hidden``."""
    c = _c(cfg)
    return c['mamba_expand'] * c['hidden_size']


# -- parameters ---------------------------------------------------------------

def param_specs(cfg=None):
    """{checkpoint key of the program's config: parameter list}, under the
    checkpoint's names, matrices (in, out), the convolution's taps as (taps,
    channels): ``mamba.conv1d.weight[j]`` weighs the position ``3 − j``
    back. A matrix is N(0, 1/fan_in) over its contracted axis (``linear``
    draws sqrt(2 / fan_in) × scale, so the scale undoes the 2); the taps
    N(0, 1/4); norm gains, ``D`` in [0.8, 1.2]; the convolution's bias
    N(0, 0.01). The harness draws in three kinds (``benchmark/weights.py``):
    Mamba-2's initialisation is met as nearly as they allow — ``A_log`` in
    [0.8, 1.2] · ln 4 (``A`` in [−5.28, −3.03], about the geometric middle
    of the init's −16…−1), ``dt_bias`` in [0.8, 1.2] · softplus⁻¹(0.01)
    (``Δ`` of a zero input 0.004…0.025, inside the init's 1e-3…0.1), ``D``
    near 1. The embedding is N(0, 1/144), so that × 12 it is a stream of
    size 1. Every sub-layer's output passes × 0.22 into the stream: the
    three output matrices (``mamba.out_proj``, ``self_attn.o_proj``,
    ``shared_mlp.output_linear``) are × 4, so that each adds about the
    stream's size; q and k are × 4, so that a score ``q·k / 64`` has a
    deviation of some 2 and a query reads tens to hundreds of keys (as a
    trained head does), not all of them alike."""
    c = _c(cfg)
    d, h, g, hd = (c['hidden_size'], c['num_attention_heads'],
                   c['num_key_value_heads'], head_dim(c))
    heads, n, w = c['mamba_n_heads'], c['mamba_d_state'], inner(c)
    conv = w + 2 * n
    lin = math.sqrt(0.5)
    specs = [('model.embed_tokens.weight', 'linear', (c['vocab_size'], d),
              math.sqrt(c['vocab_size'] / 2.0) / c['embedding_multiplier'])]
    for i in range(c['layers']):
        p = f'model.layers.{i}'
        specs.append((f'{p}.input_layernorm.weight', 'bn_weight', (d,), 1.0))
        if c['layer_types'][i] == M:
            a = f'{p}.mamba'
            specs += [
                (f'{a}.in_proj.weight', 'linear', (d, w + conv + heads), lin),
                (f'{a}.conv1d.weight', 'linear', (c['mamba_d_conv'], conv),
                 lin),
                (f'{a}.conv1d.bias', 'bias', (conv,), 0.1),
                (f'{a}.dt_bias', 'bn_weight', (heads,),
                 math.log(math.expm1(0.01))),
                (f'{a}.A_log', 'bn_weight', (heads,), math.log(4.0)),
                (f'{a}.D', 'bn_weight', (heads,), 1.0),
                (f'{a}.norm.weight', 'bn_weight', (w,), 1.0),
                (f'{a}.out_proj.weight', 'linear', (w, d), 4.0 * lin)]
        else:
            a = f'{p}.self_attn'
            specs += [
                (f'{a}.q_proj.weight', 'linear', (d, h * hd), 4.0 * lin),
                (f'{a}.k_proj.weight', 'linear', (d, g * hd), 4.0 * lin),
                (f'{a}.v_proj.weight', 'linear', (d, g * hd), lin),
                (f'{a}.o_proj.weight', 'linear', (h * hd, d), 4.0 * lin)]
        specs.append((f'{p}.post_attention_layernorm.weight', 'bn_weight',
                      (d,), 1.0))
        f = c['shared_intermediate_size']
        specs += [
            (f'{p}.shared_mlp.input_linear.weight', 'linear', (d, 2 * f), lin),
            (f'{p}.shared_mlp.output_linear.weight', 'linear', (f, d),
             4.0 * lin)]
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


def _scan(ops, xs, dt, a, b, c):
    """The recurrence over positions: ``xs`` (n, S, H, P), ``dt`` (n, S, H),
    ``a`` (H,), ``b``, ``c`` (n, S, N) → y (n, S, H, P) without the skip.
    Both the update (an outer product a head) and the read count their
    multiply-adds, H · N · P a position each."""
    n, s, heads, p = xs.shape
    size = b.shape[-1]

    def step(state, inp):                          # state (n, H, N, P)
        x_t, dt_t, b_t, c_t = inp
        decay = jnp.exp(dt_t * a)[..., None, None]
        state = decay * state + (dt_t[..., None, None] * b_t[:, None, :, None]
                                 * x_t[:, :, None, :])
        ops._count(n * heads * size * p)
        return state, ops.einsum('nk,nhkp->nhp', c_t, state)

    state = jnp.zeros((n, heads, size, p), jnp.float32)
    with ops.repeat(s):
        _, y = lax.scan(step, state, (jnp.moveaxis(xs, 1, 0),
                                      jnp.moveaxis(dt, 1, 0),
                                      jnp.moveaxis(b, 1, 0),
                                      jnp.moveaxis(c, 1, 0)))
    return jnp.moveaxis(y, 0, 1)


def _mamba(ops, p, a, x, c):
    """``GraniteMoeHybridMambaLayer`` without a cache: (n, S, D) → (n, S, D)."""
    n, s, _ = x.shape
    heads, hp, size = c['mamba_n_heads'], c['mamba_d_head'], c['mamba_d_state']
    w = inner(c)
    proj = ops.einsum('nsd,df->nsf', x, p[f'{a}.in_proj.weight'])
    z, xbc, dt = proj[..., :w], proj[..., w:2 * w + 2 * size], \
        proj[..., 2 * w + 2 * size:]
    taps = c['mamba_d_conv']
    weight = p[f'{a}.conv1d.weight']                       # (taps, channels)
    padded = jnp.concatenate(
        [jnp.zeros((n, taps - 1, xbc.shape[-1]), xbc.dtype), xbc], axis=1)
    conv = sum(weight[j] * padded[:, j:j + s] for j in range(taps))
    xbc = jax.nn.silu(conv + p[f'{a}.conv1d.bias'])
    xs, b, cc = xbc[..., :w], xbc[..., w:w + size], xbc[..., w + size:]
    dt = jax.nn.softplus(dt + p[f'{a}.dt_bias'])
    rate = -jnp.exp(p[f'{a}.A_log'])
    xs = xs.reshape(n, s, heads, hp)
    y = _scan(ops, xs, dt, rate, b, cc) + p[f'{a}.D'][:, None] * xs
    gated = y.reshape(n, s, w) * jax.nn.silu(z)
    normed = _rms(gated, p[f'{a}.norm.weight'], c['rms_norm_eps'])
    return ops.einsum('nsf,fd->nsd', normed, p[f'{a}.out_proj.weight'])


def _attention(ops, p, a, x, c):
    """Grouped-query causal attention with no positional code: a block of
    ``query_block`` query rows at a time against all keys, the ones after
    the row masked out."""
    n, s, _ = x.shape
    h, g, hd = (c['num_attention_heads'], c['num_key_value_heads'],
                head_dim(c))
    q = ops.einsum('nsd,df->nsf', x, p[f'{a}.q_proj.weight']
                   ).reshape(n, s, g, h // g, hd)
    k = ops.einsum('nsd,df->nsf', x, p[f'{a}.k_proj.weight']
                   ).reshape(n, s, g, hd)
    v = ops.einsum('nsd,df->nsf', x, p[f'{a}.v_proj.weight']
                   ).reshape(n, s, g, hd)
    blk = min(c['query_block'], s)

    def rows(q0):
        qb = lax.dynamic_slice_in_dim(q, q0, blk, axis=1)
        scores = ops.einsum('nqgrd,nkgd->ngrqk', qb, k) \
            * c['attention_multiplier']
        visible = jnp.arange(s)[None, :] <= q0 + jnp.arange(blk)[:, None]
        scores = jnp.where(visible, scores, -jnp.inf)
        return ops.einsum('ngrqk,nkgd->nqgrd',
                          jax.nn.softmax(scores, axis=-1), v)

    with ops.repeat(s // blk):
        out = lax.map(rows, jnp.arange(0, s, blk))      # (blocks, n, blk, …)
    out = jnp.moveaxis(out, 0, 1).reshape(n, s, h * hd)
    return ops.einsum('nsf,fd->nsd', out, p[f'{a}.o_proj.weight'])


def _shared_mlp(ops, x, p, m):
    gate_up = ops.einsum('nsd,df->nsf', x, p[f'{m}.input_linear.weight'])
    f = gate_up.shape[-1] // 2
    return ops.einsum('nsf,fd->nsd',
                      jax.nn.silu(gate_up[..., :f]) * gate_up[..., f:],
                      p[f'{m}.output_linear.weight'])


def forward(ops, params, units, cfg=None):
    """(n, window ids) int32 → (n, hidden) float32."""
    c = _c(cfg)
    p = params['checkpoint_path']
    eps, mult = c['rms_norm_eps'], c['residual_multiplier']
    x = p['model.embed_tokens.weight'][units] * c['embedding_multiplier']
    for i in range(c['layers']):
        b = f'model.layers.{i}'
        normed = _rms(x, p[f'{b}.input_layernorm.weight'], eps)
        kind = c['layer_types'][i]
        if kind == M:
            y = _mamba(ops, p, f'{b}.mamba', normed, c)
        elif kind == A:
            y = _attention(ops, p, f'{b}.self_attn', normed, c)
        else:
            raise ValueError(f'layer_types[{i}] = {kind!r}')
        x = x + mult * y
        normed = _rms(x, p[f'{b}.post_attention_layernorm.weight'], eps)
        x = x + mult * _shared_mlp(ops, normed, p, f'{b}.shared_mlp')
    return _rms(x, p['model.norm.weight'], eps).mean(axis=1)


# -- the model's work, for step_mfu ---------------------------------------------

def reference_waste_macs(cfg=None) -> int:
    """Attention multiply-adds :func:`forward` makes for one window beyond
    the visible pairs: each query row against every key, the later ones
    masked."""
    c = _c(cfg)
    s = window_ids(c)
    per_pair = c['num_attention_heads'] * 2 * head_dim(c)
    return (s * s - s * (s + 1) // 2) * per_pair \
        * c['layer_types'][:c['layers']].count(A)


def model_macs(counted: int, cfg=None) -> int:
    """The model's multiply-adds for one window, from the reference's own
    count ``counted`` (``Ops.macs`` after tracing one window): every
    contraction as counted — the projections, the shared MLPs, the
    recurrence's update and read, H · N · P a position each — but the
    attention scores and values over the S(S+1)/2 visible pairs alone."""
    return counted - reference_waste_macs(cfg)
