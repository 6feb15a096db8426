"""A gated power-retention token trunk (``model_type: brumby``).

The decoder trunk of Manifest AI's Brumby-14B-Base as a feature extractor:
token ids in, one hidden-state row a window out. It is Qwen3's dense block
— pre-norm residual, RMSNorm, grouped-query heads with a per-head RMSNorm
on q and k and the half-split rotary code, a SwiGLU, no biases — with every
softmax attention replaced by *power retention* (``ops/retention.py``): the
weight of position ``s`` for position ``t`` is ``(q_t·k_s)²`` times a learned
decay, which makes the mixer a linear recurrence over a state of
``d(d+1)/2 × (d + 1)`` numbers a key-value head, and a window's cost linear
in its length. One layer, ``h = RMSNorm(x)``:

    q = rope(rmsnorm_d(W_q h))   k = rope(rmsnorm_d(W_k h))   v = W_v h
    γ = log σ(W_g h + b_g)       one a key-value head, ≤ 0
    y = retention(q, k, v, γ)    query heads 5j … 5j+4 read key-value head j
    x ← x + W_o y;   x ← x + W_down(silu(W_gate h') ⊙ W_up h'),  h' = RMSNorm(x)

The published ``config.json`` names the widths; the power (2), the gate (one
log-sigmoid a key-value head, a linear map of the layer's normed input with
a bias) and the sum normaliser are the paper's. Prefill only: a window
starts from an empty state and its final state is dropped. The output is
the final RMSNorm's mean over the window's positions; the head is neither
held nor run.

The decoder around the mixer is ``models/token_trunk.py``'s, under the one
row of ``DIALECTS``. Parameters are a flat ``{dotted name: array}`` dict
under Qwen3's names, matrices as (in, out); the gate is
``self_attn.g_proj.{weight,bias}``.
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
    BaseConfig, Dialect, Mixer, Params, param_count, param_shapes, rms_norm,
)
from video_features_tpu.ops.attention import KERNEL_PASSES, rotary_half
from video_features_tpu.ops.retention import (
    resolve_retention, retention_chunked,
)

MODEL_TYPE = 'brumby'
# the step's second output: (layers, 2) positions of the batch that each
# layer's mixer put through the chunked state scan, and how many of those
# through the Mosaic kernels of its state products
COUNTER = 'retention_scanned'
SHARE_ADVICE = ('Run fewer layers here (num_hidden_layers: the rest are '
                'further pipeline stages).')
RETENTION = 'retention'

# the config keys the trunk is built from, under the published names
CONFIG_KEYS = (
    'vocab_size', 'hidden_size', 'num_hidden_layers', 'intermediate_size',
    'num_attention_heads', 'num_key_value_heads', 'head_dim', 'rope_theta',
    'rms_norm_eps',
)
# positions a chunk of the state scan: the program's, no published key and
# no option (the scan alone reads level at 256 / 512 / 1,024 on the chip, the
# whole step 16 % slower at 1,024: PERF.md §5)
RETENTION_CHUNK = 512


# -- the mixer ----------------------------------------------------------------

def retention_shapes(cfg: TrunkConfig, a: str, kind: str
                     ) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of one layer's retention mixer under prefix ``a``."""
    d = cfg.hidden_size
    h, g, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    return {f'{a}.q_proj.weight': (d, h * hd),
            f'{a}.k_proj.weight': (d, g * hd),
            f'{a}.v_proj.weight': (d, g * hd),
            f'{a}.g_proj.weight': (d, g),
            f'{a}.g_proj.bias': (g,),
            f'{a}.q_norm.weight': (hd,),
            f'{a}.k_norm.weight': (hd,),
            f'{a}.o_proj.weight': (h * hd, d)}


def retention_block(p: Params, prefix: str, x: jax.Array, cfg: TrunkConfig,
                    attn_block: Optional[int] = None,
                    platform: Optional[str] = None, kind: str = RETENTION
                    ) -> Tuple[jax.Array, jax.Array]:
    """The mixer over one window: (S, D) normed input → (S, D), causal,
    positions 0…S−1, from an empty state; and a (2,) count: how many of the
    positions it put through the state scan (what ``retention_scan``
    counts) and how many of those through the kernels. ``platform`` is
    where the graph will run (None: the default backend); with the head's
    widths, the chunk and the ambient matmul precision it decides the form
    of the state products (``ops.retention.resolve_retention``). It has no
    attention tiles and one kind: ``attn_block`` and ``kind`` are the
    loop's, not read."""
    with jax.named_scope('retention'):
        s = x.shape[0]
        h, g, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        eps, theta = cfg.rms_norm_eps, cfg.rope_theta
        positions = jnp.arange(s)
        q = jnp.dot(x, p[f'{prefix}.q_proj.weight']).reshape(s, h, d)
        k = jnp.dot(x, p[f'{prefix}.k_proj.weight']).reshape(s, g, d)
        v = jnp.dot(x, p[f'{prefix}.v_proj.weight']).reshape(s, g, d)
        q = rotary_half(rms_norm(q, p[f'{prefix}.q_norm.weight'], eps),
                        positions, theta).reshape(s, g, cfg.group, d)
        k = rotary_half(rms_norm(k, p[f'{prefix}.k_norm.weight'], eps),
                        positions, theta)
        log_gate = jax.nn.log_sigmoid(
            (jnp.dot(x, p[f'{prefix}.g_proj.weight'])
             + p[f'{prefix}.g_proj.bias']).astype(jnp.float32))
        precision = jax.config.jax_default_matmul_precision
        kernel = resolve_retention(platform or jax.default_backend(), d, d,
                                   min(RETENTION_CHUNK, s),
                                   precision) == 'kernel'
        y, _ = retention_chunked(
            q, k, v, log_gate, RETENTION_CHUNK,
            kernel_passes=KERNEL_PASSES[precision] if kernel else None)
        counted = jnp.array([s, s if kernel else 0], jnp.int32)
        return (jnp.dot(y.reshape(s, h * d), p[f'{prefix}.o_proj.weight']),
                counted)


DIALECTS = {
    MODEL_TYPE: Dialect(
        mixers={RETENTION: Mixer(retention_block, retention_shapes,
                                 counted=True)},
        config_keys=CONFIG_KEYS),
}


@dataclass(frozen=True)
class TrunkConfig(BaseConfig):
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    rms_norm_eps: float
    layer_types: Optional[Tuple[str, ...]] = None   # every layer retention
    model_type: str = MODEL_TYPE

    dialects = DIALECTS
    eps = property(attrgetter('rms_norm_eps'))

    def __post_init__(self):
        self.check_layers()
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f'num_attention_heads={self.num_attention_heads} is no '
                f'whole number of groups of num_key_value_heads='
                f'{self.num_key_value_heads}')
        if self.head_dim % 2:
            raise ValueError(f'head_dim={self.head_dim} must be even (rotary '
                             f'pairs)')

    @property
    def group(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    def is_dense(self, layer: int) -> bool:
        return True


# -- what the build says and counts ---------------------------------------------

def init_params(cfg: TrunkConfig, seed: int = 0) -> Dict[str, np.ndarray]:
    """Seeded random parameters (``token_trunk.draw_params``); the gate's
    bias in [4, 8], so a position is remembered for some 50 to 3,000
    further ones, as a trained forget gate is."""
    def gate_bias(name, shape, rng):
        if name.endswith('g_proj.bias'):
            return 4.0 + 4.0 * rng.random(shape, dtype=np.float32)
        return None
    return token_trunk.draw_params(param_shapes(cfg), seed, gate_bias)


def describe(cfg: TrunkConfig) -> str:
    return (f'{cfg.num_hidden_layers} layers of gated power retention '
            f'({cfg.num_attention_heads} query / {cfg.num_key_value_heads} '
            f'key-value heads of {cfg.head_dim}) and a dense SwiGLU of '
            f'{cfg.intermediate_size}')


def kernels(cfg: TrunkConfig, platform: str, window_ids: int,
            precision: Optional[str]) -> Dict[str, object]:
    """The form of the mixer the step compiles and its chunk for windows
    of ``window_ids`` positions: ``retention`` is 'kernel' where the two
    products with φ run as Mosaic kernels (``ops.retention.
    resolve_retention``: from the platform, the head's widths, the chunk and
    the matmul precision) and 'state' where the whole scan is XLA's."""
    chunk = min(RETENTION_CHUNK, window_ids)
    return {'retention': resolve_retention(platform, cfg.head_dim,
                                           cfg.head_dim, chunk, precision),
            'retention_chunk': chunk}


def count(tracer, counted: np.ndarray, cfg: TrunkConfig, tokens: int) -> None:
    """The step's ``(layers, 2)`` counter → the stage table. Column 0,
    ``retention_scan``: positions × layers of one fetched step mixed through
    the carried state ÷ positions × layers of the step. Column 1,
    ``retention_kernel``: those of them whose state products ran through the
    kernels ÷ those scanned."""
    scanned, through_kernel = (int(col.sum())
                               for col in np.asarray(counted).T)
    tracer.add_occupancy('retention_scan', scanned,
                         int(tokens) * cfg.num_hidden_layers)
    tracer.add_occupancy('retention_kernel', through_kernel, scanned)
