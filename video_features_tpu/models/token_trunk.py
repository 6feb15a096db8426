"""What every token trunk of the ``lm`` family is made of.

The family's trunks (``models/latent_moe.py``: latent attention + sparse
experts; ``models/retention_trunk.py``: gated power retention, dense;
``models/hybrid_trunk.py``: gated short convolutions, full and sliding-window
grouped-query attention layers by ``layer_types``, sparse experts, in two
dialects) are pre-norm residual decoders over token
ids that differ in their sequence mixers and their feed-forward's routing.
The rest is here, once: RMSNorm, the SwiGLU, the embedding lookup, the
pooled output, the seeded draw of a parameter set, and for the expert
trunks the check of a held share and the stage-table counters of a layer's
routing.

A trunk module offers ``extract/lm.py`` a few names and nothing else:

* ``MODEL_TYPE`` — the published ``config.json``'s ``model_type`` (a
  module that runs several reads ``args['model_type']`` in ``from_args``
  and keeps it on its config);
* ``TrunkConfig.from_args(args)``, ``param_shapes(cfg)``,
  ``param_count(cfg)``, ``init_params(cfg, seed)``;
* ``forward(params, ids, cfg, platform=...)`` → ``(features (B, D) float32,
  a counter array)``, the array leaving the step under ``COUNTER``;
* ``describe(cfg)`` and ``SHARE_ADVICE`` — the trunk in a few words and how
  to hold less of it, for the build's refusal of what cannot fit;
* ``kernels(cfg, platform, window_ids, precision)`` — which path the step
  compiles here, for the build's event and the run manifest;
* ``count(tracer, counter, cfg, tokens)`` — the stage-table counters filled
  from one fetched step's counter array.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from video_features_tpu.ops.moe import walk_rows

Params = Dict[str, jax.Array]


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * gain).astype(x.dtype)


# a SwiGLU's three matrices under the names most checkpoints give them
# (gate, up, down); LFM2's are w1, w3, w2
SWIGLU_NAMES = ('gate_proj', 'up_proj', 'down_proj')
# rows a row-blocked feed-forward walks at a time (:func:`mlp_rows`)
MLP_ROWS = 4096


def swiglu(x: jax.Array, p: Params, prefix: str,
           row_block: Optional[int] = None,
           names: Tuple[str, str, str] = SWIGLU_NAMES) -> jax.Array:
    """``W_down(silu(W_gate x) ⊙ W_up x)`` over (T, D) tokens. With
    ``row_block`` the tokens are walked that many rows at a time (T a
    multiple of it), so the two intermediates stand as (row_block, F) and
    never as (T, F): 32,768 tokens × 17,408 wide are 2.3 GB each."""
    gate_name, up_name, down_name = names

    def rows(x):
        gate = jnp.dot(x, p[f'{prefix}.{gate_name}.weight'])
        up = jnp.dot(x, p[f'{prefix}.{up_name}.weight'])
        return jnp.dot(jax.nn.silu(gate) * up,
                       p[f'{prefix}.{down_name}.weight'])

    t = x.shape[0]
    if not row_block or t <= row_block:
        return rows(x)
    if t % row_block:
        raise ValueError(f'{t} tokens are no whole number of row blocks of '
                         f'{row_block}')
    return lax.map(rows, x.reshape(t // row_block, row_block, -1)
                   ).reshape(t, -1)


def embed(params: Params, ids: jax.Array) -> jax.Array:
    """(B, S) int32 ids → (B, S, D) rows of the embedding."""
    return params['model.embed_tokens.weight'][ids]


def mlp_rows(tokens: int) -> Optional[int]:
    """The row block of a step's dense feed-forward: ``MLP_ROWS`` where the
    step's tokens are a whole number of them, else None (all at once)."""
    return MLP_ROWS if tokens % MLP_ROWS == 0 else None


def final_norm(x: jax.Array, params: Params, eps: float,
               name: str = 'model.norm.weight') -> jax.Array:
    """(B, S, D) residual stream → the final RMSNorm's hidden states
    (``name``: the gain under the checkpoint's own name)."""
    return rms_norm(x, params[name], eps)


def mean_features(hidden: jax.Array) -> jax.Array:
    """(B, S, D) final-norm hidden states → (B, D) float32 features: the
    mean over the window's positions."""
    return hidden.astype(jnp.float32).mean(axis=1)


def param_count(shapes: Dict[str, Tuple[int, ...]]) -> int:
    return sum(math.prod(s) for s in shapes.values())


def draw_params(shapes: Dict[str, Tuple[int, ...]], seed: int,
                special: Optional[Callable] = None) -> Dict[str, np.ndarray]:
    """Seeded random parameters (tests, ``allow_random_weights`` runs):
    matrices N(0, 1/fan_in) over the contracted axis, the embedding N(0, 1),
    norm gains near 1. ``special(name, shape, rng)`` draws what a trunk
    holds besides (a router's bias, a gate's) or returns None."""
    rng = np.random.default_rng(seed)
    out: Dict[str, np.ndarray] = {}
    for name, shape in shapes.items():
        w = special(name, shape, rng) if special else None
        if w is None and name.endswith('norm.weight'):
            w = 0.9 + 0.2 * rng.random(shape, dtype=np.float32)
        elif w is None:
            w = rng.standard_normal(shape, dtype=np.float32)
            if name != 'model.embed_tokens.weight':
                w *= np.float32(1.0 / math.sqrt(shape[-2]))
        out[name] = w
    return out


def held_experts(n_experts_held: Optional[int], first_expert: int,
                 n_routed: int) -> int:
    """How many of a layer's ``n_routed`` experts are held here from
    ``first_expert`` on (``n_experts_held`` None: all of them); a share
    that does not lie inside the router's range is refused."""
    held = n_routed if n_experts_held is None else int(n_experts_held)
    if not 0 < held <= n_routed - first_expert:
        raise ValueError(
            f'n_experts_held={held} from first_expert={first_expert} does '
            f'not lie inside the router\'s {n_routed} experts')
    return held


def count_experts(tracer, counts: np.ndarray, top_k: int, tokens: int,
                  block: int) -> None:
    """One fetched step's ``(expert layers, held)`` assignment counts → the
    stage table. Per layer: the held experts' mean load against the fullest
    one's (the one the layer waits for) → ``moe_route``; how many of all
    assignments fell on experts held here → ``moe_held``; the held
    assignments against the rows the block walk computed for them (each
    expert's rounded up to whole blocks of ``block``) → ``moe_walk``."""
    counts = np.asarray(counts, np.int64)
    if not counts.size:
        return
    layers, held = counts.shape
    assigned = int(counts.sum())
    tracer.add_occupancy('moe_route', assigned,
                         int(counts.max(axis=1).sum()) * held)
    tracer.add_occupancy('moe_held', assigned, int(tokens) * top_k * layers)
    tracer.add_occupancy('moe_walk', assigned,
                         int(walk_rows(counts, block).sum()))
