"""Sparse-expert layer ops: routing, and the grouped products of one share.

A mixture-of-experts layer picks ``top_k`` of ``n_routed`` experts a token.
In a deployment the experts of a layer are divided over several chips
(expert parallelism); a chip is *told which experts it holds*, routes over
all of them, and computes the part of the result its own experts give. On
one chip that is the whole of it: the router keeps its published width and
experts per token, the assignments that fall on absent experts are left
out, and nothing stands in for the other chips or their exchange.

The pieces, in the order a layer uses them:

* :func:`route` — sigmoid scores in float32 at ``highest`` (the product
  decides a discrete choice), the ``top_k`` largest of ``score + bias``
  (DeepSeek-V3's ``noaux_tc`` with one group: the bias corrects the load
  and takes no part in the weights), the chosen scores normalised and
  scaled.
* :func:`dispatch` — which token–expert assignments land on the held
  experts, ordered by expert, with the per-expert counts. No capacity, no
  token dropped: every buffer is sized for all assignments landing here.
* :func:`grouped_swiglu` — the experts' SwiGLU over that ragged grouping.
  ``jax.lax.ragged_dot`` is the obvious spelling, but the TPU compiler of
  this installation expands it into one dense product per group over *all*
  rows (64× the work at 64 groups; compiled for a described v5e, PR 27), so
  the groups are walked in row blocks instead: a ``fori_loop`` over
  ``ceil(count_e / block)`` blocks per expert, each a gather of its rows, three
  plain products against that expert's matrices, and one contiguous write.
  Work is proportional to the assignments held, rounded up to a block an
  expert.
* :func:`combine` — each token's weighted sum over its held assignments, by
  gather (a scatter-add of rows is the slow direction on a TPU).

:func:`routed_experts` is those four in a row, what every expert trunk's
layer calls (``models/token_trunk.py::expert_block``);
:func:`walk_rows` says how many rows the block walk computed for given
counts, which is what its padding costs.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

# rows a step of the block walk computes (:func:`grouped_swiglu`): the
# program's, no published key and no option
BLOCK = 256


def route(x: jax.Array, w_router: jax.Array, bias: jax.Array, *,
          top_k: int, scaling: float, normalise: bool = True,
          eps: float = 1e-20) -> Tuple[jax.Array, jax.Array]:
    """(T, D) tokens → ``(experts (T, top_k) int32, weights (T, top_k))``.

    ``w_router`` is (D, n_routed), ``bias`` (n_routed,) the load-balancing
    correction: it moves the choice, the weights are the raw scores, over
    their sum + ``eps`` where normalised (the constant is the model's:
    DeepSeek-V3's 1e-20, LFM2's 1e-6)."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if normalise:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + eps)
    return experts.astype(jnp.int32), weights * scaling


def dispatch(experts: jax.Array, first: int, n_held: int
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Order the (T, top_k) assignments by held expert.

    Returns ``(order, rank, counts)``: ``order`` (T·top_k,) lists the flat
    assignment indices (token · top_k + slot) expert by expert, those on
    absent experts last; ``rank`` is its inverse (the row an assignment
    sits in); ``counts`` (n_held,) int32 the assignments per held expert."""
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32)
    # a one-hot sum, not a scatter-add: 64 bins take every update
    counts = (key[:, None] == jnp.arange(n_held)).sum(axis=0, dtype=jnp.int32)
    return order, rank, counts


def grouped_swiglu(x: jax.Array, order: jax.Array, counts: jax.Array,
                   w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                   top_k: int, block: int = BLOCK) -> jax.Array:
    """``down_e(silu(gate_e x) * up_e x)`` for every held assignment.

    ``x`` (T, D); ``w_gate``/``w_up`` (E, D, F), ``w_down`` (E, F, D).
    Returns (T·top_k + block, D): row r is the output of assignment
    ``order[r]`` for r < sum(counts); later rows hold nothing of meaning."""
    n_rows = order.shape[0]
    starts = jnp.cumsum(counts) - counts
    blocks = (counts + block - 1) // block          # per expert
    block_ends = jnp.cumsum(blocks)
    # room for the last block to run past the last row
    order_padded = jnp.concatenate(
        [order, jnp.zeros((block,), order.dtype)])
    out = jnp.zeros((n_rows + block, x.shape[1]), x.dtype)

    def body(b, out):
        # b < block_ends[-1], so this is a held expert with a block left
        e = jnp.searchsorted(block_ends, b, side='right').astype(jnp.int32)
        row0 = starts[e] + (b - (block_ends[e] - blocks[e])) * block
        slots = lax.dynamic_slice(order_padded, (row0,), (block,))
        xs = x[slots // top_k]                      # (block, D) row gather
        gate = jnp.dot(xs, lax.dynamic_index_in_dim(w_gate, e, keepdims=False))
        up = jnp.dot(xs, lax.dynamic_index_in_dim(w_up, e, keepdims=False))
        ys = jnp.dot(jax.nn.silu(gate) * up,
                     lax.dynamic_index_in_dim(w_down, e, keepdims=False))
        # rows past this expert's end belong to the next experts, whose
        # own blocks come later in the walk and overwrite them
        return lax.dynamic_update_slice(out, ys.astype(out.dtype), (row0, 0))

    return lax.fori_loop(0, block_ends[-1], body, out)


def combine(rows: jax.Array, rank: jax.Array, experts: jax.Array,
            weights: jax.Array, first: int, n_held: int) -> jax.Array:
    """(T, D): each token's weighted sum over its assignments held here.

    ``rows`` is :func:`grouped_swiglu`'s output, ``rank`` the row of each
    assignment; the slots of one token are added one after another so that
    no (T, top_k, D) tensor is ever made."""
    n_tokens, top_k = experts.shape
    rank = rank.reshape(n_tokens, top_k)
    local = experts - first
    held = (local >= 0) & (local < n_held)
    out = jnp.zeros((n_tokens, rows.shape[1]), rows.dtype)
    for slot in range(top_k):
        picked = rows[rank[:, slot]] * weights[:, slot, None]
        # where, not multiply by 0: a row of an absent expert holds anything
        out = out + jnp.where(held[:, slot, None], picked, 0.0)
    return out


def moe_share(x: jax.Array, experts: jax.Array, weights: jax.Array,
              w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
              first: int, block: int = BLOCK
              ) -> Tuple[jax.Array, jax.Array]:
    """The routed part of a layer's output that the held experts give:
    ``(y (T, D), counts (n_held,) int32)``. The held experts are
    ``first … first + w_gate.shape[0] - 1`` of the router's range."""
    n_held = w_gate.shape[0]
    top_k = experts.shape[1]
    order, rank, counts = dispatch(experts, first, n_held)
    rows = grouped_swiglu(x, order, counts, w_gate, w_up, w_down, top_k,
                          block)
    y = combine(rows, rank, experts, weights.astype(x.dtype), first, n_held)
    return y, counts


def routed_experts(x: jax.Array, w_router: jax.Array, bias: jax.Array,
                   w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, *,
                   top_k: int, scaling: float, normalise: bool, eps: float,
                   first: int, block: int = BLOCK
                   ) -> Tuple[jax.Array, jax.Array]:
    """An expert layer's routed sum over (T, D) tokens, as far as the held
    experts give it (:func:`route`, then :func:`moe_share`): ``(y (T, D),
    counts (held,) int32)``. A shared expert, where a model has one, is its
    trunk's to add."""
    experts, weights = route(x, w_router, bias, top_k=top_k, scaling=scaling,
                             normalise=normalise, eps=eps)
    return moe_share(x, experts, weights, w_gate, w_up, w_down,
                     first=first, block=block)


def walk_rows(counts, block: int = BLOCK):
    """Rows the block walk computes for per-expert ``counts`` (any array
    library's integers, last axis the experts): each held expert's
    assignments rounded up to whole blocks."""
    return (counts + block - 1) // block * block
