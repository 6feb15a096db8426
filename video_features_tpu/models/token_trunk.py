"""The one decoder every token trunk of the ``lm`` family runs.

The family's six published model types (``models/latent_moe.py``:
joyai_llm_flash and dots3_note, latent attention over sparse experts;
``models/hybrid_trunk.py``: lfm2_moe and afmoe, short convolutions and
grouped-query attention over sparse experts, and granitemoehybrid, Mamba-2
state-space mixers and grouped-query attention, dense;
``models/retention_trunk.py``: brumby, gated power retention, dense) are
pre-norm residual decoders over
token ids. A layer is a (mixer kind, feed-forward kind) pair; a model type is
a :class:`Dialect` row — its layer kinds and their :class:`Mixer` s, its
published config keys, its checkpoint's names — under a config class of its
own. Everything else is here, once.

**The layer loop** (:func:`hidden_states`, :func:`forward`): the embedding
(× √hidden where the config's ``embed_scale`` says so, × its
``embedding_multiplier`` where it has one), then layer ``i`` of kind
``layer_types[i]``::

    h = x + m · post_op(mixer_kind(RMSNorm(x)))
    x = h + m · post_ffn(ffn_i(RMSNorm(h)))

``m`` the config's ``residual_multiplier`` where it has one (no multiply is
traced where it has none), ``post_op`` and ``post_ffn`` the dialect's
post-norms where it has them, the
mixer a window at a time (``lax.map``) or over the whole batch as its
:class:`Mixer` says, ``ffn_i`` a dense SwiGLU in the leading dense layers (in
row blocks where the dialect walks them, :func:`mlp_rows`; gate and up one
fused matrix where the dialect names no up matrix) and the expert layer
(:func:`expert_block`) after them; then the final norm under its
checkpoint name and the mean over a window's positions. The step's second
output stacks what each layer counts: an expert layer's ``(held,)``
assignments (zero rows where no layer has experts), or a counting mixer's
per-layer count, summed over the batch; a trunk with both gives the pair
(expert layers' stack, counting mixers' stack).

**The parameters** (:func:`param_shapes`): one walk over the layers in
checkpoint order that asks each layer's mixer for its own shapes;
:func:`param_count`, and :func:`draw_params` for seeded random sets.

A trunk module offers ``extract/lm.py`` a few names and nothing else:

* ``MODEL_TYPE`` — the ``model_type`` its config falls back to where the
  args name none of its dialects;
* ``TrunkConfig`` — a frozen dataclass under the published field names,
  subclassing :class:`BaseConfig` (``from_args``, the ``layer_types``
  check) and pointing the loop's names (``eps``, ``is_dense``; an expert
  trunk's ``routed_experts``, ``shared_experts``) at its own fields;
  ``param_shapes`` and ``param_count`` (this module's), ``init_params(cfg,
  seed)``;
* ``COUNTER`` — the name the step's second output leaves the step under,
  and ``count(tracer, counter, cfg, tokens)``, the stage-table counters
  filled from one fetched step's counter (a dialect may bring its own:
  ``Dialect.counter``);
* ``describe(cfg)`` and ``SHARE_ADVICE`` — the trunk in a few words and how
  to hold less of it, for the build's refusal of what cannot fit;
* ``kernels(cfg, platform, window_ids, precision)`` — which path the step
  compiles here, for the build's event and the run manifest.

``extract/lm.py`` runs every one through :func:`forward`.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from video_features_tpu.ops import moe

Params = Dict[str, jax.Array]
# the attention layer kinds two dialects' layer_types name
FULL, SLIDING = 'full_attention', 'sliding_attention'


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * gain).astype(x.dtype)


# a SwiGLU's three matrices under the names most checkpoints give them
# (gate, up, down); LFM2's are w1, w3, w2. An up name of None: the first
# matrix is gate and up side by side, (in, 2 · width), the gate's half first
SWIGLU_NAMES = ('gate_proj', 'up_proj', 'down_proj')
# rows a row-blocked feed-forward walks at a time (:func:`mlp_rows`)
MLP_ROWS = 4096


def swiglu(x: jax.Array, p: Params, prefix: str,
           row_block: Optional[int] = None,
           names: Tuple[str, Optional[str], str] = SWIGLU_NAMES
           ) -> jax.Array:
    """``W_down(silu(W_gate x) ⊙ W_up x)`` over (T, D) tokens (``names``'
    up None: ``[gate ‖ up] = x W_gate`` in one product). With ``row_block``
    the tokens are walked that many rows at a time (T a multiple of it), so
    the two intermediates stand as (row_block, F) and never as (T, F):
    32,768 tokens × 17,408 wide are 2.3 GB each."""
    gate_name, up_name, down_name = names

    def rows(x):
        gate = jnp.dot(x, p[f'{prefix}.{gate_name}.weight'])
        if up_name is None:
            gate, up = jnp.split(gate, 2, axis=-1)
        else:
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


def mlp_rows(tokens: int) -> Optional[int]:
    """The row block of a step's dense feed-forward: ``MLP_ROWS`` where the
    step's tokens are a whole number of them, else None (all at once)."""
    return MLP_ROWS if tokens % MLP_ROWS == 0 else None


# -- what a model type is ----------------------------------------------------

@dataclass(frozen=True)
class Mixer:
    """A layer kind's sequence mixer. ``block(p, prefix, x, cfg,
    attn_block, platform, kind)`` maps one window's (S, D) normed rows — or
    where ``per_window`` is False the whole batch's (B, S, D) — to rows of
    the same shape, and where ``counted`` to those and a count the loop sums
    over the batch and stacks a layer; a block reads of the loop's
    arguments what it needs. ``shapes(cfg, prefix, kind)`` is its
    parameters' ``{name: shape}`` in checkpoint order, all under
    ``model.layers.<i>.<prefix>``."""
    block: Callable
    shapes: Callable
    prefix: str = 'self_attn'
    per_window: bool = True
    counted: bool = False


@dataclass(frozen=True)
class Dialect:
    """What a ``model_type`` fixes beside its sizes: the layer kinds it may
    have and their mixers (in the order it names them), its published
    config keys (``renamed``: (field, published key) where the config's
    field is another model type's name for it; ``optional``: the keys that
    may be left out, the config's default then standing), whether its dense
    feed-forward walks the step's tokens in row blocks, the router
    normaliser's constant (the chosen scores over their sum + it) and the
    checkpoint's names. The next group is what its grouped-query attention
    mixers read (``models/hybrid_trunk.py``): the kinds that carry the
    rotary code, whether the heads' output is gated, whether the mixer's
    scope is the layer's kind, and the names of the per-head norms (None:
    none) and the output projection. Last, the step's counter where the
    dialect's is not its module's."""
    mixers: Dict[str, Mixer]
    config_keys: Tuple[str, ...]
    renamed: Tuple[Tuple[str, str], ...] = ()
    optional: Tuple[str, ...] = ('n_experts_held', 'first_expert')
    row_blocked_mlp: bool = True
    route_eps: float = 1e-20
    operator_norm: str = 'input_layernorm'
    ffn_norm: str = 'post_attention_layernorm'
    post_norms: Tuple[str, ...] = ()          # (after the mixer, the ffn)
    ffn: str = 'mlp'
    ffn_names: Tuple[str, Optional[str], str] = SWIGLU_NAMES
    router: str = 'gate'
    expert_bias: str = 'gate.e_score_correction_bias'
    final_norm: str = 'model.norm.weight'
    rotary: Tuple[str, ...] = ()
    gated: bool = False
    scope_by_kind: bool = False
    qk_norms: Optional[Tuple[str, str]] = ('q_norm', 'k_norm')
    out_proj: str = 'o_proj'
    # (name, count) of the step's second output where they are not the
    # trunk module's ``COUNTER`` and ``count``
    counter: Optional[Tuple[str, Callable]] = None


class BaseConfig:
    """What every trunk's config shares. A trunk's ``TrunkConfig`` is a
    frozen dataclass under its published field names, with a
    ``model_type`` field, ``layer_types`` (None: every layer its dialect's
    first kind), ``dialects`` (its module's ``DIALECTS``) and its
    ``__post_init__`` calling :meth:`check_layers`."""
    dialects: Dict[str, Dialect]
    # the field that holds a sliding layer's window (its published name)
    window_key: Optional[str] = None
    # what only some trunks' configs hold as fields, where they do not
    embed_scale = False                       # the embedding × √hidden
    embedding_multiplier = None               # the embedding × this
    residual_multiplier = None                # each sub-layer's output × this
    use_expert_bias = True                    # the router's bias is held

    @classmethod
    def from_args(cls, args):
        """The config from the args' published keys (``args['model_type']``
        picks the dialect; one the module does not run: its default).
        A key left out is refused by name."""
        model_type = args.get('model_type')
        if model_type not in cls.dialects:
            model_type = cls.model_type
        dialect = cls.dialects[model_type]
        values = {k: args.get(k) for k in dialect.config_keys}
        missing = [k for k, v in values.items()
                   if v is None and k not in dialect.optional]
        if missing:
            raise ValueError(f'the lm trunk model_type={model_type} needs '
                             f'config keys {missing}')
        values = {k: v for k, v in values.items() if v is not None}
        for field, key in dialect.renamed:
            values[field] = values.pop(key)
        return cls(**values, model_type=model_type)

    @property
    def dialect(self) -> Dialect:
        return self.dialects[self.model_type]

    def check_layers(self) -> None:
        """``layer_types`` as a tuple, one entry a layer run here, each a
        kind of the dialect; a window wherever a layer slides."""
        known = self.dialect.mixers
        kinds = (tuple(self.layer_types) if self.layer_types is not None
                 else (next(iter(known)),) * self.num_hidden_layers)
        object.__setattr__(self, 'layer_types', kinds)
        if len(kinds) != self.num_hidden_layers:
            raise ValueError(
                f'layer_types names {len(kinds)} layers, '
                f'num_hidden_layers={self.num_hidden_layers}: give one entry '
                f'a layer run here')
        for i, kind in enumerate(kinds):
            if kind not in known:
                raise ValueError(
                    f'layer_types[{i}]={kind!r} is no operator of the '
                    f'model_type={self.model_type} trunk; known: '
                    f'{", ".join(known)}')
        if SLIDING in kinds:
            window = getattr(self, self.window_key)
            if not (window and window > 0):
                raise ValueError(
                    f'sliding_attention layers need {self.window_key}, the '
                    f'keys a query sees; got {window!r}')

    def kinds(self) -> Dict[str, int]:
        """{layer kind: layers of it run here}, in the dialect's order."""
        return {kind: self.layer_types.count(kind)
                for kind in self.dialect.mixers}


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


# -- parameters --------------------------------------------------------------

def _swiglu_shapes(prefix: str, names: Tuple[str, Optional[str], str],
                   d: int, f: int, stack: Tuple[int, ...] = ()
                   ) -> Dict[str, Tuple[int, ...]]:
    gate_name, up_name, down_name = names
    if up_name is None:
        return {f'{prefix}.{gate_name}.weight': stack + (d, 2 * f),
                f'{prefix}.{down_name}.weight': stack + (f, d)}
    return {f'{prefix}.{gate_name}.weight': stack + (d, f),
            f'{prefix}.{up_name}.weight': stack + (d, f),
            f'{prefix}.{down_name}.weight': stack + (f, d)}


def param_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of every parameter held, in checkpoint order: the
    embedding; a layer's operator norm, its mixer's own, the post-operator
    norm, the feed-forward norm, the dense SwiGLU or the expert layer
    (router, bias, the held experts stacked (held, in, out), the shared
    experts), the post-feed-forward norm; the final norm."""
    d, names = cfg.hidden_size, cfg.dialect
    post_op, post_ffn = names.post_norms or (None, None)
    shapes: Dict[str, Tuple[int, ...]] = {
        'model.embed_tokens.weight': (cfg.vocab_size, d)}
    for i, kind in enumerate(cfg.layer_types):
        p = f'model.layers.{i}'
        mixer = names.mixers[kind]
        shapes[f'{p}.{names.operator_norm}.weight'] = (d,)
        shapes.update(mixer.shapes(cfg, f'{p}.{mixer.prefix}', kind))
        if post_op:
            shapes[f'{p}.{post_op}.weight'] = (d,)
        shapes[f'{p}.{names.ffn_norm}.weight'] = (d,)
        m = f'{p}.{names.ffn}'
        if cfg.is_dense(i):
            shapes.update(_swiglu_shapes(m, names.ffn_names, d,
                                         cfg.intermediate_size))
        else:
            f, routed = cfg.moe_intermediate_size, cfg.routed_experts
            shapes[f'{m}.{names.router}.weight'] = (d, routed)
            if cfg.use_expert_bias:
                shapes[f'{m}.{names.expert_bias}'] = (routed,)
            shapes.update(_swiglu_shapes(f'{m}.experts', names.ffn_names, d,
                                         f, (cfg.n_experts_held,)))
            if cfg.shared_experts:
                shapes.update(_swiglu_shapes(
                    f'{m}.shared_experts', names.ffn_names, d,
                    f * cfg.shared_experts))
        if post_ffn:
            shapes[f'{p}.{post_ffn}.weight'] = (d,)
    shapes[names.final_norm] = (d,)
    return shapes


def param_count(cfg) -> int:
    return sum(math.prod(s) for s in param_shapes(cfg).values())


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


# -- the step ----------------------------------------------------------------

def expert_block(p: Params, prefix: str, x: jax.Array, cfg,
                 moe_block: int = moe.BLOCK) -> Tuple[jax.Array, jax.Array]:
    """The expert layer's feed-forward over (T, D) tokens: the held
    experts' share of the routed sum (``ops.moe.routed_experts``, under the
    dialect's names; the router's bias zeros where the config holds none)
    plus, where the model has them, the shared experts every token takes.
    Returns the output and the (held,) assignment counts."""
    names = cfg.dialect
    gate_name, up_name, down_name = names.ffn_names
    with jax.named_scope('moe'):
        bias = (p[f'{prefix}.{names.expert_bias}'] if cfg.use_expert_bias
                else jnp.zeros((cfg.routed_experts,), jnp.float32))
        y, counts = moe.routed_experts(
            x, p[f'{prefix}.{names.router}.weight'], bias,
            p[f'{prefix}.experts.{gate_name}.weight'],
            p[f'{prefix}.experts.{up_name}.weight'],
            p[f'{prefix}.experts.{down_name}.weight'],
            top_k=cfg.num_experts_per_tok,
            scaling=cfg.routed_scaling_factor,
            normalise=cfg.norm_topk_prob, eps=names.route_eps,
            first=cfg.first_expert, block=moe_block)
        if cfg.shared_experts:
            y = y + swiglu(x, p, f'{prefix}.shared_experts',
                           names=names.ffn_names)
        return y, counts


def hidden_states(params: Params, ids: jax.Array, cfg,
                  attn_block: int = 1024, moe_block: int = moe.BLOCK,
                  platform: Optional[str] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """(B, S) int32 ids → ``(the final norm's hidden states (B, S, D),
    counter)``, the loop of the module doc. A per-window mixer runs a
    window at a time (its tiles, or its state, are the memory that
    matters); a whole-batch one takes the batch, each window within
    itself; the feed-forward takes all B·S tokens at once, so an expert
    sees the whole batch's assignments in one grouped product.
    ``platform`` is where the graph will run (None: the default backend):
    the mixers choose their kernels from it."""
    b, s = ids.shape
    d = cfg.hidden_size
    eps = cfg.eps
    names = cfg.dialect
    post_op, post_ffn = names.post_norms or (None, None)
    x = params['model.embed_tokens.weight'][ids]            # (B, S, D)
    if cfg.embed_scale:
        x = x * math.sqrt(d)
    if cfg.embedding_multiplier is not None:
        x = x * cfg.embedding_multiplier
    residual = cfg.residual_multiplier
    mixed, routed = [], []
    for i, kind in enumerate(cfg.layer_types):
        p = f'model.layers.{i}'
        mixer = names.mixers[kind]
        a = f'{p}.{mixer.prefix}'
        normed = rms_norm(x, params[f'{p}.{names.operator_norm}.weight'], eps)
        if mixer.per_window:
            y = lax.map(lambda w: mixer.block(params, a, w, cfg, attn_block,
                                              platform, kind), normed)
        else:
            y = mixer.block(params, a, normed, cfg, attn_block, platform,
                            kind)
        if mixer.counted:
            y, n = y
        if post_op:
            y = rms_norm(y, params[f'{p}.{post_op}.weight'], eps)
        if residual is not None:
            y = y * residual
        x = x + y
        if mixer.counted:
            mixed.append(n.sum(axis=0))
        normed = rms_norm(x, params[f'{p}.{names.ffn_norm}.weight'], eps
                          ).reshape(b * s, d)
        m = f'{p}.{names.ffn}'
        if cfg.is_dense(i):
            rows = mlp_rows(b * s) if names.row_blocked_mlp else None
            with jax.named_scope('dense_mlp'):
                y = swiglu(normed, params, m, row_block=rows,
                           names=names.ffn_names)
        else:
            y, c = expert_block(params, m, normed, cfg, moe_block)
            routed.append(c)
        if post_ffn:
            y = rms_norm(y, params[f'{p}.{post_ffn}.weight'], eps)
        if residual is not None:
            y = y * residual
        x = x + y.reshape(b, s, d)
    if mixed and routed:
        counter = (jnp.stack(routed), jnp.stack(mixed))
    elif mixed or routed:
        counter = jnp.stack(mixed or routed)
    else:   # no layer counted: an expert trunk's stage of dense layers only
        counter = jnp.zeros((0, cfg.n_experts_held), jnp.int32)
    return rms_norm(x, params[names.final_norm], eps), counter


def forward(params: Params, ids: jax.Array, cfg, attn_block: int = 1024,
            moe_block: int = moe.BLOCK, platform: Optional[str] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """(B, S) int32 ids → ``(features (B, D) float32, counter)``: the mean
    of each window's final hidden states (:func:`hidden_states`)."""
    hidden, counter = hidden_states(params, ids, cfg, attn_block, moe_block,
                                    platform)
    return hidden.astype(jnp.float32).mean(axis=1), counter


# -- the stage table ---------------------------------------------------------

def count_experts(tracer, counts: np.ndarray, cfg, tokens: int,
                  block: int = moe.BLOCK) -> None:
    """One fetched step's ``(expert layers, held)`` assignment counts → the
    stage table. Per layer: the held experts' mean load against the fullest
    one's (the one the layer waits for) → ``moe_route``; how many of all
    assignments fell on experts held here (``num_experts_per_tok`` a token)
    → ``moe_held``; the held assignments against the rows the block walk
    computed for them (each expert's rounded up to whole blocks of
    ``block``) → ``moe_walk``."""
    counts = np.asarray(counts, np.int64)
    if not counts.size:
        return
    layers, held = counts.shape
    assigned = int(counts.sum())
    tracer.add_occupancy('moe_route', assigned,
                         int(counts.max(axis=1).sum()) * held)
    tracer.add_occupancy('moe_held', assigned,
                         int(tokens) * cfg.num_experts_per_tok * layers)
    tracer.add_occupancy('moe_walk', assigned,
                         int(moe.walk_rows(counts, block).sum()))
