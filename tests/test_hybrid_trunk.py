"""The lm family's third trunk (``model_type=lfm2_moe``): gated short
convolutions among grouped-query attention layers over sparse experts —
at a tiny size on the CPU (hidden 64, 4 query / 2 key-value heads of 16,
8 experts of 32 with 2 a token, windows of 64 ids). The plain reference it
is held to is the benchmark's (``benchmark/references/lfm2-8b-a1b-l8.py``:
written from the config and ``transformers``' ``modeling_lfm2.py``, nothing
of the program); where ``torch`` and that module import, the reference's two
operators are held to ``Lfm2ShortConv`` and ``Lfm2Attention`` themselves."""
import json
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
for _p in (REPO / 'benchmark', REPO / 'benchmark' / 'references'):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import loader  # noqa: E402
import weights  # noqa: E402
from _layers import Ops  # noqa: E402

from video_features_tpu.config import load_config  # noqa: E402
from video_features_tpu.extract import lm as extract_lm  # noqa: E402
from video_features_tpu.models import hybrid_trunk as ht  # noqa: E402
from video_features_tpu.models import latent_moe, token_trunk  # noqa: E402
from video_features_tpu.ops import moe  # noqa: E402
from video_features_tpu.ops.attention import (  # noqa: E402
    blockwise_attention, dense_attention, resolve_causal,
)
from video_features_tpu.ops.short_conv import (  # noqa: E402
    causal_taps, gated_short_conv,
)
from video_features_tpu.registry import create_extractor  # noqa: E402

SEED = 2 ** 31 + 33
REF = loader.load_module('references', 'lfm2-8b-a1b-l8')

# five layers: both operators under both feed-forwards
KINDS = ('conv', 'full_attention', 'conv', 'conv', 'full_attention')
TINY_PROGRAM = dict(
    model_type='lfm2_moe', vocab_size=512, hidden_size=64,
    num_hidden_layers=5, layer_types=list(KINDS), conv_L_cache=3,
    num_dense_layers=2, intermediate_size=160, moe_intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, routed_scaling_factor=1.0,
    norm_topk_prob=True, use_expert_bias=True, num_attention_heads=4,
    num_key_value_heads=2, rope_theta=1e6, norm_eps=1e-5)
WINDOW = dict(stack_size=4, step_size=4, patch_grid=4)      # 64 ids
# float32 sums in another order (the block walk against the dense experts,
# tiles against whole rows): some 1e-7 an operation, far under 1e-5; a
# routing flip or a lost term reads 1e-3 and more
TOLERANCE = 1e-5


def tiny_reference_cfg(**changes):
    c = dict(REF.CFG, vocab_size=512, hidden_size=64, layers=5,
             layer_types=KINDS, intermediate_size=160,
             moe_intermediate_size=32, router_experts=8, n_routed_experts=8,
             first_expert=0, num_experts_per_tok=2, num_attention_heads=4,
             num_key_value_heads=2, frames=4, patch_grid=4, query_block=16)
    c.update(changes)
    return c


def program_cfg(**changes):
    return ht.TrunkConfig.from_args(dict(TINY_PROGRAM, **changes))


@pytest.fixture(scope='module')
def tiny():
    rcfg = tiny_reference_cfg()
    params = weights.make(REF.param_specs(rcfg)['checkpoint_path'], SEED,
                          'checkpoint_path')
    ids = np.random.default_rng(0).integers(0, 512, (3, 64)).astype(np.int32)
    return program_cfg(), rcfg, params, ids


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def run(params, ids, cfg):
    with jax.default_matmul_precision('highest'):
        return jax.jit(lambda p, i: token_trunk.forward(p, i, cfg, 16, 8))(params, ids)


# -- the two ops ----------------------------------------------------------------

def test_the_short_convolution_is_its_three_shifted_terms():
    rng = np.random.default_rng(1)
    u = rng.standard_normal((2, 10, 6)).astype(np.float32)
    w = rng.standard_normal((3, 6)).astype(np.float32)
    want = np.zeros_like(u)
    for t in range(10):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += w[j] * u[:, t - 2 + j]
    np.testing.assert_allclose(causal_taps(jnp.asarray(u), jnp.asarray(w)),
                               want, rtol=1e-6, atol=1e-6)
    # a window shorter than the taps sees only what exists
    np.testing.assert_allclose(
        causal_taps(jnp.asarray(u[:, :2]), jnp.asarray(w)), want[:, :2],
        rtol=1e-6, atol=1e-6)


def test_a_batch_of_two_windows_is_each_window_alone(tiny):
    """Nothing leaks across a window's start: the first positions of the
    second window see zeros, not the tail of the first."""
    _, rcfg, params, _ = tiny
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 64, 64)).astype(np.float32))
    a = 'model.layers.0.conv'
    w = [params[f'{a}.{n}.weight'] for n in ('in_proj', 'conv', 'out_proj')]
    with jax.default_matmul_precision('highest'):
        both = gated_short_conv(x, *w)
        alone = [gated_short_conv(x[i:i + 1], *w)[0] for i in range(2)]
        np.testing.assert_allclose(both[0], alone[0], rtol=0, atol=1e-6)
        np.testing.assert_allclose(both[1], alone[1], rtol=0, atol=1e-6)
        got = ht.conv_block(params, a, x)
    want = REF._short_conv(Ops(), params, a, x, rcfg)
    assert rel_l2(got, want) < TOLERANCE
    # and a later position changes no earlier one
    moved = gated_short_conv(x.at[0, 40].add(1.0), *w)
    np.testing.assert_array_equal(moved[0, :40], both[0, :40])
    assert float(jnp.abs(moved[0, 40:43] - both[0, 40:43]).max()) > 1e-3
    np.testing.assert_allclose(moved[0, 43:], both[0, 43:], rtol=0, atol=1e-6)


@pytest.mark.parametrize('block', [16, 64])
def test_grouped_query_causal_tiles_are_dense_attention_with_kv_repeated(
        block):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((2, 64, 8, 16)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((2, 64, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((2, 64, 2, 8)).astype(np.float32))
    k4, v4 = jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2)
    with jax.default_matmul_precision('highest'):
        got = blockwise_attention(q, k, v, block_size=block, causal=True)
        # the dense oracle at a tile's edges: position t's keys are 0…t
        edges = (0, 15, 16, 17, 63)
        want = jnp.stack([dense_attention(q[:, t:t + 1], k4[:, :t + 1],
                                          v4[:, :t + 1])[:, 0]
                          for t in edges], axis=1)
        # and everywhere: the same tiles over equal head counts
        equal = blockwise_attention(q, k4, v4, block_size=block, causal=True)
    assert got.shape == (2, 64, 8, 8)
    np.testing.assert_allclose(got[:, edges, :], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got, equal, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match='8 query heads are no whole number '
                       'of groups of 3 key-value heads'):
        blockwise_attention(q, k4[:, :, :3], v4[:, :, :3], block_size=block,
                            causal=True)


def test_the_attention_block_matches_the_reference(tiny):
    cfg, rcfg, params, _ = tiny
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (2, 64, 64)).astype(np.float32))
    a = 'model.layers.1.self_attn'
    want = REF._attention(Ops(), params, a, x, rcfg)
    with jax.default_matmul_precision('highest'):
        got = jnp.stack([ht.attention_block(params, a, w, cfg, 16)
                         for w in x])
    assert rel_l2(got, want) < TOLERANCE


def test_the_expert_block_matches_the_reference_and_takes_its_constant(tiny):
    cfg, rcfg, params, _ = tiny
    x = np.random.default_rng(6).standard_normal((2, 64, 64)).astype(
        np.float32)
    m = 'model.layers.3.feed_forward'
    want = np.asarray(REF._experts(Ops(), params, m, jnp.asarray(x), rcfg))
    with jax.default_matmul_precision('highest'):
        got, counts = token_trunk.expert_block(params, m, jnp.asarray(x).reshape(
            128, 64), cfg, 8)
    assert rel_l2(got, want.reshape(128, 64)) < TOLERANCE
    assert int(np.asarray(counts).sum()) == 128 * 2       # all held
    # the normaliser is the model's: 1e-6 here, 1e-20 in the other trunk.
    # Scores near 1e-7 (logits of -16) tell the two apart
    ones = jnp.ones((5, 4), jnp.float32)
    far = jnp.full((4, 6), -4.0, jnp.float32)
    score = float(jax.nn.sigmoid(-16.0))
    for eps, each in ((1e-6, score / (2 * score + 1e-6)), (1e-20, 0.5)):
        _, w = moe.route(ones, far, jnp.zeros((6,)), top_k=2, scaling=1.0,
                         eps=eps)
        np.testing.assert_allclose(w, np.full((5, 2), each), rtol=1e-5)
    assert ht.DIALECTS['lfm2_moe'].route_eps == 1e-6 == REF.CFG['route_eps']


def test_four_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """Four chips hold experts 0-1, 2-3, 4-5 and 6-7 of one layer. The
    parts their shares give are the uncut layer of the reference: there is
    no shared expert, so nothing is counted twice."""
    _, rcfg, params, _ = tiny
    x = np.random.default_rng(7).standard_normal((1, 64, 64)).astype(
        np.float32)
    m = 'model.layers.2.feed_forward'
    want = np.asarray(REF._experts(Ops(), params, m, jnp.asarray(x), rcfg))
    total = np.zeros((64, 64))
    held_rows = 0
    with jax.default_matmul_precision('highest'):
        for first in (0, 2, 4, 6):
            cfg = program_cfg(n_experts_held=2, first_expert=first)
            share = dict(params)
            for name in ('w1', 'w3', 'w2'):
                key = f'{m}.experts.{name}.weight'
                share[key] = params[key][first:first + 2]
            y, counts = token_trunk.expert_block(share, m, jnp.asarray(x[0]), cfg, 8)
            # one share is the reference given the same share
            part = REF._experts(Ops(), share, m, jnp.asarray(x), dict(
                rcfg, n_routed_experts=2, first_expert=first))
            assert rel_l2(y, part[0]) < TOLERANCE
            total += np.asarray(y)
            held_rows += int(np.asarray(counts).sum())
    assert held_rows == 64 * 2          # every assignment lands on one share
    assert rel_l2(total, want[0]) < TOLERANCE


# -- the trunk against the plain reference ----------------------------------------

def test_trunk_matches_the_reference(tiny):
    cfg, rcfg, params, ids = tiny
    want = REF.forward(Ops(), {'checkpoint_path': params}, ids, rcfg)
    got, counts = run(params, ids, cfg)
    assert got.shape == (3, 64) and got.dtype == jnp.float32
    assert rel_l2(got, want) < TOLERANCE
    assert counts.shape == (3, 8)
    assert np.asarray(counts).sum(axis=1).tolist() == [3 * 64 * 2] * 3
    # the reference in one bf16 pass reads far above the program
    control = REF.forward(Ops('bfloat16'), {'checkpoint_path': params}, ids,
                          rcfg)
    assert rel_l2(control, want) > 1e-3


def test_the_reference_and_the_program_hold_the_same_parameters(tiny):
    cfg, _, params, _ = tiny
    assert {k: v.shape for k, v in params.items()} == ht.param_shapes(cfg)
    assert list(params) == list(ht.param_shapes(cfg))      # checkpoint order
    assert ht.param_count(cfg) == sum(v.size for v in params.values())
    ours = ht.init_params(cfg)
    assert {k: v.shape for k, v in ours.items()} == ht.param_shapes(cfg)
    taps = ours['model.layers.0.conv.conv.weight']
    assert taps.shape == (3, 64) and 0.3 < float(taps.std()) < 0.9
    bias = ours['model.layers.2.feed_forward.expert_bias']
    assert 0 < float(np.abs(bias).max()) < 0.3
    # no bias key asked for, none held, and the choice is over the scores
    bare = program_cfg(use_expert_bias=False)
    assert not [n for n in ht.param_shapes(bare) if 'expert_bias' in n]
    got, _ = run({k: v for k, v in params.items() if 'expert_bias' not in k},
                 np.zeros((1, 64), np.int32), bare)
    assert np.isfinite(np.asarray(got)).all()


def test_the_hybrid_pattern_is_honoured(tiny):
    """Which operator and which feed-forward a layer runs is read from
    ``layer_types`` and ``num_dense_layers``: another entry, another row."""
    cfg, _, params, ids = tiny
    base, _ = run(params, ids, cfg)
    # layer 2 as attention instead of a convolution: it needs that layer's
    # parameters under the other operator's names
    other = tiny_reference_cfg(
        layer_types=('conv', 'full_attention', 'full_attention', 'conv',
                     'full_attention'))
    swapped = weights.make(REF.param_specs(other)['checkpoint_path'], SEED,
                           'checkpoint_path')
    got, _ = run(swapped, ids, program_cfg(layer_types=list(
        other['layer_types'])))
    want = REF.forward(Ops(), {'checkpoint_path': swapped}, ids, other)
    assert rel_l2(got, want) < TOLERANCE
    assert rel_l2(got, base) > 1e-2
    # the pattern's parameters under the wrong pattern are refused by name
    with pytest.raises(KeyError, match=r'model\.layers\.2\.conv'):
        run(swapped, ids, cfg)
    # one dense layer where the model has two: layer 1 has experts
    fewer = tiny_reference_cfg(num_dense_layers=1)
    p1 = weights.make(REF.param_specs(fewer)['checkpoint_path'], SEED,
                      'checkpoint_path')
    got, counts = run(p1, ids, program_cfg(num_dense_layers=1))
    assert counts.shape == (4, 8)
    assert rel_l2(got, REF.forward(Ops(), {'checkpoint_path': p1}, ids,
                                   fewer)) < TOLERANCE
    assert rel_l2(got, base) > 1e-2


def test_a_bad_pattern_is_refused_by_name():
    with pytest.raises(ValueError, match=r"layer_types\[3\]='mamba' is no "
                       r'operator of the model_type=lfm2_moe trunk; known: '
                       r'conv, full_attention'):
        program_cfg(layer_types=['conv', 'conv', 'full_attention', 'mamba',
                                 'conv'])
    with pytest.raises(ValueError, match=r'layer_types names 4 layers, '
                       r'num_hidden_layers=5'):
        program_cfg(layer_types=list(KINDS[:4]))
    missing = dict(TINY_PROGRAM)
    del missing['num_dense_layers'], missing['layer_types']
    with pytest.raises(ValueError, match=r"model_type=lfm2_moe needs config "
                       r"keys \['layer_types', 'num_dense_layers'\]"):
        ht.TrunkConfig.from_args(missing)
    with pytest.raises(ValueError, match='n_experts_held=8 from '
                       'first_expert=4'):
        program_cfg(first_expert=4)
    with pytest.raises(ValueError, match='no whole number of groups'):
        program_cfg(num_key_value_heads=3)


def test_a_later_token_changes_no_earlier_position(tiny):
    cfg, _, params, ids = tiny
    with jax.default_matmul_precision('highest'):
        hidden = jax.jit(lambda p, i: token_trunk.hidden_states(p, i, cfg, 16, 8)[0])
        a = hidden(params, ids[:1])
        changed = ids[:1].copy()
        changed[0, 40] = (changed[0, 40] + 1) % 512
        b = hidden(params, changed)
    np.testing.assert_allclose(a[0, :40], b[0, :40], rtol=0, atol=1e-5)
    assert float(jnp.abs(a[0, 40:] - b[0, 40:]).max()) > 1e-3


def test_published_sizes_count_as_the_issue_counts_them():
    body = loader.load_json('configs', 'lfm2-8b-a1b-l8')
    cut = ht.TrunkConfig.from_args(body['overrides'])
    shapes = ht.param_shapes(cut)
    assert cut.head_dim == 64 and cut.kinds() == {'conv': 6,
                                                      'full_attention': 2}

    def layer(i, part):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(f'model.layers.{i}.{part}'))
    assert layer(0, 'conv') == 16_783_360
    assert layer(2, 'self_attn') == 10_485_888
    assert layer(0, 'feed_forward') == 44_040_192
    assert layer(2, 'feed_forward.experts') == 352_321_536
    assert layer(2, 'feed_forward.gate') + layer(
        2, 'feed_forward.expert_bias') == 65_568
    assert shapes['model.embed_tokens.weight'] == (65_536, 2_048)
    assert shapes['model.layers.3.conv.conv.weight'] == (3, 2_048)
    assert ht.param_count(cut) == 2_458_327_488
    whole = ht.TrunkConfig.from_args(dict(
        body['overrides'], num_hidden_layers=24,
        layer_types=body['layer_types']))
    assert whole.kinds() == {'conv': 18, 'full_attention': 6}
    assert 8.3e9 < ht.param_count(whole) < 8.4e9
    # at these widths the causal kernel's grouped-query lane applies on a
    # TPU — four 64-wide query heads a key-value head fill two lane blocks,
    # one alone would not — and nowhere else, nor under 'highest'
    assert resolve_causal('tpu', 8_192, 64, 64, 'high', 32, 8) == 'kernel'
    assert resolve_causal('tpu', 8_192, 64, 64, 'high') == 'xla'
    for platform, precision, path in [
            ('tpu', 'high', 'kernel'), ('tpu', 'default', 'kernel'),
            ('tpu', 'highest', 'xla'), ('cpu', 'high', 'xla')]:
        assert ht.kernels(cut, platform, 8_192, precision) == {
            'causal_attention': path,
            'operators': 'conv 6, full_attention 2'}, (platform, precision)


def test_the_trunks_share_their_blocks_and_the_expert_code():
    for trunk in (ht, latent_moe):
        for name in ('rms_norm', 'param_shapes', 'param_count'):
            assert getattr(trunk, name) is getattr(token_trunk, name)
        assert trunk.count is token_trunk.count_experts
        assert trunk.TrunkConfig.from_args.__func__ is \
            token_trunk.BaseConfig.from_args.__func__
    # neither trunk module routes, walks or runs a layer by itself
    for trunk in (ht, latent_moe):
        source = Path(trunk.__file__).read_text()
        for call in ('moe.routed_experts(', 'moe.route(', 'moe.moe_share('):
            assert call not in source
        assert 'def hidden_states' not in source
        assert 'def expert_block' not in source
    assert 'moe.routed_experts(' in Path(token_trunk.__file__).read_text()


def test_the_walk_counter_is_assignments_over_rows_walked():
    class Table:
        def __init__(self):
            self.rows = {}

        def add_occupancy(self, stage, valid, capacity):
            self.rows[stage] = (valid, capacity)

    counts = np.array([[256, 1, 0, 300], [64, 64, 64, 64]])
    table = Table()
    token_trunk.count_experts(table, counts, program_cfg(), tokens=500,
                              block=256)
    assert table.rows['moe_walk'] == (813, (256 + 256 + 0 + 512) + 4 * 256)
    assert table.rows['moe_route'] == (813, (300 + 64) * 4)
    assert table.rows['moe_held'] == (813, 500 * 2 * 2)
    assert moe.walk_rows(np.array([0, 1, 256, 257])).tolist() == [
        0, 256, 256, 512]
    empty = Table()
    token_trunk.count_experts(empty, np.zeros((0, 4)), program_cfg(), 500,
                              256)
    assert not empty.rows


# -- the reference's operators against transformers' own ---------------------------

@pytest.fixture(scope='module')
def hf():
    torch = pytest.importorskip('torch')
    modeling = pytest.importorskip('transformers.models.lfm2.modeling_lfm2')
    from transformers.models.lfm2.configuration_lfm2 import Lfm2Config
    config = Lfm2Config(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
        norm_eps=1e-5, rope_theta=1e6, conv_bias=False, conv_L_cache=3,
        layer_types=list(KINDS))
    config._attn_implementation = 'eager'
    return torch, modeling, config


def test_the_references_short_convolution_is_lfm2_short_conv(hf, tiny):
    torch, modeling, config = hf
    _, rcfg, params, _ = tiny
    a = 'model.layers.0.conv'
    op = modeling.Lfm2ShortConv(config, 0).eval()
    with torch.no_grad():
        op.in_proj.weight.copy_(torch.from_numpy(
            params[f'{a}.in_proj.weight'].T.copy()))
        op.out_proj.weight.copy_(torch.from_numpy(
            params[f'{a}.out_proj.weight'].T.copy()))
        # (taps, hidden) → the checkpoint's (hidden, 1, taps)
        op.conv.weight.copy_(torch.from_numpy(
            params[f'{a}.conv.weight'].T.copy()[:, None, :]))
    x = np.random.default_rng(11).standard_normal((2, 64, 64)).astype(
        np.float32)
    with torch.no_grad():
        want = op.slow_forward(torch.from_numpy(x)).numpy()
    got = REF._short_conv(Ops(), params, a, jnp.asarray(x), rcfg)
    assert rel_l2(got, want) < TOLERANCE


def test_the_references_attention_is_lfm2_attention(hf, tiny):
    torch, modeling, config = hf
    _, rcfg, params, _ = tiny
    a = 'model.layers.1.self_attn'
    op = modeling.Lfm2Attention(config, 1).eval()
    with torch.no_grad():
        for name in ('q_proj', 'k_proj', 'v_proj', 'out_proj'):
            getattr(op, name).weight.copy_(torch.from_numpy(
                params[f'{a}.{name}.weight'].T.copy()))
        for name in ('q_layernorm', 'k_layernorm'):
            getattr(op, name).weight.copy_(torch.from_numpy(
                params[f'{a}.{name}.weight']))
    x = np.random.default_rng(12).standard_normal((2, 64, 64)).astype(
        np.float32)
    xt = torch.from_numpy(x)
    rotary = modeling.Lfm2RotaryEmbedding(config)
    positions = torch.arange(64)[None].expand(2, -1)
    mask = torch.full((64, 64), float('-inf')).triu(1)[None, None]
    with torch.no_grad():
        want, _ = op(xt, rotary(xt, positions), mask)
    got = REF._attention(Ops(), params, a, jnp.asarray(x), rcfg)
    assert rel_l2(got, want.numpy()) < TOLERANCE


# -- the extractor: one family, three trunks -----------------------------------------

def test_model_type_picks_the_third_trunk_and_the_yml_holds_its_keys():
    assert extract_lm.load_trunk('lfm2_moe') is ht
    assert extract_lm.TRUNKS[ht.MODEL_TYPE] == ht.__name__
    assert ht.TrunkConfig.model_type == ht.MODEL_TYPE == 'lfm2_moe'
    yml = load_config('lm', overrides={'video_paths': ['x.mp4'],
                                       'device': 'cpu'})
    # the published keys are in the yml, null as brumby's are
    for key in ('layer_types', 'conv_L_cache', 'num_dense_layers',
                'num_experts', 'use_expert_bias', 'norm_eps'):
        assert key in yml and yml[key] is None
    assert set(ht.CONFIG_KEYS) <= set(yml)
    with pytest.raises(ValueError, match=r'model_type=lfm2_moe needs config '
                       r'keys \[.*\'layer_types\'.*\'norm_eps\'\]'):
        ht.TrunkConfig.from_args(yml)     # the shipped sizes are another model
    with pytest.raises(ValueError, match=r"no trunk for model_type='lfm2'; "
                       r'known: afmoe, brumby, dots3_note, granitemoehybrid, '
                       r'joyai_llm_flash, lfm2_moe'):
        extract_lm.load_trunk('lfm2')


def test_a_build_that_cannot_fit_is_refused_with_the_sizes():
    body = loader.load_json('configs', 'lfm2-8b-a1b-l8')
    whole = ht.TrunkConfig.from_args(dict(
        body['overrides'], num_hidden_layers=24,
        layer_types=body['layer_types']))
    need = ht.param_count(whole) * 4
    with pytest.raises(ValueError) as refused:
        extract_lm.check_params_fit(
            need, 16 * 10 ** 9, f'lm with {ht.describe(whole)}',
            ht.SHARE_ADVICE)
    said = str(refused.value)
    assert '24 layers (18 conv + 6 full_attention)' in said
    assert '32 of 32 experts in each of the 22 expert layers' in said
    assert '33.3' in said and 'do not fit the device\'s 16.00 GB' in said
    assert 'layer_types' in said and 'n_experts_held' in said


def _extractor(tmp_path, **overrides):
    args = load_config('lm', overrides=dict(
        TINY_PROGRAM, **WINDOW, device='cpu', batch_size=2,
        video_paths=['x.mp4'], on_extraction='save_numpy',
        output_path=str(tmp_path / 'out'), tmp_path=str(tmp_path / 'tmp'),
        allow_random_weights=True, **overrides))
    return create_extractor(args)


@pytest.fixture(scope='module')
def clips(tmp_path_factory):
    sys.path.insert(0, str(REPO))
    from tools.make_sample_video import write_noise_clip
    d = tmp_path_factory.mktemp('lfm2_clips')
    return [write_noise_clip(d / f'c{i}.mp4', n, seed=20 + i)
            for i, n in enumerate([9, 3, 22, 13])]     # c1 is too short


def test_extract_packed_equals_the_per_video_loop(clips, tmp_path, capsys):
    packed = _extractor(tmp_path / 'a', pack_across_videos=True,
                        manifest_out=str(tmp_path / 'manifest.json'))
    assert packed.trunk is ht and packed.cfg.model_type == 'lfm2_moe'
    said = capsys.readouterr().err
    assert 'causal_attention=xla' in said and 'full_attention 2' in said
    packed.extract_packed(list(clips), decode_ahead=2)
    packed.finish_obs()
    loop = _extractor(tmp_path / 'b')
    for path in clips:
        loop._extract(path)
    assert packed.failed_videos == loop.failed_videos == 0
    rows = {'c0': 2, 'c1': 0, 'c2': 5, 'c3': 3}
    for stem, n in rows.items():
        a = np.load(Path(packed.output_path) / f'{stem}_lm.npy')
        b = np.load(Path(loop.output_path) / f'{stem}_lm.npy')
        assert a.shape == b.shape == (n, 64) and a.dtype == np.float32
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    # the saved rows are the trunk's, on the tokeniser's ids (the reference's)
    want, _ = token_trunk.forward(packed.params, REF.load_units(
        clips[2], range(5), tiny_reference_cfg()), packed.cfg)
    np.testing.assert_allclose(
        np.load(Path(packed.output_path) / 'c2_lm.npy'), want, atol=1e-5)
    # the span, the counters and the note the benchmark reads
    doc = json.loads((tmp_path / 'manifest.json').read_text())
    stages = doc['stages']
    assert stages['tokenise']['count'] == 10
    steps = stages['model']['count']
    assigned = steps * 2 * 64 * 2 * 3       # slots × ids × top-2 × 3 layers
    assert stages['moe_held']['occ_valid'] == assigned \
        == stages['moe_held']['occ_capacity']            # every expert held
    assert stages['moe_route']['occ_valid'] == assigned
    walk = stages['moe_walk']
    assert walk['occ_valid'] == assigned
    # 8 experts of some 32 assignments each in blocks of 256: mostly padding
    assert walk['occ_capacity'] % 256 == 0
    assert assigned < walk['occ_capacity'] <= steps * 3 * 8 * 256
    assert 'retention_scan' not in stages
    assert doc['kernels'] == {'causal_attention': 'xla',
                              'operators': 'conv 3, full_attention 2'}


def test_the_step_carries_the_scopes_a_trace_is_read_by():
    cfg = program_cfg()
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in ht.param_shapes(cfg).items()}
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = jax.jit(partial(extract_lm.ExtractLM._forward, cfg=cfg,
                           platform='tpu')).trace(params, ids).lower(
        lowering_platforms=('tpu',)).as_text(debug_info=True)
    for scope in ('short_conv', 'attention', 'moe', 'dense_mlp'):
        assert scope in text, scope
    assert 'tpu_custom_call' not in text     # 16-wide heads: the XLA tiles


# a trunk whose attention the kernel's grouped lane takes: 4 query heads of
# 64 reading 2 key-value heads, windows of 128 ids
ALIGNED = dict(TINY_PROGRAM, hidden_size=256, num_hidden_layers=3,
               layer_types=['conv', 'full_attention', 'full_attention'])


@pytest.mark.parametrize('platform,precision,calls', [
    ('tpu', 'high', 2),        # precision=mixed: one call a layer's lax.map
    ('tpu', 'default', 2),     # the control lane takes the kernel too
    ('tpu', 'highest', 0),     # the yml's default keeps the XLA path
    ('cpu', 'high', 0),        # what tier-1 and the programs lock lower
])
def test_the_step_lowered_for_a_tpu_holds_the_named_kernel(platform,
                                                           precision, calls):
    """The step as the extractor jits it, lowered for the TPU from here: a
    Mosaic call named causal_attention in each attention layer's window
    loop where ``resolve_causal`` says 'kernel', none where it does not —
    and ``kernels``, the engagement counter, says the same."""
    cfg = program_cfg(**ALIGNED)
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in ht.param_shapes(cfg).items()}
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    with jax.default_matmul_precision(precision):
        text = jax.jit(partial(extract_lm.ExtractLM._forward, cfg=cfg,
                               platform=platform)).trace(
            params, ids).lower(lowering_platforms=('tpu',)).as_text()
    assert text.count('kernel_name = "causal_attention"') == calls
    assert text.count('tpu_custom_call') == calls
    assert ht.kernels(cfg, platform, 128, precision)['causal_attention'] == (
        'kernel' if calls else 'xla')


def test_the_kernel_path_of_attention_block_is_the_xla_path_to_rounding(
        monkeypatch):
    """attention_block with the kernel forced in (interpreted: the decision
    says 'kernel' only on a TPU) against the XLA tiles, both at three
    passes; and the trunk's rows through it against the rows through the
    tiles."""
    from video_features_tpu.ops import pallas_attention
    from video_features_tpu.ops.precision import rel_l2
    cfg = program_cfg(**ALIGNED)
    params = {n: jnp.asarray(w) for n, w in ht.init_params(cfg, 3).items()}
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (128, 256)).astype(np.float32))
    ids = np.random.default_rng(7).integers(0, 512, (2, 128)).astype(np.int32)
    a = 'model.layers.1.self_attn'
    with jax.default_matmul_precision('high'):
        want = ht.attention_block(params, a, x, cfg, 64, 'cpu')
        rows, _ = token_trunk.forward(params, ids, cfg, 64, platform='cpu')
        monkeypatch.setattr(ht, 'resolve_causal', lambda *args: 'kernel')
        monkeypatch.setattr(
            pallas_attention, 'causal_attention',
            partial(pallas_attention.causal_attention, interpret=True))
        got = ht.attention_block(params, a, x, cfg, 64, 'tpu')
        through, _ = token_trunk.forward(params, ids, cfg, 64, platform='tpu')
    assert got.shape == want.shape == (128, 256)
    assert 0 < rel_l2(got, want) < 2e-5
    assert 0 < rel_l2(through, rows) < 1e-4
