"""The lm family's fourth trunk (``model_type=afmoe``): sliding-window and
full grouped-query attention layers mixed, gated, normed before and after
each sub-layer, over sparse experts with a shared one — the second dialect
of ``models/hybrid_trunk.py`` — at a tiny size on the CPU (hidden 64, 4 query
/ 2 key-value heads of 16, a window of 8 keys over 32 positions, 8 experts
of 32 with 2 a token and 4 held, layers ``S S S F`` + one more sliding, the
first dense). The plain reference it is held to is the benchmark's
(``benchmark/references/trinity-mini-ep4-l8.py``: nothing of the program)."""
import dataclasses
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
from video_features_tpu.models import token_trunk  # noqa: E402
from video_features_tpu.ops import moe  # noqa: E402
from video_features_tpu.registry import create_extractor  # noqa: E402

SEED = 2 ** 31 + 38
REF = loader.load_module('references', 'trinity-mini-ep4-l8')
S, F = 'sliding_attention', 'full_attention'

KINDS = (S, S, S, F, S)
TINY_PROGRAM = dict(
    model_type='afmoe', vocab_size=512, hidden_size=64, num_hidden_layers=5,
    layer_types=list(KINDS), sliding_window=8, head_dim=16,
    num_dense_layers=1, intermediate_size=160, moe_intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
    route_scale=2.826, route_norm=True, score_func='sigmoid',
    mup_enabled=True, num_attention_heads=4, num_key_value_heads=2,
    rope_theta=10000, rms_norm_eps=1e-5, n_experts_held=4, first_expert=0)
WINDOW = dict(stack_size=2, step_size=2, patch_grid=4)      # 32 ids
# float32 sums in another order (the block walk against the dense experts,
# tiles against row blocks): some 1e-7 an operation, far under 1e-5; a
# mechanism left out, or a routing flip, reads 1e-3 and more
TOLERANCE = 1e-5


def tiny_reference_cfg(**changes):
    c = dict(REF.CFG, vocab_size=512, hidden_size=64, layers=5,
             layer_types=KINDS, sliding_window=8, head_dim=16,
             num_dense_layers=1, intermediate_size=160,
             moe_intermediate_size=32, router_experts=8, n_routed_experts=4,
             first_expert=0, num_experts_per_tok=2, num_attention_heads=4,
             num_key_value_heads=2, frames=2, patch_grid=4, query_block=8)
    c.update(changes)
    return c


def program_cfg(**changes):
    return ht.TrunkConfig.from_args(dict(TINY_PROGRAM, **changes))


@pytest.fixture(scope='module')
def tiny():
    rcfg = tiny_reference_cfg()
    params = weights.make(REF.param_specs(rcfg)['checkpoint_path'], SEED,
                          'checkpoint_path')
    ids = np.random.default_rng(0).integers(0, 512, (3, 32)).astype(np.int32)
    want = np.asarray(REF.forward(Ops(), {'checkpoint_path': params}, ids,
                                  rcfg))
    return program_cfg(), rcfg, params, ids, want


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def run(params, ids, cfg):
    with jax.default_matmul_precision('highest'):
        return jax.jit(lambda p, i: token_trunk.forward(p, i, cfg, 8, 8))(params, ids)


# -- the trunk against the plain reference ----------------------------------------

def test_trunk_matches_the_reference(tiny):
    cfg, rcfg, params, ids, want = tiny
    got, counts = run(params, ids, cfg)
    assert got.shape == (3, 64) and got.dtype == jnp.float32
    assert rel_l2(got, want) < TOLERANCE
    # four expert layers, four of eight experts held: about half of the
    # 3 × 32 × 2 assignments a layer fall here
    assert counts.shape == (4, 4)
    assert (0 < np.asarray(counts).sum(axis=1)).all()
    assert (np.asarray(counts).sum(axis=1) < 3 * 32 * 2).all()
    # the reference in one bf16 pass reads far above the program
    control = REF.forward(Ops('bfloat16'), {'checkpoint_path': params}, ids,
                          rcfg)
    assert rel_l2(control, want) > 1e-3


def test_the_reference_and_the_program_hold_the_same_parameters(tiny):
    cfg, _, params, _, _ = tiny
    assert {k: v.shape for k, v in params.items()} == ht.param_shapes(cfg)
    assert list(params) == list(ht.param_shapes(cfg))      # checkpoint order
    assert ht.param_count(cfg) == sum(v.size for v in params.values())
    ours = ht.init_params(cfg)
    assert {k: v.shape for k, v in ours.items()} == ht.param_shapes(cfg)
    # the embedding is drawn √hidden smaller, so × √hidden leaves size 1
    assert 0.08 < float(ours['model.embed_tokens.weight'].std()) < 0.17
    bias = ours['model.layers.2.mlp.expert_bias']
    assert 0 < float(np.abs(bias).max()) < 0.3
    names = set(params)
    for name in ('model.layers.0.self_attn.gate_proj.weight',
                 'model.layers.0.post_attention_layernorm.weight',
                 'model.layers.0.post_mlp_layernorm.weight',
                 'model.layers.1.mlp.shared_experts.up_proj.weight',
                 'model.layers.1.mlp.router.gate.weight', 'model.norm.weight'):
        assert name in names, name
    assert params['model.layers.1.mlp.experts.gate_proj.weight'].shape == (
        4, 64, 32)
    assert params['model.layers.1.mlp.router.gate.weight'].shape == (64, 8)


def _window_ignored(cfg, monkeypatch):
    return dataclasses.replace(cfg, sliding_window=10 ** 6)


def _rotary_on_the_full_layer(cfg, monkeypatch):
    monkeypatch.setitem(ht.DIALECTS, 'afmoe', dataclasses.replace(
        ht.DIALECTS['afmoe'], rotary=(S, F)))
    return cfg


def _gate_dropped(cfg, monkeypatch):
    monkeypatch.setitem(ht.DIALECTS, 'afmoe', dataclasses.replace(
        ht.DIALECTS['afmoe'], gated=False))
    return cfg


def _post_norms_dropped(cfg, monkeypatch):
    monkeypatch.setitem(ht.DIALECTS, 'afmoe', dataclasses.replace(
        ht.DIALECTS['afmoe'], post_norms=()))
    return cfg


def _shared_expert_dropped(cfg, monkeypatch):
    return dataclasses.replace(cfg, num_shared_experts=0)


def _embedding_multiplier_dropped(cfg, monkeypatch):
    return dataclasses.replace(cfg, embed_scale=False)


def _bias_in_the_weight(cfg, monkeypatch):
    """The choice and the weights both from ``score + bias``."""
    def route(x, w_router, bias, *, top_k, scaling, normalise=True,
              eps=1e-20):
        scores = jax.nn.sigmoid(jnp.dot(
            x, w_router, precision=jax.lax.Precision.HIGHEST)) + bias
        weights_, experts = jax.lax.top_k(scores, top_k)
        weights_ = weights_ / (weights_.sum(axis=-1, keepdims=True) + eps)
        return experts.astype(jnp.int32), weights_ * scaling
    monkeypatch.setattr(moe, 'route', route)
    return cfg


@pytest.mark.parametrize('fault', [
    _window_ignored, _rotary_on_the_full_layer, _gate_dropped,
    _post_norms_dropped, _shared_expert_dropped,
    _embedding_multiplier_dropped, _bias_in_the_weight])
def test_each_mechanisms_fault_shows_in_what_is_compared(tiny, fault,
                                                         monkeypatch):
    """The seven mechanisms this trunk adds to the module, each broken in
    the program alone: the features leave the reference by far more than
    the tolerance the sound trunk keeps (1e-5), by more than the 1e-3 a
    one-pass control reads."""
    cfg, _, params, ids, want = tiny
    got, _ = run(params, ids, fault(cfg, monkeypatch))
    assert rel_l2(got, want) > 1e-3, fault.__name__


def test_the_sliding_layers_see_their_window_and_no_further(tiny):
    """An id changed at position 0 reaches position p of a sliding-only
    trunk through L layers only while p < L · (window − 1) + 1; a full layer
    carries it everywhere."""
    _, _, params, ids, _ = tiny

    def states(kinds, ids):
        cfg = program_cfg(num_hidden_layers=len(kinds),
                          layer_types=list(kinds), num_dense_layers=len(kinds))
        dense = {k: v for k, v in ht.init_params(cfg, 4).items()}
        with jax.default_matmul_precision('highest'):
            return np.asarray(token_trunk.hidden_states(dense, ids, cfg, 8, 8)[0])

    changed = ids[:1].copy()
    changed[0, 0] = (changed[0, 0] + 1) % 512
    for kinds, reach in (((S,), 8), ((S, S), 15), ((S, F), 32)):
        moved = np.abs(states(kinds, ids[:1]) - states(kinds, changed)
                       ).max(axis=-1)[0] > 0
        assert moved[:reach].all() and not moved[reach:].any(), kinds
    # and no later token changes an earlier position
    later = ids[:1].copy()
    later[0, 20] = (later[0, 20] + 1) % 512
    moved = np.abs(states((S, F), ids[:1]) - states((S, F), later)
                   ).max(axis=-1)[0] > 0
    assert not moved[:20].any() and moved[20:].all()


def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        tiny):
    """Four chips hold experts 0-1, 2-3, 4-5 and 6-7 of one layer and each
    computes the shared expert. The routed parts their shares give, plus the
    shared expert counted once, are the uncut layer of the reference."""
    _, rcfg, params, _, _ = tiny
    whole = weights.make(REF.param_specs(dict(rcfg, n_routed_experts=8))[
        'checkpoint_path'], SEED, 'checkpoint_path')
    x = np.random.default_rng(7).standard_normal((1, 32, 64)).astype(
        np.float32)
    m = 'model.layers.2.mlp'
    want = np.asarray(REF._experts(Ops(), whole, m, jnp.asarray(x), dict(
        rcfg, n_routed_experts=8)))
    total = np.zeros((32, 64))
    held_rows = 0
    with jax.default_matmul_precision('highest'):
        for first in (0, 2, 4, 6):
            cfg = program_cfg(n_experts_held=2, first_expert=first)
            share = dict(whole)
            for name in ('gate_proj', 'up_proj', 'down_proj'):
                key = f'{m}.experts.{name}.weight'
                share[key] = whole[key][first:first + 2]
            y, counts = token_trunk.expert_block(share, m, jnp.asarray(x[0]), cfg, 8)
            # one share is the reference given the same share
            part = REF._experts(Ops(), share, m, jnp.asarray(x), dict(
                rcfg, n_routed_experts=2, first_expert=first))
            assert rel_l2(y, part[0]) < TOLERANCE
            routed, _ = token_trunk.expert_block(share, m, jnp.asarray(x[0]),
                                        dataclasses.replace(
                                            cfg, num_shared_experts=0), 8)
            total += np.asarray(routed)
            held_rows += int(np.asarray(counts).sum())
            shared = np.asarray(y) - np.asarray(routed)
    assert held_rows == 32 * 2          # every assignment lands on one share
    # the four routed parts alone miss the layer by the shared expert
    assert rel_l2(total, want[0]) > 0.1
    assert rel_l2(total + shared, want[0]) < TOLERANCE
    np.testing.assert_allclose(shared, np.asarray(token_trunk.swiglu(
        jnp.asarray(x[0]), {k: jnp.asarray(v) for k, v in whole.items()},
        f'{m}.shared_experts')), atol=1e-5)


# -- the extractor: one family, four trunks -----------------------------------------

def test_model_type_picks_the_second_dialect_and_the_yml_holds_its_keys():
    assert extract_lm.load_trunk('afmoe') is ht
    assert extract_lm.TRUNKS['afmoe'] == ht.__name__
    cfg = program_cfg()
    assert cfg.model_type == 'afmoe' and cfg.dialect is ht.DIALECTS['afmoe']
    assert (cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.norm_eps,
            cfg.embed_scale, cfg.use_expert_bias) == (2.826, True, 1e-5,
                                                      True, True)
    yml = load_config('lm', overrides={'video_paths': ['x.mp4'],
                                       'device': 'cpu'})
    # the published keys are in the yml under their published names, null
    for key in ('sliding_window', 'num_shared_experts', 'route_scale',
                'route_norm', 'score_func', 'mup_enabled'):
        assert key in yml and yml[key] is None
    assert set(ht.AFMOE_CONFIG_KEYS) <= set(yml)
    with pytest.raises(ValueError, match=r'model_type=afmoe needs config '
                       r'keys \[.*\'sliding_window\'.*\'score_func\'.*\]'):
        ht.TrunkConfig.from_args(dict(yml, model_type='afmoe'))
    with pytest.raises(ValueError, match=r"no trunk for model_type='afm'; "
                       r'known: afmoe, brumby, dots3_note, granitemoehybrid, '
                       r'joyai_llm_flash, lfm2_moe'):
        extract_lm.load_trunk('afm')


@pytest.mark.parametrize('changes,match', [
    (dict(score_func='softmax'), r"score_func='softmax'.*sigmoid"),
    (dict(layer_types=[S, 'conv', S, F, S]),
     r"layer_types\[1\]='conv' is no operator of the model_type=afmoe "
     r'trunk; known: sliding_attention, full_attention'),
    (dict(sliding_window=0), r'sliding_attention layers need sliding_window'),
    (dict(head_dim=15), r'head_dim=15 is no even head width'),
    (dict(n_experts_held=6, first_expert=4), r'does not lie inside'),
])
def test_what_the_trunk_cannot_run_is_refused_by_name(changes, match):
    with pytest.raises(ValueError, match=match):
        program_cfg(**changes)


def test_a_build_that_cannot_fit_is_refused_with_the_sizes():
    body = loader.load_json('configs', 'trinity-mini-ep4-l8')
    whole = ht.TrunkConfig.from_args(dict(
        body['overrides'], num_hidden_layers=32,
        layer_types=body['layer_types'], n_experts_held=None))
    need = ht.param_count(whole) * 4
    with pytest.raises(ValueError) as refused:
        extract_lm.check_params_fit(
            need, 16 * 10 ** 9, f'lm with {ht.describe(whole)}',
            ht.SHARE_ADVICE)
    said = str(refused.value)
    assert '32 layers (24 sliding_attention + 8 full_attention)' in said
    assert '128 of 128 experts in each of the 30 expert layers' in said
    assert '102.8' in said and 'do not fit the device\'s 16.00 GB' in said
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
    d = tmp_path_factory.mktemp('afmoe_clips')
    return [write_noise_clip(d / f'c{i}.mp4', n, seed=40 + i)
            for i, n in enumerate([5, 1, 11, 7])]      # c1 is too short


def test_extract_packed_equals_the_per_video_loop(clips, tmp_path, capsys):
    packed = _extractor(tmp_path / 'a', pack_across_videos=True,
                        manifest_out=str(tmp_path / 'manifest.json'))
    assert packed.trunk is ht and packed.cfg.model_type == 'afmoe'
    said = capsys.readouterr().err
    assert 'model_type=afmoe' in said
    assert 'sliding_attention=xla' in said and 'full_attention=xla' in said
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
    assigned = steps * 2 * 32 * 2 * 4       # slots × ids × top-2 × 4 layers
    assert 0 < stages['moe_held']['occ_valid'] < assigned \
        == stages['moe_held']['occ_capacity']          # 4 of 8 experts held
    assert stages['moe_route']['occ_valid'] \
        == stages['moe_walk']['occ_valid'] == stages['moe_held']['occ_valid']
    assert stages['moe_walk']['occ_capacity'] % 256 == 0
    assert doc['kernels'] == {
        'sliding_attention': 'xla', 'full_attention': 'xla',
        'sliding_window': 8,
        'window_tiles': "1 of the triangle's 1 (query, key) tiles of "
                        '32 x 32',
        'operators': 'sliding_attention 4, full_attention 1'}


def test_the_step_carries_the_scopes_a_trace_is_read_by():
    cfg = program_cfg()
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in ht.param_shapes(cfg).items()}
    ids = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    text = jax.jit(partial(extract_lm.ExtractLM._forward, cfg=cfg,
                           platform='tpu')).trace(params, ids).lower(
        lowering_platforms=('tpu',)).as_text(debug_info=True)
    import re
    for scope in ('sliding_attention', 'full_attention', 'moe', 'dense_mlp'):
        assert re.search(rf'[/"]{scope}/', text), scope
    # lfm2's scope is not this dialect's, and 16-wide heads keep the tiles
    assert not re.search(r'[/"]attention/', text)
    assert 'tpu_custom_call' not in text


# a trunk both lanes of the kernel take: 8 query heads of 128 over 1
# key-value head, windows of 256 ids, a window of 128 keys
ALIGNED = dict(TINY_PROGRAM, hidden_size=256, num_attention_heads=8,
               num_key_value_heads=1, head_dim=128, sliding_window=128,
               num_hidden_layers=3, layer_types=[S, F, S],
               num_dense_layers=3)


@pytest.mark.parametrize('platform,precision,windowed,plain', [
    ('tpu', 'high', 2, 1),        # precision=mixed: a call a layer's lax.map
    ('tpu', 'default', 2, 1),     # the control lane takes the kernel too
    ('tpu', 'highest', 0, 0),     # the yml's default keeps the XLA path
    ('cpu', 'high', 0, 0),        # what tier-1 lowers
])
def test_the_step_lowered_for_a_tpu_holds_both_named_kernels(
        platform, precision, windowed, plain):
    """The step as the extractor jits it, lowered for the TPU from here:
    the sliding layers a Mosaic call named window_attention, the full layer
    one named causal_attention, where ``resolve_causal`` says 'kernel' — and
    ``kernels``, the engagement counter, says the same per kind."""
    cfg = program_cfg(**ALIGNED)
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in ht.param_shapes(cfg).items()}
    ids = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    with jax.default_matmul_precision(precision):
        text = jax.jit(partial(extract_lm.ExtractLM._forward, cfg=cfg,
                               platform=platform)).trace(
            params, ids).lower(lowering_platforms=('tpu',)).as_text()
    assert text.count('kernel_name = "window_attention"') == windowed
    assert text.count('kernel_name = "causal_attention"') == plain
    assert text.count('tpu_custom_call') == windowed + plain
    notes = ht.kernels(cfg, platform, 256, precision)
    want = 'kernel' if windowed else 'xla'
    assert (notes[S], notes[F]) == (want, want)
    assert notes['sliding_window'] == 128
    # one window of 256 ids is two query tiles of the kernel's, one of XLA's
    assert notes['window_tiles'] == (
        "2 of the triangle's 2 (query, key) tiles of 128 x 256" if windowed
        else "1 of the triangle's 1 (query, key) tiles of 256 x 256")


def test_the_kernel_path_of_a_sliding_layer_is_the_xla_path_to_rounding(
        monkeypatch):
    """attention_block with the kernel forced in (interpreted: the decision
    says 'kernel' only on a TPU) against the XLA tiles, both at three
    passes, a sliding layer and a full one."""
    from video_features_tpu.ops import pallas_attention
    from video_features_tpu.ops.precision import rel_l2
    cfg = program_cfg(**ALIGNED)
    params = {n: jnp.asarray(w) for n, w in ht.init_params(cfg, 3).items()}
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (256, 256)).astype(np.float32))
    for layer, kind in ((0, S), (1, F)):
        a = f'model.layers.{layer}.self_attn'
        with jax.default_matmul_precision('high'):
            want = ht.attention_block(params, a, x, cfg, 64, 'cpu', kind)
            with monkeypatch.context() as forced:
                forced.setattr(ht, 'resolve_causal', lambda *args: 'kernel')
                forced.setattr(
                    pallas_attention, 'causal_attention',
                    partial(pallas_attention.causal_attention,
                            interpret=True, block_q=16, block_k=64))
                got = ht.attention_block(params, a, x, cfg, 64, 'tpu', kind)
        assert got.shape == want.shape == (256, 256)
        assert 0 < rel_l2(got, want) < 2e-5, kind
