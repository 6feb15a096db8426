"""The lm family's sixth trunk (``model_type=granitemoehybrid``,
``models/hybrid_trunk.py``'s third dialect): Mamba-2 mixers among
grouped-query attention layers with no positional code, a dense SwiGLU in
every layer, the embedding and every sub-layer's output multiplied — at a
tiny size on the CPU (hidden 64, 4 Mamba heads of 32 over a state of 16, 4
query / 2 key-value heads of 16). The plain reference it is held to is the
benchmark's (``benchmark/references/granite-4.0-h-micro-l20.py``: written from
the config and ``transformers``' ``modeling_granitemoehybrid.py``, the scan
in its recurrence form, nothing of the program)."""
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
from video_features_tpu.registry import create_extractor  # noqa: E402

SEED = 2 ** 31 + 44
REF = loader.load_module('references', 'granite-4.0-h-micro-l20')

# four layers: both mixers, the attention layer between Mamba layers
KINDS = ('mamba', 'mamba', 'attention', 'mamba')
WIDTHS = dict(vocab_size=512, hidden_size=64, shared_intermediate_size=96,
              num_attention_heads=4, num_key_value_heads=2,
              attention_multiplier=0.0625, mamba_n_heads=4, mamba_d_head=32,
              mamba_d_state=16)
TINY_PROGRAM = dict(
    model_type='granitemoehybrid', num_hidden_layers=4,
    layer_types=list(KINDS), embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0,
    position_embedding_type='nope', rms_norm_eps=1e-5, num_local_experts=0,
    mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=16,
    mamba_conv_bias=True, mamba_proj_bias=False, **WIDTHS)
WINDOW = dict(stack_size=4, step_size=4, patch_grid=4)      # 64 ids
# float32 sums in another order (the chunked scan against the recurrence,
# tiles against whole rows): some 1e-7; a lost term or a multiplier off
# reads 1e-3 and more
TOLERANCE = 1e-5


def tiny_reference_cfg(**changes):
    return dict(REF.CFG, layers=4, layer_types=KINDS, frames=4, patch_grid=4,
                query_block=16, **dict(WIDTHS, **changes))


def program_cfg(**changes):
    return ht.TrunkConfig.from_args(dict(TINY_PROGRAM, **changes))


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope='module')
def tiny():
    cfg = program_cfg()
    specs = REF.param_specs(tiny_reference_cfg())['checkpoint_path']
    params = weights.make(specs, SEED, 'checkpoint_path')
    ids = np.random.default_rng(4).integers(0, 512, (3, 64)).astype(np.int32)
    return cfg, params, ids


def forward(params, ids, cfg):
    with jax.default_matmul_precision('highest'):
        return token_trunk.forward(
            {k: jnp.asarray(v) for k, v in params.items()}, ids, cfg, 16)


# -- the trunk against the plain reference ---------------------------------------

def test_trunk_matches_the_reference(tiny):
    cfg, params, ids = tiny
    got, counter = forward(params, ids, cfg)
    want = REF.forward(Ops(), {'checkpoint_path': params}, ids,
                       tiny_reference_cfg())
    assert got.shape == (3, 64) and got.dtype == jnp.float32
    assert rel_l2(got, want) < TOLERANCE
    # the counter: a row a Mamba layer, the batch's positions, chunks
    # scanned and none through the kernel on the CPU
    np.testing.assert_array_equal(np.asarray(counter),
                                  [[3 * 64, 3 * 4, 0]] * 3)


def test_the_reference_and_the_program_hold_the_same_parameters(tiny):
    cfg, params, _ = tiny
    assert {n: tuple(v.shape) for n, v in params.items()} == \
        ht.param_shapes(cfg)
    names = list(ht.param_shapes(cfg))
    assert names[:5] == ['model.embed_tokens.weight',
                         'model.layers.0.input_layernorm.weight',
                         'model.layers.0.mamba.in_proj.weight',
                         'model.layers.0.mamba.conv1d.weight',
                         'model.layers.0.mamba.conv1d.bias']
    assert 'model.layers.2.self_attn.o_proj.weight' in names
    assert not any('q_norm' in n or 'k_norm' in n for n in names)
    assert names[-1] == 'model.norm.weight'
    assert ht.param_shapes(cfg)['model.layers.0.mamba.in_proj.weight'] == (
        64, 2 * 128 + 2 * 16 + 4)
    assert ht.param_shapes(cfg)[
        'model.layers.1.shared_mlp.input_linear.weight'] == (64, 2 * 96)


@pytest.mark.parametrize('changes', [
    dict(embedding_multiplier=1.0), dict(residual_multiplier=1.0),
    dict(attention_multiplier=0.25), dict(mamba_d_conv=3),
])
def test_each_mechanisms_fault_shows_in_what_is_compared(tiny, changes):
    """The program with one published number off (taps: the conv read one
    tap short) is no longer the reference."""
    cfg, params, ids = tiny
    want = REF.forward(Ops(), {'checkpoint_path': params}, ids,
                       tiny_reference_cfg())
    if 'mamba_d_conv' in changes:
        params = {k: (v[1:] if k.endswith('conv1d.weight') else v)
                  for k, v in params.items()}
    got, _ = forward(params, ids, program_cfg(**changes))
    assert rel_l2(got, want) > 1e-3


def test_a_later_token_changes_no_earlier_position(tiny):
    cfg, params, ids = tiny
    p = {k: jnp.asarray(v) for k, v in params.items()}
    with jax.default_matmul_precision('highest'):
        hidden = jax.jit(
            lambda p, i: token_trunk.hidden_states(p, i, cfg, 16)[0])
        a = hidden(p, ids[:1])
        changed = ids[:1].copy()
        changed[0, 40] = (changed[0, 40] + 1) % 512
        b = hidden(p, changed)
    np.testing.assert_allclose(a[0, :40], b[0, :40], rtol=0, atol=1e-5)
    assert np.abs(np.asarray(a[0, 40:]) - np.asarray(b[0, 40:])).max() > 1e-4


def test_a_batch_of_windows_is_each_window_alone(tiny):
    """No state crosses from one window to the next: the scan starts from
    zero and the convolution sees zeros before each window's start."""
    cfg, params, ids = tiny
    both, _ = forward(params, ids, cfg)
    for i in range(3):
        alone, _ = forward(params, ids[i:i + 1], cfg)
        np.testing.assert_allclose(both[i], alone[0], rtol=0, atol=1e-6)


# -- what the trunk reads and refuses ---------------------------------------------

@pytest.mark.parametrize('changes,match', [
    (dict(position_embedding_type='rope'),
     r"position_embedding_type='rope': .* no positional code \('nope'\)"),
    (dict(num_local_experts=62),
     r'num_local_experts=62: .* dense stage \(shared_mlp\)'),
    (dict(mamba_n_groups=2), r'mamba_n_groups=2: .* has no groups'),
    (dict(mamba_proj_bias=True),
     r'mamba_proj_bias=True: .* none on its projections'),
    (dict(mamba_n_heads=5),
     r'mamba_n_heads=5 x mamba_d_head=32 is not mamba_expand=2 x '
     r'hidden_size=64'),
    (dict(layer_types=['mamba', 'conv', 'attention', 'mamba']),
     r"layer_types\[1\]='conv' is no operator of the "
     r'model_type=granitemoehybrid trunk; known: mamba, attention'),
])
def test_what_the_trunk_cannot_run_is_refused_by_name(changes, match):
    with pytest.raises(ValueError, match=match):
        program_cfg(**changes)


def test_a_published_key_left_out_is_refused_by_name():
    missing = dict(TINY_PROGRAM)
    del missing['mamba_d_state'], missing['residual_multiplier']
    with pytest.raises(ValueError, match=r"model_type=granitemoehybrid needs "
                       r"config keys \['residual_multiplier', "
                       r"'mamba_d_state'\]"):
        ht.TrunkConfig.from_args(missing)


def test_the_cells_configuration_counts_as_the_issue_counts_it():
    body = loader.load_json('configs', 'granite-4.0-h-micro-l20')
    cut = ht.TrunkConfig.from_args(body['overrides'])
    assert (cut.num_dense_layers, cut.n_experts_held) == (20, 0)
    assert cut.kinds() == {'mamba': 18, 'attention': 2}
    assert (cut.head_dim, cut.mamba_inner, cut.mamba_conv_dim) == (
        64, 4096, 4352)
    assert ht.param_count(cut) == 1_698_459_520
    whole = ht.TrunkConfig.from_args(dict(
        body['overrides'], num_hidden_layers=40,
        layer_types=body['layer_types']))
    assert ht.param_count(whole) == 3_191_396_096
    assert ht.describe(whole) == ('40 layers (36 mamba + 4 attention) and a '
                                  'dense SwiGLU of 8192')
    with pytest.raises(ValueError) as refused:
        extract_lm.check_params_fit(
            ht.param_count(whole) * 4, 12 * 10 ** 9,
            f'lm with {ht.describe(whole)}', ht.SHARE_ADVICE)
    assert '12.77 GB of float32 parameters' in str(refused.value)
    for platform, precision, path in [
            ('tpu', 'high', 'kernel'), ('tpu', 'default', 'kernel'),
            ('tpu', 'highest', 'xla'), ('cpu', 'high', 'xla')]:
        assert ht.kernels(cut, platform, 32_768, precision) == {
            'causal_attention': path, 'ssd': path, 'ssd_chunk': 256,
            'operators': 'mamba 18, attention 2'}, (platform, precision)


def test_model_type_picks_the_third_dialect_and_the_yml_holds_its_keys():
    assert extract_lm.load_trunk('granitemoehybrid') is ht
    assert extract_lm.TRUNKS[ht.GRANITE] == ht.__name__
    assert extract_lm.step_counter(program_cfg()) == (ht.SSD_COUNTER,
                                                      ht.count_ssd)
    yml = load_config('lm', overrides={'video_paths': ['x.mp4'],
                                       'device': 'cpu'})
    assert set(ht.GRANITE_CONFIG_KEYS) <= set(yml)
    for key in ('shared_intermediate_size', 'attention_multiplier',
                'embedding_multiplier', 'residual_multiplier',
                'position_embedding_type', 'num_local_experts',
                'mamba_n_heads', 'mamba_chunk_size', 'mamba_conv_bias'):
        assert yml[key] is None, key
    with pytest.raises(ValueError, match=r'model_type=granitemoehybrid needs '
                       r'config keys \[.*\'layer_types\'.*\'mamba_d_state\''):
        ht.TrunkConfig.from_args(dict(yml, model_type='granitemoehybrid'))


def test_the_other_dialects_keep_their_counter_and_multiply_nothing():
    lfm2 = ht.TrunkConfig.from_args(dict(
        loader.load_json('configs', 'lfm2-8b-a1b-l8')['overrides']))
    assert extract_lm.step_counter(lfm2) == (ht.COUNTER, ht.count)
    assert (lfm2.embedding_multiplier, lfm2.residual_multiplier,
            lfm2.attention_multiplier) == (None, None, None)


# -- the extractor: the packed path, the counters, the scopes, the kernel ---------

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
    d = tmp_path_factory.mktemp('granite_clips')
    return [write_noise_clip(d / f'c{i}.mp4', n, seed=40 + i)
            for i, n in enumerate([9, 3, 13])]     # c1 is too short


def test_extract_packed_equals_the_per_video_loop(clips, tmp_path, capsys):
    packed = _extractor(tmp_path / 'a', pack_across_videos=True,
                        manifest_out=str(tmp_path / 'manifest.json'))
    assert packed.trunk is ht and packed.cfg.model_type == 'granitemoehybrid'
    said = capsys.readouterr().err
    assert 'ssd=xla' in said and 'mamba 3, attention 1' in said
    packed.extract_packed(list(clips), decode_ahead=2)
    packed.finish_obs()
    loop = _extractor(tmp_path / 'b')
    for path in clips:
        loop._extract(path)
    assert packed.failed_videos == loop.failed_videos == 0
    for stem, n in {'c0': 2, 'c1': 0, 'c2': 3}.items():
        a = np.load(Path(packed.output_path) / f'{stem}_lm.npy')
        b = np.load(Path(loop.output_path) / f'{stem}_lm.npy')
        assert a.shape == b.shape == (n, 64) and a.dtype == np.float32
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    # the saved rows are the trunk's, on the tokeniser's ids (the reference's)
    want, _ = token_trunk.forward(packed.params, REF.load_units(
        clips[2], range(3), tiny_reference_cfg()), packed.cfg)
    np.testing.assert_allclose(
        np.load(Path(packed.output_path) / 'c2_lm.npy'), want, atol=1e-5)
    doc = json.loads((tmp_path / 'manifest.json').read_text())
    stages = doc['stages']
    steps = stages['model']['count']
    # every slot's positions through each of the three Mamba layers, 4 chunks
    # of 16 a window, none through the kernel on the CPU
    scan = stages['ssd_scan']
    assert scan['occ_valid'] == scan['occ_capacity'] == steps * 2 * 64 * 3
    assert stages['ssd_kernel']['occ_capacity'] == steps * 2 * 4 * 3
    assert stages['ssd_kernel']['occ_valid'] == 0
    assert 'moe_route' not in stages and 'retention_scan' not in stages
    assert doc['kernels'] == {'causal_attention': 'xla', 'ssd': 'xla',
                              'ssd_chunk': 16,
                              'operators': 'mamba 3, attention 1'}


def test_the_step_carries_the_scopes_a_trace_is_read_by():
    cfg = program_cfg()
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in ht.param_shapes(cfg).items()}
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = jax.jit(partial(extract_lm.ExtractLM._forward, cfg=cfg,
                           platform='tpu')).trace(params, ids).lower(
        lowering_platforms=('tpu',)).as_text(debug_info=True)
    for scope in ('mamba/ssd', 'attention', 'dense_mlp'):
        assert scope in text, scope
    assert 'moe' not in text
    assert 'tpu_custom_call' not in text     # 32-wide heads: XLA's forms


# a trunk whose scan and attention the kernels take: 2 Mamba heads of 64 (one
# 128-lane group) over a state of 128 in chunks of 128; 4 query heads of 64
# reading 2 key-value heads; windows of 256 ids
ALIGNED = dict(TINY_PROGRAM, hidden_size=64, mamba_n_heads=2, mamba_d_head=64,
               mamba_d_state=128, mamba_chunk_size=128, num_attention_heads=1,
               num_key_value_heads=1)


@pytest.mark.parametrize('platform,precision,calls', [
    ('tpu', 'high', 3),        # precision=mixed: one call a Mamba layer
    ('tpu', 'default', 3),     # the control lane takes the kernel too
    ('tpu', 'highest', 0),     # the yml's default keeps XLA's form
    ('cpu', 'high', 0),        # what tier-1 lowers
])
def test_the_step_lowered_for_a_tpu_holds_the_named_kernel(platform,
                                                           precision, calls):
    cfg = program_cfg(**ALIGNED)
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in ht.param_shapes(cfg).items()}
    ids = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    with jax.default_matmul_precision(precision):
        text = jax.jit(partial(extract_lm.ExtractLM._forward, cfg=cfg,
                               platform=platform)).trace(
            params, ids).lower(lowering_platforms=('tpu',)).as_text()
    assert text.count('kernel_name = "ssd_scan"') == calls
    assert ht.kernels(cfg, platform, 256, precision)['ssd'] == (
        'kernel' if calls else 'xla')


def test_the_kernel_path_of_mamba_block_is_the_xla_path_to_rounding(
        monkeypatch):
    """mamba_block with the kernel forced in (interpreted: the decision says
    'kernel' only on a TPU) against XLA's form, both at three passes."""
    from jax.experimental.pallas import tpu as pltpu

    from video_features_tpu.ops.precision import rel_l2 as rel
    cfg = program_cfg(**ALIGNED)
    params = {n: jnp.asarray(w) for n, w in ht.init_params(cfg, 3).items()}
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (256, 64)).astype(np.float32))
    a = 'model.layers.0.mamba'
    with jax.default_matmul_precision('high'):
        want, n_xla = ht.mamba_block(params, a, x, cfg, None, 'cpu')
        monkeypatch.setattr(ht, 'resolve_ssd', lambda *args: 'kernel')
        with pltpu.force_tpu_interpret_mode():
            got, n_kernel = ht.mamba_block(params, a, x, cfg, None, 'tpu')
    assert got.shape == want.shape == (256, 64)
    assert 0 < rel(got, want) < 2e-5
    np.testing.assert_array_equal(np.asarray(n_xla), [256, 2, 0])
    np.testing.assert_array_equal(np.asarray(n_kernel), [256, 2, 2])
