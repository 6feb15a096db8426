"""The lm family's second trunk (``model_type=brumby``): gated power
retention in a dense grouped-query block, and the extractor's choice of a
trunk by ``model_type`` — at a tiny size on the CPU (hidden 64, 4 query /
2 key-value heads of 8, windows of 64 ids; the scan's chunk, a constant of
the trunk's module, set to 16 where a test wants several). The plain reference
it is held to is the benchmark's (``benchmark/references/brumby-14b-l4.py``:
the attention form, nothing of the program). The mixer's own forms:
``tests/test_retention.py``."""
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
from video_features_tpu.models import latent_moe  # noqa: E402
from video_features_tpu.models import retention_trunk as rt  # noqa: E402
from video_features_tpu.models import token_trunk  # noqa: E402
from video_features_tpu.registry import create_extractor  # noqa: E402

SEED = 2 ** 31 + 31
REF = loader.load_module('references', 'brumby-14b-l4')

TINY_PROGRAM = dict(
    model_type='brumby', vocab_size=512, hidden_size=64, num_hidden_layers=3,
    intermediate_size=160, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, rope_theta=1e6, rms_norm_eps=1e-6)
WINDOW = dict(stack_size=4, step_size=4, patch_grid=4)      # 64 ids


def tiny_reference_cfg(**changes):
    c = dict(REF.CFG, vocab_size=512, hidden_size=64, layers=3,
             intermediate_size=160, num_attention_heads=4,
             num_key_value_heads=2, head_dim=8, frames=4, patch_grid=4,
             query_block=16, row_block=32)
    c.update(changes)
    return c


@pytest.fixture()
def chunks_of_16(monkeypatch):
    """Four chunks a 64-id window: the state hands over three times."""
    monkeypatch.setattr(rt, 'RETENTION_CHUNK', 16)


@pytest.fixture(scope='module')
def tiny():
    rcfg = tiny_reference_cfg()
    params = weights.make(REF.param_specs(rcfg)['checkpoint_path'], SEED,
                          'checkpoint_path')
    ids = np.random.default_rng(0).integers(0, 512, (3, 64)).astype(np.int32)
    return rt.TrunkConfig.from_args(TINY_PROGRAM), rcfg, params, ids


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# -- block, trunk and parameter set against the plain reference -------------------

def test_retention_block_matches_the_reference(tiny, chunks_of_16):
    cfg, rcfg, params, _ = tiny
    x = np.random.default_rng(5).standard_normal((2, 64, 64)).astype(
        np.float32)
    a = 'model.layers.1.self_attn'
    want = REF._retention(Ops(), params, a, jnp.asarray(x), rcfg)
    with jax.default_matmul_precision('highest'):
        got = jnp.stack([rt.retention_block(params, a, w, cfg)[0] for w in x])
        counted = rt.retention_block(params, a, x[0], cfg)[1]
    assert rel_l2(got, want) < 1e-5
    # 64 positions through the state scan, none of them through the
    # kernels (the CPU takes XLA's form)
    assert counted.tolist() == [64, 0] and counted.dtype == jnp.int32
    # the seeded gate remembers: without the state's part the block is wrong
    cut = jnp.stack([
        jnp.concatenate([rt.retention_block(params, a, w[i:i + 16], cfg)[0]
                         for i in range(0, 64, 16)]) for w in x])
    assert rel_l2(cut, want) > 1e-2


def test_trunk_matches_the_reference(tiny, chunks_of_16):
    cfg, rcfg, params, ids = tiny
    want = REF.forward(Ops(), {'checkpoint_path': params}, ids, rcfg)
    with jax.default_matmul_precision('highest'):
        got, scanned = jax.jit(lambda p, i: token_trunk.forward(p, i, cfg))(params,
                                                                   ids)
    assert got.shape == (3, 64) and got.dtype == jnp.float32
    assert rel_l2(got, want) < 1e-5
    assert np.asarray(scanned).tolist() == [[3 * 64, 0]] * 3
    # the reference in one bf16 pass reads far above the program
    control = REF.forward(Ops('bfloat16'), {'checkpoint_path': params}, ids,
                          rcfg)
    assert rel_l2(control, want) > 1e-3


@pytest.mark.parametrize('chunk,noted', [(8, 8), (64, 64), (512, 64),
                                         (48, 48)])
def test_every_chunk_and_a_padded_window_give_the_same_rows(
        tiny, monkeypatch, chunk, noted):
    """512 is the shipped constant (one chunk of the whole 64-id window);
    48 leaves a ragged tail, which the scan pads and cuts back."""
    cfg, _, params, ids = tiny
    with jax.default_matmul_precision('highest'):
        monkeypatch.setattr(rt, 'RETENTION_CHUNK', 16)
        want, _ = token_trunk.forward(params, ids, cfg)
        monkeypatch.setattr(rt, 'RETENTION_CHUNK', chunk)
        got, scanned = token_trunk.forward(params, ids, cfg)
    assert rel_l2(got, want) < 1e-5
    assert rt.kernels(cfg, 'cpu', 64, None) == {
        'retention': 'state', 'retention_chunk': noted}
    assert np.asarray(scanned).tolist() == [[3 * 64, 0]] * 3


def test_a_later_token_changes_no_earlier_position(tiny, chunks_of_16):
    cfg, _, params, ids = tiny
    with jax.default_matmul_precision('highest'):
        base, _ = token_trunk.hidden_states(params, ids[:1], cfg)
        changed = ids[:1].copy()
        changed[0, 40] = (changed[0, 40] + 1) % 512
        other, _ = token_trunk.hidden_states(params, changed, cfg)
    base, other = np.asarray(base), np.asarray(other)
    np.testing.assert_array_equal(base[0, :40], other[0, :40])
    assert np.abs(base[0, 40:] - other[0, 40:]).max() > 1e-3


def test_the_reference_and_the_program_hold_the_same_parameters(tiny):
    cfg, _, params, _ = tiny
    assert {k: v.shape for k, v in params.items()} == rt.param_shapes(cfg)
    assert rt.param_count(cfg) == sum(v.size for v in params.values())
    ours = rt.init_params(cfg)
    assert {k: v.shape for k, v in ours.items()} == rt.param_shapes(cfg)
    bias = ours['model.layers.0.self_attn.g_proj.bias']
    assert bias.min() >= 4.0 and bias.max() <= 8.0


def test_published_sizes_count_as_the_issue_counts_them():
    body = loader.load_json('configs', 'brumby-14b-l4')
    args = load_config('lm', overrides=dict(
        body['overrides'], video_paths=['x.mp4'], device='cpu'))
    cfg = rt.TrunkConfig.from_args(args)
    shapes = rt.param_shapes(cfg)
    layer = sum(int(np.prod(s)) for n, s in shapes.items()
                if n.startswith('model.layers.0.'))
    assert layer == 330_352_904
    assert int(np.prod(shapes['model.embed_tokens.weight'])) == 777_912_320
    assert rt.param_count(cfg) == 2_099_329_056          # 8.40 GB
    assert args['stack_size'] * args['patch_grid'] ** 2 == 32_768 \
        == body['max_position_embeddings']
    # the cell's step: the kernels under precision=mixed and the control
    # lane, XLA's form under 'highest' and off the chip
    for precision, form in (('high', 'kernel'), ('default', 'kernel'),
                            ('highest', 'state')):
        assert rt.kernels(cfg, 'tpu', 32_768, precision) == {
            'retention': form, 'retention_chunk': 512}
    assert rt.kernels(cfg, 'cpu', 32_768, 'high')['retention'] == 'state'
    # the model's work a window in the recurrent form: 100.0 TFLOP
    macs = 32_768 * 4 * (62_955_520 + 267_386_880 + 8_256 * 129 * (8 + 40))
    assert 2 * macs == body['flops_per_unit'] == 99_998_381_375_488


def test_both_trunks_take_their_blocks_from_one_place():
    for trunk in (rt, latent_moe):
        for name in ('rms_norm', 'param_shapes', 'param_count'):
            assert getattr(trunk, name) is getattr(token_trunk, name)
        assert trunk.TrunkConfig.from_args.__func__ is \
            token_trunk.BaseConfig.from_args.__func__
        source = Path(trunk.__file__).read_text()
        assert 'def hidden_states' not in source
        assert 'def forward' not in source


def test_the_row_blocked_feed_forward_is_the_feed_forward(tiny):
    _, _, params, _ = tiny
    x = jnp.asarray(np.random.default_rng(8).standard_normal(
        (96, 64)).astype(np.float32))
    whole = token_trunk.swiglu(x, params, 'model.layers.0.mlp')
    blocks = token_trunk.swiglu(x, params, 'model.layers.0.mlp', row_block=32)
    np.testing.assert_allclose(blocks, whole, rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match='whole number of row blocks'):
        token_trunk.swiglu(x, params, 'model.layers.0.mlp', row_block=64)


# -- the extractor: one family, two trunks --------------------------------------------

def test_model_type_picks_the_trunk_and_an_unknown_one_is_refused_by_name():
    assert extract_lm.load_trunk('brumby') is rt
    assert extract_lm.load_trunk('joyai_llm_flash') is latent_moe
    for trunk in (rt, latent_moe):
        assert extract_lm.TRUNKS[trunk.MODEL_TYPE] == trunk.__name__
        assert trunk.TrunkConfig.model_type == trunk.MODEL_TYPE
    yml = load_config('lm', overrides={'video_paths': ['x.mp4'],
                                       'device': 'cpu'})
    assert yml['model_type'] == 'joyai_llm_flash'
    with pytest.raises(ValueError, match=r"no trunk for model_type='qwen3'"
                       r'; known: afmoe, brumby, dots3_note, granitemoehybrid, '
                       r'joyai_llm_flash'):
        create_extractor(load_config('lm', overrides=dict(
            TINY_PROGRAM, **WINDOW, model_type='qwen3', device='cpu',
            video_paths=['x.mp4'], allow_random_weights=True)))
    with pytest.raises(ValueError, match=r'model_type=brumby needs config '
                       r"keys \['num_key_value_heads', 'head_dim'\]"):
        rt.TrunkConfig.from_args(yml)     # the shipped sizes are another model


def test_a_build_that_cannot_fit_is_refused_in_the_trunks_own_words():
    body = loader.load_json('configs', 'brumby-14b-l4')
    whole = rt.TrunkConfig.from_args(dict(body['overrides'],
                                          num_hidden_layers=40))
    need = rt.param_count(whole) * 4
    assert 55e9 < need < 57e9
    with pytest.raises(ValueError) as refused:
        extract_lm.check_params_fit(
            need, 16 * 10 ** 9, f'lm with {rt.describe(whole)}',
            rt.SHARE_ADVICE)
    said = str(refused.value)
    assert '40 layers of gated power retention' in said
    assert 'do not fit the device\'s 16.00 GB' in said
    assert 'num_hidden_layers' in said and 'expert' not in said
    assert 'n_experts_held' in latent_moe.SHARE_ADVICE


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
    d = tmp_path_factory.mktemp('brumby_clips')
    return [write_noise_clip(d / f'c{i}.mp4', n, seed=10 + i)
            for i, n in enumerate([9, 3, 22, 13])]     # c1 is too short


def test_extract_packed_equals_the_per_video_loop(clips, tmp_path, capsys,
                                                  chunks_of_16):
    packed = _extractor(tmp_path / 'a', pack_across_videos=True,
                        manifest_out=str(tmp_path / 'manifest.json'))
    assert packed.trunk is rt and packed.cfg.model_type == 'brumby'
    assert 'retention=state' in capsys.readouterr().err
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
    # the saved rows are the trunk's, on the tokeniser's ids (which are the
    # reference's: the joyai reference's rule at this grid)
    want, _ = token_trunk.forward(packed.params, REF.load_units(
        clips[2], range(5), tiny_reference_cfg()), packed.cfg)
    np.testing.assert_allclose(
        np.load(Path(packed.output_path) / 'c2_lm.npy'), want, atol=1e-5)
    # the span, the counter and the note the benchmark reads
    doc = json.loads((tmp_path / 'manifest.json').read_text())
    stages = doc['stages']
    assert stages['tokenise']['count'] == 10
    steps = stages['model']['count']
    assert stages['model']['occ_valid'] == 10
    scan = stages['retention_scan']
    assert scan['occ_valid'] == scan['occ_capacity'] == steps * 2 * 64 * 3
    through = stages['retention_kernel']
    assert through['occ_valid'] == 0
    assert through['occ_capacity'] == scan['occ_valid']
    assert 'moe_route' not in stages
    assert doc['kernels'] == {'retention': 'state', 'retention_chunk': 16}


def test_the_chunk_is_no_option_and_a_ragged_window_counts_whole(
        clips, tmp_path, monkeypatch):
    # no key of the yml or the command line reaches the chunk
    assert 'retention_chunk' not in load_config('lm', overrides={
        'video_paths': ['x.mp4'], 'device': 'cpu'})
    assert not hasattr(rt.TrunkConfig.from_args(TINY_PROGRAM),
                       'retention_chunk')
    assert rt.RETENTION_CHUNK == 512
    whole = _extractor(tmp_path / 'whole', pack_across_videos=True)
    assert whole.kernel_notes == {'retention': 'state', 'retention_chunk': 64}
    whole.extract_packed([clips[0]], decode_ahead=2)
    monkeypatch.setattr(rt, 'RETENTION_CHUNK', 48)
    ex = _extractor(tmp_path / 'ragged', pack_across_videos=True,
                    manifest_out=str(tmp_path / 'manifest.json'))
    assert ex.kernel_notes == {'retention': 'state', 'retention_chunk': 48}
    ex.extract_packed([clips[0]], decode_ahead=2)
    ex.finish_obs()
    scan = json.loads((tmp_path / 'manifest.json').read_text())[
        'stages']['retention_scan']
    assert scan['occ_valid'] == scan['occ_capacity'] == 2 * 64 * 3
    np.testing.assert_allclose(
        np.load(Path(ex.output_path) / 'c0_lm.npy'),
        np.load(Path(whole.output_path) / 'c0_lm.npy'), rtol=0, atol=1e-5)


def test_the_step_carries_the_scopes_a_trace_is_read_by():
    cfg = rt.TrunkConfig.from_args(TINY_PROGRAM)
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in rt.param_shapes(cfg).items()}
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = jax.jit(partial(extract_lm.ExtractLM._forward, cfg=cfg,
                           platform='tpu')).trace(params, ids).lower(
        lowering_platforms=('tpu',)).as_text(debug_info=True)
    assert 'retention' in text and 'dense_mlp' in text
    assert 'tpu_custom_call' not in text         # XLA only: no kernel yet
