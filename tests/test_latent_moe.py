"""The lm family (``feature_type=lm``): the latent-attention, sparse-expert
trunk, its ops and its extractor, at a tiny size on the CPU — the same code
as the published widths, small counts (hidden 64, 16 experts of which 4 are
held, 2 heads, windows of 64 ids). The plain reference it is held to is the
benchmark's (``benchmark/references/joyai-llm-flash-ep4.py``), which imports
nothing of the program."""
import math
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
from video_features_tpu.models import latent_moe as lm  # noqa: E402
from video_features_tpu.models import token_trunk  # noqa: E402
from video_features_tpu.ops import moe  # noqa: E402
from video_features_tpu.ops.attention import (  # noqa: E402
    blockwise_attention, rotary_interleaved,
)
from video_features_tpu.registry import create_extractor  # noqa: E402

SEED = 2 ** 31 + 27
REF = loader.load_module('references', 'joyai-llm-flash-ep4')

# one tiny trunk, under the program's names and under the reference's
TINY_PROGRAM = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, intermediate_size=160, moe_intermediate_size=32,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
    routed_scaling_factor=2.5, norm_topk_prob=True, num_attention_heads=2,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=32e6, rms_norm_eps=1e-6,
    n_experts_held=4, first_expert=0)
WINDOW = dict(stack_size=4, step_size=4, patch_grid=4)      # 64 ids


def tiny_reference_cfg(**changes):
    c = dict(REF.CFG, vocab_size=512, hidden_size=64, layers=3,
             intermediate_size=160, moe_intermediate_size=32,
             router_experts=16, n_routed_experts=4, first_expert=0,
             num_experts_per_tok=4, num_attention_heads=2, q_lora_rank=48,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, frames=4, patch_grid=4, query_block=16)
    c.update(changes)
    return c


@pytest.fixture(scope='module')
def tiny():
    cfg = lm.TrunkConfig(**TINY_PROGRAM)
    rcfg = tiny_reference_cfg()
    params = weights.make(REF.param_specs(rcfg)['checkpoint_path'], SEED,
                          'checkpoint_path')
    ids = np.random.default_rng(0).integers(0, 512, (3, 64)).astype(np.int32)
    return cfg, rcfg, params, ids


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# -- ops ----------------------------------------------------------------------

def test_rotary_is_a_complex_rotation_of_interleaved_pairs():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((7, 3, 8)).astype(np.float32)    # (S, H, d)
    theta = 32e6
    got = np.asarray(rotary_interleaved(jnp.asarray(x), jnp.arange(7), theta))
    z = x[..., 0::2].astype(np.complex128) + 1j * x[..., 1::2]
    freq = theta ** (-np.arange(0, 8, 2) / 8)
    turned = z * np.exp(1j * np.arange(7)[:, None, None] * freq)
    want = np.stack([turned.real, turned.imag], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # position 0 is the identity, and a rotation keeps every pair's length
    np.testing.assert_allclose(got[0], x[0], atol=1e-7)
    np.testing.assert_allclose(np.hypot(got[..., 0::2], got[..., 1::2]),
                               np.abs(z), rtol=1e-5)


@pytest.mark.parametrize('block', [4, 8, 16])
def test_causal_blockwise_attention_with_a_narrower_value_head(block):
    rng = np.random.default_rng(2)
    q = rng.standard_normal((2, 16, 3, 12)).astype(np.float32)
    k = rng.standard_normal((2, 16, 3, 12)).astype(np.float32)
    v = rng.standard_normal((2, 16, 3, 5)).astype(np.float32)
    with jax.default_matmul_precision('highest'):
        got = np.asarray(blockwise_attention(q, k, v, block_size=block,
                                             causal=True))
    s = np.einsum('bqhd,bkhd->bhqk', q, k) / math.sqrt(12)
    s = np.where(np.tril(np.ones((16, 16), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum('bhqk,bkhd->bqhd', p / p.sum(-1, keepdims=True), v)
    assert got.shape == (2, 16, 3, 5)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_causal_attention_refuses_what_it_cannot_tile():
    q = jnp.zeros((1, 10, 1, 4))
    with pytest.raises(ValueError, match='multiple of block_size'):
        blockwise_attention(q, q, q, block_size=4, causal=True)
    with pytest.raises(ValueError, match='self-attention'):
        blockwise_attention(q, jnp.zeros((1, 12, 1, 4)), q, causal=True)


def test_route_takes_the_top_k_of_score_plus_bias_and_weighs_by_score():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((9, 16)).astype(np.float32)
    w = rng.standard_normal((16, 8)).astype(np.float32)
    bias = np.zeros(8, np.float32)
    bias[5] = 10.0                       # expert 5 is always chosen ...
    experts, weights_ = moe.route(x, w, bias, top_k=3, scaling=2.5)
    experts, weights_ = np.asarray(experts), np.asarray(weights_)
    score = 1 / (1 + np.exp(-(x.astype(np.float64) @ w)))
    assert (experts == 5).any(axis=1).all()
    # ... but its weight is its score, not score + bias
    np.testing.assert_allclose(weights_.sum(axis=1), 2.5, rtol=1e-5)
    chosen = np.take_along_axis(score, experts, axis=1)
    np.testing.assert_allclose(
        weights_, 2.5 * chosen / chosen.sum(axis=1, keepdims=True), rtol=1e-5)
    others = np.sort(np.where(np.arange(8) == 5, -1, score), axis=1)[:, -2:]
    got_others = np.sort(np.where(experts == 5, -1, chosen), axis=1)[:, -2:]
    np.testing.assert_allclose(got_others, others, rtol=1e-6)


def test_dispatch_orders_held_assignments_by_expert():
    experts = jnp.asarray([[0, 5], [5, 9], [4, 6], [6, 5]], jnp.int32)
    order, rank, counts = (np.asarray(a)
                           for a in moe.dispatch(experts, first=4, n_held=3))
    assert counts.tolist() == [1, 3, 2]                  # experts 4, 5, 6
    flat = np.asarray(experts).reshape(-1)
    assert flat[order[:6]].tolist() == [4, 5, 5, 5, 6, 6]
    assert sorted(flat[order[6:]].tolist()) == [0, 9]    # absent ones last
    assert (order[rank] == np.arange(8)).all()


@pytest.mark.parametrize('block', [2, 8, 64])
def test_moe_share_is_the_dense_sum_over_the_held_experts(block):
    rng = np.random.default_rng(4)
    t, d, f, n, k = 40, 16, 8, 12, 3
    x = rng.standard_normal((t, d)).astype(np.float32)
    w_gate, w_up = (rng.standard_normal((n, d, f)).astype(np.float32)
                    for _ in range(2))
    w_down = rng.standard_normal((n, f, d)).astype(np.float32)
    experts = np.stack([rng.permutation(n)[:k] for _ in range(t)])
    experts[:7] = [1, 2, 3]             # a crowded expert, and empty ones
    weights_ = rng.random((t, k)).astype(np.float32)

    def dense(first, held):
        y = np.zeros((t, d))
        for e in range(first, first + held):
            h = x @ w_gate[e]
            out = (h / (1 + np.exp(-h)) * (x @ w_up[e])) @ w_down[e]
            y += np.where(experts == e, weights_, 0).sum(1)[:, None] * out
        return y

    with jax.default_matmul_precision('highest'):
        for first, held in [(0, 12), (0, 4), (4, 4), (8, 4), (2, 1)]:
            sl = slice(first, first + held)
            y, counts = moe.moe_share(
                jnp.asarray(x), jnp.asarray(experts, jnp.int32),
                jnp.asarray(weights_), w_gate[sl], w_up[sl], w_down[sl],
                first=first, block=block)
            np.testing.assert_allclose(np.asarray(y), dense(first, held),
                                       atol=2e-4)
            assert np.asarray(counts).tolist() == [
                int((experts == e).sum()) for e in range(first, first + held)]


# -- blocks and trunk against the plain reference --------------------------------

def test_mla_block_matches_the_reference(tiny):
    cfg, rcfg, params, _ = tiny
    x = np.random.default_rng(5).standard_normal((2, 64, 64)).astype(
        np.float32)
    a = 'model.layers.1.self_attn'
    want = REF._attention(Ops(), params, a, jnp.asarray(x), rcfg)
    with jax.default_matmul_precision('highest'):
        got = jnp.stack([lm.mla_block(params, a, w, cfg, 16) for w in x])
    assert rel_l2(got, want) < 1e-5


def test_a_later_token_changes_no_earlier_position(tiny):
    cfg, _, params, ids = tiny
    with jax.default_matmul_precision('highest'):
        base, _ = token_trunk.hidden_states(params, ids[:1], cfg, 16, 8)
        changed = ids[:1].copy()
        changed[0, 40] = (changed[0, 40] + 1) % 512
        other, _ = token_trunk.hidden_states(params, changed, cfg, 16, 8)
    base, other = np.asarray(base), np.asarray(other)
    np.testing.assert_array_equal(base[0, :40], other[0, :40])
    assert np.abs(base[0, 40:] - other[0, 40:]).max() > 1e-3


def test_expert_block_matches_the_reference(tiny):
    cfg, rcfg, params, _ = tiny
    x = np.random.default_rng(6).standard_normal((2, 64, 64)).astype(
        np.float32)
    m = 'model.layers.2.mlp'
    want = REF._experts(Ops(), params, m, jnp.asarray(x), rcfg)
    with jax.default_matmul_precision('highest'):
        got, counts = token_trunk.expert_block(params, m, jnp.asarray(x).reshape(
            128, 64), cfg, 8)
    assert rel_l2(got, np.asarray(want).reshape(128, 64)) < 1e-5
    assert 0 < int(np.asarray(counts).sum()) < 128 * 4


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """Four chips hold experts 0-3, 4-7, 8-11 and 12-15 of one layer. The
    routed parts their shares give, with what every chip computes alike
    (the shared expert) counted once, are the uncut layer of the reference."""
    whole = tiny_reference_cfg(n_routed_experts=16)
    params = weights.make(REF.param_specs(whole)['checkpoint_path'], SEED,
                          'checkpoint_path')
    x = np.random.default_rng(7).standard_normal((1, 64, 64)).astype(
        np.float32)
    m = 'model.layers.1.mlp'
    want = np.asarray(REF._experts(Ops(), params, m, jnp.asarray(x), whole))
    shared = np.asarray(token_trunk.swiglu(jnp.asarray(x[0]), params,
                                  f'{m}.shared_experts'))
    total = np.zeros((64, 64))
    held_rows = 0
    with jax.default_matmul_precision('highest'):
        for first in (0, 4, 8, 12):
            cfg = lm.TrunkConfig(**dict(TINY_PROGRAM, first_expert=first))
            share = dict(params)
            for name in ('gate_proj', 'up_proj', 'down_proj'):
                key = f'{m}.experts.{name}.weight'
                share[key] = params[key][first:first + 4]
            y, counts = token_trunk.expert_block(share, m, jnp.asarray(x[0]), cfg, 8)
            total += np.asarray(y) - shared
            held_rows += int(np.asarray(counts).sum())
    assert held_rows == 64 * 4          # every assignment lands on one share
    assert rel_l2(total + shared, want[0]) < 1e-5


def test_trunk_matches_the_reference(tiny):
    cfg, rcfg, params, ids = tiny
    want = REF.forward(Ops(), {'checkpoint_path': params}, ids, rcfg)
    with jax.default_matmul_precision('highest'):
        got, counts = jax.jit(
            lambda p, i: token_trunk.forward(p, i, cfg, 16, 8))(params, ids)
    assert got.shape == (3, 64) and got.dtype == jnp.float32
    assert rel_l2(got, want) < 1e-5
    assert counts.shape == (2, 4)
    # the reference in one bf16 pass reads far above the program
    control = REF.forward(Ops('bfloat16'), {'checkpoint_path': params}, ids,
                          rcfg)
    assert rel_l2(control, want) > 1e-3


def test_the_reference_and_the_program_hold_the_same_parameters(tiny):
    cfg, rcfg, params, _ = tiny
    assert {k: v.shape for k, v in params.items()} == lm.param_shapes(cfg)
    assert lm.param_count(cfg) == sum(v.size for v in params.values())
    ours = lm.init_params(cfg)
    assert {k: v.shape for k, v in ours.items()} == lm.param_shapes(cfg)


def test_published_sizes_count_as_the_issue_counts_them():
    args = load_config('lm', overrides={'video_paths': ['x.mp4'],
                                        'device': 'cpu'})
    cut = lm.TrunkConfig.from_args(dict(args, num_hidden_layers=5,
                                        n_experts_held=64))
    shapes = lm.param_shapes(cut)
    matrices = sum(math.prod(s) for s in shapes.values() if len(s) > 1)
    assert matrices == 1_669_464_064            # ISSUE 27's arithmetic
    assert lm.param_count(cut) == matrices + 33_792   # + gains and biases


# -- the extractor ------------------------------------------------------------------

def test_the_shipped_yml_is_the_whole_model_and_a_build_that_cannot_fit_is_refused():
    from video_features_tpu.extract.lm import check_params_fit
    args = load_config('lm', overrides={'video_paths': ['x.mp4'],
                                        'device': 'cpu'})
    assert args['n_experts_held'] is None and args['num_hidden_layers'] == 40
    cfg = lm.TrunkConfig.from_args(args)
    assert cfg.n_experts_held == 256
    need = lm.param_count(cfg) * 4
    assert 190e9 < need < 195e9
    with pytest.raises(ValueError, match=r'19\d\.\d\d GB of float32 '
                       r'parameters do not fit the device\'s 16\.00 GB'):
        check_params_fit(need, 16 * 10 ** 9, 'lm')
    check_params_fit(need, None, 'lm')          # CPU: nothing known
    check_params_fit(6_700_000_000, 16 * 10 ** 9, 'lm')


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
    d = tmp_path_factory.mktemp('lm_clips')
    return [write_noise_clip(d / f'c{i}.mp4', n, seed=10 + i)
            for i, n in enumerate([9, 3, 22, 13])]     # c1 is too short


def test_the_programs_tokeniser_is_the_references_byte_for_byte(clips):
    from video_features_tpu.extract.lm import tokenise_frames
    from video_features_tpu.io.video import VideoLoader
    rcfg = tiny_reference_cfg()
    loader_ = VideoLoader(clips[2], batch_size=64)
    frames = np.concatenate([np.stack(b[0]) for b in loader_])
    loader_.close()
    assert frames.shape[0] == 22
    ours = np.stack([tokenise_frames(frames[r * 4:r * 4 + 4], 4, 512)
                     for r in range(5)])
    theirs = REF.load_units(clips[2], range(5), rcfg)
    assert ours.dtype == theirs.dtype == np.int32
    np.testing.assert_array_equal(ours, theirs)
    assert REF.rows_of(22, rcfg) == 5 and len(set(ours.ravel())) > 50
    # at the published window: 340x256 frames give 256 ids each, from the
    # centred 336 columns
    big = np.random.default_rng(8).integers(0, 256, (2, 256, 340, 3),
                                            dtype=np.uint8)
    ids = tokenise_frames(big, 16, 129280)
    assert ids.shape == (512,) and 0 <= ids.min() and ids.max() < 129280
    patch = big[0, :16, 2:23].astype(np.uint64).sum()
    assert ids[0] == (int(patch) * 2654435761 % 2 ** 32) % 129280
    np.testing.assert_array_equal(ids, REF.tokenise(big))


def test_extract_packed_equals_the_per_video_loop(clips, tmp_path):
    packed = _extractor(tmp_path / 'a', pack_across_videos=True,
                        manifest_out=str(tmp_path / 'manifest.json'))
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
    # the saved rows are the trunk's, on the tokeniser's ids
    want, _ = token_trunk.forward(packed.params, REF.load_units(
        clips[2], range(5), tiny_reference_cfg()), packed.cfg, 16, 8)
    np.testing.assert_allclose(
        np.load(Path(packed.output_path) / 'c2_lm.npy'), want, atol=1e-5)
    # the spans and counters the benchmark reads are on the stage table
    import json
    stages = json.loads((tmp_path / 'manifest.json').read_text())['stages']
    assert stages['tokenise']['count'] == 10
    steps = stages['model']['count']
    assert stages['model']['occ_valid'] == 10
    held, routed = stages['moe_held'], stages['moe_route']
    assert held['occ_capacity'] == steps * 2 * 64 * 4 * 2   # tokens x k x layers
    assert 0 < held['occ_valid'] < held['occ_capacity']
    assert routed['occ_valid'] == held['occ_valid']
    assert routed['occ_valid'] <= routed['occ_capacity']


def test_the_checkpoint_goes_to_the_device_array_by_array(tiny, tmp_path):
    from video_features_tpu.extract.weights import load_npz_to_device
    cfg, _, params, _ = tiny
    shapes = lm.param_shapes(cfg)
    device = jax.devices('cpu')[0]
    stacked = load_npz_to_device(weights.save(params, str(tmp_path / 's.npz')),
                                 shapes, device)
    assert set(stacked) == set(shapes)
    # the published layout keeps a layer's experts one by one
    one_by_one = {}
    for name, arr in params.items():
        head, sep, tail = name.partition('.experts.')
        if sep:
            one_by_one.update({f'{head}.experts.{j}.{tail}': arr[j]
                               for j in range(arr.shape[0])})
        else:
            one_by_one[name] = arr
    unstacked = load_npz_to_device(
        weights.save(one_by_one, str(tmp_path / 'u.npz')), shapes, device)
    for name in shapes:
        np.testing.assert_array_equal(stacked[name], params[name])
        np.testing.assert_array_equal(unstacked[name], params[name])
    del one_by_one['model.norm.weight']
    with pytest.raises(KeyError, match='model.norm.weight'):
        load_npz_to_device(weights.save(one_by_one, str(tmp_path / 'm.npz')),
                           shapes, device)
    with pytest.raises(ValueError, match='has shape'):
        load_npz_to_device(
            weights.save(dict(params, **{'model.norm.weight': np.ones(3)}),
                         str(tmp_path / 'w.npz')), shapes, device)


def test_lm_is_packed_but_in_no_lane_it_has_not_earned():
    from video_features_tpu import registry
    assert 'lm' in registry.EXTRACTORS and 'lm' in registry.PACKED_FEATURES
    for lane in (registry.BF16_FEATURES, registry.INT8_FEATURES,
                 registry.LIVE_FEATURES, registry.DATA_PARALLEL_FEATURES):
        assert 'lm' not in lane
    with pytest.raises(ValueError, match='compute_dtype'):
        load_config('lm', overrides={'video_paths': ['x.mp4'],
                                     'device': 'cpu',
                                     'compute_dtype': 'bfloat16'})


# -- the causal kernel behind the step (ops/pallas_attention.py) ---------------

# a trunk whose window the kernel applies to: one 128-id tile, a 128-wide
# value head, 64 + 64 query/key dims
ALIGNED = dict(TINY_PROGRAM, num_attention_heads=2, qk_nope_head_dim=64,
               qk_rope_head_dim=64, v_head_dim=128)


@pytest.mark.parametrize('platform,precision,calls', [
    ('tpu', 'high', 3),        # precision=mixed: one call a layer's lax.map
    ('tpu', 'default', 3),     # the control lane takes the kernel too
    ('tpu', 'highest', 0),     # the yml's default keeps the XLA path
    ('cpu', 'high', 0),        # what tier-1 and the programs lock lower
])
def test_the_lm_step_lowered_for_a_tpu_holds_the_named_kernel(
        platform, precision, calls):
    """The step as the extractor jits it (``ExtractLM._forward`` with the
    device's platform bound), lowered for the TPU from here: a Mosaic call
    named causal_attention in every layer's window loop where the kernel
    applies, none where it does not."""
    from video_features_tpu.extract.lm import ExtractLM
    cfg = lm.TrunkConfig(**ALIGNED)
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in lm.param_shapes(cfg).items()}
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    with jax.default_matmul_precision(precision):
        text = jax.jit(partial(ExtractLM._forward, cfg=cfg,
                               platform=platform)).trace(
            params, ids).lower(lowering_platforms=('tpu',)).as_text()
    assert text.count('kernel_name = "causal_attention"') == calls
    assert text.count('tpu_custom_call') == calls


def test_the_kernel_path_of_mla_block_is_the_xla_path_to_rounding(
        monkeypatch):
    """mla_block with the kernel forced in (interpreted: the decision says
    'kernel' only on a TPU) against the XLA path, both at three passes."""
    from video_features_tpu.ops import pallas_attention
    cfg = lm.TrunkConfig(**ALIGNED)
    params = {n: jnp.asarray(w) for n, w in lm.init_params(cfg, 3).items()
              if '.layers.1.self_attn.' in n}
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (128, 64)).astype(np.float32))
    a = 'model.layers.1.self_attn'
    with jax.default_matmul_precision('high'):
        want = lm.mla_block(params, a, x, cfg, 128, 'cpu')
        monkeypatch.setattr(lm, 'resolve_causal', lambda *args: 'kernel')
        monkeypatch.setattr(
            pallas_attention, 'causal_attention',
            partial(pallas_attention.causal_attention, interpret=True))
        got = lm.mla_block(params, a, x, cfg, 128, 'tpu')
    assert 0 < rel_l2(got, want) < 2e-5


def test_the_extractor_says_which_causal_path_it_compiled(tmp_path, capsys):
    """On the CPU: 'xla', on stderr and in the manifest's kernels section."""
    import json
    manifest = tmp_path / 'manifest.json'
    ex = create_extractor(load_config('lm', overrides=dict(
        TINY_PROGRAM, **WINDOW, device='cpu', allow_random_weights=True,
        precision='mixed', video_paths=['x.mp4'],
        output_path=str(tmp_path / 'out'), tmp_path=str(tmp_path / 'tmp'),
        manifest_out=str(manifest))))
    assert ex.kernel_notes == {'causal_attention': 'xla'}
    assert 'causal_attention=xla' in capsys.readouterr().err
    assert ex.manifest.document()['kernels'] == {'causal_attention': 'xla'}
