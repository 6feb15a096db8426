"""The ``dots3_note`` trunk (``models/latent_moe.py``'s second dialect) at a
tiny size on the CPU: latent attention under the lightning indexer's
selection of keys and under a window with widths of its own, gated a head,
its latents rescaled, over sparse experts with a shared one — against the
benchmark's plain reference (``benchmark/references/
dots3-note-prev-ep32-l5.py``, which imports nothing of the program) on
seeded weights, on the XLA tiles and through the kernel's keep and windowed
lanes interpreted; each mechanism shown to matter; the share; the
extractor end to end. Tiny: hidden 64, 4 full / 2 sliding heads, 4 index
heads of 16, ``index_topk`` 8, a window of 5, 8 experts with 2 a token,
windows of 32 ids."""
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
from video_features_tpu.registry import create_extractor  # noqa: E402

SEED = 2 ** 31 + 4001
REF = loader.load_module('references', 'dots3-note-prev-ep32-l5')
S_, F_ = 'sliding_attention', 'full_attention'
KINDS = [F_, F_, S_, S_, S_]

# one tiny trunk under the program's names; the reference's take the same
# numbers (tiny_reference_cfg)
WIDTHS = dict(
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    swa_num_attention_heads=2, swa_q_lora_rank=48, swa_kv_lora_rank=40,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16)
# the kernel takes column groups of 64
ALIGNED = dict(WIDTHS, qk_nope_head_dim=64, qk_rope_head_dim=64,
               v_head_dim=64, swa_qk_nope_head_dim=128,
               swa_qk_rope_head_dim=64, swa_v_head_dim=64)
TINY_PROGRAM = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=5, layer_types=KINDS,
    first_k_dense_replace=1, intermediate_size=160, moe_intermediate_size=32,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    routed_scaling_factor=1.0, norm_topk_prob=True, rope_theta=8e7,
    rms_norm_eps=1e-5, n_experts_held=8, first_expert=0,
    sliding_window_size=5, swa_rope_theta=5e4, index_n_heads=4,
    index_head_dim=16, index_topk=8, attention_gate_type='headwise',
    swa_attention_gate_type='headwise', apply_mla_qkv_lora_rescale=True,
    **WIDTHS)
WINDOW = dict(stack_size=2, step_size=2, patch_grid=4)         # 32 ids


def program_cfg(**changes):
    return lm.TrunkConfig(**dict(TINY_PROGRAM, **changes),
                          model_type='dots3_note')


def tiny_reference_cfg(**changes):
    c = dict(REF.CFG, vocab_size=512, hidden_size=64, layers=5,
             layer_types=tuple(KINDS), intermediate_size=160,
             moe_intermediate_size=32, router_experts=8, n_routed_experts=8,
             num_experts_per_tok=2, sliding_window_size=5, index_n_heads=4,
             index_head_dim=16, index_topk=8, frames=2, patch_grid=4,
             query_block=8, index_block=8, **WIDTHS)
    c.update(changes)
    return c


def draw(rcfg, seed=SEED):
    return weights.make(REF.param_specs(rcfg)['checkpoint_path'], seed,
                        'checkpoint_path')


@pytest.fixture(scope='module')
def tiny():
    rcfg = tiny_reference_cfg()
    ids = np.random.default_rng(0).integers(0, 512, (2, 32)).astype(np.int32)
    return program_cfg(), rcfg, draw(rcfg), ids


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def run(params, ids, cfg, precision='highest', attn_block=8):
    with jax.default_matmul_precision(precision):
        return token_trunk.forward({k: jnp.asarray(v) for k, v in params.items()},
                          jnp.asarray(ids), cfg, attn_block, 8)


def reference(params, ids, rcfg):
    return REF.forward(Ops(), {'checkpoint_path': params}, jnp.asarray(ids),
                       rcfg)


def forced_kernel(monkeypatch):
    """The kernel's lanes on the CPU: the decision says 'kernel', the
    kernel runs interpreted."""
    from video_features_tpu.ops import pallas_attention
    monkeypatch.setattr(lm, 'resolve_causal', lambda *args: 'kernel')
    monkeypatch.setattr(
        pallas_attention, 'causal_attention',
        partial(pallas_attention.causal_attention, interpret=True))


# -- against the reference ------------------------------------------------------

def test_trunk_matches_the_reference_on_the_xla_tiles(tiny):
    """float32 both sides: the selection is the same and the rest rounding
    (5.6e-7 when written)."""
    cfg, rcfg, params, ids = tiny
    got, (counts, index) = run(params, ids, cfg)
    assert got.shape == (2, 64) and counts.shape == (4, 8)
    # each full layer scores its one block of 32 rows in both windows, by
    # XLA; of those rows the tie rule settled 1 and 2: at 4 index heads a
    # key whose every product is negative scores exactly 0, and a row whose
    # 8th score is such a 0 has more of them than it keeps
    assert np.asarray(index).tolist() == [[2, 0, 1, 64], [2, 0, 2, 64]]
    assert rel_l2(got, reference(params, ids, rcfg)) < 1e-5


def test_trunk_matches_the_reference_through_the_kernels_lanes(monkeypatch):
    """The full layers through the keep lane, the sliding ones through the
    windowed lane with column groups, interpreted, at three passes (what
    precision=mixed runs): the passes' rounding against float32 (the
    kernel alone reads 1.7e-5 on one layer, tests/test_sparse_attention.py),
    carried through five layers."""
    rcfg = tiny_reference_cfg(**ALIGNED)
    params = draw(rcfg)
    ids = np.random.default_rng(1).integers(0, 512, (2, 32)).astype(np.int32)
    want = reference(params, ids, rcfg)
    cfg = program_cfg(**ALIGNED)
    xla, _ = run(params, ids, cfg, 'high', 32)
    forced_kernel(monkeypatch)
    got, _ = run(params, ids, cfg, 'high', 32)
    assert rel_l2(xla, want) < 1e-4
    assert 0 < rel_l2(got, want) < 1e-4


def test_the_reference_and_the_program_hold_the_same_parameters(tiny):
    cfg, _, params, _ = tiny
    assert {k: tuple(v.shape) for k, v in params.items()} == \
        lm.param_shapes(cfg)
    assert lm.param_count(cfg) == sum(v.size for v in params.values())


# -- each mechanism matters ------------------------------------------------------

@pytest.mark.parametrize('changes', [
    dict(attention_gate_type=None),
    dict(swa_attention_gate_type=None),
    dict(apply_mla_qkv_lora_rescale=False),
    dict(index_topk=4),
    dict(sliding_window_size=9),
], ids=['full_gate', 'sliding_gate', 'rescale', 'topk', 'window'])
def test_each_mechanism_moves_the_output(tiny, changes):
    cfg, _, params, ids = tiny
    base, _ = run(params, ids, cfg)
    moved, _ = run(params, ids, program_cfg(**changes))
    assert rel_l2(moved, base) > 1e-3


def test_a_topk_of_the_whole_window_is_plain_causal_latent_attention(tiny):
    """index_topk ≥ S keeps every key before a query: the full layer is the
    gated, rescaled latent attention with no indexer at all."""
    _, _, params, ids = tiny
    whole, _ = run(params, ids, program_cfg(index_topk=32))
    plain, _ = run(params, ids, program_cfg(index_topk=0))
    assert rel_l2(whole, plain) < 1e-6
    selected, _ = run(params, ids, program_cfg(index_topk=31))
    assert rel_l2(selected, plain) > 1e-6


def test_a_sliding_layer_sees_its_window_and_no_further(tiny):
    """Position t of a sliding layer's output moves with the input at
    t − 4 … t and not at t − 5: a window of 5, the query's own key among
    them."""
    cfg, _, params, _ = tiny
    a = 'model.layers.2.self_attn'
    p = {k: jnp.asarray(v) for k, v in params.items() if k.startswith(a)}
    x = jnp.asarray(np.random.default_rng(2).standard_normal((32, 64)),
                    jnp.float32)
    with jax.default_matmul_precision('highest'):
        base = lm.mla_block(p, a, x, cfg, 8, kind=S_)
        for lag, moves in ((4, True), (5, False), (9, False)):
            got = lm.mla_block(p, a, x.at[20 - lag].add(1.0), cfg, 8,
                               kind=S_)
            assert bool(jnp.abs(got[20] - base[20]).max() > 1e-6) is moves


# -- the share ----------------------------------------------------------------------

def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        tiny):
    """The guide's share test: an expert layer of 8 experts held whole,
    against four chips' shares of 2 (experts 0-1, 2-3, 4-5, 6-7): the routed
    parts add up, and the shared expert, which each chip computes alike,
    counts once."""
    cfg, _, params, _ = tiny
    m = 'model.layers.2.mlp'
    p = {k: jnp.asarray(v) for k, v in params.items() if k.startswith(m)}
    x = jnp.asarray(np.random.default_rng(4).standard_normal((64, 64)),
                    jnp.float32)
    with jax.default_matmul_precision('highest'):
        whole, counts = token_trunk.expert_block(p, m, x, cfg, 8)
        parts, shares = [], []
        for first in (0, 2, 4, 6):
            share = program_cfg(n_experts_held=2, first_expert=first)
            held = {k: (v[first:first + 2] if '.experts.' in k else v)
                    for k, v in p.items()}
            y, c = token_trunk.expert_block(held, m, x, share, 8)
            parts.append(y)
            shares.append(c)
        shared = token_trunk.swiglu(x, p, f'{m}.shared_experts')
    summed = sum(parts) - 3 * shared
    assert rel_l2(summed, whole) < 1e-5
    assert np.concatenate([np.asarray(c) for c in shares]).tolist() == \
        np.asarray(counts).tolist()
    assert int(np.asarray(counts).sum()) == 64 * 2


# -- the config -------------------------------------------------------------------

def test_model_type_picks_the_second_dialect_and_the_yml_holds_its_keys():
    args = load_config('lm', overrides=dict(
        TINY_PROGRAM, **WINDOW, model_type='dots3_note',
        video_paths=['x.mp4'], device='cpu'))
    cfg = lm.TrunkConfig.from_args(args)
    assert cfg.model_type == 'dots3_note' and cfg.layer_types == tuple(KINDS)
    assert cfg.latent(F_) == lm.Latent(4, 48, 32, 16, 8, 16, 8e7, None, True,
                                     True, 4, 16, 8)
    assert cfg.latent(S_) == lm.Latent(2, 48, 40, 24, 8, 16, 5e4, 5, True,
                                     True)
    from video_features_tpu.extract.lm import load_trunk
    assert load_trunk('dots3_note') is lm
    # joyai's keys alone still build joyai's trunk, every layer full
    joyai = lm.TrunkConfig.from_args(load_config('lm', overrides=dict(
        video_paths=['x.mp4'], device='cpu')))
    assert joyai.model_type == 'joyai_llm_flash'
    assert set(joyai.layer_types) == {F_} and joyai.latent().index_topk == 0


@pytest.mark.parametrize('changes,match', [
    (dict(layer_types=[F_, F_, 'conv', S_, S_]), 'known: full_attention'),
    (dict(layer_types=[F_, F_, S_]), 'give one entry'),
    (dict(attention_gate_type='elementwise'), 'headwise'),
    (dict(sliding_window_size=0), 'sliding_window_size'),
])
def test_what_the_trunk_cannot_run_is_refused_by_name(changes, match):
    with pytest.raises(ValueError, match=match):
        program_cfg(**changes)


def test_a_key_left_out_is_refused_by_name():
    args = load_config('lm', overrides=dict(
        {k: v for k, v in TINY_PROGRAM.items() if k != 'index_topk'},
        **WINDOW, model_type='dots3_note', video_paths=['x.mp4'],
        device='cpu'))
    with pytest.raises(ValueError, match='index_topk'):
        lm.TrunkConfig.from_args(args)


# -- the step ---------------------------------------------------------------------

def test_the_step_carries_the_scopes_a_trace_is_read_by(tiny):
    from video_features_tpu.extract.lm import ExtractLM
    cfg, _, params, _ = tiny
    shapes = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in lm.param_shapes(cfg).items()}
    text = jax.jit(partial(ExtractLM._forward, cfg=cfg,
                           platform='cpu')).lower(
        shapes, jax.ShapeDtypeStruct((2, 32), jnp.int32)).as_text(
            debug_info=True)
    for scope in ('sparse_mla/mla_indexer', 'window_mla', 'moe',
                  'dense_mlp'):
        assert scope in text, scope
    assert '/mla/' not in text


@pytest.mark.parametrize('platform,precision,calls', [
    ('tpu', 'high', (2, 3)),        # precision=mixed: each kind its lane
    ('tpu', 'default', (2, 3)),     # the control lane too
    ('tpu', 'highest', (0, 0)),     # highest keeps the XLA tiles
    ('cpu', 'high', (0, 0)),
])
def test_the_step_lowered_for_a_tpu_holds_both_named_lanes(platform,
                                                           precision, calls):
    """At 128-lane-aligned shapes (256 ids a window, the kernel's widths):
    one sparse_attention call in each full layer's window loop, one
    window_attention call in each sliding layer's."""
    from video_features_tpu.extract.lm import ExtractLM
    cfg = program_cfg(**dict(ALIGNED, v_head_dim=128, swa_v_head_dim=128))
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in lm.param_shapes(cfg).items()}
    ids = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    with jax.default_matmul_precision(precision):
        text = jax.jit(partial(ExtractLM._forward, cfg=cfg,
                               platform=platform)).trace(
            params, ids).lower(lowering_platforms=('tpu',)).as_text()
    assert (text.count('kernel_name = "sparse_attention"'),
            text.count('kernel_name = "window_attention"')) == calls
    assert text.count('tpu_custom_call') == sum(calls)
    notes = lm.kernels(cfg, platform, 256, precision)
    want = 'kernel' if sum(calls) else 'xla'
    assert (notes['sparse_attention'], notes['window_attention']) == (
        want, want)


def test_extract_packed_saves_one_row_a_window(tmp_path, capsys):
    """The CLI's path: the tokeniser, the packed scheduler and the step;
    one (hidden,) float32 row a window, the step's paths said on stderr."""
    sys.path.insert(0, str(REPO))
    from tools.make_sample_video import write_noise_clip
    clips = [str(write_noise_clip(tmp_path / f'c{i}.mp4', n, seed=i))
             for i, n in enumerate((5, 9))]
    ex = create_extractor(load_config('lm', overrides=dict(
        TINY_PROGRAM, **WINDOW, model_type='dots3_note', device='cpu',
        allow_random_weights=True, precision='mixed', batch_size=2,
        on_extraction='save_numpy', pack_across_videos=True,
        video_paths=clips, output_path=str(tmp_path / 'out'),
        tmp_path=str(tmp_path / 'tmp'))))
    assert ex.kernel_notes['sparse_attention'] == 'xla'
    assert ex.kernel_notes['window_attention'] == 'xla'
    assert 'sparse_attention=xla' in capsys.readouterr().err
    ex.extract_packed(clips)
    for clip, rows in zip(clips, (2, 4)):
        out = np.load(Path(ex.output_path) / f'{Path(clip).stem}_lm.npy')
        assert out.shape == (rows, 64) and out.dtype == np.float32
        assert np.isfinite(out).all()
