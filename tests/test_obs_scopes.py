"""Device scopes (``obs/scopes.py``): the pinned vocabulary, the map from
HLO instruction to ``jax.named_scope`` path on really compiled small
programs, and the seam that takes it — once per executable, off the hot
path, and not at all with tracing off."""
import json
import re
import sys
from functools import cache, partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tools.make_sample_video import write_noise_clip
from video_features_tpu.config import load_config
from video_features_tpu.obs import scopes
from video_features_tpu.registry import create_extractor

REPO = Path(__file__).resolve().parent.parent
PACKAGE = REPO / 'video_features_tpu'


# -- the vocabulary ----------------------------------------------------------

def test_every_named_scope_literal_is_a_member_and_none_is_unused():
    literal = re.compile(r'named_scope\(\s*\'([^\']+)\'\s*\)')
    found, calls = set(), 0
    for sub in ('models', 'extract'):
        for path in (PACKAGE / sub).rglob('*.py'):
            text = path.read_text()
            found.update(literal.findall(text))
            calls += len(re.findall(r'named_scope\(', text))
            # a scope name is a literal: a computed one escapes the pin
            assert len(literal.findall(text)) == \
                len(re.findall(r'named_scope\(', text)), path
    assert calls >= 14
    assert found == set(scopes.SCOPES)
    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES)


def test_path_of_keeps_members_in_order_and_nothing_else():
    assert scopes.path_of(
        'jit(step)/raft_update/while/body/closed_call/raft_gru/dot_general'
    ) == 'raft_update/raft_gru'
    assert scopes.path_of('jit(step)/reduce_sum') == ''
    # jax repeats a scope that spans a nested jaxpr: one member, once
    assert scopes.path_of('jit(lm_step)/moe/while/body/moe/moe/dot') == 'moe'
    assert scopes.path_of('jit(s)/moe/dense_mlp/moe/mul') == \
        'moe/dense_mlp/moe'
    # a member is a whole component, never a substring
    assert scopes.path_of('jit(step)/moe_route/attention_mask/add') == ''


# -- scope_map on really compiled programs ------------------------------------

@cache
def refine_map():
    """RAFT's ``_refine`` at a tiny shape, compiled here."""
    from video_features_tpu.models import raft
    from video_features_tpu.transplant.torch2jax import transplant
    params = {'update_block': transplant(raft.init_state_dict())[
        'update_block']}
    rng = np.random.RandomState(0)
    f1, f2, cn = (jnp.asarray(rng.randn(2, 8, 8, 256).astype(np.float32))
                  for _ in range(3))
    compiled = jax.jit(partial(raft._refine, iters=2, platform='cpu')).lower(
        params, f1, f2, cn).compile()
    return compiled.as_text(), scopes.scope_map(compiled.as_text())


@pytest.mark.parametrize('path', [
    'raft_corr', 'raft_update', 'raft_update/raft_lookup',
    'raft_update/raft_motion', 'raft_update/raft_motion/raft_convf1',
    'raft_update/raft_gru', 'raft_update/raft_flow_head', 'raft_upsample'])
def test_refine_has_instructions_under(path):
    assert path in set(refine_map()[1].values())


def test_refines_while_is_under_raft_update_and_parameters_are_left_out():
    text, table = refine_map()
    whiles = re.findall(r'^\s+(?:ROOT )?(%[\w.\-]+) = [^\n]*? while\(', text,
                        re.M)
    assert whiles and all(table[w] == 'raft_update' for w in whiles)
    # every instruction of the module is in the map or has no metadata;
    # of those, the record names the ones that can run as ops of their own
    names = re.findall(r'^\s+(?:ROOT )?(%[\w.\-]+) = ', text, re.M)
    left_out = {n for n in names if n not in table}
    assert left_out and len(left_out) + len(table) == len(set(names))
    bare = scopes.compiled_scopes('', text)['no_metadata']
    assert set(bare) <= left_out and not set(bare) & set(table)
    assert not any(re.search(rf'{re.escape(n)} = [^\n]*? parameter\(', text)
                   for n in bare)
    assert all(v == '' or set(v.split('/')) <= set(scopes.SCOPES)
               for v in table.values())


LM_TINY = {
    'joyai_llm_flash': dict(
        hidden_size=64, num_hidden_layers=3, n_routed_experts=16,
        n_experts_held=4, num_experts_per_tok=4, num_attention_heads=2,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, vocab_size=512,
        intermediate_size=160, moe_intermediate_size=32),
    'brumby': dict(
        model_type='brumby', vocab_size=512, hidden_size=64,
        num_hidden_layers=3, intermediate_size=160, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, rope_theta=1000000),
    'lfm2_moe': dict(
        model_type='lfm2_moe', vocab_size=512, hidden_size=64,
        num_hidden_layers=4,
        layer_types=['conv', 'conv', 'full_attention', 'conv'],
        conv_L_cache=3, num_dense_layers=2, intermediate_size=160,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        routed_scaling_factor=1, norm_topk_prob=True, use_expert_bias=True,
        num_attention_heads=4, num_key_value_heads=2, rope_theta=1000000,
        norm_eps=0.00001),
}


@pytest.fixture(scope='module')
def lm_step_record(tmp_path_factory):
    """One tiny step of an ``lm`` trunk through the manifest's own door,
    compiled once a trunk."""
    from video_features_tpu.obs.manifest import xla_cost_analysis

    @cache
    def record(trunk):
        work = tmp_path_factory.mktemp(f'lm-{trunk}')
        ex = create_extractor(load_config('lm', overrides=dict(
            LM_TINY[trunk], video_paths=['x.mp4'], device='cpu',
            allow_random_weights=True, stack_size=4, step_size=4,
            patch_grid=4, batch_size=2, on_extraction='save_numpy',
            output_path=str(work / 'out'), tmp_path=str(work / 'tmp'))))
        (spec,) = ex.program_specs()
        return xla_cost_analysis(spec.jitted, *spec.args, **spec.kwargs)
    return record


@pytest.mark.parametrize('trunk,scope', [
    ('joyai_llm_flash', 'mla'), ('joyai_llm_flash', 'moe'),
    ('joyai_llm_flash', 'dense_mlp'),
    ('brumby', 'retention'), ('brumby', 'dense_mlp'),
    ('lfm2_moe', 'short_conv'), ('lfm2_moe', 'attention'),
    ('lfm2_moe', 'moe'), ('lfm2_moe', 'dense_mlp')])
def test_an_lm_trunks_step_has_instructions_under(lm_step_record, trunk,
                                                  scope):
    record = lm_step_record(trunk)['scopes']
    assert record['program'] == 'jit_lm_step' and record['missing'] == []
    seen = {part for path in set(record['instructions'].values())
            for part in path.split('/')}
    assert scope in seen


def test_cost_and_map_come_from_one_compile_and_say_what_the_flops_are():
    from jax import lax

    def f(x, w):
        with jax.named_scope('raft_update'):
            def body(c, _):
                with jax.named_scope('raft_gru'):
                    return jnp.tanh(c @ w), None
            c, _ = lax.scan(body, x, None, length=5)
        return c.sum()

    from video_features_tpu.obs import manifest
    out = manifest.xla_cost_analysis(jax.jit(f), jnp.ones((8, 8)),
                                     jnp.ones((8, 8)))
    assert out['flops'] > 0 and out['bytes_accessed'] > 0
    # XLA counts the scan's body once: the record says so
    assert out['loops_counted_once'] is True
    record = out['scopes']
    assert record['program'] == 'jit_f' and record['missing'] == []
    assert 'raft_update/raft_gru' in set(record['instructions'].values())
    assert isinstance(record['no_metadata'], list)
    flat = manifest.xla_cost_analysis(jax.jit(lambda x: x * 2),
                                      jnp.ones((8,)))
    assert 'loops_counted_once' not in flat


def test_a_compiled_text_that_lacks_a_scope_the_lowering_names_is_missing():
    """What a compilation cache written before a scope was added serves:
    jax's cache key leaves metadata out."""
    def old(x):
        with jax.named_scope('moe'):
            return x * 2

    def new(x):
        with jax.named_scope('moe'):
            with jax.named_scope('dense_mlp'):
                return x * 2

    x = jnp.ones((8,))
    stale = jax.jit(old).lower(x).compile().as_text()
    lowered = jax.jit(new).lower(x).as_text(debug_info=True)
    assert scopes.lowered_scopes(lowered) == ['dense_mlp', 'moe']
    assert scopes.compiled_scopes(lowered, stale)['missing'] == ['dense_mlp']
    fresh = jax.jit(new).lower(x).compile().as_text()
    assert scopes.compiled_scopes(lowered, fresh)['missing'] == []


def test_note_keeps_one_record_a_program_and_counts_colliding_executables(
        monkeypatch):
    monkeypatch.setattr(scopes, '_NOTED', {})
    record = {'program': 'jit_a', 'instructions': {'%f.1': 'moe'},
              'missing': [], 'no_metadata': []}
    scopes.note('jit_a', record)
    scopes.note('jit_a', dict(record))              # the same executable
    scopes.note(None, record)                       # no name: not kept
    assert scopes.noted() == {'jit_a': dict(record, variants=1)}
    scopes.note('jit_a', dict(record, instructions={'%f.1': 'mla'}))
    assert scopes.noted() == {'jit_a': dict(record, variants=2)}


# -- the seam: once per executable, off the hot path, not at all when off ----

@pytest.fixture(scope='module')
def clips(tmp_path_factory):
    d = tmp_path_factory.mktemp('scopevids')
    return [write_noise_clip(d / f'v{i}.mp4', n, seed=i)
            for i, n in enumerate((9, 6))]


def resnet(clips, tmp_path, **kw):
    return create_extractor(load_config('resnet', overrides=dict(
        video_paths=clips, device='cpu', model_name='resnet18', batch_size=4,
        allow_random_weights=True, on_extraction='save_numpy',
        output_path=str(tmp_path / 'out'), tmp_path=str(tmp_path / 'tmp'),
        **kw)))


@pytest.fixture
def lowerings(monkeypatch):
    """Counts every lowering made through the manifest's seam."""
    from video_features_tpu.analysis import programs
    calls = []
    real = programs.abstract_lowering
    monkeypatch.setattr(programs, 'abstract_lowering',
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    return calls


def test_two_packed_calls_with_a_manifest_lower_each_identity_once(
        clips, tmp_path, lowerings, monkeypatch):
    from video_features_tpu.parallel.packing import VideoTask
    monkeypatch.setattr(scopes, '_NOTED', {})
    ex = resnet(clips, tmp_path, manifest_out=str(tmp_path / 'm.json'))
    ex.extract_packed(clips)
    assert len(lowerings) == 1
    (identity,) = ex.manifest.executables
    ex.extract_packed([VideoTask(p, out_root=str(tmp_path / 'again'))
                       for p in clips])
    assert len(lowerings) == 1              # the second pass lowers nothing
    ex.finish_obs()
    doc = json.loads((tmp_path / 'm.json').read_text())
    entry = doc['executables'][identity]
    assert identity.startswith('resnet:(4, ') and entry['batch'] == 4
    assert entry['compute_dtype'] == 'float32' and entry['flops'] > 0
    assert entry['scopes']['program'] == 'jit_resnet_step'
    assert entry['scopes']['missing'] == []
    # ... and the same record is there for a reader in this process
    assert scopes.noted()['jit_resnet_step']['instructions'] == \
        entry['scopes']['instructions']
    # what was remembered for the lowering is dropped once noted
    assert all('jitted' not in r for r in ex._dispatched.values())


def test_the_per_video_loop_notes_its_step_after_the_first_video(
        clips, tmp_path, lowerings, monkeypatch):
    monkeypatch.setattr(scopes, '_NOTED', {})
    ex = resnet(clips, tmp_path, manifest_out=str(tmp_path / 'm.json'))
    ex._extract(clips[0])
    assert len(lowerings) == 1 and len(ex.manifest.executables) == 1
    assert 'jit_resnet_step' in scopes.noted()
    ex._extract(clips[1])                   # the same geometry: nothing new
    assert len(lowerings) == 1


def test_the_i3d_step_is_lowered_with_its_statics_and_names_every_part(
        tmp_path, lowerings, monkeypatch):
    """The cell the metrics land on: the per-video loop, a step with static
    arguments (``pads=``, ``streams=``, ``resize_to=``), every scope of the
    fused step in the map of the executable really run."""
    monkeypatch.setattr(scopes, '_NOTED', {})
    clip = write_noise_clip(tmp_path / 'a.mp4', 23, seed=7)
    ex = create_extractor(load_config('i3d', overrides=dict(
        video_paths=[clip], device='cpu', stack_size=10, step_size=10,
        batch_size=2, raft_iters=2, allow_random_weights=True,
        on_extraction='save_numpy', output_path=str(tmp_path / 'out'),
        tmp_path=str(tmp_path / 'tmp'),
        manifest_out=str(tmp_path / 'm.json'))))
    ex._extract(clip)
    assert ex.failed_videos == 0 and len(lowerings) == 1
    record = scopes.noted()['jit_i3d_two_stream_step']
    assert record['missing'] == [] and record['variants'] == 1
    paths = set(record['instructions'].values())
    assert {'raft_encoders', 'raft_corr', 'raft_update',
            'raft_update/raft_lookup', 'raft_update/raft_motion/raft_convf1',
            'raft_update/raft_gru', 'raft_update/raft_flow_head',
            'raft_upsample', 'flow_quantise', 'i3d_towers',
            'i3d_towers/i3d_stem'} <= paths
    (entry,) = ex.manifest.executables.values()
    assert entry['loops_counted_once'] is True and entry['batch'] == 2


def test_with_tracing_off_nothing_is_lowered_noted_or_imported(
        clips, tmp_path, lowerings, monkeypatch):
    monkeypatch.setattr(scopes, '_NOTED', {})
    monkeypatch.delitem(sys.modules, 'video_features_tpu.obs.scopes')
    monkeypatch.delitem(sys.modules, 'video_features_tpu.obs.manifest',
                        raising=False)
    ex = resnet(clips, tmp_path)
    assert not ex.tracer.enabled and ex.manifest is None
    ex._extract(clips[0])
    ex.extract_packed(clips)
    ex.finish_obs()
    assert ex.failed_videos == 0
    assert lowerings == [] and ex._dispatched == {}
    assert scopes.noted() == {}
    assert 'video_features_tpu.obs.scopes' not in sys.modules
    assert 'video_features_tpu.obs.manifest' not in sys.modules


def test_the_program_lock_does_not_move():
    """A scope is metadata: the lock hashes the lowered program without
    locations. raft's step carries most of this PR's new scopes; its pinned
    hash must be the committed one (i3d's is in test_programs' slow lane and
    the CI gate). If this moves, a scope changed a program: find out why
    before re-pinning."""
    from video_features_tpu.analysis.programs import (
        build_family, family_lock_hashes, program_signature,
    )
    ex = build_family('raft')       # forward: encoders, _refine, upsample
    (spec,) = ex.program_specs()
    assert program_signature(spec)['stablehlo_sha256'] == \
        family_lock_hashes('raft')['mesh1'][spec.name]
