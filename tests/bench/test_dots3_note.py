"""The cell ``dots3-note.corpus`` and its configuration
``dots3-note-prev-ep32-l5``: the configuration file against the published
config key by key, the parameter and FLOP counts recounted, a whole run of
the cell through ``harness.run`` at a tiny size on the CPU — sound, then
broken underneath —, the kernel's yardstick (operations and bytes against a
brute count, the event pattern, the roofline reader) and the scope metrics'
names. (The trunk and its mechanisms against the plain reference:
``tests/test_dots3_trunk.py``; the selection on both causal paths:
``tests/test_sparse_attention.py``.)"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import loader
from _layers import Ops

CELL = 'dots3-note.corpus'
CONFIG = 'dots3-note-prev-ep32-l5'
SEED = 2 ** 31 + 4093
REF = loader.load_module('references', CONFIG)
S, F = 'sliding_attention', 'full_attention'
METRICS = ('sparse_mla_ms.clips', 'mla_indexer_ms.clips',
           'window_mla_ms.clips', 'sparse_attention_roofline')

# https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json,
# as the catalog beside the model-configs guide holds it
PUBLISHED = {
    'apply_mla_qkv_lora_rescale': True, 'attention_bias': False,
    'attention_gate_type': 'headwise', 'first_k_dense_replace': 1,
    'hidden_act': 'silu', 'hidden_size': 5120, 'index_head_dim': 128,
    'index_n_heads': 64, 'index_topk': 2048, 'intermediate_size': 13824,
    'kv_lora_rank': 512, 'layer_types': [F, F] + [S, S, S, F] * 11,
    'max_position_embeddings': 524288, 'model_type': 'dots3_note',
    'moe_intermediate_size': 1536, 'moe_layer_freq': 1,
    'n_routed_experts': 256, 'n_shared_experts': 1, 'norm_topk_prob': True,
    'num_attention_heads': 128, 'num_experts_per_tok': 8,
    'num_hidden_layers': 46, 'num_key_value_heads': 128, 'q_lora_rank': 1024,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'rms_norm_eps': 1e-05,
    'rope_scaling': None, 'rope_theta': 80000000,
    'routed_scaling_factor': 1, 'scoring_func': 'sigmoid',
    'sliding_window_size': 513, 'swa_attention_gate_type': 'headwise',
    'swa_kv_lora_rank': 1024, 'swa_num_attention_heads': 64,
    'swa_num_key_value_heads': 64, 'swa_q_lora_rank': 1024,
    'swa_qk_nope_head_dim': 192, 'swa_qk_rope_head_dim': 64,
    'swa_rope_theta': 50000, 'swa_v_head_dim': 128,
    'tie_word_embeddings': False, 'topk_method': 'noaux_tc',
    'v_head_dim': 128, 'vocab_size': 152064}
REDUCED = {'layers': (46, 5), 'n_routed_experts': (256, 8),
           'vocab_size': (152064, 19008)}

# the trunk at a size a test run can hold: the program's overrides, and the
# same sizes under the reference's names
KINDS = [F, F, S, S, S]
WIDTHS = dict(
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    swa_num_attention_heads=2, swa_q_lora_rank=48, swa_kv_lora_rank=40,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16)
TINY_PROGRAM = dict(
    device='cpu', batch_size=2, vocab_size=512, hidden_size=64,
    num_hidden_layers=5, layer_types=KINDS, intermediate_size=160,
    moe_intermediate_size=32, n_routed_experts=8, n_experts_held=4,
    num_experts_per_tok=2, sliding_window_size=5, index_n_heads=4,
    index_head_dim=16, index_topk=8, stack_size=2, step_size=2,
    patch_grid=4, **WIDTHS)
TINY_REFERENCE = dict(
    REF.CFG, vocab_size=512, hidden_size=64, layers=5,
    layer_types=tuple(KINDS), intermediate_size=160, moe_intermediate_size=32,
    router_experts=8, n_routed_experts=4, num_experts_per_tok=2,
    sliding_window_size=5, index_n_heads=4, index_head_dim=16, index_topk=8,
    frames=2, patch_grid=4, query_block=8, index_block=8, **WIDTHS)
TINY = dict(
    require_tpu=False, program_overrides=TINY_PROGRAM,
    traffic_overrides={'clips': 3, 'frames': [5, 11, 7], 'width': 96,
                       'height': 64},
    workload_overrides={'sample': {'videos': 3, 'rows': 4, 'block': 2}})
ARGV = ['--workload', CELL, '--seed', str(SEED), '--seconds', '0.3',
        '--trace', '0']


@pytest.fixture()
def tiny_reference(monkeypatch):
    monkeypatch.setattr(REF, 'CFG', TINY_REFERENCE)


# -- the configuration and the cell, as files -------------------------------------

def test_the_cell_reports_its_end_to_end_and_its_own_metrics(bench_json):
    cell = {'name': CELL, 'bench': bench_json}
    assert {m['name'] for m in harness.metrics_of(cell, 'end_to_end')} == {
        'clips_per_s', 'setup_s'}
    per_layer = {m['name'] for m in harness.metrics_of(cell, 'per_layer')}
    # the four list-less .clips metrics and the cell's own four; a later PR
    # may list more for the cell
    assert per_layer >= {'batch_occupancy.clips', 'decode_busy.clips',
                         'device_idle.clips', 'step_mfu.clips', *METRICS}
    entry = [w for w in bench_json['workloads'] if w['name'] == CELL][0]
    assert (entry['config'], entry['traffic'], entry['chips']) == (
        CONFIG, 'corpus-8', 1)
    workload = loader.load_json('workloads', CELL)
    assert workload['driver'] == 'packed' and workload['warm_clips'] == [0]
    assert workload['sample'] == {'videos': 4, 'rows': 2, 'block': 1}


def test_the_scope_metrics_name_the_programs_scopes_and_the_kernel_its_name(
        bench_json):
    from video_features_tpu.obs.scopes import SCOPES
    from video_features_tpu.ops import pallas_attention
    listed = {m['name']: m for m in bench_json['per_layer']}
    for name in METRICS:
        spec = loader.load_json('metrics', name)
        assert spec['workloads'] == listed[name]['workloads'] == [CELL]
        assert spec['moves'] == listed[name]['moves'] == 'clips_per_s'
        assert spec['layer'] == listed[name]['layer']
        if spec['reader'] == 'scope_time':
            assert spec['scope'] in SCOPES and spec['unit'] == 'ms/clip'
            assert name == f'{spec["scope"]}_ms.clips'
        else:
            assert spec['kernel'] == pallas_attention.SPARSE_NAME
    assert [s for s in SCOPES if 'mla' in s] == [
        'mla', 'sparse_mla', 'mla_indexer', 'window_mla']


def test_the_configuration_keeps_every_published_key_but_the_cut(bench_json):
    body = loader.load_json('configs', CONFIG)
    entry = [c for c in bench_json['configs'] if c['name'] == CONFIG][0]
    assert body['reduced'] == entry['reduced'] == sorted(REDUCED)
    assert body['source'] == entry['source'] == (
        'https://huggingface.co/dots-studio/dots3-note-prev/blob/main/'
        'config.json')
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert body[key] == value, key
    for key, (published, held) in REDUCED.items():
        assert body[key] == held and body['published'][key] == published
    assert '32 chips' in body['deployment'] and 'ten pipeline stages' in \
        body['deployment'] and body['departures']
    assert set(body['assumed']) >= {
        'tokeniser', 'parameters', 'lora_rescale', 'head_gate', 'indexer',
        'indexer_rotary', 'window', 'checkpoint_names', 'weights',
        'batch_size', 'positions'}
    assert 'LongCat-Flash' in body['assumed']['lora_rescale']
    assert 'Hadamard' in body['departures'] and 'fp8' in body['departures']
    assert body['control_overrides'] == {'precision': 'default'}
    # what the program is handed spells every model key at the published
    # value but for depth, share and vocabulary
    over = body['overrides']
    from video_features_tpu.models import latent_moe
    for key in latent_moe.DOTS3_CONFIG_KEYS:
        if key in ('num_hidden_layers', 'layer_types', 'vocab_size',
                   'n_experts_held', 'first_expert'):
            continue
        assert over[key] == PUBLISHED[key], key
    assert over['layer_types'] == PUBLISHED['layer_types'][:5] == KINDS \
        == list(REF.CFG['layer_types'])
    assert (over['model_type'], over['num_hidden_layers'], over['vocab_size'],
            over['n_experts_held'], over['first_expert']) == (
        'dots3_note', 5, 19008, 8, 0)
    assert (over['device'], over['precision'], over['on_extraction'],
            over['pack_across_videos'], over['batch_size']) == (
        'tpu', 'mixed', 'save_numpy', True, 2)
    assert (over['stack_size'], over['step_size'], over['patch_grid']) == (
        32, 32, 16)
    # and the reference holds the same numbers under its own names
    for key in ('hidden_size', 'intermediate_size', 'moe_intermediate_size',
                'num_attention_heads', 'q_lora_rank', 'kv_lora_rank',
                'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim',
                'rope_theta', 'sliding_window_size', 'swa_num_attention_heads',
                'swa_q_lora_rank', 'swa_kv_lora_rank', 'swa_qk_nope_head_dim',
                'swa_qk_rope_head_dim', 'swa_v_head_dim', 'swa_rope_theta',
                'index_n_heads', 'index_head_dim', 'index_topk',
                'rms_norm_eps', 'num_experts_per_tok',
                'routed_scaling_factor', 'first_k_dense_replace'):
        assert REF.CFG[key] == PUBLISHED[key], key
    assert (REF.CFG['router_experts'], REF.CFG['n_routed_experts'],
            REF.CFG['vocab_size'], REF.CFG['layers']) == (256, 8, 19008, 5)
    assert REF.window_ids() == 8192


def test_the_parameters_are_recounted_from_the_programs_shapes():
    body = loader.load_json('configs', CONFIG)
    from video_features_tpu.config import load_config
    from video_features_tpu.models import latent_moe
    args = load_config('lm', overrides=dict(
        body['overrides'], video_paths=['x.mp4'], device='cpu'))
    cfg = latent_moe.TrunkConfig.from_args(args)
    assert (cfg.model_type, cfg.n_experts_held, cfg.n_routed_experts) == (
        'dots3_note', 8, 256)
    shapes = latent_moe.param_shapes(cfg)

    def count(*parts):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if all(p in n for p in parts))
    assert count('layers.1.self_attn') == 144_049_920
    assert count('layers.1.self_attn.indexer') == 9_371_904
    assert count('layers.1.self_attn.gate_proj') == 655_360
    assert count('layers.2.self_attn') == 90_834_944
    assert count('layers.2.mlp.shared_experts') == 23_592_960
    assert count('layers.2.mlp.experts') == 8 * 23_592_960
    assert count('layers.2.mlp.gate.') == 1_310_976
    assert count('layers.0.mlp') == 212_336_640
    assert count('embed_tokens') == 97_320_960
    assert latent_moe.param_count(cfg) == 1_724_909_056
    specs = REF.param_specs()['checkpoint_path']
    assert {n: tuple(s) for n, _, s, _ in specs} == shapes
    assert '1,724,909,056 parameters = 6.90 GB' in body['departures']
    assert 1_724_909_056 * 4 / 16.9e9 == pytest.approx(0.408, abs=0.001)


def test_the_cells_pass_is_71_windows_in_36_steps_of_2():
    traffic = loader.load_json('traffic', 'corpus-8')
    rows = [REF.rows_of(n) for n in traffic['frames']]
    assert sum(rows) == 71
    driver = loader.load_module('drivers', 'packed')

    class Two:
        def packed_batch_size(self):
            return 2
    assert driver.batch_slots(Two(), rows) == 72           # one padded slot
    # 2 windows a step: 512 assignments a held expert and layer at even
    # routing, 1/16 of the 32-chip deployment's 8,192
    assert 2 * REF.window_ids() * 8 // 256 == 512


def test_flops_per_unit_is_the_models_work_recounted():
    """Trace the reference at the published widths (shapes only: nothing is
    computed), take its waste away and put the model's work in."""
    specs = REF.param_specs()['checkpoint_path']
    params = {'checkpoint_path': {
        name: jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
        for name, _, shape, _ in specs}}
    ops = Ops()
    out = jax.eval_shape(lambda p, u: REF.forward(ops, p, u), params,
                         jax.ShapeDtypeStruct((1, 8192), jnp.int32))
    assert out.shape == (1, 5120)
    s = 8192
    # a token's multiply-adds outside the routed experts and the pairs
    full = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
            + 128 * 128 * 5120 + 5120 * 128
            + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64)
    sliding = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088
               + 1024 * 64 * 320 + 64 * 128 * 5120 + 5120 * 64)
    assert (full, sliding) == (144_048_128, 90_832_896)
    outside = s * (2 * full + 3 * sliding + 3 * 5120 * 13824
                   + 4 * 5120 * 256 + 4 * 3 * 5120 * 1536)
    waste = REF.reference_waste_macs()
    assert ops.macs == outside + sum(waste)
    assert waste[2] == s * 8 * 3 * 5120 * 1536 * 4
    assert waste[1] == 2 * s * s * 64 * 129
    assert waste[0] == s * (2 * s * 128 * 320 + 3 * 640 * 64 * 384)
    selected = sum(min(t + 1, 2048) for t in range(s))
    band = sum(min(t + 1, 513) for t in range(s))
    assert (selected, band) == (14_681_088, 4_071_168)
    assert selected == REF.visible_pairs(s, 2048)
    assert band == REF.visible_pairs(s, 513)
    pairs = (2 * selected * 128 * 320 + 3 * band * 64 * 384
             + 2 * s * (s + 1) // 2 * 64 * 129)
    routed = s * 8 * 8 * 3 * 5120 * 1536 * 4 // 256
    total = REF.model_macs(ops.macs)
    assert total == outside + pairs + routed == 9_398_125_068_288
    body = loader.load_json('configs', CONFIG)
    assert body['flops_per_unit'] == 2 * total == 18_796_250_136_576
    # the mixers this configuration brings do most of the work
    full_mixers = s * 2 * full + 2 * selected * 128 * 320 \
        + 2 * s * (s + 1) // 2 * 64 * 129
    sliding_mixers = s * 3 * sliding + 3 * band * 64 * 384
    assert round(100 * full_mixers / total, 1) == 43.8
    assert round(100 * sliding_mixers / total, 1) == 26.9
    assert 100 * (full_mixers + sliding_mixers) / total == pytest.approx(
        70.75, abs=0.01)
    assert round(100 * 2 * selected * 128 * 320 / total, 1) == 12.8


# -- a whole run, sound and broken --------------------------------------------------

def test_a_sound_tiny_run_is_correct(tiny_reference):
    result = harness.run(ARGV, **TINY)
    assert result['correct'] is True
    assert result['failed'] == 0 and result['attempted'] % 3 == 0
    assert set(result['metrics']) == {'clips_per_s', 'setup_s'}
    # on the CPU the program computes in float32: it sits on the reference,
    # decode, tokeniser, packing, scatter and save included
    assert result['checks']['rel_l2']['value'] < 1e-5
    assert result['checks']['rows_off']['value'] == 0
    json.dumps(result)


def _alter_a_row(extractor):
    step = extractor.packed_step

    def bad(batch):
        out = dict(step(batch))
        out['lm'] = out['lm'].at[0].multiply(1.05)
        return out
    extractor.packed_step = bad


def _lose_the_tail(extractor):
    result = extractor.packed_result

    def bad(task):
        return {k: v[:-1] for k, v in result(task).items()}
    extractor.packed_result = bad


@pytest.mark.parametrize('fault,number', [
    (_alter_a_row, 'row_rel_l2_max'),
    (_lose_the_tail, 'rows_off'),
])
def test_a_broken_timed_path_is_not_correct(tiny_reference, fault, number):
    result = harness.run(ARGV, before_window=fault, **TINY)
    assert result['correct'] is False
    check = result['checks'][number]
    assert check['value'] > check['limit']


def test_the_precision_control_is_not_correct(tiny_reference, tmp_path):
    """The reference in one bfloat16 pass, saved as the program would have
    saved it, fails ``rel_l2`` under the cell's own limits."""
    import compare
    import traffic_gen
    cell = harness.load_cell(CELL)
    ckpts = harness.make_weights(REF, SEED, tmp_path)
    corpus = traffic_gen.generate(
        dict(cell['traffic'], **TINY['traffic_overrides']), SEED,
        str(tmp_path / 'corpus'))
    items = traffic_gen.pass_paths(corpus, 'p0')
    for item in items:
        units = REF.load_units(item['path'],
                               range(REF.rows_of(item['frames'])))
        np.save(item['path'] + '.npy', compare.reference_rows(
            REF, ckpts, units, 2, mode='bfloat16'))
    done = compare.collect([items], lambda p: p + '.npy', REF)
    workload = dict(cell['workload'], **TINY['workload_overrides'])
    checks, n = compare.compare(done, REF, ckpts, workload, SEED)
    assert checks['rows_off']['ok'] and checks['nonfinite']['ok']
    assert not checks['rel_l2']['ok']


# -- the kernel's yardstick ---------------------------------------------------------

CALL = {'positions': 8192, 'topk': 2048, 'heads': 128, 'qk_dim': 192,
        'v_dim': 128}


@pytest.fixture(scope='module')
def kernel():
    return loader.load_module('kernels', 'sparse_attention')


@pytest.fixture(scope='module')
def v5e():
    peaks = json.loads((loader.BENCH / 'peaks.json').read_text())
    return peaks['devices']['TPU v5 lite']


@pytest.mark.parametrize('positions,topk', [
    (4, 1), (4, 3), (4, 4), (4, 9), (64, 8), (96, 33), (256, 255)])
def test_selected_pairs_are_min_t_plus_1_topk_by_brute_count(kernel,
                                                            positions, topk):
    brute = sum(min(t + 1, topk) for t in range(positions))
    assert kernel.selected_pairs(positions, topk) == brute
    shape = {'positions': positions, 'topk': topk, 'heads': 6, 'qk_dim': 3,
             'v_dim': 2}
    assert kernel.flops(**shape) == 2 * brute * 6 * (3 + 2)
    assert kernel.bytes_moved(**shape) == \
        positions * 2 * 6 * 5 * 4 + positions * positions // 8


def test_the_cells_window_layer_from_its_keys(kernel, v5e):
    cfg = loader.load_json('configs', CONFIG)
    # one event is one window of one full layer: the batch does not enter
    assert kernel.shapes(cfg, 1) == kernel.shapes(cfg, 2) == CALL
    assert kernel.flops(**CALL) == 2 * 14_681_088 * 128 * 320 \
        == 1_202_674_728_960
    # Q, K, V and O float32 a head, and the selection as bits
    assert kernel.bytes_moved(**CALL) == \
        8192 * 256 * 320 * 4 + 8192 * 8192 // 8 == 2_692_743_168
    least, bound = kernel.min_seconds(v5e, **CALL)
    assert bound == 'flops'
    assert least * 1e3 == pytest.approx(6.105, abs=0.005)
    # the triangle would be 2.29 times the selected work
    full = loader.load_module('kernels', 'causal_attention')
    assert full.flops(positions=8192, heads=128, kv_heads=128, qk_dim=192,
                      v_dim=128) / kernel.flops(**CALL) == pytest.approx(
        2.286, abs=0.001)


def test_the_event_pattern_matches_the_sparse_call_and_no_other(kernel):
    rx = re.compile(kernel.EVENT_MATCH)
    spec = loader.load_json('metrics', 'sparse_attention_roofline')
    assert (spec['reader'], spec['kernel'], spec['match'],
            spec['events_per_call'], spec['unit']) == (
        'kernel_roofline', 'sparse_attention', kernel.EVENT_MATCH,
        kernel.EVENTS_PER_CALL, '%')
    mine = ('%sparse_attention.3 = f32[1,8192,16384]{2,1,0:T(8,128)} '
            'custom-call(f32[1,128,8192,128]{3,2,1,0:T(8,128)} %fusion.2), '
            'custom_call_target="tpu_custom_call"')
    for other in ('window_attention', 'causal_attention'):
        assert not rx.search(mine.replace('%sparse_attention', f'%{other}'))
        assert not re.compile(loader.load_module(
            'kernels', other).EVENT_MATCH).search(mine)
    assert rx.search(mine)


def test_the_roofline_reader_counts_one_call_an_event(kernel, v5e):
    import trace_reduce
    roof = loader.load_module('readers', 'kernel_roofline')
    cfg = loader.load_json('configs', CONFIG)
    least, _ = kernel.min_seconds(v5e, **CALL)
    name = ('%{}.{} = f32[1,8192,16384]{{2,1,0:T(8,128)}} '
            'custom-call(f32[1,8192,16384]{{2,1,0}} %q), '
            'custom_call_target="tpu_custom_call"')
    # 8 events (2 steps x 2 windows x 2 full layers), each ten times the
    # least: 10 %; the sliding layers' calls beside them are not counted
    events = [(name.format('sparse_attention', i % 2), 1e8 * i,
               10 * least * 1e9) for i in range(8)]
    events += [(name.format('window_attention', i), 5e7 + 1e8 * i, 2e8)
               for i in range(12)]
    trace = {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': trace_reduce.OPS_LINE, 'events': events}]}]}
    ctx = {'metric': loader.load_json('metrics', 'sparse_attention_roofline'),
           'trace': trace, 'config': cfg, 'batch_size': 2, 'peaks': v5e,
           'log': lambda *a: None}
    assert roof.read(ctx) == pytest.approx(10.0)
    # a parent without the lane: no such event, nothing to read, no number
    trace['planes'][0]['lines'][0]['events'] = events[8:]
    assert roof.read(ctx) is None
