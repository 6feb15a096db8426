"""The cell ``trinity-mini.corpus`` and its configuration
``trinity-mini-ep4-l8``: the configuration file against the published
config, the parameter and FLOP counts recounted, a whole run of the cell
through ``harness.run`` at a tiny size on the CPU — sound, then broken
underneath —, and the windowed kernel's yardstick (operations and bytes
against a brute count, the event pattern, the roofline reader). (The trunk
and its mechanisms against the plain reference:
``tests/test_afmoe_trunk.py``; the window on both causal paths:
``tests/test_window_attention.py``.)"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import loader
from _layers import Ops

CELL = 'trinity-mini.corpus'
CONFIG = 'trinity-mini-ep4-l8'
SEED = 2 ** 31 + 3833
REF = loader.load_module('references', CONFIG)
S, F = 'sliding_attention', 'full_attention'

# https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json, as the
# catalog beside the model-configs guide holds it
PUBLISHED = {
    'global_attn_every_n_layers': 4, 'head_dim': 128, 'hidden_act': 'silu',
    'hidden_size': 2048, 'intermediate_size': 6144,
    'layer_types': [S, S, S, F] * 8, 'load_balance_coeff': 0.001,
    'max_position_embeddings': 131072, 'model_type': 'afmoe',
    'moe_intermediate_size': 1024, 'mup_enabled': True, 'n_group': 1,
    'num_attention_heads': 32, 'num_dense_layers': 2, 'num_expert_groups': 1,
    'num_experts': 128, 'num_experts_per_tok': 8, 'num_hidden_layers': 32,
    'num_key_value_heads': 4, 'num_limited_groups': 1,
    'num_shared_experts': 1, 'rms_norm_eps': 1e-05, 'rope_scaling': None,
    'rope_theta': 10000, 'route_norm': True, 'route_scale': 2.826,
    'score_func': 'sigmoid', 'sliding_window': 2048,
    'tie_word_embeddings': False, 'topk_group': 1, 'use_grouped_mm': True,
    'vocab_size': 200192}
# the keys the program's trunk is built from (models/hybrid_trunk.py's afmoe
# dialect) that the cut leaves as published
MODEL_KEYS = ('vocab_size', 'hidden_size', 'sliding_window', 'head_dim',
              'num_dense_layers', 'intermediate_size',
              'moe_intermediate_size', 'num_experts', 'num_experts_per_tok',
              'num_shared_experts', 'route_scale', 'route_norm', 'score_func',
              'mup_enabled', 'num_attention_heads', 'num_key_value_heads',
              'rope_theta', 'rms_norm_eps')

# the trunk at a size a test run can hold: the program's overrides, and the
# same sizes under the reference's names
KINDS = [S, S, S, F, S]
TINY_PROGRAM = dict(
    device='cpu', batch_size=2, vocab_size=512, hidden_size=64,
    num_hidden_layers=5, layer_types=KINDS, sliding_window=8, head_dim=16,
    num_dense_layers=1, intermediate_size=160, moe_intermediate_size=32,
    num_experts=8, n_experts_held=4, num_experts_per_tok=2,
    num_attention_heads=4, num_key_value_heads=2, stack_size=2, step_size=2,
    patch_grid=4)
TINY_REFERENCE = dict(
    REF.CFG, vocab_size=512, hidden_size=64, layers=5,
    layer_types=tuple(KINDS), sliding_window=8, head_dim=16,
    num_dense_layers=1, intermediate_size=160, moe_intermediate_size=32,
    router_experts=8, n_routed_experts=4, num_experts_per_tok=2,
    num_attention_heads=4, num_key_value_heads=2, frames=2, patch_grid=4,
    query_block=8)
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

def test_the_cell_reports_its_end_to_end_metrics(bench_json):
    got = harness.metrics_of({'name': CELL, 'bench': bench_json},
                             'end_to_end')
    assert {m['name'] for m in got} == {'clips_per_s', 'setup_s'}
    per_layer = {m['name'] for m in harness.metrics_of(
        {'name': CELL, 'bench': bench_json}, 'per_layer')}
    # the four list-less .clips metrics and the cell's own three; a later
    # PR may list more for the cell
    assert per_layer >= {
        'batch_occupancy.clips', 'decode_busy.clips', 'device_idle.clips',
        'step_mfu.clips', 'sliding_attention_ms.clips',
        'full_attention_ms.clips', 'window_attention_roofline'}
    entry = [w for w in bench_json['workloads'] if w['name'] == CELL][0]
    assert (entry['config'], entry['traffic'], entry['chips']) == (
        CONFIG, 'corpus-6', 1)
    assert loader.load_json('workloads', CELL)['driver'] == 'packed'
    for name, scope in (('sliding_attention_ms.clips', S),
                        ('full_attention_ms.clips', F)):
        spec = loader.load_json('metrics', name)
        assert (spec['reader'], spec['scope'], spec['unit'],
                spec['workloads']) == ('scope_time', scope, 'ms/clip', [CELL])


def test_the_configuration_keeps_every_published_key_but_the_cut(bench_json):
    body = loader.load_json('configs', CONFIG)
    entry = [c for c in bench_json['configs'] if c['name'] == CONFIG][0]
    assert body['reduced'] == entry['reduced'] == ['layers', 'num_experts']
    assert body['source'] == ('https://huggingface.co/arcee-ai/Trinity-Mini/'
                              'blob/main/config.json')
    for key, value in PUBLISHED.items():
        if key != 'num_experts':
            assert body[key] == value, key
    assert (body['layers'], body['num_experts']) == (8, 32)
    assert body['published'] == {'layers': 32, 'num_hidden_layers': 32,
                                 'num_experts': 128}
    assert '4 chips' in body['deployment'] and '4 pipeline stages' in \
        body['deployment'] and body['departures']
    assert set(body['assumed']) >= {
        'tokeniser', 'parameters', 'output_gate', 'qk_norm', 'four_norms',
        'no_rotary_on_full_layers', 'window', 'normaliser',
        'embedding_multiplier', 'weights', 'positions', 'batch_size'}
    assert all('modeling_afmoe.py' in body['assumed'][k] for k in (
        'output_gate', 'qk_norm', 'four_norms', 'no_rotary_on_full_layers',
        'window', 'normaliser', 'embedding_multiplier'))
    for key in ('load_balance_coeff', 'use_grouped_mm', 'n_group',
                'topk_group', 'num_expert_groups', 'num_limited_groups'):
        assert key in body['not_spelled'], key
    assert body['feature_type'] == 'lm'
    assert body['control_overrides'] == {'precision': 'default'}
    # what the program is handed spells every model key (the shipped yml is
    # another model's), at the published value but for depth and share
    over = body['overrides']
    assert over['model_type'] == 'afmoe' and over['num_hidden_layers'] == 8
    assert over['layer_types'] == PUBLISHED['layer_types'][:8] \
        == list(REF.CFG['layer_types']) == [S, S, S, F, S, S, S, F]
    # two whole periods: 6 sliding : 2 full as the published 24 : 8
    assert PUBLISHED['layer_types'].count(S) == 24
    for key in MODEL_KEYS:
        assert over[key] == PUBLISHED[key], key
    assert (over['n_experts_held'], over['first_expert']) == (32, 0)
    for key in ('vocab_size', 'hidden_size', 'sliding_window', 'head_dim',
                'num_dense_layers', 'intermediate_size',
                'moe_intermediate_size', 'num_experts_per_tok',
                'num_shared_experts', 'route_scale', 'num_attention_heads',
                'num_key_value_heads', 'rope_theta', 'rms_norm_eps'):
        assert REF.CFG[key] == PUBLISHED[key], key
    assert (REF.CFG['router_experts'], REF.CFG['n_routed_experts'],
            REF.CFG['layers']) == (128, 32, 8)
    assert (over['device'], over['precision'], over['on_extraction'],
            over['pack_across_videos'], over['batch_size']) == (
        'tpu', 'mixed', 'save_numpy', True, 1)
    assert (over['stack_size'], over['step_size'], over['patch_grid']) == (
        32, 32, 32)
    assert REF.window_ids() == 32 * 32 ** 2 == 32768
    from video_features_tpu.config import load_config
    from video_features_tpu.models import hybrid_trunk
    args = load_config('lm', overrides=dict(over, video_paths=['x.mp4'],
                                            device='cpu'))
    cfg = hybrid_trunk.TrunkConfig.from_args(args)
    assert (cfg.model_type, cfg.n_experts_held, cfg.num_experts) == (
        'afmoe', 32, 128)
    # the issue's count, part by part, recounted from the program's shapes
    shapes = hybrid_trunk.param_shapes(cfg)

    def count(*parts):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if all(p in n for p in parts))
    assert count('layers.0.self_attn') == 27_263_232
    assert count('layers.0.', 'layernorm') == 8_192
    assert count('layers.0.mlp') == 37_748_736
    assert count('layers.2.mlp.router') + count('layers.2.mlp.expert_bias') \
        == 262_144 + 128
    assert count('layers.2.mlp.shared_experts') == 6_291_456
    assert count('layers.2.mlp.experts') == 32 * 6_291_456
    assert count('layers.0.') == 65_020_160
    assert count('layers.2.') == 235_151_744
    assert count('embed_tokens') == 409_993_216
    assert hybrid_trunk.param_count(cfg) == 1_950_946_048
    assert '1,950,946,048 parameters = 7.80 GB' in body['departures']


def test_the_cells_pass_is_10_windows_in_10_steps():
    traffic = loader.load_json('traffic', 'corpus-6')
    rows = [REF.rows_of(n) for n in traffic['frames']]
    assert rows == [1, 1, 1, 2, 2, 3] and sum(rows) == 10
    driver = loader.load_module('drivers', 'packed')

    class One:
        def packed_batch_size(self):
            return 1
    assert driver.batch_slots(One(), rows) == 10           # no padded slot
    # 1 window a step: 2,048 assignments a held expert and layer at even
    # routing, a quarter of what 4 data-parallel chips would send
    assert REF.window_ids() * 8 // 128 == 2048
    workload = loader.load_json('workloads', CELL)
    assert workload['warm_clips'] == [0]
    assert workload['sample'] == {'videos': 3, 'rows': 2, 'block': 1}


def test_flops_per_unit_is_the_models_work_recounted():
    """Trace the reference at the published widths (shapes only: nothing is
    computed), take its waste away and put the model's work in."""
    specs = REF.param_specs()['checkpoint_path']
    params = {'checkpoint_path': {
        name: jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
        for name, _, shape, _ in specs}}
    assert sum(int(np.prod(s)) for _, _, s, _ in specs) == 1_950_946_048
    ops = Ops()
    out = jax.eval_shape(lambda p, u: REF.forward(ops, p, u), params,
                         jax.ShapeDtypeStruct((1, 32768), jnp.int32))
    assert out.shape == (1, 2048)
    s = 32768
    # a token's multiply-adds outside the routed experts and the score pairs
    projections = 8 * (3 * 2048 * 4096 + 2 * 2048 * 512)
    dense = 2 * 3 * 2048 * 6144
    routers = 6 * 2048 * 128
    shared = 6 * 3 * 2048 * 1024
    assert (projections, dense, routers, shared) == (
        218_103_808, 75_497_472, 1_572_864, 37_748_736)
    outside = s * (projections + dense + routers + shared)
    waste_attn, waste_routed = REF.reference_waste_macs()
    assert ops.macs == outside + waste_attn + waste_routed
    assert waste_routed == s * 32 * 3 * 2048 * 1024 * 6
    # whole rows of a full layer, a span of 2,047 + 256 keys of a sliding one
    assert waste_attn == s * (2 * s + 6 * 2303) * 32 * 256
    band = sum(min(i + 1, 2048) for i in range(s))
    assert band == REF.visible_pairs(s, 2048) == 65_012_736
    assert REF.visible_pairs(s) == s * (s + 1) // 2
    pairs_full = 2 * (s * (s + 1) // 2) * 32 * 256
    pairs_sliding = 6 * band * 32 * 256
    assert (pairs_full // s, pairs_sliding // s) == (268_443_648, 97_519_104)
    routed = s * 2 * 3 * 2048 * 1024 * 6      # 2 of a token's 8 assignments
    total = REF.model_macs(ops.macs)
    assert total == outside + pairs_full + pairs_sliding + routed \
        == s * 774_383_104
    body = loader.load_json('configs', CONFIG)
    assert body['flops_per_unit'] == 2 * total == 50_749_971_103_744
    # attention is the step: the mixers three quarters, the pairs alone 47 %
    assert round(100 * (s * projections + pairs_full + pairs_sliding)
                 / total) == 75
    assert round(100 * (pairs_full + pairs_sliding) / total) == 47
    assert round(100 * (routed + s * (shared + routers)) / total) == 15
    assert round(100 * s * dense / total) == 10
    # under a mask and no band the sliding pairs would be 8.3 times as many
    assert round(6 * (s + 1) // 2 * 32 * 256 / 1e6) == 805


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


def _shift_the_rows(extractor):
    step = extractor.packed_step

    def bad(batch):
        out = dict(step(batch))
        out['lm'] = jnp.roll(out['lm'], 1, axis=0)
        return out
    extractor.packed_step = bad


def _lose_the_tail(extractor):
    result = extractor.packed_result

    def bad(task):
        return {k: v[:-1] for k, v in result(task).items()}
    extractor.packed_result = bad


@pytest.mark.parametrize('fault,number', [
    (_alter_a_row, 'row_rel_l2_max'),
    (_shift_the_rows, 'row_rel_l2_max'),
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
    assert n == 9                       # 2 + 4 of 5 + 3 windows
    assert checks['rows_off']['ok'] and checks['nonfinite']['ok']
    assert not checks['rel_l2']['ok']


# -- the windowed kernel's yardstick ------------------------------------------------

CALL = {'positions': 32768, 'window': 2048, 'heads': 32, 'kv_heads': 4,
        'qk_dim': 128, 'v_dim': 128}


@pytest.fixture(scope='module')
def kernel():
    return loader.load_module('kernels', 'window_attention')


@pytest.fixture(scope='module')
def v5e():
    peaks = json.loads((loader.BENCH / 'peaks.json').read_text())
    return peaks['devices']['TPU v5 lite']


@pytest.mark.parametrize('positions,window', [
    (4, 1), (4, 3), (4, 4), (4, 9), (64, 8), (96, 33), (256, 255)])
def test_visible_pairs_are_the_bands_by_brute_count(kernel, positions,
                                                    window):
    brute = sum(1 for i in range(positions) for j in range(positions)
                if 0 <= i - j < window)
    assert kernel.visible_pairs(positions, window) == brute
    shape = {'positions': positions, 'window': window, 'heads': 6,
             'kv_heads': 2, 'qk_dim': 3, 'v_dim': 2}
    # every query head meets every visible key: 2 FLOPs a pair and column
    assert kernel.flops(**shape) == 2 * brute * 6 * (3 + 2)
    assert kernel.bytes_moved(**shape) == positions * (6 + 2) * 5 * 4


def test_the_cells_window_layer_by_hand(kernel, v5e):
    cfg = loader.load_json('configs', CONFIG)
    # one event is one window of one layer: the batch does not enter
    assert kernel.shapes(cfg, 1) == kernel.shapes(cfg, 4) == CALL
    # 65,012,736 pairs a head x 32 heads x (128 + 128) columns x 2
    assert kernel.flops(**CALL) == 2 * 65_012_736 * 32 * 256 \
        == 1_065_168_666_624
    # Q read and O written a query head, K and V read a key-value head:
    # 32,768 x (32 + 4) x 256 x 4
    assert kernel.bytes_moved(**CALL) == 1_207_959_552
    least, bound = kernel.min_seconds(v5e, **CALL)
    assert bound == 'flops'
    assert least * 1e3 == pytest.approx(5.407, abs=0.005)
    assert kernel.bytes_moved(**CALL) / v5e['hbm_bytes_per_s'] * 1e3 \
        == pytest.approx(1.475, abs=0.005)
    # the whole triangle would be 8.3 times the work
    full = loader.load_module('kernels', 'causal_attention')
    assert full.flops(**{k: v for k, v in CALL.items() if k != 'window'}) \
        / kernel.flops(**CALL) == pytest.approx(8.26, abs=0.01)


def _spec(kernel):
    """The metric's own file: its pattern and count are the kernel file's."""
    spec = loader.load_json('metrics', 'window_attention_roofline')
    assert (spec['reader'], spec['kernel'], spec['match'],
            spec['events_per_call'], spec['unit']) == (
        'kernel_roofline', 'window_attention', kernel.EVENT_MATCH,
        kernel.EVENTS_PER_CALL, '%')
    assert CELL in spec['workloads']
    return spec


def test_the_event_pattern_matches_the_windowed_call_and_not_the_causal_one(
        kernel):
    rx = re.compile(kernel.EVENT_MATCH)
    mine = ('%window_attention.3 = f32[1,32768,4096]{2,1,0:T(8,128)} '
            'custom-call(f32[1,32768,4096]{2,1,0:T(8,128)} %fusion.2), '
            'custom_call_target="tpu_custom_call", operand_layout_'
            'constraints={f32[1,32768,4096]{2,1,0}}')
    causal = mine.replace('%window_attention.3', '%causal_attention.7')
    loop = ('%while.55 = (s32[], f32[1,32768,2048]) while(%tuple.9), '
            'condition=%cond, body=%window_attention_body')
    assert rx.search(mine)
    assert not rx.search(causal) and not rx.search(loop)
    # the listed causal_attention_roofline matches by its own prefix: the
    # windowed call's name must not fall under it
    causal_rx = re.compile(loader.load_module(
        'kernels', 'causal_attention').EVENT_MATCH)
    assert causal_rx.search(causal) and not causal_rx.search(mine)
    # and the program's names for the two calls are the ones matched
    from video_features_tpu.ops import pallas_attention
    assert mine.startswith(f'%{pallas_attention.WINDOW_NAME}.')
    assert causal.startswith(f'%{pallas_attention.NAME}.')
    assert not pallas_attention.WINDOW_NAME.startswith(pallas_attention.NAME)


def test_the_roofline_reader_counts_one_call_an_event(kernel, v5e):
    import trace_reduce
    roof = loader.load_module('readers', 'kernel_roofline')
    cfg = loader.load_json('configs', CONFIG)
    least, _ = kernel.min_seconds(v5e, **CALL)
    name = ('%{}.{} = f32[1,32768,4096]{{2,1,0:T(8,128)}} '
            'custom-call(f32[1,32768,4096]{{2,1,0}} %q), '
            'custom_call_target="tpu_custom_call"')
    # 12 events (2 steps x 6 sliding layers), each five times the least:
    # 20 %; the full layers' causal calls beside them are not counted
    events = [(name.format('window_attention', i % 6), 1e8 * i,
               5 * least * 1e9) for i in range(12)]
    events += [(name.format('causal_attention', i), 5e7 + 1e8 * i, 2e8)
               for i in range(4)]
    trace = {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': trace_reduce.OPS_LINE, 'events': events}]}]}
    ctx = {'metric': _spec(kernel), 'trace': trace, 'config': cfg,
           'batch_size': 1, 'peaks': v5e, 'log': lambda *a: None}
    assert roof.read(ctx) == pytest.approx(20.0)
    # a parent without the lane: no such event, nothing to read, no number
    trace['planes'][0]['lines'][0]['events'] = events[12:]
    assert roof.read(ctx) is None
