"""The causal-attention kernel's yardstick: operations and bytes by hand at
the two cells' shapes (latent attention's equal heads, grouped queries), the
event pattern against the name the lowered step emits, and the roofline
reader over the kernel file through the listed metric's own file."""
import json
import re

import pytest

import loader

CELL = {'positions': 8192, 'heads': 32, 'kv_heads': 32, 'qk_dim': 192,
        'v_dim': 128}
GQA_CELL = {'positions': 8192, 'heads': 32, 'kv_heads': 8, 'qk_dim': 64,
            'v_dim': 64}


@pytest.fixture(scope='module')
def kernel():
    return loader.load_module('kernels', 'causal_attention')


@pytest.fixture(scope='module')
def v5e():
    peaks = json.loads((loader.BENCH / 'peaks.json').read_text())
    return peaks['devices']['TPU v5 lite']


@pytest.mark.parametrize('shape,pairs', [
    # 4 positions: 1 + 2 + 3 + 4 visible pairs
    ({'positions': 4, 'heads': 1, 'kv_heads': 1, 'qk_dim': 3, 'v_dim': 2},
     10),
    ({'positions': 4, 'heads': 5, 'kv_heads': 5, 'qk_dim': 3, 'v_dim': 2},
     50),
    # grouped queries: every query head meets every visible key
    ({'positions': 4, 'heads': 6, 'kv_heads': 2, 'qk_dim': 3, 'v_dim': 2},
     60),
    (CELL, 8192 * 8193 // 2 * 32),
    (GQA_CELL, 8192 * 8193 // 2 * 32),
])
def test_flops_are_two_a_visible_pair_and_column(kernel, shape, pairs):
    assert kernel.flops(**shape) == 2 * pairs * (shape['qk_dim']
                                                 + shape['v_dim'])


def test_the_cells_window_layer_by_hand(kernel, v5e):
    # 33,558,528 pairs a head x 32 heads x (192 + 128) columns x 2
    assert kernel.flops(**CELL) == 687_278_653_440
    assert kernel.flops(**CELL) / 1e9 == pytest.approx(687.3, abs=0.05)
    # Q + K + V read, O written, once, float32: 8192 x 32 x 640 x 4
    assert kernel.bytes_moved(**CELL) == 671_088_640
    least, bound = kernel.min_seconds(v5e, **CELL)
    assert bound == 'flops'
    assert least * 1e3 == pytest.approx(3.49, abs=0.005)
    assert kernel.bytes_moved(**CELL) / v5e['hbm_bytes_per_s'] * 1e3 \
        == pytest.approx(0.82, abs=0.005)


def test_the_grouped_query_cells_window_layer_by_hand(kernel, v5e):
    # 33,558,528 pairs a head x 32 query heads x (64 + 64) columns x 2
    assert kernel.flops(**GQA_CELL) == 274_911_461_376
    assert kernel.flops(**GQA_CELL) / 1e9 == pytest.approx(274.9, abs=0.05)
    # Q read and O written a query head, K and V read a key-value head:
    # 8192 x (32 + 8) x 128 x 4
    assert kernel.bytes_moved(**GQA_CELL) == 167_772_160
    least, bound = kernel.min_seconds(v5e, **GQA_CELL)
    assert bound == 'flops'
    assert least * 1e3 == pytest.approx(1.395, abs=0.005)
    assert kernel.bytes_moved(**GQA_CELL) / v5e['hbm_bytes_per_s'] * 1e3 \
        == pytest.approx(0.205, abs=0.005)


@pytest.mark.parametrize('config,shape', [
    ('joyai-llm-flash-ep4', CELL),
    ('lfm2-8b-a1b-l8', GQA_CELL),
])
def test_shapes_come_from_the_configuration_and_the_shipped_yml(
        kernel, config, shape):
    cfg = loader.load_json('configs', config)
    # neither cell spells a window: the program's shipped configs/lm.yml
    assert not {'stack_size', 'patch_grid'} & set(cfg['overrides'])
    assert kernel.window_positions(cfg) == 32 * 16 ** 2
    # one event is one window of one layer: the batch does not enter
    assert kernel.shapes(cfg, 4) == kernel.shapes(cfg, 1) == shape


def test_a_configuration_that_spells_its_window_is_read_at_that_window(
        kernel):
    # brumby's cell runs windows of 32 x 32^2 ids; a grouped-query trunk at
    # its widths would be sized there, not at the yml's 8,192
    cfg = loader.load_json('configs', 'brumby-14b-l4')
    assert kernel.window_positions(cfg) == 32768
    assert kernel.shapes(cfg, 1) == {
        'positions': 32768, 'heads': 40, 'kv_heads': 8, 'qk_dim': 128,
        'v_dim': 128}
    # the head's width where the configuration spells none: hidden / heads
    lfm2 = loader.load_json('configs', 'lfm2-8b-a1b-l8')
    assert 'head_dim' not in lfm2
    assert lfm2['hidden_size'] // lfm2['num_attention_heads'] == 64
    assert lfm2['assumed']['head_dim'].startswith(
        'hidden_size / num_attention_heads = 64')


def _spec(kernel):
    """The metric's own file: its pattern and count are the kernel file's."""
    spec = loader.load_json('metrics', 'causal_attention_roofline')
    assert (spec['reader'], spec['kernel'], spec['match'],
            spec['events_per_call']) == (
        'kernel_roofline', 'causal_attention', kernel.EVENT_MATCH,
        kernel.EVENTS_PER_CALL)
    assert spec['workloads'] == ['joyai-flash.corpus', 'lfm2-moe.corpus']
    return spec


def test_the_event_pattern_matches_the_lowered_kernel_and_nothing_else(
        kernel):
    rx = re.compile(kernel.EVENT_MATCH)
    # an op event is named by its whole HLO instruction, and the compiler
    # names a Mosaic call after pallas_call(name=...): the form it gave the
    # kernel compiled for a described v5e (PERF.md section 6, PR 30)
    mine = ('%causal_attention.1 = f32[1,8192,4096]{2,1,0:T(8,128)} '
            'custom-call(f32[1,32,8192,128]{3,2,1,0:T(8,128)} %fusion.2), '
            'custom_call_target="tpu_custom_call", operand_layout_'
            'constraints={f32[1,32,8192,128]{3,2,1,0}}')
    lookup = ('%raft_corr_lookup_lanes.19 = f32[81,176128]{1,0:T(8,128)} '
              'custom-call(s32[1,176128]{1,0} %a), custom_call_target='
              '"tpu_custom_call"')
    loop = ('%while.1055 = (s32[], f32[4,8192,2048]) while(%tuple.9), '
            'condition=%cond, body=%causal_attention_body')
    assert rx.search(mine)
    assert not rx.search(lookup) and not rx.search(loop)
    # and the program's name for the kernel is the one matched
    from video_features_tpu.ops import pallas_attention
    assert mine.startswith(f'%{pallas_attention.NAME}.')
    assert kernel.EVENTS_PER_CALL == 1


@pytest.mark.parametrize('config,shape', [
    ('joyai-llm-flash-ep4', CELL),
    ('lfm2-8b-a1b-l8', GQA_CELL),
])
def test_the_roofline_reader_counts_one_call_an_event(kernel, v5e, config,
                                                      shape):
    import trace_reduce
    roof = loader.load_module('readers', 'kernel_roofline')
    cfg = loader.load_json('configs', config)
    least, _ = kernel.min_seconds(v5e, **shape)
    name = ('%causal_attention.{} = f32[1,8192,4096]{{2,1,0:T(8,128)}} '
            'custom-call(f32[1,32,8192,128]{{3,2,1,0}} %q), '
            'custom_call_target="tpu_custom_call"')
    # 20 events (4 windows x 5 layers), each five times the least: 20 %
    events = [(name.format(i % 5), 1e8 * i, 5 * least * 1e9)
              for i in range(20)]
    events.append(('%while.1055 = (s32[]) while(%t), body=%b', 0.0, 9e9))
    trace = {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': trace_reduce.OPS_LINE, 'events': events}]}]}
    ctx = {'metric': _spec(kernel), 'trace': trace, 'config': cfg,
           'batch_size': 4, 'peaks': v5e, 'log': lambda *a: None}
    assert roof.read(ctx) == pytest.approx(20.0)
    # a parent without the kernel: no event, nothing to read, no number
    trace['planes'][0]['lines'][0]['events'] = events[-1:]
    assert roof.read(ctx) is None
