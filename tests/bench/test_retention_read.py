"""The state-read kernel's yardstick: operations and bytes by hand at the
cell's shapes, the event pattern against the name the compiled scan emits,
and the roofline reader over the kernel file."""
import json
import re

import pytest

import loader

CELL = {'positions': 512, 'heads': 40, 'kv_heads': 8, 'd': 128, 'v_dim': 128}


@pytest.fixture(scope='module')
def kernel():
    return loader.load_module('kernels', 'retention_read')


@pytest.fixture(scope='module')
def v5e():
    peaks = json.loads((loader.BENCH / 'peaks.json').read_text())
    return peaks['devices']['TPU v5 lite']


@pytest.mark.parametrize('shape,macs', [
    # d = 2: a feature map of 3; 4 positions × 1 head × 3 features × 5 values
    ({'positions': 4, 'heads': 1, 'kv_heads': 1, 'd': 2, 'v_dim': 5}, 60),
    # 6 query heads over 2 key-value heads: the rows are the query heads'
    ({'positions': 4, 'heads': 6, 'kv_heads': 2, 'd': 2, 'v_dim': 5}, 360),
    (CELL, 40 * 512 * 8256 * 128),
])
def test_flops_are_two_a_row_and_state_entry(kernel, shape, macs):
    assert kernel.flops(**shape) == 2 * macs


def test_the_cells_chunk_layer_by_hand(kernel, v5e):
    assert kernel.feature_dim(128) == 8256
    # 2 · 40 · 512 · 8,256 · 128
    assert kernel.flops(**CELL) == 43_285_217_280
    # q 40 × 512 × 128, the state 8 × 8,256 × 128, the output as q: float32
    assert kernel.bytes_moved(**CELL) == (2 * 2_621_440 + 8_454_144) * 4
    assert kernel.bytes_moved(**CELL) == 54_788_096
    least, bound = kernel.min_seconds(v5e, **CELL)
    assert bound == 'flops'
    # one pass: 0.22 ms; the three passes of precision=mixed: 0.66 ms
    assert least * 1e3 == pytest.approx(0.2197, abs=5e-4)
    assert 3 * least * 1e3 == pytest.approx(0.659, abs=1e-3)
    assert kernel.bytes_moved(**CELL) / v5e['hbm_bytes_per_s'] * 1e3 \
        == pytest.approx(0.0669, abs=5e-4)
    # φ(q), which no one has to move: 676 MB a call
    assert 40 * 512 * 8256 * 4 == 676_331_520


def test_shapes_come_from_the_configuration_and_the_programs_chunk(kernel):
    from video_features_tpu.models.retention_trunk import RETENTION_CHUNK
    cfg = loader.load_json('configs', 'brumby-14b-l4')
    assert kernel.chunk_positions(cfg) == RETENTION_CHUNK == 512
    # one event is one chunk of one layer of one window: no batch in it
    assert kernel.shapes(cfg, 4) == kernel.shapes(cfg, 1) == CELL
    # a window shorter than the chunk is one chunk
    short = dict(cfg, overrides=dict(cfg['overrides'], stack_size=1,
                                     patch_grid=16))
    assert kernel.chunk_positions(short) == 256


def _spec(kernel):
    """The metric's own file: its pattern and count are the kernel file's."""
    spec = loader.load_json('metrics', 'retention_read_roofline')
    assert (spec['reader'], spec['kernel'], spec['match'],
            spec['events_per_call']) == (
        'kernel_roofline', 'retention_read', kernel.EVENT_MATCH,
        kernel.EVENTS_PER_CALL)
    assert spec['workloads'] == ['brumby.corpus']
    return spec


def test_the_event_pattern_matches_the_read_and_nothing_else(kernel):
    rx = re.compile(kernel.EVENT_MATCH)
    # the forms the compiler gave the scan's two Mosaic calls, compiled for
    # a described v5e (PERF.md section 6, PR 32)
    mine = ('%retention_read.4 = f32[8,2560,128]{2,1,0:T(8,128)S(1)} '
            'custom-call(%bitcast.91, %copy-done), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={'
            'f32[8,2560,128]{2,1,0}, f32[8,8256,128]{2,1,0}}')
    update = ('%retention_update.4 = f32[8,8256,128]{2,1,0:T(8,128)S(1)} '
              'custom-call(%bitcast.97, %bitcast_multiply_fusion.2), '
              'custom_call_target="tpu_custom_call"')
    attention = ('%causal_attention.1 = f32[1,8192,4096]{2,1,0:T(8,128)} '
                 'custom-call(%fusion.2), custom_call_target='
                 '"tpu_custom_call"')
    loop = ('%while.44 = (s32[], f32[8,8256,128]) while(%tuple.9), '
            'condition=%cond, body=%retention_read_body')
    assert rx.search(mine)
    assert not any(rx.search(x) for x in (update, attention, loop))
    # and the program's name for the kernel is the one matched
    from video_features_tpu.ops import pallas_retention
    assert mine.startswith(f'%{pallas_retention.READ_NAME}.')
    assert update.startswith(f'%{pallas_retention.UPDATE_NAME}.')
    assert kernel.EVENTS_PER_CALL == 1


def test_the_roofline_reader_counts_one_call_an_event(kernel, v5e):
    import trace_reduce
    roof = loader.load_module('readers', 'kernel_roofline')
    cfg = loader.load_json('configs', 'brumby-14b-l4')
    least, _ = kernel.min_seconds(v5e, **CELL)
    name = ('%retention_read.{} = f32[8,2560,128]{{2,1,0:T(8,128)S(1)}} '
            'custom-call(%q, %s), custom_call_target="tpu_custom_call"')
    # 256 events (64 chunks × 4 layers), each four times the least: 25 %
    events = [(name.format(i % 4), 1e7 * i, 4 * least * 1e9)
              for i in range(256)]
    events.append(('%retention_update.4 = f32[8,8256,128]{2,1,0} '
                   'custom-call(%k, %v), custom_call_target='
                   '"tpu_custom_call"', 0.0, 9e9))
    trace = {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': trace_reduce.OPS_LINE, 'events': events}]}]}
    ctx = {'metric': _spec(kernel), 'trace': trace, 'config': cfg,
           'batch_size': 1, 'peaks': v5e, 'log': lambda *a: None}
    assert roof.read(ctx) == pytest.approx(25.0)
    # a parent without the kernel: no event, nothing to read, no number
    trace['planes'][0]['lines'][0]['events'] = events[-1:]
    assert roof.read(ctx) is None
