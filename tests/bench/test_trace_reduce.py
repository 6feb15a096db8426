"""trace_reduce on small synthetic traces: busy union, idle share, per-name
sums, gaps, clipping, the whole reduction."""
import pytest

import trace_reduce as tr


def trace(*planes):
    return {'planes': [
        {'name': name, 'lines': [{'name': ln, 'events': ev}
                                 for ln, ev in lines.items()]}
        for name, lines in planes]}


OPS = [('fusion.1', 0.0, 10.0), ('conv.2', 10.0, 5.0), ('fusion.1', 30.0, 10.0),
       ('copy.3', 35.0, 10.0)]         # the last two overlap by 5
MODS = [('jit_step(123)', 0.0, 15.0), ('jit_step(123)', 30.0, 15.0)]
ONE = trace(('/device:TPU:0', {'XLA Ops': OPS, 'XLA Modules': MODS}),
            ('/host:CPU', {'python': [('x', 0.0, 1000.0)]}))


@pytest.mark.parametrize('events,want', [
    ([], 0.0),
    ([('a', 0.0, 10.0)], 10.0),
    ([('a', 0.0, 10.0), ('b', 10.0, 5.0)], 15.0),            # touching
    ([('a', 0.0, 10.0), ('b', 5.0, 10.0)], 15.0),            # overlapping
    ([('a', 0.0, 10.0), ('b', 2.0, 3.0)], 10.0),             # nested
    ([('b', 20.0, 5.0), ('a', 0.0, 10.0)], 15.0),            # unsorted, gap
    (OPS, 30.0),
])
def test_busy_union(events, want):
    assert tr.busy_union_ns(events) == want


def test_sum_by_name_and_matching():
    assert tr.sum_by_name(OPS) == {'fusion.1': 20.0, 'conv.2': 5.0,
                                   'copy.3': 10.0}
    assert tr.sum_matching(OPS, r'^fusion') == (20.0, 2)
    assert tr.sum_matching(OPS, r'nothing') == (0.0, 0)


def test_gaps_name_what_ran_before():
    got = tr.gaps(MODS, -5.0, 50.0)
    assert got == [(-5.0, 5.0, '(window start)'), (15.0, 15.0, 'jit_step(123)'),
                   (45.0, 5.0, 'jit_step(123)')]
    assert tr.gaps([], 0.0, 7.0) == [(0.0, 7.0, '(window start)')]


def test_device_planes_leave_the_host_out():
    assert [p['name'] for p in tr.device_planes(ONE)] == ['/device:TPU:0']
    assert tr.window_of(ONE) == (0.0, 45.0)


def test_strip_fingerprint():
    assert tr.strip_fingerprint('jit_step(123)') == 'jit_step'
    assert tr.strip_fingerprint('fusion.1') == 'fusion.1'


@pytest.mark.parametrize('name,want', [
    ('%while.1 = (s32[]{:T(128)}, f32[8,4]{1,0:T(8,128)}) while((s32[]{:T(128)}'
     ') %tuple), condition=%c, body=%b', '%while.1 while'),
    ('%closed_call.22 = f32[81,176128]{1,0:T(8,128)} custom-call(s32[1,176128]'
     '{1,0:T(1,128)S(1)} %gte.1), custom_call_target="tpu_custom_call"',
     '%closed_call.22 custom-call'),
    ('%copy.611 = f32[176128,324]{1,0:T(8,128)} copy(f32[176128,324]{0,1:T(8,'
     '128)} %pad_maximum_fusion.83)', '%copy.611 copy'),
    ('fusion.1', 'fusion.1'),
])
def test_short_op_names(name, want):
    assert tr.short_op(name) == want


def test_reduce_one_chip():
    red = tr.reduce(ONE, window_s=60e-9)
    assert red['chips'] == 1
    assert red['busy_s'] == pytest.approx(30e-9)
    assert red['window_s'] == 60e-9
    assert red['module_s'] == {'jit_step': pytest.approx(30e-9)}
    assert red['modules_total_s'] == pytest.approx(30e-9)
    assert red['device_ops'][0] == ['fusion.1', pytest.approx(20e-9)]
    assert red['idle_gaps'] == [['after jit_step', pytest.approx(15e-9)]]
    # idle share as the harness' reader computes it
    assert 1 - red['busy_s'] / red['window_s'] == pytest.approx(0.5)


def test_reduce_averages_over_chips():
    two = trace(('/device:TPU:0', {'XLA Ops': OPS, 'XLA Modules': MODS}),
                ('/device:TPU:1', {'XLA Ops': [('fusion.1', 0.0, 10.0)],
                                   'XLA Modules': [('jit_step(9)', 0.0, 10.0)]}))
    red = tr.reduce(two, window_s=1.0)
    assert red['chips'] == 2
    assert red['busy_s'] == pytest.approx((30e-9 + 10e-9) / 2)
    assert red['module_s']['jit_step'] == pytest.approx((30e-9 + 10e-9) / 2)


def test_reduce_falls_back_to_modules_without_an_op_line():
    red = tr.reduce(trace(('/device:TPU:0', {'XLA Modules': MODS})), 1.0)
    assert red['busy_s'] == pytest.approx(30e-9)
    assert red['device_ops'] == []


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(trace(('/host:CPU', {'python': []})), 1.0)


def test_readers_on_a_reduced_trace():
    """device_idle and step_mfu read the reduction; a reader with nothing to
    read returns nothing (never 0)."""
    import loader
    red = tr.reduce(ONE, window_s=60e-9)
    idle = loader.load_module('readers', 'device_idle')
    assert idle.read({'reduced': red}) == pytest.approx(50.0)
    mfu = loader.load_module('readers', 'step_mfu')
    ctx = {'reduced': red, 'units': 3, 'config': {'flops_per_unit': 1000.0},
           'peaks': {'bf16_flops_per_s': 1e12}}
    assert mfu.read(ctx) == pytest.approx(100 * 3000.0 / (30e-9 * 1e12))
    assert mfu.read(dict(ctx, units=0)) is None
    roof = loader.load_module('readers', 'kernel_roofline')
    spec = {'name': 'k', 'kernel': 'raft_lookup', 'match': 'no-such-op'}
    assert roof.read({'metric': spec, 'trace': ONE}) is None
    occ = loader.load_module('readers', 'batch_occupancy')
    assert occ.read({'stages': {'model': {'occ_valid': 9, 'occ_capacity': 12}},
                     'slots': 0, 'units': 0}) == pytest.approx(75.0)
    assert occ.read({'stages': {}, 'slots': 16, 'units': 12}) == 75.0
    assert occ.read({'stages': {}, 'slots': 0, 'units': 0}) is None
    busy = loader.load_module('readers', 'span_busy')
    ctx = {'metric': {'span': 'decode+preprocess'}, 'window_s': 4.0,
           'stages': {'decode+preprocess': {'count': 3, 'total_s': 1.0}}}
    assert busy.read(ctx) == pytest.approx(25.0)
    assert busy.read(dict(ctx, stages={})) is None


def test_harness_reads_every_per_layer_metric_of_a_cell(monkeypatch,
                                                        bench_json):
    """The traced half of a run, on a synthetic trace: each of the cell's
    per-layer metrics comes out once, with its unit, and none reads 0."""
    import harness
    import loader
    lookup = ('%raft_corr_lookup_lanes.1 = f32[81,176128]{1,0} custom-call('
              's32[1,176128]{1,0} %a), custom_call_target="tpu_custom_call"')
    synthetic = trace(('/device:TPU:0', {
        'XLA Ops': [('%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)', 0.0, 4e9),
                    (lookup, 4e9, 1e9)] * 1,
        'XLA Modules': [('jit__unknown(1)', 0.0, 5e9)]}))
    monkeypatch.setattr(tr, 'find_xplane', lambda d: d)
    monkeypatch.setattr(tr, 'load_xplane', lambda p: synthetic)
    cell = {'name': 'i3d.corpus', 'bench': bench_json}
    config = loader.load_json('configs', 'i3d-two-stream-raft')
    ctx = {'workload': {}, 'config': config, 'window_s': 10.0, 'units': 144,
           'slots': 168, 'batch_size': 8, 'log': lambda *a: None,
           'peaks': harness.peaks_for('TPU v5 lite'),
           'stages': {'decode+preprocess': {'count': 48, 'total_s': 1.0}}}
    metrics, reduced = harness.per_layer_metrics(cell, ctx, 'unused')
    assert set(metrics) == {'batch_occupancy.clips', 'decode_busy.clips',
                            'device_idle.clips', 'step_mfu.clips',
                            'raft_lookup_roofline'}
    assert all(m['unit'] == '%' and m['value'] > 0 for m in metrics.values())
    assert metrics['device_idle.clips']['value'] == pytest.approx(50.0)
    assert metrics['batch_occupancy.clips']['value'] == pytest.approx(
        100 * 144 / 168)
    assert reduced['busy_s'] == pytest.approx(5.0)
    # the resnet50 cell has no kernel metric and reads the .frames ones
    cell = {'name': 'resnet50.corpus', 'bench': bench_json}
    config = loader.load_json('configs', 'resnet50-framewise')
    metrics, _ = harness.per_layer_metrics(
        cell, dict(ctx, config=config, units=9560, slots=10240), 'unused')
    assert set(metrics) == {'batch_occupancy.frames', 'decode_busy.frames',
                            'device_idle.frames', 'step_mfu.frames'}
