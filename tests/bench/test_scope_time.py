"""The scope_time reader on synthetic traces: self-time arithmetic, the join
of op events to modules to the program's instruction → scope map, nested
scopes, every refusal, the one shared table, and the five metrics of
``i3d.corpus`` it is written for. ``BENCHMARK.json`` does not list them yet:
``tests/bench/test_lfm2_moe.py`` holds ``moe_walk_fill.clips`` to the last
place of ``per_layer`` and a PR that is not a ``benchmark`` PR may only append
(PERF.md §7 row 11). ``SPECS`` below is what that PR writes out as
``benchmark/metrics/<name>.json`` and, less ``reader`` / ``scope`` / ``what``,
as ``per_layer`` entries."""
import copy
import re

import pytest

import loader

from .test_trace_reduce import trace

st = loader.load_module('readers', 'scope_time')

S = 1e9      # a second of trace time, in ns


def hlo(name, op='fusion'):
    """An op event's name, as the device trace spells it."""
    return f'{name} = f32[8]{{0}} {op}(f32[8]{{0}} %p.1), kind=kLoop'


# one step: a while covering two body ops and a gap, then a tower fusion
#   %while.3     0..10 s   (covers %fusion.1 1..4, %fusion.2 5..8: self 4)
#   %fusion.9   10..12 s
STEP_OPS = [(hlo('%while.3', 'while'), 0.0, 10 * S),
            (hlo('%fusion.1'), 1 * S, 3 * S),
            (hlo('%fusion.2'), 5 * S, 3 * S),
            (hlo('%fusion.9'), 10 * S, 2 * S)]
STEP = trace(('/device:TPU:0', {'XLA Ops': STEP_OPS,
                                'XLA Modules': [('jit_step(77)', 0.0, 12 * S)]}))
STEP_MAP = {'jit_step': {'instructions': {
    '%while.3': 'raft_update', '%fusion.1': 'raft_update/raft_lookup',
    '%fusion.2': 'raft_update/raft_gru', '%fusion.9': 'i3d_towers',
    '%fusion.77': ''}, 'missing': [], 'variants': 1}}


def test_self_time_of_a_while_is_its_gaps():
    got = {n.split(' ')[0]: own for n, _, own in st.self_times(STEP_OPS)}
    assert got == {'%while.3': 4 * S, '%fusion.1': 3 * S, '%fusion.2': 3 * S,
                   '%fusion.9': 2 * S}
    # self times sum to the union, whatever nests
    assert sum(got.values()) == 12 * S
    # an event that outlives the one it starts in keeps its whole duration
    overlap = [('a', 0.0, 10.0), ('b', 5.0, 10.0)]
    assert [own for _, _, own in st.self_times(overlap)] == [5.0, 10.0]
    # a grandchild is taken from its parent alone
    nest = [('a', 0.0, 10.0), ('b', 2.0, 6.0), ('c', 3.0, 2.0)]
    assert [own for _, _, own in st.self_times(nest)] == [4.0, 4.0, 2.0]


def test_the_gap_goes_to_the_whiles_scope_and_nested_scopes_count_in_both():
    got = st.attribute(STEP, STEP_MAP)
    assert got['paths'] == {'raft_update': 4.0,
                            'raft_update/raft_lookup': 3.0,
                            'raft_update/raft_gru': 3.0, 'i3d_towers': 2.0}
    assert got['scopes'] == {'raft_update': 10.0, 'raft_lookup': 3.0,
                             'raft_gru': 3.0, 'i3d_towers': 2.0,
                             st.UNSCOPED: 0.0}
    assert got['unmapped_s'] == got['outside_s'] == 0.0
    assert got['in_program_gaps_s'] == 0.0
    assert got['programs'] == {'jit_step'} and got['events'] == 4


def test_two_programs_reuse_an_instruction_name_under_different_scopes():
    two = trace(('/device:TPU:0', {
        'XLA Ops': [(hlo('%fusion.1'), 0.0, 2 * S),
                    (hlo('%fusion.1'), 4 * S, 3 * S)],
        'XLA Modules': [('jit_step(1)', 0.0, 2 * S),
                        ('jit_other(2)', 4 * S, 4 * S)]}))
    maps = dict(STEP_MAP, jit_other={
        'instructions': {'%fusion.1': 'moe'}, 'missing': []})
    got = st.attribute(two, maps)
    assert got['scopes']['raft_lookup'] == 2.0 and got['scopes']['moe'] == 3.0
    assert got['in_program_gaps_s'] == 1.0      # jit_other's last second


def test_an_event_outside_every_module_event_is_in_no_row():
    ops = STEP_OPS + [(hlo('%fusion.1'), 13 * S, 0.1 * S),    # after it
                      (hlo('%fusion.2'), -1 * S, 0.05 * S)]   # before it
    got = st.attribute(trace(('/device:TPU:0', {
        'XLA Ops': ops, 'XLA Modules': [('jit_step(77)', 0.0, 12 * S)]})),
        STEP_MAP)
    assert got['outside_s'] == pytest.approx(0.15)
    assert got['scopes']['raft_lookup'] == 3.0
    assert got['in_program_gaps_s'] == pytest.approx(0.0)


def test_unscoped_compiler_inserted_and_a_little_unmapped_time_are_rows():
    """Three kinds of leftover, three rows: in the map under no scope; in the
    module without an op_name (a copy the compiler inserted: the record names
    it); in neither (under 1 %: a row, over it: a refusal)."""
    ops = STEP_OPS + [(hlo('%fusion.77'), 12 * S, 0.5 * S),    # in the map: ''
                      (hlo('%copy.782', 'copy'), 12.5 * S, 0.3 * S),
                      (hlo('%copy.5', 'copy'), 12.8 * S, 0.1 * S)]  # unknown
    maps = {'jit_step': dict(STEP_MAP['jit_step'], no_metadata=['%copy.782'])}
    got = st.attribute(trace(('/device:TPU:0', {
        'XLA Ops': ops, 'XLA Modules': [('jit_step(77)', 0.0, 13 * S)]})),
        maps)
    assert got['scopes'][st.UNSCOPED] == 0.5
    assert got['no_op_name_s'] == pytest.approx(0.3)
    assert got['unmapped_s'] == pytest.approx(0.1)
    assert '' not in got['paths']


def test_planes_are_averaged():
    both = trace(
        ('/device:TPU:0', {'XLA Ops': STEP_OPS,
                           'XLA Modules': [('jit_step(77)', 0.0, 12 * S)]}),
        ('/device:TPU:1', {'XLA Ops': [(hlo('%fusion.9'), 0.0, 4 * S)],
                           'XLA Modules': [('jit_step(77)', 0.0, 4 * S)]}))
    got = st.attribute(both, STEP_MAP)
    assert got['scopes']['i3d_towers'] == 3.0       # (2 + 4) / 2
    assert got['scopes']['raft_update'] == 5.0


REFUSALS = {
    'no map noted (a parent commit, a synthetic trace)':
        (STEP, {}, 144, 'no scope map is noted'),
    'missing is not empty: the cache served older metadata':
        (STEP, {'jit_step': dict(STEP_MAP['jit_step'],
                                 missing=['raft_gru'])}, 144,
         'older program'),
    'events not in the map hold over 1 %':
        (STEP, {'jit_step': {'instructions': {'%while.3': 'raft_update'},
                             'missing': []}}, 144, 'not found in the map'),
    'self times do not sum to the union':
        (trace(('/device:TPU:0', {
            'XLA Ops': [(hlo('%fusion.1'), 0.0, -2 * S),
                        (hlo('%fusion.9'), 0.0, 3 * S)],
            'XLA Modules': [('jit_step(77)', 0.0, 4 * S)]})),
         STEP_MAP, 144, 'self times sum to'),
    'no unit was saved':
        (STEP, STEP_MAP, 0, 'no unit was saved'),
    'two executables of one program':
        (STEP, {'jit_step': dict(STEP_MAP['jit_step'], variants=2)}, 144,
         'different executables'),
}


@pytest.mark.parametrize('case', sorted(REFUSALS))
def test_refusals_give_no_number_and_say_why(case, monkeypatch):
    synthetic, maps, units, why = REFUSALS[case]
    logged = []
    monkeypatch.setattr(st, '_noted', lambda: maps)
    monkeypatch.setattr(st, '_memo', {})
    ctx = {'trace': synthetic, 'units': units, 'metric': {'scope': 'raft_gru'},
           'reduced': {'modules_total_s': 12.0},
           'log': lambda *a: logged.append(' '.join(map(str, a)))}
    assert st.read(ctx) is None
    assert len(logged) == 1 and 'no number' in logged[0] and why in logged[0]


def test_a_program_without_the_module_gives_nothing_and_does_not_raise(
        monkeypatch):
    """The parent commit: ``obs.scopes`` is not there to import."""
    import builtins
    real = builtins.__import__

    def no_scopes(name, globals=None, locals=None, fromlist=(), level=0):
        if name == 'video_features_tpu.obs' and 'scopes' in (fromlist or ()):
            raise ImportError('a parent has no obs.scopes')
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, '__import__', no_scopes)
    assert st._noted() == {}


FIVE = ('raft_update_ms.clips', 'raft_lookup_ms.clips', 'raft_gru_ms.clips',
        'i3d_towers_ms.clips', 'unscoped_ms.clips')
SPECS = {name: loader.load_json('metrics', name) for name in FIVE}
LM_SCOPES = {
    'mla_ms.clips': ('mla', {'joyai-flash.corpus'}),
    'moe_ms.clips': ('moe', {'joyai-flash.corpus', 'lfm2-moe.corpus'}),
    'dense_mlp_ms.clips': ('dense_mlp', {'joyai-flash.corpus',
                                         'brumby.corpus', 'lfm2-moe.corpus'}),
    'retention_ms.clips': ('retention', {'brumby.corpus'}),
    'attention_ms.clips': ('attention', {'lfm2-moe.corpus'}),
    'short_conv_ms.clips': ('short_conv', {'lfm2-moe.corpus'}),
}


def test_the_five_metrics_share_one_table(monkeypatch):
    logged, calls = [], []
    real = st.attribute
    monkeypatch.setattr(st, 'attribute',
                        lambda *a: calls.append(1) or real(*a))
    monkeypatch.setattr(st, '_noted', lambda: STEP_MAP)
    monkeypatch.setattr(st, '_memo', {})
    ctx = {'trace': STEP, 'units': 4, 'reduced': {'modules_total_s': 12.0},
           'log': lambda *a: logged.append(' '.join(map(str, a)))}
    specs = SPECS
    assert len(specs) == 5
    got = {name: st.read(dict(ctx, metric=spec))
           for name, spec in specs.items()}
    assert got == {'raft_update_ms.clips': 2500.0,
                   'raft_lookup_ms.clips': 750.0, 'raft_gru_ms.clips': 750.0,
                   'i3d_towers_ms.clips': 500.0, 'unscoped_ms.clips': 0.0}
    assert calls == [1]                            # one table for the five
    # ... logged whole, once: every path, the two leftover rows, the sum
    text = '\n'.join(logged)
    for row in ('raft_update/raft_lookup', 'raft_update/raft_gru',
                'i3d_towers', st.UNSCOPED, st.NO_OP_NAME, st.UNMAPPED):
        assert text.count(f'  {row}\n') >= 1, row
    assert 'rows sum to 12.0000 s = 100.00 % of modules_total_s' in text
    assert '1000.000 ms/unit' in text       # raft_update's own 4 s over 4
    # a scope no event ran under: nothing to read, not 0
    assert st.read(dict(ctx, metric={'scope': 'moe'})) is None


def test_the_five_files_name_the_programs_scopes_and_the_cells_that_run_them(
        bench_json):
    from video_features_tpu.obs.scopes import SCOPES
    assert {s['scope'] for s in SPECS.values()} == {
        'raft_update', 'raft_lookup', 'raft_gru', 'i3d_towers', st.UNSCOPED}
    assert {s['scope'] for s in SPECS.values()} - {st.UNSCOPED} <= set(SCOPES)
    clips = [m for m in bench_json['end_to_end']
             if m['name'] == 'clips_per_s'][0]
    for name, spec in SPECS.items():
        assert (spec['unit'], spec['better'], spec['source'], spec['moves'],
                spec['reader']) == ('ms/clip', 'lower', 'device_trace',
                                    'clips_per_s', 'scope_time')
        assert len(spec['what']) <= 200
        # what is not the program's own scope is every clips cell's to report
        assert set(spec['workloads']) == (
            set(clips['workloads']) if name == 'unscoped_ms.clips'
            else {'i3d.corpus'})


@pytest.mark.parametrize('name', sorted(LM_SCOPES))
def test_an_lm_scope_metric_is_listed_where_a_trunk_opens_the_scope(
        name, bench_json):
    """One file a scope, the cells whose trunk opens it in ``workloads``; the
    entry in BENCHMARK.json says the same (test_benchmark_json holds every
    entry to its file)."""
    from video_features_tpu.obs.scopes import SCOPES
    scope, cells = LM_SCOPES[name]
    spec = loader.load_json('metrics', name)
    assert scope in SCOPES
    assert (spec['reader'], spec['scope'], spec['unit'], spec['better'],
            spec['source'], spec['moves'], spec['layer']) == (
        'scope_time', scope, 'ms/clip', 'lower', 'device_trace',
        'clips_per_s', 'device step')
    assert set(spec['workloads']) == cells
    entry = [m for m in bench_json['per_layer'] if m['name'] == name][0]
    assert set(entry['workloads']) == cells


def test_the_cell_reads_the_five_through_the_harness(monkeypatch, bench_json):
    """The traced half of a run of i3d.corpus on a synthetic trace, the
    program's map noted: the five come out in ms/clip beside the cell's old
    metrics; without a map (a parent) the old set comes out alone."""
    import harness
    import trace_reduce as tr
    lookup = ('%raft_corr_lookup_lanes.1 = f32[81,176128]{1,0} custom-call('
              's32[1,176128]{1,0} %a), custom_call_target="tpu_custom_call"')
    synthetic = trace(('/device:TPU:0', {
        'XLA Ops': STEP_OPS + [(lookup, 1.5 * S, 1 * S)],
        'XLA Modules': [('jit_step(77)', 0.0, 12 * S)]}))
    maps = {'jit_step': dict(STEP_MAP['jit_step'], instructions=dict(
        STEP_MAP['jit_step']['instructions'],
        **{'%raft_corr_lookup_lanes.1': 'raft_update/raft_lookup'}))}
    monkeypatch.setattr(tr, 'find_xplane', lambda d: d)
    monkeypatch.setattr(tr, 'load_xplane', lambda p: synthetic)
    monkeypatch.setattr(st, '_memo', {})
    cell = {'name': 'i3d.corpus', 'bench': bench_json}
    config = loader.load_json('configs', 'i3d-two-stream-raft')
    ctx = {'workload': {}, 'config': config, 'window_s': 20.0, 'units': 144,
           'slots': 168, 'batch_size': 8, 'log': lambda *a: None,
           'peaks': harness.peaks_for('TPU v5 lite'),
           'stages': {'decode+preprocess': {'count': 48, 'total_s': 1.0}}}
    old = {'batch_occupancy.clips', 'decode_busy.clips', 'device_idle.clips',
           'step_mfu.clips', 'raft_lookup_roofline'}
    monkeypatch.setattr(st, '_noted', lambda: maps)
    metrics, reduced = harness.per_layer_metrics(cell, ctx, 'unused')
    assert set(metrics) == old | set(SPECS)
    assert metrics['raft_lookup_ms.clips'] == {
        'value': pytest.approx(1e3 * 3.0 / 144), 'unit': 'ms/clip'}
    # ... and the breakdown names device time by scope path, largest first
    assert reduced['device_ops'] == [
        ['raft_update', 4.0], ['raft_update/raft_lookup', 3.0],
        ['raft_update/raft_gru', 3.0], ['i3d_towers', 2.0]]
    monkeypatch.setattr(st, '_noted', lambda: {})
    monkeypatch.setattr(st, '_memo', {})
    metrics, reduced = harness.per_layer_metrics(cell, ctx, 'unused')
    assert set(metrics) == old
    # no map: the HLO names, as before
    assert reduced['device_ops'][0] == ['%while.3 while', 10.0]


def test_device_ops_by_scope_path_ranks_the_leftovers_too_and_keeps_ten(
        monkeypatch):
    """``breakdown.device_ops`` where a map is noted: self times by scope
    path with ``(unscoped)``, the compiler's copies and the unmapped rest as
    rows among them, largest first, rows of nothing left out, at most
    ``top``; ``None`` where the join refuses."""
    ops = STEP_OPS + [(hlo('%fusion.77'), 12 * S, 3.5 * S),    # in the map: ''
                      (hlo('%copy.782', 'copy'), 15.5 * S, 0.3 * S),
                      (hlo('%copy.5', 'copy'), 15.8 * S, 0.1 * S)]  # unknown
    synthetic = trace(('/device:TPU:0', {
        'XLA Ops': ops, 'XLA Modules': [('jit_step(77)', 0.0, 16 * S)]}))
    maps = {'jit_step': dict(STEP_MAP['jit_step'], no_metadata=['%copy.782'])}
    monkeypatch.setattr(st, '_noted', lambda: maps)
    monkeypatch.setattr(st, '_memo', {})
    got = st.device_ops(synthetic)
    assert [name for name, _ in got] == [
        'raft_update', st.UNSCOPED, 'raft_update/raft_lookup',
        'raft_update/raft_gru', 'i3d_towers', st.NO_OP_NAME, st.UNMAPPED]
    assert [s for _, s in got] == pytest.approx(
        [4.0, 3.5, 3.0, 3.0, 2.0, 0.3, 0.1])
    assert st.device_ops(synthetic, top=2) == got[:2]
    # the metrics and the breakdown share one join of the trace
    calls = []
    real = st.attribute
    monkeypatch.setattr(st, 'attribute',
                        lambda *a: calls.append(1) or real(*a))
    monkeypatch.setattr(st, '_memo', {})
    ctx = {'trace': synthetic, 'units': 4, 'log': lambda *a: None,
           'reduced': {'modules_total_s': 16.0},
           'metric': {'scope': 'raft_gru'}}
    assert st.read(ctx) == 750.0 and st.device_ops(synthetic) == got
    assert calls == [1]
    # a parent, a stale cache, a lost op line: no rows, the harness keeps
    # the HLO names; and so where the program opens no scope (resnet's step)
    monkeypatch.setattr(st, '_noted', lambda: {})
    monkeypatch.setattr(st, '_memo', {})
    assert st.device_ops(synthetic) is None
    bare = {'jit_step': {'instructions': dict.fromkeys(
        STEP_MAP['jit_step']['instructions'], ''), 'missing': []}}
    monkeypatch.setattr(st, '_noted', lambda: bare)
    monkeypatch.setattr(st, '_memo', {})
    assert st.attribute(STEP, bare)['scopes'] == {st.UNSCOPED: 12.0}
    assert st.device_ops(STEP) is None
