"""BENCHMARK.json and the files under benchmark/ agree, and every name and
unit uses only the characters the contract allows."""
import json
import re

import pytest

from .conftest import BENCH, REPO

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
PATH = re.compile(r'^[A-Za-z0-9_.\-/]{1,200}$')
KEYS = {'command', 'paths', 'run_seconds', 'configs', 'workloads',
        'end_to_end', 'per_layer'}
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}
WIDTH = re.compile(r'(_dim|_rank)$|hidden|intermediate|latent|state|proj|'
                   r'head|expan|experts_per')


def line(text):
    return 1 <= len(text) <= 200 and '\n' not in text and '\t' not in text


def test_top_level_keys_and_sizes(bench_json):
    assert set(bench_json) == KEYS
    assert len((REPO / 'BENCHMARK.json').read_bytes()) <= 64 * 1024
    assert isinstance(bench_json['run_seconds'], int)
    assert 1 <= bench_json['run_seconds'] <= 51
    assert 1 <= len(bench_json['command']) <= 32
    assert all(line(w) for w in bench_json['command'])
    assert not any(w.startswith('/') or '..' in w
                   for w in bench_json['command'])


def test_paths_hold_the_benchmark_and_the_command_lives_there(bench_json):
    paths = bench_json['paths']
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and (REPO / p).is_dir()
    files = [w for w in bench_json['command'] if (REPO / w).is_file()]
    assert files and all(any(f.startswith(p + '/') for p in paths)
                         for f in files)


def test_every_file_under_paths_is_named_from_allowed_characters(bench_json):
    for p in bench_json['paths']:
        for f in (REPO / p).rglob('*'):
            if '__pycache__' in f.parts or f.suffix == '.pyc':
                continue
            assert PATH.match(str(f.relative_to(REPO))), f


def test_configs(bench_json):
    configs = bench_json['configs']
    assert 1 <= len(configs) <= 24
    names = [c['name'] for c in configs]
    assert len(set(names)) == len(names)
    files = [c['file'] for c in configs]
    assert len(set(files)) == len(files)
    used = {w['config'] for w in bench_json['workloads']}
    for c in configs:
        assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
        assert NAME.match(c['name']) and c['name'] in used
        assert line(c['source']) and line(c['why'])
        assert c['file'] == f'benchmark/configs/{c["name"]}.json'
        body = json.loads((REPO / c['file']).read_text())
        assert body['name'] == c['name']
        assert body['source'] == c['source']
        assert body['reduced'] == c['reduced']
        assert len(c['reduced']) <= 16
        assert not any(WIDTH.search(k) for k in c['reduced'])
        # its plain reference lies beside it, and is what it names
        assert (BENCH / 'references' / f'{body["reference"]}.py').is_file()
        assert body['flops_per_unit'] > 0
        assert body['control_overrides']


def test_workloads(bench_json):
    cells = bench_json['workloads']
    assert 1 <= len(cells) <= 24
    names = [w['name'] for w in cells]
    assert len(set(names)) == len(names)
    pairs = [(w['config'], w['traffic']) for w in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c['name'] for c in bench_json['configs']}
    four = sum(1 for w in cells if w['chips'] == 4)
    assert four <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert NAME.match(w['name']) and NAME.match(w['traffic'])
        assert w['config'] in configs and w['chips'] in (1, 4)
        assert line(w['why'])
        body = json.loads((BENCH / 'workloads' / f'{w["name"]}.json')
                          .read_text())
        assert body['config'] == w['config']
        assert body['traffic'] == w['traffic']
        assert (BENCH / 'drivers' / f'{body["driver"]}.py').is_file()
        traffic = json.loads((BENCH / 'traffic' / f'{w["traffic"]}.json')
                             .read_text())
        assert traffic['kind'] == 'corpus'
        for key in ('videos_failed', 'rows_off', 'nonfinite', 'rel_l2',
                    'row_rel_l2_max'):
            assert key in body['limits']
        # the exact comparisons have the limit 0, the others a measured one
        assert body['limits']['videos_failed'] == 0
        assert body['limits']['rows_off'] == 0
        assert body['limits']['nonfinite'] == 0
        assert 0 < body['limits']['rel_l2'] < 0.1
        assert 0 < body['limits']['row_rel_l2_max'] < 0.1


def test_end_to_end_metrics(bench_json):
    metrics = bench_json['end_to_end']
    assert 1 <= len(metrics) <= 16
    names = [m['name'] for m in metrics]
    assert 'setup_s' in names and len(set(names)) == len(names)
    cells = {w['name'] for w in bench_json['workloads']}
    for m in metrics:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                          'source'}
        assert NAME.match(m['name']) and UNIT.match(m['unit'])
        assert m['better'] in ('lower', 'higher')
        assert m['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= m['bound'] <= 0.1
        assert set(m.get('workloads', cells)) <= cells
    for cell in cells:      # set-up and at least one other metric, per cell
        mine = [m for m in metrics if cell in m.get('workloads', cells)]
        assert 'setup_s' in [m['name'] for m in mine] and len(mine) >= 2


def test_per_layer_metrics_have_their_files(bench_json):
    metrics = bench_json['per_layer']
    assert 1 <= len(metrics) <= 128
    e2e = {m['name']: m for m in bench_json['end_to_end']}
    cells = {w['name'] for w in bench_json['workloads']}
    names = [m['name'] for m in metrics]
    assert len(set(names)) == len(names) and not set(names) & set(e2e)
    for m in metrics:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'source',
                                          'layer', 'moves'}
        assert NAME.match(m['name']) and UNIT.match(m['unit'])
        assert m['source'] in SOURCES and m['better'] in ('lower', 'higher')
        assert line(m['layer']) and m['moves'] in e2e
        spec = json.loads((BENCH / 'metrics' / f'{m["name"]}.json')
                          .read_text())
        for key in ('name', 'unit', 'better', 'source', 'layer', 'moves'):
            assert spec[key] == m[key], (m['name'], key)
        assert spec.get('workloads') == m.get('workloads')
        assert (BENCH / 'readers' / f'{spec["reader"]}.py').is_file()
        # each cell that reports it reports the metric it moves
        moved = e2e[m['moves']]
        assert set(m.get('workloads', moved.get('workloads', cells))) \
            <= set(moved.get('workloads', cells))
        if m['name'].endswith('_roofline') or 'mfu' in m['name']:
            assert m['unit'] == '%'
    # every cell reports at least one per-layer metric
    import harness
    for cell in cells:
        c = {'name': cell, 'bench': bench_json}
        assert harness.metrics_of(c, 'per_layer')
    # beside every kernel's roofline stands the whole step's mfu
    for m in metrics:
        if m['name'].endswith('_roofline'):
            assert any('mfu' in re.split(r'[_.]', o['name'])
                       and o['moves'] == m['moves'] for o in metrics)


def test_no_stray_metric_files(bench_json):
    listed = {m['name'] for m in bench_json['per_layer']}
    on_disk = {f.stem for f in (BENCH / 'metrics').glob('*.json')}
    assert on_disk == listed
    cells = {w['name'] for w in bench_json['workloads']}
    assert {f.stem for f in (BENCH / 'workloads').glob('*.json')} == cells
    configs = {c['name'] for c in bench_json['configs']}
    assert {f.stem for f in (BENCH / 'configs').glob('*.json')} == configs


@pytest.mark.parametrize('cell,metric_names', [
    ('i3d.corpus', {'clips_per_s', 'setup_s'}),
    ('resnet50.corpus', {'frames_per_s', 'setup_s'}),
])
def test_cells_report_their_end_to_end_metrics(bench_json, cell,
                                               metric_names):
    import harness
    got = harness.metrics_of({'name': cell, 'bench': bench_json},
                             'end_to_end')
    assert {m['name'] for m in got} == metric_names
