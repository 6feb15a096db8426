"""BENCHMARK.json and the files under benchmark/ agree, every name and unit
uses only the characters the contract allows, and a later PR's appends fit:
no test under tests/bench depends on an entry's place or on how many there
are."""
import ast
import copy
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


# -- the gate: a later PR appends, and nothing that is there may mind --------

LISTS = ('configs', 'workloads', 'per_layer')


def test_an_appended_configuration_cell_and_metric_change_no_other_cell(
        bench_json):
    """What a program PR may do to BENCHMARK.json: append a configuration, a
    one-chip cell that reports ``clips_per_s`` and a per-layer entry that
    lists only that cell. Every cell that is there keeps its metrics, name
    for name; the new cell gets the list-less ``.clips`` metrics and its
    own."""
    import harness

    def names(bench, cell, group):
        return [m['name'] for m in harness.metrics_of(
            {'name': cell, 'bench': bench}, group)]

    cells = [w['name'] for w in bench_json['workloads']]
    before = {(c, g): names(bench_json, c, g)
              for c in cells for g in ('end_to_end', 'per_layer')}
    grown = copy.deepcopy(bench_json)
    grown['configs'].append({
        'name': 'appended-config', 'source': 'https://example.org/config.json',
        'file': 'benchmark/configs/appended-config.json', 'reduced': [],
        'why': 'a configuration a later PR appends'})
    grown['workloads'].append({
        'name': 'appended.corpus', 'config': 'appended-config',
        'traffic': 'corpus-8', 'chips': 1, 'why': 'a cell a later PR appends'})
    for m in grown['end_to_end']:
        if m['name'] == 'clips_per_s':
            m['workloads'].append('appended.corpus')
    grown['per_layer'].append({
        'name': 'appended_ms.clips', 'unit': 'ms/clip', 'better': 'lower',
        'source': 'device_trace', 'layer': 'device step',
        'moves': 'clips_per_s', 'workloads': ['appended.corpus']})
    for (cell, group), was in before.items():
        assert names(grown, cell, group) == was, (cell, group)
    assert names(grown, 'appended.corpus', 'end_to_end') == [
        'clips_per_s', 'setup_s']
    listless = [m['name'] for m in bench_json['per_layer']
                if 'workloads' not in m and m['moves'] == 'clips_per_s']
    assert listless and names(grown, 'appended.corpus', 'per_layer') == \
        listless + ['appended_ms.clips']
    # a per-layer entry appended for a cell that is there reaches that cell
    # and no other
    grown['per_layer'].append({
        'name': 'appended_too.clips', 'unit': '%', 'better': 'higher',
        'source': 'program_counter', 'layer': 'device step',
        'moves': 'clips_per_s', 'workloads': [cells[0]]})
    for (cell, group), was in before.items():
        more = ['appended_too.clips'] if (cell, group) == (
            cells[0], 'per_layer') else []
        assert names(grown, cell, group) == was + more, (cell, group)


def pinned_places(source: str):
    """Where a test's source holds an entry of ``bench_json['configs' |
    'workloads' | 'per_layer']`` to a place, or the list to a length:
    ``(line, what)`` for an integer index of the list, a ``len(`` of it as a
    side of a comparison other than the contract's own range check
    (``1 <= len(x) <= limit``), and an ``==`` between a comprehension over
    the whole list and a list written out or multiplied out. The list is
    ``bench_json[key]`` itself or, within one function, a name it was
    assigned to."""
    tree = ast.parse(source)
    functions = [n for n in tree.body if isinstance(n, ast.FunctionDef)]
    found = _pinned_in([n for n in tree.body if n not in functions])
    for function in functions:
        found |= _pinned_in([function])
    return sorted(found)


def _pinned_in(statements):
    aliases = set()

    def is_list(node):
        if isinstance(node, ast.Name):
            return node.id in aliases
        return (isinstance(node, ast.Subscript)
                and isinstance(node.value, ast.Name)
                and node.value.id.startswith('bench')
                and isinstance(node.slice, ast.Constant)
                and node.slice.value in LISTS)

    def is_len(node):
        return (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == 'len' and len(node.args) == 1
                and is_list(node.args[0]))

    def whole(node):
        return (isinstance(node, (ast.ListComp, ast.GeneratorExp, ast.SetComp))
                and len(node.generators) == 1
                and is_list(node.generators[0].iter)
                and not node.generators[0].ifs)

    def written_out(node):
        return isinstance(node, (ast.List, ast.Tuple)) or (
            isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult))

    nodes = [n for statement in statements for n in ast.walk(statement)]
    for node in nodes:
        if isinstance(node, ast.Assign) and is_list(node.value):
            aliases.update(t.id for t in node.targets
                           if isinstance(t, ast.Name))
    found = set()
    for node in nodes:
        if isinstance(node, ast.Subscript) and is_list(node.value):
            index = node.slice
            if isinstance(index, ast.UnaryOp):
                index = index.operand
            if isinstance(index, ast.Constant) and isinstance(index.value,
                                                              int):
                found.add((node.lineno, 'an index'))
        if not isinstance(node, ast.Compare):
            continue
        sides = [node.left] + node.comparators
        the_range = (len(sides) == 3 and is_len(sides[1])
                     and all(isinstance(op, ast.LtE) for op in node.ops)
                     and isinstance(sides[0], ast.Constant)
                     and isinstance(sides[2], ast.Constant))
        if any(is_len(x) for x in sides) and not the_range:
            found.add((node.lineno, 'a len('))
        if any(isinstance(op, ast.Eq) for op in node.ops) \
                and any(whole(x) for x in sides) \
                and any(written_out(x) for x in sides):
            found.add((node.lineno, 'a literal list length'))
    return found


PINS = {
    'the last cell': ("assert bench_json['workloads'][-1]['name'] == CELL",
                      'an index'),
    'the last configuration': (
        "assert bench_json['configs'][-1]['name'] == CONFIG", 'an index'),
    'the last metric': ("assert bench_json['per_layer'][-1] == entry",
                        'an index'),
    'the first, through a name': (
        "metrics = bench_json['per_layer']\nassert metrics[0] == entry",
        'an index'),
    'five cells': ("assert [w['chips'] for w in bench_json['workloads']] "
                   "== [1] * 5", 'a literal list length'),
    'names written out': (
        "cells = bench_json['workloads']\n"
        "assert [w['name'] for w in cells] == ['a', 'b']",
        'a literal list length'),
    'a count': ("assert len(bench_json['configs']) == 5", 'a len('),
    'a count through a name': (
        "cells = bench_json['workloads']\nassert len(cells) > 4", 'a len('),
}
NO_PINS = {
    'presence': "entry = [w for w in bench_json['workloads'] "
                "if w['name'] == CELL][0]",
    "the contract's range": "configs = bench_json['configs']\n"
                            "assert 1 <= len(configs) <= 24",
    'a set of names': "assert {w['name'] for w in bench_json['workloads']} "
                      ">= {'i3d.corpus'}",
    "another list's index": "assert bench_json['paths'][0] == 'benchmark'",
    'a filtered count': "assert len([w for w in bench_json['workloads'] "
                        "if w['chips'] == 4]) <= 1",
}


@pytest.mark.parametrize('case', sorted(PINS))
def test_the_scan_finds_a_pinned_place_or_count(case):
    source, what = PINS[case]
    assert [w for _, w in pinned_places(source)] == [what]


@pytest.mark.parametrize('case', sorted(NO_PINS))
def test_the_scan_lets_presence_and_the_contracts_limits_be(case):
    assert pinned_places(NO_PINS[case]) == []


def test_no_test_of_the_benchmark_pins_an_entrys_place_or_a_count():
    """Entries are appended; a test may say an entry is present and right,
    never that it is last or that there are N (benchmark/README.md)."""
    files = sorted((REPO / 'tests' / 'bench').glob('*.py'))
    assert files
    pinned = {f.name: pinned_places(f.read_text()) for f in files}
    assert {k: v for k, v in pinned.items() if v} == {}
