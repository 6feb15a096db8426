"""``correct`` comes out true for a sound run and false for a broken one.

These tests skip the harness's look for a chip and drive the rest of a run —
weights and corpus from the seed, the program's extractor through the cell's
driver, the saved files read back, the plain reference, the comparison with
the cell's own limits — at a size a test run can hold: the cell's own frame
geometry and model, a batch of 8 and a corpus of three short clips, on the
CPU. Then the timed path is broken underneath, once for each fault this kind
of cell can have:

* an answer altered where it is produced (one row of a device batch);
* rows handed back to the wrong place (the batch shifted by one slot);
* the tail of a video lost (its last row never saved).

A cell that serves no state and spans one chip cannot have the others (a step
that returns its state unchanged, a mean over half the batch, an exchange
between chips left out). The precision control is kept here too: the
reference in bfloat16, put in the program's place, fails the cell's limits.
"""
import json

import numpy as np
import pytest

import harness
import loader

SEED = 2 ** 31 + 2024
TINY = dict(
    require_tpu=False,
    program_overrides={'device': 'cpu', 'batch_size': 8},
    traffic_overrides={'clips': 3, 'frames': [9, 20, 13], 'width': 96,
                       'height': 64},
    workload_overrides={'sample': {'videos': 3, 'rows': 20, 'block': 8}})
ARGV = ['--workload', 'resnet50.corpus', '--seed', str(SEED), '--seconds',
        '0.5', '--trace', '0']


def _alter_one_row(extractor):
    step = extractor.packed_step

    def bad(batch):
        return {k: v.at[1].multiply(1.05) for k, v in step(batch).items()}
    extractor.packed_step = bad


def _shift_rows(extractor):
    import jax.numpy as jnp
    step = extractor.packed_step

    def bad(batch):
        return {k: jnp.roll(v, 1, axis=0) for k, v in step(batch).items()}
    extractor.packed_step = bad


def _lose_the_tail(extractor):
    result = extractor.packed_result

    def bad(task):
        return {k: (v[:-1] if getattr(v, 'ndim', 0) >= 1 else v)
                for k, v in result(task).items()}
    extractor.packed_result = bad


@pytest.fixture(scope='module')
def sound():
    return harness.run(ARGV, **TINY)


def test_sound_run_is_correct_and_well_formed(sound):
    assert list(sound)[:5] == ['correct', 'attempted', 'failed', 'metrics',
                               'device']
    assert list(sound)[-1] == 'checks'        # the numbers compared come last
    assert sound['correct'] is True
    assert sound['attempted'] == 3 and sound['failed'] == 0
    assert set(sound['metrics']) == {'frames_per_s', 'setup_s'}
    assert all(v['value'] > 0 for v in sound['metrics'].values())
    assert set(sound['device']) == {'platform', 'kind', 'count',
                                    'memory_peak_bytes'}
    checks = sound['checks']
    assert set(checks) == {'videos_failed', 'rows_off', 'nonfinite', 'rel_l2',
                           'row_rel_l2_max'}
    assert all(set(c) == {'value', 'limit'} for c in checks.values())
    # on the CPU the program computes in full float32: it sits on the
    # reference, decode, resize, crop, batching, scatter and save included
    assert checks['rel_l2']['value'] < 1e-5
    json.dumps(sound)                          # plain JSON, no NaN or inf


@pytest.mark.parametrize('fault,number', [
    (_alter_one_row, 'row_rel_l2_max'),
    (_shift_rows, 'row_rel_l2_max'),
    (_lose_the_tail, 'rows_off'),
])
def test_a_broken_timed_path_is_not_correct(fault, number):
    result = harness.run(ARGV, before_window=fault, **TINY)
    assert result['correct'] is False
    check = result['checks'][number]
    assert check['value'] > check['limit']


def test_the_precision_control_is_not_correct(tmp_path):
    """The reference in bfloat16, saved as the program would have saved it,
    fails ``rel_l2`` under the cell's own limits."""
    import compare
    import traffic_gen
    cell = harness.load_cell('resnet50.corpus')
    ref = loader.load_module('references', cell['config']['reference'])
    ckpts = harness.make_weights(ref, SEED, tmp_path)
    corpus = traffic_gen.generate(
        dict(cell['traffic'], **TINY['traffic_overrides']), SEED,
        str(tmp_path / 'corpus'))
    items = traffic_gen.pass_paths(corpus, 'p0')
    for item in items:
        units = ref.load_units(item['path'], range(item['frames']))
        rows = compare.reference_rows(ref, ckpts, units, 8, mode='bfloat16')
        np.save(item['path'] + '.npy', rows)
    done = compare.collect([items], lambda p: p + '.npy', ref)
    workload = dict(cell['workload'], **TINY['workload_overrides'])
    checks, n = compare.compare(done, ref, ckpts, workload, SEED)
    assert n == 42
    assert checks['rows_off']['ok'] and checks['nonfinite']['ok']
    assert not checks['rel_l2']['ok']
    assert checks['rel_l2']['value'] > 3 * checks['rel_l2']['limit']


@pytest.mark.slow
def test_i3d_cell_alter_one_row_is_not_correct():
    """The same for the i3d cell, at its own geometry (340x256 clips, RAFT at
    256x344, 20 updates): minutes on the CPU, so outside the fast lane."""
    def alter(extractor):
        step = extractor._step

        def bad(*a, **k):
            return {s: v.at[0].multiply(1.05) for s, v in step(*a, **k).items()}
        extractor._step = bad

    argv = ['--workload', 'i3d.corpus', '--seed', str(SEED), '--seconds',
            '0.5', '--trace', '0']
    tiny = dict(
        require_tpu=False,
        program_overrides={'device': 'cpu', 'batch_size': 2},
        traffic_overrides={'clips': 1, 'frames': [33]},
        workload_overrides={'sample': {'videos': 1, 'rows': 2, 'block': 2}})
    sound = harness.run(argv, **tiny)
    assert sound['correct'] is True
    broken = harness.run(argv, before_window=alter, **tiny)
    assert broken['correct'] is False
