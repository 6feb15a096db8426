"""Packed corpus mode (pack_across_videos): the batch-major outer loop
must be externally indistinguishable from the per-video loop — identical
output files, identical resume/skip behavior, per-video fault isolation —
while filling device batches across video boundaries (parallel/packing.py).

All fixtures are synthesized with cv2 so the suite runs without the
reference sample corpus.
"""
import os
import time
from pathlib import Path

import numpy as np
import pytest

from video_features_tpu.config import load_config
from video_features_tpu.registry import create_extractor
from video_features_tpu.utils.output import make_path


from tools.make_sample_video import write_noise_clip as _write_clip  # noqa: E402


@pytest.fixture(scope='module')
def mixed_worklist(tmp_path_factory):
    """Three clips of DIFFERENT lengths: none fills a whole device batch
    alone, so packing across boundaries is actually exercised."""
    d = tmp_path_factory.mktemp('packvids')
    return [_write_clip(d / f'vid{i}.mp4', n, seed=i)
            for i, n in enumerate((9, 4, 14))]


def _resnet_args(paths, out, tmp, **kw):
    over = dict(video_paths=paths, device='cpu', model_name='resnet18',
                batch_size=4, allow_random_weights=True,
                on_extraction='save_numpy', output_path=str(out),
                tmp_path=str(tmp))
    over.update(kw)
    return load_config('resnet', overrides=over)


RESNET_KEYS = ('resnet', 'fps', 'timestamps_ms')


def _load_outputs(out_path, paths, keys=RESNET_KEYS):
    return {(p, k): np.load(make_path(str(out_path), p, k, '.npy'))
            for p in paths for k in keys}


def test_packed_matches_per_video_framewise(mixed_worklist, tmp_path):
    """Packed outputs are element-identical to the per-video path on a
    mixed-length worklist: same filenames, same arrays — the batches
    differ (packed slots carry other videos' frames where the per-video
    loop carried padding), but per-sample results must not."""
    ex_pv = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'pv', tmp_path / 'tmp1'))
    for p in mixed_worklist:
        ex_pv._extract(p)
    ex_pk = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'pk', tmp_path / 'tmp2'))
    ex_pk.extract_packed(mixed_worklist)

    a = _load_outputs(ex_pv.output_path, mixed_worklist)
    b = _load_outputs(ex_pk.output_path, mixed_worklist)
    assert set(Path(f).name for f in os.listdir(ex_pv.output_path)) == \
        set(Path(f).name for f in os.listdir(ex_pk.output_path))
    for key in a:
        assert a[key].shape == b[key].shape, key
        np.testing.assert_array_equal(a[key], b[key], err_msg=str(key))


def test_packed_matches_per_video_i3d_stacks(tmp_path, tmp_path_factory):
    """The stack family: i3d rgb stream over windows that straddle the
    batch across videos (stack 10, batch 2 → 2+1 windows from 2 clips)."""
    d = tmp_path_factory.mktemp('i3dvids')
    paths = [_write_clip(d / 'a.mp4', 25, seed=7),
             _write_clip(d / 'b.mp4', 12, seed=8)]

    # ONE extractor runs both loops (per-task out_roots keep the output
    # trees apart) — the i3d transplant+compile dominates this test's
    # cost and the parity contract is about the LOOPS, not the build
    from video_features_tpu.parallel.packing import VideoTask
    ex = create_extractor(load_config('i3d', overrides=dict(
        video_paths=paths, device='cpu', streams='rgb',
        stack_size=10, step_size=10, batch_size=2,
        concat_rgb_flow=False, allow_random_weights=True,
        on_extraction='save_numpy', output_path=str(tmp_path / 'pv'),
        tmp_path=str(tmp_path / 'tmp1'))))
    for p in paths:
        ex._extract(p)
    pk_root = str(tmp_path / 'pk')
    ex.extract_packed([VideoTask(p, out_root=pk_root) for p in paths])

    for p, n_windows in zip(paths, (2, 1)):
        a = np.load(make_path(ex.output_path, p, 'rgb', '.npy'))
        b = np.load(make_path(pk_root, p, 'rgb', '.npy'))
        assert a.shape == b.shape == (n_windows, 1024)
        np.testing.assert_array_equal(a, b, err_msg=p)


def test_packed_fault_isolation_bad_file(mixed_worklist, tmp_path):
    """A video that fails to open mid-worklist must not poison the batches
    it would have shared: the good videos' outputs are still written and
    still identical to a clean run's."""
    clean = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'clean', tmp_path / 'tmpc'))
    clean.extract_packed(mixed_worklist)

    bad = str(tmp_path / 'gone.mp4')          # never created
    worklist = mixed_worklist[:1] + [bad] + mixed_worklist[1:]
    ex = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'faulty', tmp_path / 'tmpf'))
    ex.extract_packed(worklist)               # must not raise

    for p in mixed_worklist:
        for k in RESNET_KEYS:
            got = np.load(make_path(ex.output_path, p, k, '.npy'))
            ref = np.load(make_path(clean.output_path, p, k, '.npy'))
            np.testing.assert_array_equal(got, ref)
    assert not Path(make_path(ex.output_path, bad, 'resnet',
                              '.npy')).exists()


def test_packed_fault_isolation_mid_stream(mixed_worklist, tmp_path):
    """A decode failure MID-video (after windows already entered shared
    batches): the failing video saves nothing, its batch-mates save
    everything, bit-identical to a clean run."""
    clean = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'clean2', tmp_path / 'tmpc2'))
    clean.extract_packed(mixed_worklist)

    victim = mixed_worklist[1]
    ex = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'mid', tmp_path / 'tmpm'))
    orig = ex.packed_windows

    def flaky(task):
        it = orig(task)
        if task.path == victim:
            yield next(it)                    # one frame reaches the pool
            raise RuntimeError('decoder died mid-video')
        yield from it

    ex.packed_windows = flaky
    ex.extract_packed(mixed_worklist)         # must not raise

    assert not Path(make_path(ex.output_path, victim, 'resnet',
                              '.npy')).exists()
    for p in mixed_worklist:
        if p == victim:
            continue
        for k in RESNET_KEYS:
            got = np.load(make_path(ex.output_path, p, k, '.npy'))
            ref = np.load(make_path(clean.output_path, p, k, '.npy'))
            np.testing.assert_array_equal(got, ref)


def _r21d_args(paths, out, tmp, **kw):
    over = dict(video_paths=paths, device='cpu', stack_size=4, step_size=4,
                batch_size=2, allow_random_weights=True,
                on_extraction='save_numpy', output_path=str(out),
                tmp_path=str(tmp))
    over.update(kw)
    return load_config('r21d', overrides=over)


@pytest.fixture(scope='module')
def mixed_geometry_worklist(tmp_path_factory):
    """Three clips where the MIDDLE one has a different resolution: its
    windows pool separately (stack families ship decode-geometry windows)
    and only flush at the final drain."""
    d = tmp_path_factory.mktemp('geomvids')
    return [_write_clip(d / 'a.mp4', 9, w=64, h=48, seed=1),
            _write_clip(d / 'odd.mp4', 5, w=80, h=64, seed=2),
            _write_clip(d / 'c.mp4', 9, w=64, h=48, seed=3)]


def test_packed_mixed_geometry_parity_and_no_head_blocking(
        mixed_geometry_worklist, tmp_path):
    """A mixed-resolution corpus packs per geometry and still matches the
    per-video path; and a video whose pool can't fill (the lone odd clip)
    must NOT hold up the flush of completed videos behind it — its own
    output simply lands at the final drain."""
    paths = mixed_geometry_worklist
    ex_pv = create_extractor(_r21d_args(paths, tmp_path / 'pv',
                                        tmp_path / 'tmp1'))
    for p in paths:
        ex_pv._extract(p)
    ex_pk = create_extractor(_r21d_args(paths, tmp_path / 'pk',
                                        tmp_path / 'tmp2'))
    save_order = []
    orig_save = ex_pk.action_on_extraction

    def recording_save(feats_dict, video_path):
        save_order.append(Path(video_path).stem)
        return orig_save(feats_dict, video_path)

    ex_pk.action_on_extraction = recording_save
    ex_pk.extract_packed(paths)

    for p, n_windows in zip(paths, (2, 1, 2)):
        a = np.load(make_path(ex_pv.output_path, p, 'r21d', '.npy'))
        b = np.load(make_path(ex_pk.output_path, p, 'r21d', '.npy'))
        assert a.shape == b.shape == (n_windows, 512)
        np.testing.assert_array_equal(a, b, err_msg=p)
    # 'c' completes while 'odd' is still pooled — it must flush before
    # 'odd', not behind it (head-of-line regression guard)
    assert save_order.index('c') < save_order.index('odd')


def test_packed_device_step_fault_isolation(mixed_geometry_worklist,
                                            tmp_path):
    """A device-step failure (e.g. a geometry that won't compile) fails
    exactly the videos in that batch and the worklist continues — same
    blast radius as the per-video loop."""
    paths = mixed_geometry_worklist
    ex = create_extractor(_r21d_args(paths, tmp_path / 'stepf',
                                     tmp_path / 'tmpsf'))
    orig_step = ex.packed_step

    def bad_step(stacks):
        if stacks.shape[2] == 64:     # the odd 80x64 clip's geometry
            raise RuntimeError('no executable for this geometry')
        return orig_step(stacks)

    ex.packed_step = bad_step
    ex.extract_packed(paths)          # must not raise

    victim = paths[1]
    assert not Path(make_path(ex.output_path, victim, 'r21d',
                              '.npy')).exists()
    for p, n_windows in zip(paths, (2, 1, 2)):
        if p == victim:
            continue
        feats = np.load(make_path(ex.output_path, p, 'r21d', '.npy'))
        assert feats.shape == (n_windows, 512)


def test_packed_resume_contract(mixed_worklist, tmp_path, capsys):
    """is_already_exist semantics survive the inversion: a second packed
    run skips every video without rewriting anything, and after deleting
    one video's outputs (interrupted-run shape) only that video is
    re-extracted."""
    ex = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'res', tmp_path / 'tmpr'))
    ex.extract_packed(mixed_worklist)
    files = sorted(Path(ex.output_path).glob('*.npy'))
    assert len(files) == len(mixed_worklist) * len(RESNET_KEYS)
    mtimes = {f: f.stat().st_mtime_ns for f in files}

    capsys.readouterr()
    ex.extract_packed(mixed_worklist)         # resume: everything skips
    out = capsys.readouterr().out
    assert out.count('already exist') == len(mixed_worklist)
    assert {f: f.stat().st_mtime_ns for f in files} == mtimes

    # resume-after-interrupt: one video's outputs lost mid-corpus
    victim = mixed_worklist[1]
    removed = [f for f in files
               if f.name.startswith(Path(victim).stem + '_')]
    assert removed
    for f in removed:
        f.unlink()
    time.sleep(0.01)                          # mtime resolution guard
    ex.extract_packed(mixed_worklist)
    for f in files:
        if f in removed:
            assert f.exists()                 # re-extracted
        else:
            assert f.stat().st_mtime_ns == mtimes[f], f  # untouched


def test_packed_batch_occupancy_reported(mixed_worklist, tmp_path, capsys):
    """The packed run reports batch occupancy: 9+4+14=27 frames in batches
    of 4 → 7 batches, 27/28 slots real (the per-video loop would run 9
    batches at 27/36). The occ% and ramp columns land in the summary."""
    ex = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'occ', tmp_path / 'tmpo', profile=True))
    real_summary = {}
    real_reset = ex.tracer.reset
    ex.tracer.reset = lambda: real_summary.update(ex.tracer.report()) \
        or real_reset()
    ex.extract_packed(mixed_worklist)
    ex.tracer.reset = real_reset
    captured = capsys.readouterr()
    # the stage table is a diagnostic and prints to STDERR — stdout
    # belongs to the feature stream (vft-lint: stdout-purity)
    err = captured.err
    assert 'occ%' in err and 'ramp' in err
    assert 'packed worklist' in err
    assert 'occ%' not in captured.out

    model = real_summary['model']
    assert model['count'] == 7                # vs 9 in the per-video loop
    assert model['occupancy'] == pytest.approx(27 / 28)
    assert model['occupancy'] > 27 / 36       # strictly beats per-video
    assert 'ramp' in model                    # first-call wall measured


def test_packed_zero_window_video(tmp_path, tmp_path_factory):
    """A clip shorter than one stack window still produces its (empty)
    output files, exactly like the per-video path — resume depends on it."""
    d = tmp_path_factory.mktemp('tiny')
    paths = [_write_clip(d / 'long.mp4', 25, seed=3),
             _write_clip(d / 'short.mp4', 5, seed=4)]
    ex = create_extractor(load_config('i3d', overrides=dict(
        video_paths=paths, device='cpu', streams='rgb',
        stack_size=10, step_size=10, batch_size=2,
        concat_rgb_flow=False, allow_random_weights=True,
        on_extraction='save_numpy', output_path=str(tmp_path / 'zout'),
        tmp_path=str(tmp_path / 'ztmp'))))
    ex.extract_packed(paths)
    long_feats = np.load(make_path(ex.output_path, paths[0], 'rgb', '.npy'))
    short_feats = np.load(make_path(ex.output_path, paths[1], 'rgb', '.npy'))
    assert long_feats.shape == (2, 1024)
    assert short_feats.shape == (0, 1024)


# -- async device loop (inflight > 1): parity + deferred fault isolation ----

def _output_bytes(out_path):
    return {f.name: f.read_bytes()
            for f in sorted(Path(out_path).rglob('*.npy'))}


def test_async_parity_resnet_and_r21d(mixed_worklist,
                                      mixed_geometry_worklist, tmp_path):
    """The deferred-D2H loop must be externally invisible: packed outputs
    at inflight=2 (and deeper) are BYTE-identical to the synchronous
    inflight=1 loop — framewise (resnet) and stack (r21d, mixed
    geometry) families."""
    # ONE extractor per family, driven at both depths via the run-level
    # inflight override with per-task output roots — the serve warm-pool
    # reuse pattern, and it halves the transplant+compile cost of this
    # tier-1 test without weakening the byte-parity contract
    from video_features_tpu.parallel.packing import VideoTask
    ex = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 's1', tmp_path / 'ts1', inflight=1))
    ex.extract_packed(mixed_worklist)
    deep_root = str(tmp_path / 's2' / 'resnet' / 'resnet18')
    ex.extract_packed([VideoTask(p, out_root=deep_root)
                       for p in mixed_worklist], inflight=3)
    a, b = _output_bytes(ex.output_path), _output_bytes(deep_root)
    assert a and a == b

    paths = mixed_geometry_worklist
    ex = create_extractor(_r21d_args(paths, tmp_path / 'r1',
                                     tmp_path / 'tr1', inflight=1))
    ex.extract_packed(paths)
    deep_root = str(tmp_path / 'r2' / 'r21d')
    ex.extract_packed([VideoTask(p, out_root=deep_root)
                       for p in paths], inflight=2)
    a, b = _output_bytes(ex.output_path), _output_bytes(deep_root)
    assert a and a == b


def test_async_parity_i3d_and_s3d(tmp_path, tmp_path_factory):
    """The stack families with geometry-cached executables (i3d rgb,
    s3d): async packed outputs byte-identical to the synchronous loop."""
    d = tmp_path_factory.mktemp('asyncvids')
    paths = [_write_clip(d / 'a.mp4', 25, seed=21),
             _write_clip(d / 'b.mp4', 18, seed=22)]

    from video_features_tpu.parallel.packing import VideoTask

    def run_both(feature_type, **kw):
        # ONE extractor per family (the transplant+compile dominates
        # this test's cost), run synchronous then async with per-task
        # output roots — the serve warm-pool reuse pattern
        over = dict(video_paths=paths, device='cpu',
                    allow_random_weights=True, on_extraction='save_numpy',
                    output_path=str(tmp_path / f'{feature_type}_1'),
                    tmp_path=str(tmp_path / f'tmp_{feature_type}'),
                    inflight=1)
        over.update(kw)
        ex = create_extractor(load_config(feature_type, overrides=over))
        ex.extract_packed(paths)
        deep_root = str(tmp_path / f'{feature_type}_2')
        ex.extract_packed([VideoTask(p, out_root=deep_root)
                           for p in paths], inflight=2)
        return _output_bytes(ex.output_path), _output_bytes(deep_root)

    a, b = run_both('i3d', streams='rgb', stack_size=10, step_size=10,
                    batch_size=2, concat_rgb_flow=False)
    assert a and a == b
    a, b = run_both('s3d', stack_size=16, step_size=16, batch_size=2)
    assert a and a == b


def test_async_fault_isolation_at_sync_point(mixed_geometry_worklist,
                                             tmp_path):
    """An execution fault that only surfaces at the DEFERRED sync point
    (fetch_outputs — where async backends raise) must doom exactly the
    videos of the batch that produced it; batch-mates and neighbors
    still save, identical to a clean run."""
    paths = mixed_geometry_worklist
    clean = create_extractor(_r21d_args(paths, tmp_path / 'clean',
                                        tmp_path / 'tmpc', inflight=2))
    clean.extract_packed(paths)

    ex = create_extractor(_r21d_args(paths, tmp_path / 'sync',
                                     tmp_path / 'tmps', inflight=2))
    orig_step, orig_fetch = ex.packed_step, ex.fetch_outputs
    # strong references + identity checks (never id(): a freed array's
    # address can be recycled by a later innocent batch)
    poisoned = []

    def marking_step(stacks):
        out = orig_step(stacks)
        if stacks.shape[2] == 64:         # the odd 80x64 clip's geometry
            poisoned.append(out[ex.feature_type])
        return out

    def bad_fetch(out):
        if any(out[ex.feature_type] is p for p in poisoned):
            raise RuntimeError('async execution fault surfaced at D2H')
        return orig_fetch(out)

    ex.packed_step, ex.fetch_outputs = marking_step, bad_fetch
    ex.extract_packed(paths)              # must not raise
    assert poisoned                       # the bad batch really dispatched

    victim = paths[1]
    assert not Path(make_path(ex.output_path, victim, 'r21d',
                              '.npy')).exists()
    for p in paths:
        if p == victim:
            continue
        got = np.load(make_path(ex.output_path, p, 'r21d', '.npy'))
        ref = np.load(make_path(clean.output_path, p, 'r21d', '.npy'))
        np.testing.assert_array_equal(got, ref, err_msg=p)


def test_async_stage_split_model_plus_d2h(mixed_worklist, tmp_path):
    """The stage table shows model (dispatch) and d2h (deferred
    readback) as distinct stages with one record each per batch, and
    both carry the batch-occupancy accounting."""
    ex = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'st', tmp_path / 'tmpst',
        profile=True, inflight=2))
    rep = {}
    real_reset = ex.tracer.reset
    ex.tracer.reset = lambda: rep.update(ex.tracer.report()) or real_reset()
    ex.extract_packed(mixed_worklist)
    ex.tracer.reset = real_reset
    assert rep['model']['count'] == rep['d2h']['count'] == 7
    assert rep['model']['occupancy'] == pytest.approx(27 / 28)
    assert rep['d2h']['occupancy'] == pytest.approx(27 / 28)


def test_sanity_check_gates_packing(tmp_path):
    """pack_across_videos degrades (with a warning) for families without
    packed support and for the per-video show_pred debug surface."""
    clip = _write_clip(tmp_path / 'c.mp4', 4)
    args = load_config('vggish', overrides=dict(
        video_paths=clip, device='cpu', pack_across_videos=True,
        output_path=str(tmp_path / 'o'), tmp_path=str(tmp_path / 't')))
    assert args['pack_across_videos'] is False
    args = load_config('resnet', overrides=dict(
        video_paths=clip, device='cpu', model_name='resnet18',
        pack_across_videos=True, show_pred=True,
        output_path=str(tmp_path / 'o2'), tmp_path=str(tmp_path / 't2')))
    assert args['pack_across_videos'] is False


def test_inflight_knob_default_and_validation(tmp_path):
    """The async-depth knob is injected into every merged config
    (default 2) and sanity_check rejects non-positive depths."""
    clip = _write_clip(tmp_path / 'k.mp4', 4)
    common = dict(video_paths=clip, device='cpu', model_name='resnet18',
                  output_path=str(tmp_path / 'o'),
                  tmp_path=str(tmp_path / 't'))
    args = load_config('resnet', overrides=dict(common))
    assert args['inflight'] == 2
    args = load_config('resnet', overrides=dict(common, inflight='1'))
    assert args['inflight'] == 1              # coerced to int
    with pytest.raises(ValueError):
        load_config('resnet', overrides=dict(common, inflight=0))


def test_cli_routes_packed(tmp_path, tmp_path_factory, capsys):
    """End to end through the CLI: pack_across_videos=true drives the
    packed scheduler and writes the standard outputs."""
    from video_features_tpu.cli import main

    d = tmp_path_factory.mktemp('clivids')
    paths = [str(_write_clip(d / f'v{i}.mp4', n, seed=i))
             for i, n in enumerate((6, 9))]
    out = tmp_path / 'cliout'
    rc = main([
        'feature_type=resnet', 'model_name=resnet18', 'device=cpu',
        f'video_paths=[{",".join(paths)}]', 'pack_across_videos=true',
        'batch_size=4', 'allow_random_weights=true',
        'on_extraction=save_numpy', f'output_path={out}',
        f'tmp_path={tmp_path / "clitmp"}'])
    assert rc == 0
    assert 'Packing device batches across 2 videos' in capsys.readouterr().out
    for p in paths:
        # sanity_check appends <feature_type>/<model_name> to output_path
        feats = np.load(make_path(str(out / 'resnet' / 'resnet18'), p,
                                  'resnet', '.npy'))
        assert feats.shape[1] == 512


# -- decode lanes: several videos of a packed worklist at once ---------------

@pytest.mark.parametrize('cores,videos,explicit,lanes,farm_workers', [
    (8, None, None, 4, 0),    # unset, a serve feed: cores halved
    (8, 32, None, 4, 0),
    (16, 32, None, 4, 0),     # at most 4
    (6, 32, None, 3, 0),
    (2, 32, None, 1, 0),
    (1, 32, None, 1, 0),      # at least 1
    (8, 1, None, 1, 0),       # never more than the videos at hand
    (8, 3, None, 3, 0),
    (8, 0, None, 1, 0),
    (8, 32, 1, 1, 0),         # explicit 1: the serial windower
    (8, 32, 4, 1, 4),         # explicit N > 1: the farm's route, as before
])
def test_decode_lane_plan(cores, videos, explicit, lanes, farm_workers):
    from video_features_tpu.extract.streaming import decode_lane_plan
    plan = decode_lane_plan(explicit, videos=videos, cores=cores)
    assert (plan['lanes'], plan['farm_workers']) == (lanes, farm_workers)
    assert plan['cores'] == cores and plan['videos'] == videos
    assert plan['decode_workers'] == explicit and plan['why']


def test_decode_plan_reads_the_extractor_and_the_worklist(monkeypatch):
    """The run-level value wins over the extractor's; unset on both means
    lanes from the cores the process may use; only a sized worklist says
    how many videos are at hand; an extractor built without the attribute
    keeps the serial default."""
    import os as _os
    import types

    from video_features_tpu.parallel.packing import _decode_plan
    monkeypatch.setattr(_os, 'sched_getaffinity', lambda pid: set(range(8)),
                        raising=False)
    unset = types.SimpleNamespace(decode_workers=None)
    assert _decode_plan(unset, None, ['a', 'b', 'c'])['lanes'] == 3
    assert _decode_plan(unset, None, iter(['a']))['lanes'] == 4
    assert _decode_plan(unset, 1, ['a'] * 9)['lanes'] == 1
    assert _decode_plan(unset, 2, ['a'] * 9)['farm_workers'] == 2
    farm = types.SimpleNamespace(decode_workers=2)
    assert _decode_plan(farm, None, ['a'] * 9)['farm_workers'] == 2
    assert _decode_plan(farm, 1, ['a'] * 9)['farm_workers'] == 0
    assert _decode_plan(types.SimpleNamespace(), None,
                        ['a'] * 9)['lanes'] == 1


def _cores(monkeypatch, n):
    import os as _os
    monkeypatch.setattr(_os, 'sched_getaffinity', lambda pid: set(range(n)),
                        raising=False)


@pytest.fixture(scope='module')
def lane_worklist(tmp_path_factory):
    """Eight entries of mixed lengths: one file that cannot be opened,
    one clip too short for a single stack, six that pack across
    boundaries."""
    d = tmp_path_factory.mktemp('lanevids')
    paths = [_write_clip(d / f'l{i}.mp4', n, seed=40 + i)
             for i, n in enumerate((9, 21, 5, 13, 30, 17))]
    gone = str(d / 'gone.mp4')                # never created
    short = _write_clip(d / 'short.mp4', 2, seed=50)
    return paths[:2] + [gone] + paths[2:4] + [short] + paths[4:]


@pytest.fixture(scope='module')
def lane_reference(lane_worklist, tmp_path_factory):
    """ONE r21d extractor for every lane count (the compile dominates) and
    the serial windower's saved bytes (explicit ``decode_workers=1``)."""
    from video_features_tpu.parallel.packing import VideoTask
    root = tmp_path_factory.mktemp('laneout')
    ex = create_extractor(_r21d_args(lane_worklist, root / 'unused',
                                     root / 'tmp'))
    assert ex.decode_workers is None          # the shipped default: unset
    done = []
    ex.extract_packed([VideoTask(p, out_root=str(root / 'serial'))
                       for p in lane_worklist],
                      decode_workers=1, on_video_done=done.append)
    failed = {Path(t.path).name for t in done if t.failed}
    return ex, _output_bytes(root / 'serial'), failed


@pytest.mark.parametrize('cores,lanes', [(2, 1), (6, 3), (8, 4)])
def test_lanes_save_the_serial_windowers_bytes(
        lane_worklist, lane_reference, tmp_path, monkeypatch, capsys,
        cores, lanes):
    """The derived default at 1, 3 and 4 lanes: the saved files are the
    serial run's byte for byte, the same video fails, every task is
    finalised and the scheduler loses no window."""
    from video_features_tpu.extract import streaming
    from video_features_tpu.parallel.packing import VideoTask
    ex, serial_bytes, serial_failed = lane_reference
    assert serial_failed == {'gone.mp4'}
    assert len(serial_bytes) == len(lane_worklist) - 1

    _cores(monkeypatch, cores)
    engaged = []
    real = streaming.stream_windows_across_lanes

    def spy(tasks, open_windows, n, **kw):
        engaged.append(n)
        return real(tasks, open_windows, n, **kw)

    monkeypatch.setattr(streaming, 'stream_windows_across_lanes', spy)
    done = []
    ex.failed_videos = 0
    ex.extract_packed([VideoTask(p, out_root=str(tmp_path / 'lanes'))
                       for p in lane_worklist],
                      on_video_done=done.append)      # must not raise
    assert engaged == ([lanes] if lanes > 1 else [])
    assert _output_bytes(tmp_path / 'lanes') == serial_bytes
    assert {Path(t.path).name for t in done if t.failed} == serial_failed
    assert len(done) == len(lane_worklist)
    assert all(t.finalized and t.exhausted and t.done == t.emitted
               for t in done)
    assert ex.failed_videos == 1


class _LaneTask:
    """The task fields the windowers touch."""

    def __init__(self, path):
        self.path, self.emitted = path, 0
        self.exhausted = self.failed = False


def test_lanes_keep_running_videos_flowing_while_the_source_blocks():
    """A dynamic source that blocks in ``next()``: the windows of a video
    already running arrive, and it ends, while the dispatcher waits; a
    ``FLUSH`` of the source comes after the last window of every video
    dispatched before it, in arrival order."""
    import threading

    from video_features_tpu.extract.streaming import (
        FLUSH, NUDGE, stream_windows_across_lanes,
    )
    gate = threading.Event()
    slow, quick, late, empty = (_LaneTask(n) for n in
                                ('slow', 'quick', 'late', 'empty'))

    def source():
        yield slow
        yield quick
        yield FLUSH               # behind slow's and quick's last windows
        assert gate.wait(30)      # the feed is idle
        yield late
        yield empty
        yield FLUSH

    def open_windows(task):
        n = {'slow': 70, 'quick': 3, 'late': 2, 'empty': 0}[task.path]
        for i in range(n):
            if task.path == 'slow':
                time.sleep(0.002)
            yield np.full((2,), i), i

    seen = []
    for item in stream_windows_across_lanes(source(), open_windows, 3):
        seen.append(item)
        if item is FLUSH and not gate.is_set():
            # everything dispatched before the FLUSH has ended, while the
            # source still blocks
            assert slow.exhausted and quick.exhausted
            assert (slow.emitted, quick.emitted) == (70, 3)
            assert not late.exhausted and late.emitted == 0
            gate.set()
    first_flush = seen.index(FLUSH)
    assert sum(1 for s in seen[:first_flush] if s is not NUDGE) == 73
    assert [m for t, _, m in seen[:first_flush] if t is slow] == \
        list(range(70))                       # a video's windows in order
    tail = seen[first_flush + 1:]          # late ‖ empty, then the FLUSH
    assert tail[-1] is FLUSH and tail.count(NUDGE) == 1 and len(tail) == 4
    assert [(t.path, m) for t, _, m in
            (s for s in tail[:-1] if s is not NUDGE)] == \
        [('late', 0), ('late', 1)]
    assert all(t.exhausted for t in (slow, quick, late, empty))
    assert empty.emitted == 0


def test_lane_handover_is_bounded_and_close_joins_every_lane(monkeypatch):
    """The hand-over queue never holds more than ``lanes`` chunks however
    slowly the consumer reads, and closing the merged generator early
    stops and joins every lane and closes every window source."""
    import queue as _queue
    import threading

    from video_features_tpu.extract import streaming
    depths = []

    class SpyQueue(_queue.Queue):
        def put(self, item, block=True, timeout=None):
            super().put(item, block, timeout)
            depths.append((self.qsize(), self.maxsize))

    monkeypatch.setattr(streaming.queue, 'Queue', SpyQueue)
    monkeypatch.setattr(streaming, 'CHUNK_WINDOWS', 4)
    opened, closed = [], []

    def open_windows(task):
        opened.append(task.path)
        try:
            for i in range(400):
                yield np.zeros((8,), np.uint8), i
        finally:
            closed.append(task.path)

    tasks = [_LaneTask(f'v{i}') for i in range(9)]
    lanes = 3
    gen = streaming.stream_windows_across_lanes(iter(tasks), open_windows,
                                                lanes)
    for _ in range(50):
        next(gen)
        time.sleep(0.002)         # a slow consumer: lanes run into the bound
    assert depths and max(d for d, _ in depths) <= lanes
    assert {m for _, m in depths} == {lanes}
    gen.close()
    alive = [t.name for t in threading.enumerate()
             if t.name.startswith('vft-decode-')]
    assert alive == []
    assert sorted(opened) == sorted(closed) and 0 < len(opened) <= lanes + 1
    # a byte bound too: one window over CHUNK_BYTES is a chunk of its own
    monkeypatch.setattr(streaming, 'CHUNK_BYTES', 4)
    depths.clear()
    big = [_LaneTask('big')]
    out = list(streaming.stream_windows_across_lanes(
        iter(big), lambda t: ((np.zeros((8,), np.uint8), i)
                              for i in range(5)), 2))
    assert len(out) == 5 and big[0].emitted == 5 and big[0].exhausted
    assert len(depths) >= 5 + 1           # 5 chunks of one, and the end


def test_lane_spans_carry_the_lane_and_sum_to_its_busy_seconds():
    """Tracer on: one ``decode+preprocess`` span a chunk, under the lane's
    ``span_tid``, with the video's provenance; the spans' seconds are the
    lanes' busy seconds (the manifest's ``decode`` section), and a live
    source's lull is ``queue_idle``, not decode."""
    from video_features_tpu.extract import streaming
    from video_features_tpu.obs.spans import SpanRecorder
    from video_features_tpu.utils.tracing import Tracer
    tracer = Tracer(enabled=True, recorder=SpanRecorder())
    tasks = [_LaneTask(f'v{i}') for i in range(5)]

    def open_windows(task):
        for i in range(70):
            if task.path == 'v2' and i == 40:
                time.sleep(0.05)
                yield streaming.FLUSH           # a live session's lull
            yield np.zeros((8,), np.uint8), i

    stats = []
    out = list(streaming.stream_windows_across_lanes(
        iter(tasks), open_windows, 2, tracer=tracer,
        span_attrs=lambda t: {'video': t.path}, stats=stats))
    assert sum(1 for o in out if o is not streaming.FLUSH) == 350
    spans = [e for e in tracer.recorder.snapshot()
             if e.get('ph') == 'X' and e['name'] == 'decode+preprocess']
    assert {e['tid'] for e in spans} == {0, 1}
    assert all(e['args']['lane'] == e['tid'] for e in spans)
    assert {e['args']['video'] for e in spans} == {t.path for t in tasks}
    # 70 windows are chunks of 32, 32 and 6; v2's lull cuts 32, 8 and 30
    assert len(spans) == 5 * 3 == sum(s['chunks'] for s in stats)
    assert sorted(e['args']['windows'] for e in spans
                  if e['args']['video'] == 'v2') == [8, 30, 32]
    assert sum(e['args']['windows'] for e in spans) == 350
    assert sum(s['videos'] for s in stats) == 5
    assert sum(s['windows'] for s in stats) == 350
    busy = sum(s['busy_s'] for s in stats)
    assert busy == pytest.approx(sum(e['dur'] for e in spans) / 1e6,
                                 abs=1e-4)
    assert busy == pytest.approx(
        tracer.report()['decode+preprocess']['total_s'])
    idle = [e for e in tracer.recorder.snapshot()
            if e.get('ph') == 'X' and e['name'] == 'queue_idle']
    assert len(idle) == 1 and idle[0]['dur'] >= 0.04e6
    assert idle[0]['tid'] in (0, 1)
    assert all(s['blocked_s'] >= 0 for s in stats)


def test_lanes_read_no_clock_with_the_tracer_off(monkeypatch):
    """Tracer off: the lanes read no clock and record nothing; the counts
    of videos, windows and chunks are kept all the same."""
    import types

    from video_features_tpu.extract import streaming
    from video_features_tpu.utils.tracing import NULL_TRACER
    reads = []
    monkeypatch.setattr(streaming, 'time', types.SimpleNamespace(
        perf_counter=lambda: reads.append(1) or 0.0))
    tasks = [_LaneTask(f'v{i}') for i in range(4)]
    stats = []
    out = list(streaming.stream_windows_across_lanes(
        iter(tasks), lambda t: ((np.zeros((4,), np.uint8), i)
                                for i in range(40)), 3, stats=stats))
    assert len(out) == 160 and reads == []
    assert NULL_TRACER.report() == {}
    assert sum(s['windows'] for s in stats) == 160
    assert all(s['busy_s'] == 0.0 == s['blocked_s'] for s in stats)


def test_lane_failure_is_the_videos_own_and_consumer_failure_stops_it():
    """A video whose window source raises fails its own task and its lane
    takes the next one; a task the consumer fails stops being decoded."""
    from video_features_tpu.extract.streaming import (
        NUDGE, stream_windows_across_lanes,
    )
    tasks = [_LaneTask(n) for n in ('ok0', 'bad', 'doomed', 'ok1')]
    pulled = {'doomed': 0}

    def open_windows(task):
        if task.path == 'bad':
            raise IOError('cannot open')
        for i in range(5000 if task.path == 'doomed' else 40):
            if task.path == 'doomed':
                pulled['doomed'] += 1
            yield np.zeros((4,), np.uint8), i

    got = {t.path: 0 for t in tasks}
    nudges = 0
    for item in stream_windows_across_lanes(iter(tasks), open_windows, 2):
        if item is NUDGE:
            nudges += 1
            continue
        task = item[0]
        got[task.path] += 1
        if task.path == 'doomed' and got['doomed'] == 3:
            task.failed = True        # a device-step fault, at the consumer
    assert got['ok0'] == got['ok1'] == 40 and got['bad'] == 0
    assert nudges == 1 and tasks[1].failed and tasks[1].exhausted
    assert got['doomed'] == 3 and tasks[2].emitted == 3
    assert pulled['doomed'] < 5000    # its lane stopped early
    assert all(t.exhausted for t in tasks)


def test_lanes_under_a_short_switch_interval_lose_no_window():
    """More lanes than cores, threads switched every 10 us: every window of
    every video arrives once, in the video's order, the counts the merging
    generator keeps are the videos' own, and every zero-window video is
    told by one NUDGE."""
    import sys

    from video_features_tpu.extract.streaming import (
        NUDGE, stream_windows_across_lanes,
    )
    tasks = [_LaneTask(f'v{i}') for i in range(60)]
    sizes = {t.path: (i * 7) % 45 for i, t in enumerate(tasks)}

    def open_windows(task):
        for i in range(sizes[task.path]):
            yield np.full((3,), i, np.int32), i

    seen, nudges = {}, 0
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    t0 = time.monotonic()
    try:
        for item in stream_windows_across_lanes(iter(tasks), open_windows,
                                                12):
            if item is NUDGE:
                nudges += 1
                continue
            assert int(item[1][0]) == item[2]
            seen.setdefault(item[0].path, []).append(item[2])
    finally:
        sys.setswitchinterval(interval)
    assert time.monotonic() - t0 < 60
    for t in tasks:
        assert seen.get(t.path, []) == list(range(sizes[t.path])), t.path
        assert t.emitted == sizes[t.path] and t.exhausted
    assert nudges == sum(1 for n in sizes.values() if n == 0) > 0


def test_packed_run_names_its_lanes_in_manifest_and_header(
        mixed_worklist, tmp_path, monkeypatch, capsys):
    """The run manifest's ``decode`` section says how many lanes ran and
    why, with per-lane counters whose busy seconds are the stage table's
    ``decode+preprocess`` total; the stage-table header names the count."""
    import json
    _cores(monkeypatch, 8)
    manifest = tmp_path / 'manifest.json'
    ex = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'mo', tmp_path / 'tmpmo', profile=True,
        manifest_out=str(manifest)))
    ex.extract_packed(mixed_worklist)
    ex.finish_obs()
    assert '3 decode lanes)' in capsys.readouterr().err
    doc = json.loads(manifest.read_text())
    dec = doc['decode']
    assert dec['lanes'] == 3 and dec['cores'] == 8 and dec['videos'] == 3
    assert dec['decode_workers'] is None and dec['calls'] == 1
    assert 'video(s) at hand' in dec['why']
    assert [lane['videos'] for lane in dec['per_lane']] == [1, 1, 1]
    assert sum(lane['windows'] for lane in dec['per_lane']) == 27
    assert sum(lane['busy_s'] for lane in dec['per_lane']) == \
        pytest.approx(doc['stages']['decode+preprocess']['total_s'],
                      abs=1e-4)
    assert doc['farm'] == {}
    # explicit 1: the serial windower says so, and keeps no lane counters
    ex.extract_packed(mixed_worklist, decode_workers=1)
    ex.finish_obs()
    assert '1 decode lane)' in capsys.readouterr().err


# -- batch assembly in recycled host buffers ---------------------------------


def _stack_packer(windows, batch, max_pool_age_s=None, family_of=None,
                  family_batch=None, clock=time.monotonic):
    """The plain packer ``packed_batches`` must match: windows pooled as
    references, one ``np.stack`` a flush, the tail repeating the last."""
    from video_features_tpu.parallel.packing import FLUSH, NUDGE
    pools, ages = {}, {}

    def cap_of(key):
        return family_batch[key[0]] if family_batch is not None else batch

    def flush(key):
        pool, pools[key] = pools[key], []
        ages.pop(key, None)
        wins = [w for _, w, _ in pool]
        wins += [wins[-1]] * (cap_of(key) - len(wins))
        return np.stack(wins), [(t, m) for t, _, m in pool], len(pool)

    for item in windows:
        if item is FLUSH or item is NUDGE:
            if item is FLUSH:
                for key in list(pools):
                    if pools[key]:
                        yield flush(key)
            yield None, [], 0
            continue
        task, window, meta = item
        key = (window.shape, window.dtype.str)
        if family_of is not None:
            key = (family_of(meta),) + key
        pool = pools.setdefault(key, [])
        if not pool:
            ages[key] = clock()
        pool.append(item)
        if len(pool) == cap_of(key):
            yield flush(key)
        if max_pool_age_s is not None:
            now = clock()
            for k in list(pools):
                if pools[k] and now - ages[k] >= max_pool_age_s:
                    yield flush(k)
    for key in list(pools):
        if pools[key]:
            yield flush(key)


def _win(rng, shape, dtype):
    return (rng.integers(0, 250, size=shape)).astype(dtype)


def _stream_mixed_geometries(rng):
    shapes = [((2, 3), np.float32), ((4,), np.uint8), ((2, 3), np.float64)]
    items = [(f't{i % 4}', _win(rng, *shapes[i % 3]), i) for i in range(23)]
    return items, {'batch': 3}


def _stream_flush_and_nudge(rng):
    from video_features_tpu.parallel.packing import FLUSH, NUDGE
    w = lambda i: (f't{i}', _win(rng, (3, 2), np.int32), i)   # noqa: E731
    items = [w(0), w(1), FLUSH, FLUSH, NUDGE, w(2), w(3), w(4), w(5), NUDGE,
             w(6), FLUSH, w(7), w(8), w(9), w(10), w(11)]
    return items, {'batch': 4}


def _stream_pool_aging(rng):
    odd = lambda i: (f'o{i}', _win(rng, (3, 3), np.float32), i)  # noqa: E731
    main = lambda i: (f'm{i}', _win(rng, (2, 2), np.float32), i)  # noqa: E731
    items = [odd(0), ('tick', 0.06)] + [main(i) for i in range(5)]
    items += [odd(1), main(5), ('tick', 0.02), main(6), ('tick', 0.04)]
    items += [main(i) for i in range(7, 12)] + [odd(2)]
    return items, {'batch': 4, 'max_pool_age_s': 0.05}


def _stream_family_capacity(rng):
    items = []
    for i in range(9):                # two families on one geometry
        items.append((f't{i}', _win(rng, (4, 4, 3), np.uint8), ('a', i)))
        items.append((f't{i}', _win(rng, (4, 4, 3), np.uint8), ('b', i)))
    return items, {'batch': 8, 'family_of': lambda m: m[0],
                   'family_batch': {'a': 2, 'b': 4}}


def _stream_short_tail(rng):
    items = [(f't{i // 3}', _win(rng, (5,), np.int16), i) for i in range(11)]
    return items, {'batch': 4}


@pytest.mark.parametrize('make_stream', [
    _stream_mixed_geometries, _stream_flush_and_nudge, _stream_pool_aging,
    _stream_family_capacity, _stream_short_tail],
    ids=['mixed_geometries', 'flush_and_nudge', 'pool_aging',
         'family_capacity', 'short_tail'])
def test_recycled_buffers_pack_the_bytes_np_stack_packs(make_stream,
                                                        monkeypatch):
    """``packed_batches`` copying windows into recycled buffers as they
    arrive yields, batch for batch, the bytes, ``valid`` and provenance of
    a packer that pools references and ``np.stack``s them at flush — with
    every buffer given back (and scribbled over) as soon as its batch is
    read, so recycled buffers really are refilled."""
    import types

    from video_features_tpu.parallel import packing
    now = [0.0]
    monkeypatch.setattr(packing, 'time', types.SimpleNamespace(
        monotonic=lambda: now[0], perf_counter=time.perf_counter))
    items, kw = make_stream(np.random.default_rng(7))

    def stream():
        for item in items:
            if isinstance(item, tuple) and item[0] == 'tick':
                now[0] += item[1]
            else:
                yield item

    now[0] = 0.0
    expected = list(_stack_packer(stream(), clock=lambda: now[0], **kw))
    now[0] = 0.0
    buffers = packing.BatchBuffers()
    got = []
    for stacked, prov, valid in packing.packed_batches(
            stream(), buffers=buffers, **kw):
        if stacked is None:
            got.append((None, prov, valid))
            continue
        got.append((stacked.copy(), prov, valid))
        stacked.view(np.uint8).fill(0xA5)         # stale bytes must not leak
        buffers.give_back(stacked)
    assert len(got) == len(expected) > 2
    for (g, gp, gv), (e, ep, ev) in zip(got, expected):
        assert (gp, gv) == (ep, ev)
        if e is None:
            assert g is None
        else:
            assert (g.shape, g.dtype) == (e.shape, e.dtype)
            assert g.tobytes() == e.tobytes()
    stats = buffers.stats()
    assert sum(s['batches'] for s in stats.values()) == \
        sum(1 for e, _, _ in expected if e is not None)
    assert sum(s['recycled'] for s in stats.values()) > 0


def _fake_device_loop(ex, fetch_delay_s=0.0):
    """Device hooks that read the host batch at FETCH time: ``put`` hands
    back the host array itself, as the CPU backend may alias it, and
    records what was put; the fetch compares the array with that record."""
    from collections import deque
    put_copies, checks = deque(), []

    def put(batch):
        put_copies.append(batch.copy())
        return batch

    def fetch(out):
        if fetch_delay_s:
            time.sleep(fetch_delay_s)      # the packer runs on meanwhile
        arr = out['resnet']
        checks.append(np.array_equal(arr, put_copies.popleft()))
        return {'resnet': arr.reshape(len(arr), -1)[:, :4].astype(np.float32)}

    ex.put_input = put
    ex.packed_step = lambda dev: {'resnet': dev}
    ex.fetch_outputs = fetch
    return checks


@pytest.mark.parametrize('inflight', [2, 3])
def test_a_buffer_is_not_refilled_while_its_step_is_in_flight(
        mixed_worklist, tmp_path, inflight):
    """A step aliasing its host buffer reads it only when fetched: with
    ``inflight`` steps outstanding and 14 batches of one geometry, every
    fetched batch is still what was put, buffers were recycled, and the
    manifest's ``decode`` section names the buffers per geometry within
    the pipeline's bound."""
    import json
    manifest = tmp_path / 'manifest.json'
    ex = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'out', tmp_path / 'tmp',
        manifest_out=str(manifest)))
    checks = _fake_device_loop(ex, fetch_delay_s=0.005)
    ex.extract_packed(mixed_worklist, batch_size=2, inflight=inflight)
    ex.finish_obs()
    assert ex.failed_videos == 0
    assert len(checks) == 14 and all(checks)          # 27 frames, batch 2
    doc = json.loads(manifest.read_text())
    (geometry, st), = doc['decode']['batch_buffers'].items()
    assert geometry == '2x224x224x3 uint8'
    assert st['batches'] == 14 and st['recycled'] > 0
    assert st['pack_recycled'] == st['recycled'] / 14
    # at most the lookahead of 2 and the batch filling, the transfer
    # thread's two staged batches and the one it places, the steps in flight
    assert st['buffers'] + st['recycled'] == 14
    assert st['buffers'] <= 2 + 1 + 3 + inflight
    rec = doc['stages']['pack_recycled']
    assert (rec['occ_valid'], rec['occ_capacity']) == (st['recycled'], 14)


def test_a_second_run_recycles_the_first_runs_buffers_and_counts_its_own(
        mixed_worklist, tmp_path):
    """The extractor keeps its batch buffers from run to run: a second
    packed run starts in the first run's buffers, and the manifest's
    ``batch_buffers`` counts that run's batches alone (its buffers beside
    every one the geometry was given)."""
    import json
    import shutil
    manifest = tmp_path / 'manifest.json'
    ex = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'out', tmp_path / 'tmp',
        manifest_out=str(manifest)))
    _fake_device_loop(ex)
    ex.extract_packed(mixed_worklist, batch_size=2)
    first = ex.batch_buffers.stats()['2x224x224x3 uint8']
    assert first['batches'] == 14
    shutil.rmtree(tmp_path / 'out')            # nothing skipped: run again
    ex.extract_packed(mixed_worklist, batch_size=2)
    ex.finish_obs()
    assert ex.failed_videos == 0
    st = json.loads(manifest.read_text())['decode']['batch_buffers'][
        '2x224x224x3 uint8']
    assert st['batches'] == 14
    assert st['recycled'] + (st['buffers'] - first['buffers']) == 14
    assert st['recycled'] >= first['buffers'] > 0
    assert st['pack_recycled'] == st['recycled'] / 14


def test_pack_and_h2d_run_on_different_threads_and_pack_times_copies_only(
        mixed_worklist, tmp_path):
    """In a packed run the ``pack`` spans (the copies, on the thread that
    drains the window stream) and the ``h2d`` spans (the transfer thread)
    sit on different threads. Under a window source that waits 20 ms a
    window, the waits show as ``decode+preprocess`` and ``pack`` stays at
    the copies' size: no pack span covers a wait."""
    import json
    manifest = tmp_path / 'manifest.json'
    ex = create_extractor(_resnet_args(
        mixed_worklist, tmp_path / 'out', tmp_path / 'tmp',
        manifest_out=str(manifest)))
    _fake_device_loop(ex)
    real = ex.packed_windows

    def slow(task):
        for item in real(task):
            time.sleep(0.02)
            yield item

    ex.packed_windows = slow
    ex.extract_packed(mixed_worklist, decode_workers=1)
    ex.finish_obs()
    assert ex.failed_videos == 0
    spans = [e for e in ex.tracer.recorder.snapshot() if e.get('ph') == 'X']
    tids = {name: {e['tid'] for e in spans if e['name'] == name}
            for name in ('pack', 'h2d', 'model', 'decode+preprocess')}
    assert tids['pack'] and tids['h2d'] and tids['model']
    assert not tids['pack'] & tids['h2d']
    assert not tids['pack'] & tids['model']
    assert tids['decode+preprocess'] == tids['pack']   # the same thread
    stages = json.loads(manifest.read_text())['stages']
    assert stages['decode+preprocess']['total_s'] >= 27 * 0.02 * 0.9
    assert stages['pack']['total_s'] < 0.1 * stages['decode+preprocess'][
        'total_s']
    assert max(e['dur'] for e in spans if e['name'] == 'pack') < 0.02e6
    # the copies of a batch: its 4 windows in runs, and a flush span that
    # carries the batch's videos
    flushes = [e for e in spans if e['name'] == 'pack'
               and 'videos' in (e.get('args') or {})]
    assert len(flushes) == 7                           # 27 frames, batch 4
    assert sum(e['args']['windows'] for e in spans if e['name'] == 'pack'
               and 'windows' in (e.get('args') or {})) == 27


def test_batch_buffers_never_hand_one_buffer_to_two_holders():
    """Takes and gives back from more threads than cores, switched every
    10 us: no buffer is ever held twice at once, and the counts add up."""
    import sys
    import threading

    from video_features_tpu.parallel.packing import BatchBuffers
    buffers = BatchBuffers()
    held, lock, clashes = set(), threading.Lock(), []
    n_threads, rounds = 3 * (os.cpu_count() or 2), 300

    def worker(i):
        shape = (2, 3) if i % 2 else (4,)
        for _ in range(rounds):
            buf, _ = buffers.take(shape, np.uint8)
            with lock:
                if id(buf) in held:
                    clashes.append(id(buf))
                held.add(id(buf))
            buf.fill(i % 251)
            with lock:
                held.discard(id(buf))
            buffers.give_back(buf)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert clashes == []
    stats = buffers.stats()
    assert set(stats) == {'2x3 uint8', '4 uint8'}
    assert sum(s['batches'] for s in stats.values()) == n_threads * rounds
    assert all(s['buffers'] + s['recycled'] == s['batches']
               and s['recycled'] > 0 for s in stats.values())
