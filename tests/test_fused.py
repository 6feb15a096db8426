"""Fused multi-family worklists (``features=[...]``): decode once,
extract many.

The contract under test is BYTE-IDENTITY plus AMORTIZATION: a fused run
over N families produces exactly the files N sequential runs produce
(same names, same bytes, same cache keys), while decoding and
content-hashing each video exactly ONCE — the `decode_pass` instant and
`cache.key.hash_file_stats()` are the designed observables
(docs/decode_farm.md § multi-recipe).

Budget discipline (tier-1): ONE extractor per family for the whole
module (the transplant+compile dominates; the contracts are about the
LOOPS), tiny clips, and the farm/serve e2e variants are ``slow``.
"""
import os
import shutil
from pathlib import Path

import numpy as np
import pytest

from video_features_tpu.config import (
    load_config, load_fused_configs, resolve_fused_features,
    split_fused_overrides,
)
from video_features_tpu.registry import create_extractor
from video_features_tpu.utils.output import make_path

from tools.make_sample_video import write_noise_clip as _write_clip  # noqa: E402

FAMS = ('resnet', 'clip')
KEYS = {'resnet': ('resnet', 'fps', 'timestamps_ms'),
        'clip': ('clip', 'fps', 'timestamps_ms')}


# -- config layer (no jax device work) ---------------------------------------


def test_resolve_fused_features_normalizes_and_validates():
    assert resolve_fused_features(['resnet', 'clip']) == ['resnet', 'clip']
    # comma string (the non-YAML CLI spelling) and dedup, user order kept
    assert resolve_fused_features('clip, resnet,clip') == ['clip', 'resnet']
    # single family is legal — routes to the ordinary path
    assert resolve_fused_features('i3d') == ['i3d']
    with pytest.raises(ValueError, match='unknown family'):
        resolve_fused_features(['resnet', 'nosuch'])
    with pytest.raises(ValueError, match='at least one'):
        resolve_fused_features([])
    with pytest.raises(ValueError, match='features must be'):
        resolve_fused_features(42)


def test_split_fused_overrides_scopes_and_drops_routing_keys():
    shared, scoped = split_fused_overrides(
        {'features': ['resnet', 'clip'], 'feature_type': 'resnet',
         'batch_size': 4, 'clip.model_name': 'ViT-B/32',
         'resnet.batch_size': 8, 'some.dotted.path': 1},
        ['resnet', 'clip'])
    # routing keys never reach a merged config: 'features' leaking in
    # would fragment the fail-closed cache fingerprint vs sequential
    assert 'features' not in shared and 'feature_type' not in shared
    assert shared['batch_size'] == 4
    # a dotted key whose head is not a requested family stays shared
    assert shared['some.dotted.path'] == 1
    assert scoped['clip'] == {'model_name': 'ViT-B/32'}
    assert scoped['resnet'] == {'batch_size': 8}


def test_fused_configs_equal_sequential_configs(tmp_path):
    """Cache-key identity at its root: each family's fused merged config
    must equal the sequential `load_config(family, ...)` one — equal
    configs make `config_fingerprint` (and with the shared video hash,
    every per-(family, video) cache key) identical."""
    from video_features_tpu.cache import config_fingerprint
    over = dict(device='cpu', batch_size=4, allow_random_weights=True,
                on_extraction='save_numpy', output_path=str(tmp_path),
                tmp_path=str(tmp_path / 'tmp'))
    fused = load_fused_configs(
        ['resnet', 'clip'],
        overrides=dict(over, features=['resnet', 'clip'],
                       **{'resnet.model_name': 'resnet18',
                          'clip.model_name': 'ViT-B/32'}),
        run_sanity_check=False)
    seq = {'resnet': load_config('resnet',
                                 overrides=dict(over, model_name='resnet18'),
                                 run_sanity_check=False),
           'clip': load_config('clip',
                               overrides=dict(over, model_name='ViT-B/32'),
                               run_sanity_check=False)}
    for fam in ('resnet', 'clip'):
        assert dict(fused[fam]) == dict(seq[fam]), fam
        assert config_fingerprint(fused[fam]) == config_fingerprint(seq[fam])


# -- packer: per-family pooling ----------------------------------------------


def test_packed_batches_pool_per_family_at_own_cap():
    """Fused pools key (family, shape, dtype) and fill at THAT family's
    packed batch size — resnet/clip share 224x224x3 uint8 geometry, and
    a shared pool would feed one family's compiled program the other's
    batch capacity (a new program identity, an AOT-store miss)."""
    from video_features_tpu.parallel.packing import packed_batches
    from video_features_tpu.utils.tracing import NULL_TRACER

    win = np.zeros((4, 4, 3), dtype=np.uint8)

    def windows():
        for i in range(6):            # interleaved families, same shape
            yield f't{i}', win, ('a', i)
            yield f't{i}', win, ('b', i)

    out = list(packed_batches(windows(), 8, tracer=NULL_TRACER,
                              family_of=lambda m: m[0],
                              family_batch={'a': 2, 'b': 4}))
    got = [(m[0][1][0], len(m), v, s.shape[0]) for s, m, v in out if m]
    # family a flushes every 2 windows, family b every 4 — each padded
    # to its OWN capacity
    assert got == [('a', 2, 2, 2), ('a', 2, 2, 2), ('b', 4, 4, 4),
                   ('a', 2, 2, 2), ('b', 2, 2, 4)]
    for stacked, metas, valid in out:
        fams = {m[0] for _, m in metas}
        assert len(fams) == 1          # never mixed across families


def test_run_packed_fused_rejects_mismatched_signatures():
    class Fake:
        def __init__(self, sig):
            self._sig = sig

        def fused_decode_signature(self):
            return self._sig

    from video_features_tpu.parallel.packing import run_packed_fused
    with pytest.raises(ValueError, match='cannot share one decode pass'):
        run_packed_fused({'a': Fake(('framewise', None, None, 'auto')),
                          'b': Fake(('framewise', 5, None, 'auto'))}, [])
    with pytest.raises(ValueError, match='cannot share one decode pass'):
        run_packed_fused({'a': Fake(None), 'b': Fake(None)}, [])


# -- shared extractors (ONE per family for the whole module) -----------------


@pytest.fixture(scope='module')
def fused_clips(tmp_path_factory):
    d = tmp_path_factory.mktemp('fusedvids')
    return [str(_write_clip(d / f'fv{i}.mp4', n, seed=40 + i))
            for i, n in enumerate((7, 4))]


@pytest.fixture(scope='module')
def fused_exs(fused_clips, tmp_path_factory):
    base = tmp_path_factory.mktemp('fusedexs')
    models = {'resnet': 'resnet18', 'clip': 'ViT-B/32'}
    exs = {}
    for fam in FAMS:
        exs[fam] = create_extractor(load_config(fam, overrides=dict(
            video_paths=fused_clips, device='cpu', model_name=models[fam],
            batch_size=4, allow_random_weights=True,
            on_extraction='save_numpy', profile=True,
            output_path=str(base / 'out' / fam),
            tmp_path=str(base / 'tmp' / fam))))
    sigs = {f: e.fused_decode_signature() for f, e in exs.items()}
    assert len(set(sigs.values())) == 1 and None not in sigs.values(), sigs
    return exs


def _fused_tasks(exs, paths, root):
    from video_features_tpu.parallel.packing import FusedTask
    tasks = []
    for p in paths:
        c = FusedTask(p, list(exs))
        for fam, sub in c.subtasks.items():
            sub.out_root = str(Path(root) / fam)
        tasks.append(c)
    return tasks


def _run_fused(exs, tasks, **kw):
    """Run the fused driver with a fresh recorder on the lead tracer;
    returns the recorded events (the tracer itself stays module-shared)."""
    from video_features_tpu.obs.spans import SpanRecorder
    from video_features_tpu.parallel.packing import run_packed_fused
    lead = exs[next(iter(exs))]
    rec = SpanRecorder(capacity=4096)
    lead.tracer.recorder = rec
    try:
        run_packed_fused(exs, tasks, **kw)
    finally:
        lead.tracer.recorder = None
    return rec.snapshot()


def _outputs(root, paths, keys):
    return {(Path(p).name, k): np.load(make_path(str(root), p, k, '.npy'))
            for p in paths for k in keys}


@pytest.fixture(scope='module')
def fused_run(fused_exs, fused_clips, tmp_path_factory):
    """ONE fused pass + ONE sequential pass per family over the module
    extractors; several tests assert different contracts over it."""
    from video_features_tpu.parallel.packing import VideoTask
    root = tmp_path_factory.mktemp('fusedrun')
    events = _run_fused(fused_exs,
                        _fused_tasks(fused_exs, fused_clips, root / 'fused'))
    for fam, ex in fused_exs.items():
        ex.extract_packed([VideoTask(p, out_root=str(root / 'seq' / fam))
                           for p in fused_clips])
    return {'root': root, 'events': events}


def test_fused_outputs_byte_identical_to_sequential(fused_run, fused_exs,
                                                    fused_clips):
    root = fused_run['root']
    for fam in fused_exs:
        a = _outputs(root / 'seq' / fam, fused_clips, KEYS[fam])
        b = _outputs(root / 'fused' / fam, fused_clips, KEYS[fam])
        assert set(os.listdir(root / 'seq' / fam)) == \
            set(os.listdir(root / 'fused' / fam)), fam
        for key in a:
            np.testing.assert_array_equal(a[key], b[key],
                                          err_msg=f'{fam}:{key}')


def test_fused_run_decodes_each_video_exactly_once(fused_run, fused_exs,
                                                   fused_clips):
    """The amortization guard's decode half: exactly one `decode_pass`
    instant per video, each fanning out to EVERY family — N families'
    worth of outputs from one decode span set."""
    passes = [e for e in fused_run['events']
              if e['ph'] == 'i' and e['name'] == 'decode_pass']
    assert len(passes) == len(fused_clips)
    assert sorted(e['args']['video'] for e in passes) == sorted(fused_clips)
    for e in passes:
        assert e['args']['families'] == list(fused_exs)
    starts = [e for e in fused_run['events']
              if e['ph'] == 'i' and e['name'] == 'video_start']
    assert len(starts) == len(fused_clips)


def test_fused_run_hashes_each_video_exactly_once(fused_exs, fused_clips,
                                                  tmp_path):
    """The amortization guard's sha256 half: with the content cache on,
    a fused run streams each video's bytes through sha256 ONCE — every
    other family's cache key rides the stat-keyed memo. Fresh file
    copies make the memo provably cold."""
    from video_features_tpu.cache.key import (
        hash_file_stats, reset_hash_file_stats,
    )
    from video_features_tpu.cache.store import FeatureCache
    clips = [str(shutil.copy(p, tmp_path / Path(p).name))
             for p in fused_clips]
    cache = FeatureCache(str(tmp_path / 'cache'))
    for ex in fused_exs.values():
        assert ex.run_fingerprint is not None
        ex.cache = cache
    try:
        reset_hash_file_stats()
        events = _run_fused(fused_exs,
                            _fused_tasks(fused_exs, clips, tmp_path / 'out'))
        stats = hash_file_stats()
    finally:
        for ex in fused_exs.values():
            ex.cache = None
    assert stats['passes'] == len(clips), stats
    # admission keys for the second family + publish-time keys all memo
    assert stats['memo_hits'] >= len(clips), stats
    assert sum(1 for e in events
               if e['ph'] == 'i' and e['name'] == 'decode_pass') == len(clips)
    # and the cache now holds every (family, video) object
    assert cache.stats()['entries'] == len(fused_exs) * len(clips)


def test_fused_family_fault_isolated_to_its_subtask(fused_exs, fused_clips,
                                                    tmp_path):
    """One family's device-step fault must not poison its siblings: the
    shared decode keeps feeding the healthy family, whose outputs stay
    byte-identical to a clean run's."""
    boom_fam = 'clip'

    def boom(_dev):
        raise RuntimeError('injected device fault')

    orig = fused_exs[boom_fam].packed_step
    fused_exs[boom_fam].packed_step = boom
    try:
        _run_fused(fused_exs,
                   _fused_tasks(fused_exs, fused_clips, tmp_path / 'f'))
    finally:
        fused_exs[boom_fam].packed_step = orig
    ok_fam = 'resnet'
    got = _outputs(tmp_path / 'f' / ok_fam, fused_clips, KEYS[ok_fam])
    ref = _run_fused_single_reference(fused_exs, ok_fam, fused_clips,
                                      tmp_path / 'ref')
    for key in ref:
        np.testing.assert_array_equal(got[key], ref[key], err_msg=str(key))
    # the faulted family wrote nothing
    for p in fused_clips:
        assert not Path(make_path(str(tmp_path / 'f' / boom_fam), p,
                                  boom_fam, '.npy')).exists()


def _run_fused_single_reference(exs, fam, clips, root):
    from video_features_tpu.parallel.packing import VideoTask
    exs[fam].extract_packed([VideoTask(p, out_root=str(root))
                             for p in clips])
    return _outputs(root, clips, KEYS[fam])


def test_fused_decode_fault_fails_all_families_for_that_video_only(
        fused_exs, fused_clips, tmp_path):
    """A decode fault is the carrier's: the unopenable video fails for
    EVERY family, while the healthy videos' outputs are untouched."""
    bad = str(tmp_path / 'gone.mp4')          # never created
    worklist = fused_clips[:1] + [bad] + fused_clips[1:]
    _run_fused(fused_exs, _fused_tasks(fused_exs, worklist, tmp_path / 'd'))
    for fam in fused_exs:
        for p in fused_clips:
            assert Path(make_path(str(tmp_path / 'd' / fam), p, fam,
                                  '.npy')).exists(), (fam, p)
        assert not Path(make_path(str(tmp_path / 'd' / fam), bad, fam,
                                  '.npy')).exists(), fam


# -- CLI routing -------------------------------------------------------------


def test_cli_features_routes_fused(tmp_path, tmp_path_factory):
    """`features=[resnet]` exercises the fused CLI surface end to end
    (config fan-out, signature grouping, packed run) at single-family
    cost; the multi-family CLI pass is the slow lane's."""
    from video_features_tpu.cli import main
    d = tmp_path_factory.mktemp('clifused')
    clip_path = str(_write_clip(d / 'c.mp4', 4, seed=91))
    out = tmp_path / 'out'
    argv = ['features=[resnet]', 'device=cpu', 'model_name=resnet18',
            'batch_size=4', 'allow_random_weights=true',
            'on_extraction=save_numpy', f'output_path={out}',
            f'tmp_path={tmp_path / "tmp"}']
    assert main(argv + [f'video_paths=[{clip_path}]']) == 0
    # sanity_check appends <family>/<model_name> to the output root
    final = out / 'resnet' / 'resnet18'
    for k in KEYS['resnet']:
        assert Path(make_path(str(final), clip_path, k, '.npy')).exists(), k
    # a failed video keeps the fused worklist going (the saved clip is a
    # resume skip here) but shows in the exit code
    gone = tmp_path / 'gone.mp4'              # never created
    assert main(argv + [f'video_paths=[{gone},{clip_path}]']) == 1


@pytest.mark.slow
def test_cli_features_multi_family_fused_e2e(tmp_path, tmp_path_factory):
    from video_features_tpu.cli import main
    d = tmp_path_factory.mktemp('clifused2')
    clip_path = str(_write_clip(d / 'c.mp4', 5, seed=92))
    out = tmp_path / 'out'
    rc = main(['features=[resnet,clip]', f'video_paths=[{clip_path}]',
               'device=cpu', 'batch_size=4', 'resnet.model_name=resnet18',
               'clip.model_name=ViT-B/32', 'allow_random_weights=true',
               'on_extraction=save_numpy', f'output_path={out}',
               f'tmp_path={tmp_path / "tmp"}'])
    assert rc == 0
    for fam, model in (('resnet', 'resnet18'), ('clip', 'ViT-B_32')):
        root = out / fam / model
        assert Path(make_path(str(root), clip_path, fam, '.npy')).exists(), \
            fam


# -- serve: fused submit ------------------------------------------------------


def test_serve_fused_submit_rejections(tmp_path):
    """The fan-out rejection surface costs no extraction: unknown
    families, non-packable families, and empty worklists reject the
    whole fused request before any child admits."""
    from video_features_tpu.serve.server import ExtractionServer
    srv = ExtractionServer(base_overrides={
        'device': 'cpu', 'model_name': 'resnet18', 'batch_size': 4,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'tmp_path': str(tmp_path / 'tmp'),
        'output_path': str(tmp_path / 'out')}, queue_depth=4).start()
    try:
        r = srv.submit(None, ['/x.mp4'], features=['resnet', 'nosuch'])
        assert not r['ok'] and 'nosuch' in r['error']
        r = srv.submit(None, ['/x.mp4'], features=['vggish'])
        assert not r['ok']
        r = srv.submit(None, [], features=['resnet'])
        assert not r['ok']
        r = srv.submit(None, ['/x.mp4'], features='')
        assert not r['ok']
    finally:
        srv.drain()


@pytest.mark.slow
def test_serve_fused_submit_e2e(tmp_path, tmp_path_factory):
    """Umbrella + per-family children over the loopback socket; a
    resubmit answers terminal-at-birth from the cache."""
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer
    d = tmp_path_factory.mktemp('servefusedvids')
    clips = [str(_write_clip(d / f's{i}.mp4', n, seed=60 + i))
             for i, n in enumerate((6, 4))]
    srv = ExtractionServer(base_overrides={
        'device': 'cpu', 'model_name': 'resnet18', 'batch_size': 4,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'tmp_path': str(tmp_path / 'tmp'),
        'output_path': str(tmp_path / 'out'),
        'cache_enabled': True, 'cache_dir': str(tmp_path / 'cache')},
        queue_depth=32, pool_size=2).start()
    try:
        c = ServeClient(srv.port)
        over = {'clip.model_name': 'ViT-B/32'}
        rid = c.submit(None, clips, features=['resnet', 'clip'],
                       overrides=over)
        st = c.wait(rid, timeout_s=420)
        assert st['state'] == 'done'
        assert set(st['requests']) == {'resnet', 'clip'}
        assert set(st['videos']) == {'resnet', 'clip'}
        for fam, vids in st['videos'].items():
            assert set(vids) == set(clips)
            assert all(v in ('saved', 'cached') for v in vids.values()), \
                (fam, vids)
        # all-hit resubmit: terminal before the submit response returns
        rid2 = c.submit(None, clips, features=['resnet', 'clip'],
                        overrides=over)
        assert c.status(rid2)['state'] == 'done'
    finally:
        c.drain()


# -- decode farm --------------------------------------------------------------


@pytest.mark.slow
def test_fused_farm_matches_in_process(fused_exs, fused_clips, tmp_path):
    """decode_workers>1 ships the SAME FusedRecipe to the farm workers;
    the tagged window stream back over the ring must reproduce the
    in-process fused outputs byte for byte."""
    _run_fused(fused_exs,
               _fused_tasks(fused_exs, fused_clips, tmp_path / 'farm'),
               decode_workers=2)
    for fam in fused_exs:
        ref = _run_fused_single_reference(fused_exs, fam, fused_clips,
                                          tmp_path / 'ref' / fam)
        got = _outputs(tmp_path / 'farm' / fam, fused_clips, KEYS[fam])
        for key in ref:
            np.testing.assert_array_equal(got[key], ref[key],
                                          err_msg=f'{fam}:{key}')
