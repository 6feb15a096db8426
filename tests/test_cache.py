"""Content-addressed feature cache (cache/): the one invariant threaded
through every path is that a cache hit's output files are BYTE-IDENTICAL
to a cold extraction's, while skipping decode + inference entirely
(tracer-verified stage counts). Covers the CLI per-video loop, the
packed worklist (hits drop out before batch planning), the serve daemon
(hits answered before admission control), LRU eviction under size
pressure, corrupt-entry eviction, config-aware resume, and the offline
GC tool.

Fixture weight class matches tests/test_serve.py: resnet18 random
(seeded → deterministic) weights on CPU over tiny noise clips.
"""
import json
import os
from pathlib import Path

import numpy as np
import pytest

from video_features_tpu.config import load_config
from video_features_tpu.registry import create_extractor
from video_features_tpu.utils.output import make_path


from tools.make_sample_video import write_noise_clip as _write_clip  # noqa: E402

RESNET_KEYS = ('resnet', 'fps', 'timestamps_ms')


@pytest.fixture(scope='module')
def cache_clips(tmp_path_factory):
    d = tmp_path_factory.mktemp('cachevids')
    return [_write_clip(d / f'cv{i}.mp4', n, seed=i)
            for i, n in enumerate((9, 5))]


def _args(paths, out, tmp, **kw):
    over = dict(video_paths=paths, device='cpu', model_name='resnet18',
                batch_size=4, allow_random_weights=True,
                on_extraction='save_numpy', output_path=str(out),
                tmp_path=str(tmp))
    over.update(kw)
    return load_config('resnet', overrides=over)


def _extractor(paths, out, tmp, **kw):
    return create_extractor(_args(paths, out, tmp, **kw))


def _assert_identical_outputs(root_a, root_b, paths, keys=RESNET_KEYS):
    for p in paths:
        for key in keys:
            a = Path(make_path(str(root_a), p, key, '.npy'))
            b = Path(make_path(str(root_b), p, key, '.npy'))
            assert a.read_bytes() == b.read_bytes(), (p, key)


# -- key derivation (no jax, no extraction) ----------------------------------

def test_fingerprint_ignores_irrelevant_keys_and_tracks_relevant():
    from video_features_tpu.cache import config_fingerprint

    base = {'feature_type': 'resnet', 'model_name': 'resnet18',
            'batch_size': 4, 'output_path': '/a', 'tmp_path': '/b',
            'device': 'cpu', 'profile': False, 'cache_enabled': True,
            'cache_dir': '/c', 'pack_across_videos': False}
    fp = config_fingerprint(base)
    # routing/device/profiling/cache knobs must not fragment the key space
    assert config_fingerprint(dict(base, output_path='/x', tmp_path='/y',
                                   device='tpu', profile=True,
                                   cache_enabled=False, cache_dir='/z',
                                   pack_across_videos=True)) == fp
    # extraction-relevant knobs must invalidate
    assert config_fingerprint(dict(base, model_name='resnet50')) != fp
    assert config_fingerprint(dict(base, extraction_fps=5)) != fp
    # unknown future knobs stay IN the fingerprint (fail-closed)
    assert config_fingerprint(dict(base, new_knob=1)) != fp


def test_weights_fingerprint_tracks_checkpoint_content(tmp_path):
    from video_features_tpu.cache import weights_fingerprint

    ckpt = tmp_path / 'w.npz'
    ckpt.write_bytes(b'weights-v1')
    a = weights_fingerprint({'checkpoint_path': str(ckpt)})
    # same content under a different path → same identity
    copy = tmp_path / 'w_copy.npz'
    copy.write_bytes(b'weights-v1')
    assert weights_fingerprint({'checkpoint_path': str(copy)}) == a
    # swapped content under the SAME path → invalidates
    ckpt.write_bytes(b'weights-v2')
    os.utime(ckpt, ns=(1, 1))          # defeat the stat memo deliberately
    assert weights_fingerprint({'checkpoint_path': str(ckpt)}) != a
    # null checkpoint (random weights) is a distinct, stable identity
    assert weights_fingerprint({'checkpoint_path': None}) \
        == weights_fingerprint({'checkpoint_path': None})


def test_video_key_is_content_addressed(tmp_path):
    from video_features_tpu.cache import video_cache_key

    v1 = tmp_path / 'a.mp4'
    v1.write_bytes(b'same bytes')
    v2 = tmp_path / 'b.mp4'
    v2.write_bytes(b'same bytes')
    v3 = tmp_path / 'c.mp4'
    v3.write_bytes(b'other bytes')
    assert video_cache_key(str(v1), 'fp') == video_cache_key(str(v2), 'fp')
    assert video_cache_key(str(v1), 'fp') != video_cache_key(str(v3), 'fp')
    assert video_cache_key(str(v1), 'fp') != video_cache_key(str(v1), 'fp2')


# -- store mechanics (no jax) ------------------------------------------------

def _fill_store(tmp_path, n_entries, file_bytes=1000, max_bytes=None):
    from video_features_tpu.cache.store import FeatureCache

    cache = FeatureCache(str(tmp_path / 'store'), max_bytes=max_bytes)
    src_dir = tmp_path / 'srcs'
    src_dir.mkdir(exist_ok=True)
    for i in range(n_entries):
        src = src_dir / f's{i}.npy'
        src.write_bytes(bytes([i % 251]) * file_bytes)
        cache.put(f'key{i:04d}', {'feat': (str(src), '.npy')})
    return cache


def test_lru_eviction_under_size_pressure(tmp_path):
    from video_features_tpu.cache.store import FeatureCache

    cache = _fill_store(tmp_path, 4, file_bytes=1000)
    # touch entry 0 so it is the MOST recently used despite oldest insert
    out = tmp_path / 'out'
    assert cache.fetch_to('key0000', str(out), '/v/clip.mp4')
    report = cache.gc(target_bytes=2000)
    assert report['lru_evicted'] == 2
    # LRU order: 1 and 2 evicted; 0 (touched) and 3 (newest) survive
    assert cache.contains('key0000') and cache.contains('key0003')
    assert not cache.contains('key0001') and not cache.contains('key0002')
    assert cache.stats()['bytes'] <= 2000
    # a fresh instance replaying the compacted manifest agrees
    reloaded = FeatureCache(cache.cache_dir)
    assert reloaded.stats()['entries'] == 2
    assert reloaded.contains('key0000') and reloaded.contains('key0003')


def test_inline_eviction_on_publish_over_max_bytes(tmp_path):
    cache = _fill_store(tmp_path, 5, file_bytes=1000, max_bytes=3000)
    st = cache.stats()
    assert st['bytes'] <= 3000
    assert st['evictions'] >= 2
    assert cache.contains('key0004')            # the newest always survives


def test_on_evict_callback_may_reenter_the_cache(tmp_path):
    """Eviction subscribers fire OUTSIDE the store lock: a callback that
    calls back into the cache (the index ingest thread does exactly
    this) must neither deadlock nor see a stale index."""
    cache = _fill_store(tmp_path, 4, file_bytes=1000)
    seen = []

    def reentrant(key, corrupt):
        # re-enter through the locked public surface — a lock held
        # across the callback would deadlock right here
        seen.append((key, corrupt, cache.contains(key)))
        cache.stats()

    cache.on_evict.append(reentrant)
    report = cache.gc(target_bytes=2000)
    assert report['lru_evicted'] == 2
    assert len(seen) == 2
    # by notification time the entry is already gone from the index
    assert all(not present for _, _, present in seen)
    assert all(not corrupt for _, corrupt, _ in seen)


def test_corrupt_entry_evicted_not_served(tmp_path):
    cache = _fill_store(tmp_path, 2)
    edir = Path(cache.cache_dir) / 'objects' / 'ke' / 'key0000'
    (edir / 'feat.npy').write_bytes(b'short')   # truncate
    out = tmp_path / 'o'
    assert not cache.fetch_to('key0000', str(out), '/v/x.mp4')
    st = cache.stats()
    assert st['corrupt_evicted'] == 1 and not cache.contains('key0000')
    assert not Path(make_path(str(out), '/v/x.mp4', 'feat', '.npy')).exists()
    # the healthy entry still serves
    assert cache.fetch_to('key0001', str(out), '/v/y.mp4')


def test_gc_verify_catches_same_size_bit_rot(tmp_path):
    cache = _fill_store(tmp_path, 2, file_bytes=64)
    edir = Path(cache.cache_dir) / 'objects' / 'ke' / 'key0000'
    (edir / 'feat.npy').write_bytes(b'X' * 64)  # same size, wrong bytes
    assert cache.gc(verify=False)['corrupt_evicted'] == 0  # size check blind
    report = cache.gc(verify=True)
    assert report['corrupt_evicted'] == 1
    assert not cache.contains('key0000') and cache.contains('key0001')


def test_manifest_tolerates_torn_tail_line(tmp_path):
    from video_features_tpu.cache.store import FeatureCache

    cache = _fill_store(tmp_path, 2)
    with open(cache.manifest_path, 'a') as f:
        f.write('{"op": "put", "key": "torn')   # crash mid-append
    reloaded = FeatureCache(cache.cache_dir)
    assert reloaded.stats()['entries'] == 2


def test_cache_gc_tool_exit_codes_and_report(tmp_path, capsys):
    import tools.cache_gc as gc_tool

    cache = _fill_store(tmp_path, 3, file_bytes=500)
    # clean run: exit 0, JSON report on stdout
    assert gc_tool.main(['--cache-dir', cache.cache_dir]) == 0
    report = json.loads(capsys.readouterr().out.strip())
    assert report['entries_after'] == 3 and report['corrupt_evicted'] == 0
    # corrupt an entry: --verify finds it, exit 1
    edir = Path(cache.cache_dir) / 'objects' / 'ke' / 'key0001'
    (edir / 'feat.npy').write_bytes(b'Z' * 500)
    assert gc_tool.main(['--cache-dir', cache.cache_dir, '--verify']) == 1
    report = json.loads(capsys.readouterr().out.strip())
    assert report['corrupt_evicted'] == 1
    # size pressure: evict down to one entry's bytes
    assert gc_tool.main(['--cache-dir', cache.cache_dir,
                         '--target-bytes', '500']) == 0
    report = json.loads(capsys.readouterr().out.strip())
    assert report['bytes_after'] <= 500
    # usage errors: exit 2
    assert gc_tool.main(['--cache-dir', str(tmp_path / 'nope')]) == 2
    assert gc_tool.main(['--cache-dir', cache.cache_dir,
                         '--target-bytes', '-1']) == 2


def test_corrupt_output_error_raised_on_truncated_files(tmp_path):
    from video_features_tpu.utils.output import (
        CorruptOutputError, load_numpy, load_pickle, write_numpy,
        write_pickle,
    )

    npy = str(tmp_path / 'a.npy')
    write_numpy(npy, np.arange(8))
    Path(npy).write_bytes(Path(npy).read_bytes()[:20])      # truncate
    with pytest.raises(CorruptOutputError):
        load_numpy(npy)
    pkl = str(tmp_path / 'b.pkl')
    write_pickle(pkl, {'x': 1})
    Path(pkl).write_bytes(b'')                              # empty
    with pytest.raises(CorruptOutputError):
        load_pickle(pkl)
    with pytest.raises(FileNotFoundError):                  # NOT corruption
        load_numpy(str(tmp_path / 'missing.npy'))


# -- CLI per-video loop ------------------------------------------------------

def test_cli_path_hit_is_byte_identical_and_skips_compute(
        cache_clips, tmp_path):
    cache_dir = str(tmp_path / 'fc')

    def run_pass(tag):
        ex = _extractor(cache_clips, tmp_path / tag, tmp_path / 'tmp',
                        cache_enabled=True, cache_dir=cache_dir,
                        profile=True)
        ex.tracer.reset = lambda: None   # accumulate stages across videos
        for p in cache_clips:
            ex._extract(p)
        return ex, ex.tracer.report()

    ex1, rep1 = run_pass('cold')
    assert rep1['model']['count'] > 0
    assert ex1.cache.stats()['puts'] == len(cache_clips)

    ex2, rep2 = run_pass('warm')
    # the acceptance tracer check: hits ran no decode and no model step
    assert 'model' not in rep2 and 'decode+preprocess' not in rep2, rep2
    assert rep2['cache_lookup']['count'] == len(cache_clips)
    assert ex2.cache.stats()['hits'] == len(cache_clips)
    _assert_identical_outputs(ex1.output_path, ex2.output_path, cache_clips)


def test_cli_path_corrupt_entry_is_reextracted_not_served(
        cache_clips, tmp_path):
    """A stored object truncated on disk: the next consult evicts it,
    that video falls back to a cold extraction (the model runs again) and
    re-publishes — and what it saves is what the cold pass saved, never
    the bad bytes."""
    cache_dir = str(tmp_path / 'fc')

    def run_pass(tag):
        ex = _extractor(cache_clips, tmp_path / tag, tmp_path / 'tmp',
                        cache_enabled=True, cache_dir=cache_dir,
                        profile=True)
        ex.tracer.reset = lambda: None   # accumulate stages across videos
        for p in cache_clips:
            ex._extract(p)
        return ex, ex.tracer.report()

    ex1, _ = run_pass('cold')
    victim = next((Path(cache_dir) / 'objects').glob('*/*/resnet.npy'))
    victim.write_bytes(victim.read_bytes()[:16])

    ex2, rep2 = run_pass('corrupt')
    st = ex2.cache.stats()
    assert st['corrupt_evicted'] == 1, st
    assert st['hits'] == len(cache_clips) - 1, st
    assert st['puts'] == len(cache_clips) + 1, st   # the victim re-published
    assert rep2['model']['count'] > 0           # the victim re-extracted
    _assert_identical_outputs(ex1.output_path, ex2.output_path, cache_clips)


def test_cache_disabled_reproduces_legacy_behavior(cache_clips, tmp_path):
    """Without cache_enabled nothing consults or populates a cache and no
    cache stages appear — today's behavior exactly."""
    ex = _extractor(cache_clips, tmp_path / 'out', tmp_path / 'tmp',
                    profile=True)
    assert ex.cache is None
    ex.tracer.reset = lambda: None
    for p in cache_clips:
        ex._extract(p)
    rep = ex.tracer.report()
    assert 'cache_lookup' not in rep and 'cache_publish' not in rep
    # outputs still produced through the unchanged save path
    for p in cache_clips:
        assert Path(make_path(ex.output_path, p, 'resnet', '.npy')).exists()


def test_packed_worklist_drops_hits_before_batch_planning(
        cache_clips, tmp_path):
    cache_dir = str(tmp_path / 'fc_packed')

    def run_pass(tag):
        ex = _extractor(cache_clips, tmp_path / tag, tmp_path / 'tmp',
                        cache_enabled=True, cache_dir=cache_dir,
                        pack_across_videos=True, profile=True)
        ex.tracer.reset = lambda: None
        ex.extract_packed(cache_clips)
        return ex, ex.tracer.report()

    ex1, rep1 = run_pass('pk_cold')
    assert rep1['model']['count'] > 0
    ex2, rep2 = run_pass('pk_warm')
    # hits never produced windows: no device batch ever packed
    assert 'model' not in rep2 and 'h2d' not in rep2, rep2
    assert ex2.cache.stats()['hits'] == len(cache_clips)
    _assert_identical_outputs(ex1.output_path, ex2.output_path, cache_clips)


# -- config-aware resume (satellite) -----------------------------------------

def test_resume_reextracts_on_config_change_with_warning(
        cache_clips, tmp_path, capsys):
    out, tmp = tmp_path / 'out', tmp_path / 'tmp'
    clip = cache_clips[0]
    ex_a = _extractor([clip], out, tmp)
    ex_a._extract(clip)
    # same config skips (fingerprint sidecar matches)
    capsys.readouterr()
    ex_a2 = _extractor([clip], out, tmp)
    ex_a2._extract(clip)
    assert 'already exist' in capsys.readouterr().out

    # a different extraction recipe must NOT reuse those outputs
    feat_path = Path(make_path(str(out / 'resnet' / 'resnet18'), clip,
                               'resnet', '.npy'))
    before = feat_path.read_bytes()
    with pytest.warns(UserWarning, match='different config'):
        ex_b = _extractor([clip], out, tmp, extraction_fps=2)
        ex_b._extract(clip)
    after = feat_path.read_bytes()
    assert before != after            # re-extracted under the new recipe
    # and the sidecar now records the new fingerprint → new config skips
    capsys.readouterr()
    ex_b2 = _extractor([clip], out, tmp, extraction_fps=2)
    ex_b2._extract(clip)
    assert 'already exist' in capsys.readouterr().out


def test_resume_legacy_outputs_without_sidecar_still_skip(
        cache_clips, tmp_path, capsys):
    out, tmp = tmp_path / 'out', tmp_path / 'tmp'
    clip = cache_clips[0]
    ex = _extractor([clip], out, tmp)
    ex._extract(clip)
    # simulate pre-fingerprint outputs: drop the sidecar
    side = Path(make_path(str(out / 'resnet' / 'resnet18'), clip,
                          'fingerprint', '.json'))
    side.unlink()
    capsys.readouterr()
    ex2 = _extractor([clip], out, tmp)
    ex2._extract(clip)
    assert 'already exist' in capsys.readouterr().out   # legacy skip kept


# -- serve path --------------------------------------------------------------

def test_serve_answers_hits_before_admission(cache_clips, tmp_path):
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer

    server = ExtractionServer(
        base_overrides={
            'device': 'cpu', 'model_name': 'resnet18', 'batch_size': 4,
            'allow_random_weights': True, 'on_extraction': 'save_numpy',
            'tmp_path': str(tmp_path / 'serve_tmp'),
            'cache_enabled': True,
            'cache_dir': str(tmp_path / 'serve_cache'),
        },
        queue_depth=8, pool_size=2).start()
    try:
        client = ServeClient(port=server.port)
        out_cold = str(tmp_path / 'cold')
        rid = client.submit('resnet', cache_clips,
                            overrides={'output_path': out_cold})
        st = client.wait(rid, timeout_s=180)
        assert st['state'] == 'done', st
        assert set(st['videos'].values()) == {'saved'}

        # warm pass: every video answered from cache, request terminal at
        # birth — no queue slot, no worker wakeup
        depth_before = server.metrics()['queue']['depth']
        out_warm = str(tmp_path / 'warm')
        rid2 = client.submit('resnet', cache_clips,
                             overrides={'output_path': out_warm})
        st2 = client.status(rid2)      # no wait: must already be terminal
        assert st2['state'] == 'done', st2
        assert set(st2['videos'].values()) == {'cached'}
        m = client.metrics()
        assert m['queue']['depth'] == depth_before   # never occupied a slot
        assert m['cache']['hits'] == len(cache_clips)
        assert m['cache']['bytes_saved'] > 0
        assert m['requests']['cached_videos'] == len(cache_clips)
        _assert_identical_outputs(
            os.path.join(out_cold, 'resnet', 'resnet18'),
            os.path.join(out_warm, 'resnet', 'resnet18'), cache_clips)

        # a mixed request: one known video (hit) + one new (extracted)
        extra = _write_clip(tmp_path / 'extra.mp4', 7, seed=9)
        out_mix = str(tmp_path / 'mix')
        rid3 = client.submit('resnet', [cache_clips[0], str(extra)],
                             overrides={'output_path': out_mix})
        st3 = client.wait(rid3, timeout_s=180)
        assert st3['state'] == 'done', st3
        assert st3['videos'][cache_clips[0]] == 'cached'
        assert st3['videos'][str(extra)] == 'saved'
    finally:
        server.drain(wait=True, grace_s=60)
