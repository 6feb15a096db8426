"""vft-aot: the persistent executable store (aot/) — zero cold start.

Tier-1 budget discipline (the 870 s cap): the extractor-building
coverage shares ONE module-scoped cold fixture — a single resnet18
build whose packed run publishes the store — and every downstream test
(warm CLI repeat, serve compile-free boot) consumes that store instead
of paying its own cold build; multi-family store coverage lives in the
slow lane. Store/runtime units and the GC tool run on fabricated
stores and toy jits — no extractor builds at all.
"""
import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from tools.make_sample_video import write_noise_clip  # noqa: E402

from video_features_tpu.aot.store import ExecStore, exec_digest  # noqa: E402


def _mkstore(tmp_path, **kw) -> ExecStore:
    # fresh instance, NOT ExecStore.get: unit tests must not share the
    # process-global registry (counters would bleed across tests)
    return ExecStore(str(tmp_path / 'store'), **kw)


# -- store units (jax-free) ---------------------------------------------------


def test_store_roundtrip_idempotent_and_replay(tmp_path):
    store = _mkstore(tmp_path)
    digest = exec_digest({'program_sha': 'abc', 'lane': 'mesh1'})
    payload = b'x' * 1024
    assert store.fetch(digest) is None           # cold miss
    store.put(digest, payload, meta={'program_sha': 'abc',
                                     'feature_type': 'toy'})
    store.put(digest, payload)                   # idempotent (touch only)
    assert store.puts == 1
    assert store.fetch(digest) == payload
    assert store.stats()['hits'] == 1 and store.stats()['entries'] == 1
    # a FRESH instance replays the manifest and serves the same bytes
    again = ExecStore(store.aot_dir)
    assert again.fetch(digest) == payload
    assert again.stats()['bytes'] == len(payload)


def test_store_truncated_payload_evicted_not_served(tmp_path):
    store = _mkstore(tmp_path)
    digest = exec_digest({'program_sha': 'corrupt-me', 'lane': 'mesh1'})
    store.put(digest, b'y' * 512)
    victim = Path(store._payload_path(digest))
    victim.write_bytes(victim.read_bytes()[:100])     # torn write / rot
    assert store.fetch(digest) is None
    st = store.stats()
    assert st['corrupt_evicted'] == 1 and st['entries'] == 0
    # a deserialize-time failure reported back also purges
    digest2 = exec_digest({'program_sha': 'poisoned', 'lane': 'mesh1'})
    store.put(digest2, b'z' * 64)
    store.evict_corrupt(digest2)
    assert store.fetch(digest2) is None
    assert store.stats()['corrupt_evicted'] == 2


def test_store_lru_gc_to_target_bytes(tmp_path):
    store = _mkstore(tmp_path)
    digests = []
    for i in range(4):
        d = exec_digest({'program_sha': f'p{i}', 'lane': 'mesh1'})
        store.put(d, bytes([i]) * 1000)
        digests.append(d)
    store.fetch(digests[0])                      # refresh oldest → MRU
    report = store.gc(target_bytes=2000)
    assert report['lru_evicted'] == 2
    assert store.fetch(digests[0]) is not None   # refreshed survivor
    assert store.fetch(digests[3]) is not None   # newest survivor
    assert store.fetch(digests[1]) is None and store.fetch(digests[2]) is None
    # inline pressure on publish: max_bytes bounds the store online too
    bounded = ExecStore(str(tmp_path / 'bounded'), max_bytes=2500)
    for i in range(3):
        bounded.put(exec_digest({'program_sha': f'b{i}', 'lane': 'm'}),
                    bytes([i]) * 1000)
    assert bounded.stats()['bytes'] <= 2500


def test_store_gc_compaction_keeps_concurrent_puts(tmp_path):
    """A put another process appends WHILE a (long) gc sweep runs must
    survive the compaction rewrite — dropping its record would turn a
    later orphan sweep into data loss for an entry a live daemon still
    serves. Simulated by publishing through a SECOND instance after the
    first instance loaded its view."""
    store = _mkstore(tmp_path)
    kept = exec_digest({'program_sha': 'kept', 'lane': 'mesh1'})
    store.put(kept, b'k' * 100)
    # a concurrent process publishes AFTER `store` loaded its view...
    other = ExecStore(store.aot_dir)
    racing = exec_digest({'program_sha': 'racing', 'lane': 'mesh1'})
    other.put(racing, b'r' * 100)
    # ...which `store`'s in-memory index has never seen; its gc reloads,
    # but the race window is between that reload and the compaction —
    # emulate it by publishing during the sweep via the reload hook
    real_load = store._load_manifest
    state = {'raced': False}

    def load_then_race():
        real_load()
        if not state['raced']:
            state['raced'] = True
            late = ExecStore(store.aot_dir)
            late.put(exec_digest({'program_sha': 'late', 'lane': 'm'}),
                     b'l' * 100)

    store._load_manifest = load_then_race
    store.gc(verify=True)
    # every entry survives the rewrite — including the one that landed
    # mid-sweep
    final = ExecStore(store.aot_dir)
    assert final.fetch(kept) is not None
    assert final.fetch(racing) is not None
    assert final.fetch(exec_digest({'program_sha': 'late',
                                    'lane': 'm'})) is not None


def test_aot_gc_tool_exit_codes(tmp_path):
    from tools.aot_gc import main as gc_main

    store = ExecStore(str(tmp_path / 'store'))
    good = exec_digest({'program_sha': 'good', 'lane': 'mesh1'})
    bad = exec_digest({'program_sha': 'bad', 'lane': 'mesh1'})
    store.put(good, b'g' * 256)
    store.put(bad, b'b' * 256)
    # same-size bit rot: only --verify's re-hash can see it
    Path(store._payload_path(bad)).write_bytes(b'B' * 256)

    assert gc_main(['--aot-dir', store.aot_dir]) == 0     # size check ok
    assert gc_main(['--aot-dir', store.aot_dir, '--verify']) == 1
    assert gc_main(['--aot-dir', store.aot_dir, '--verify']) == 0  # purged
    assert ExecStore(store.aot_dir).fetch(bad) is None
    assert ExecStore(store.aot_dir).fetch(good) is not None
    assert gc_main(['--aot-dir', str(tmp_path / 'nope')]) == 2
    assert gc_main(['--aot-dir', store.aot_dir,
                    '--target-bytes', '-1']) == 2


# -- runtime units (toy jit; no extractor builds) -----------------------------


def test_runtime_roundtrip_and_environment_miss(tmp_path, monkeypatch):
    """ensure_program: compile+publish → a fresh consult LOADS with
    byte-identical outputs; a jax-version (or device-kind) drift is a
    SILENT miss that recompiles AND names the drift in a structured
    event — never an error."""
    import jax
    import jax.numpy as jnp

    from video_features_tpu.aot import runtime

    jitted = jax.jit(lambda p, x: jnp.tanh(x @ p['w']))
    p = {'w': np.random.RandomState(0).rand(16, 8).astype(np.float32)}
    x = np.random.RandomState(1).rand(4, 16).astype(np.float32)
    store = _mkstore(tmp_path)

    prog1, path1 = runtime.ensure_program(store, 'toy', jitted, (p, x),
                                          lane='mesh1', feature_type='t')
    assert path1 == 'compiled' and store.puts == 1
    prog2, path2 = runtime.ensure_program(store, 'toy', jitted, (p, x),
                                          lane='mesh1', feature_type='t')
    assert path2 == 'loaded'
    a = np.asarray(prog1(p, x))
    b = np.asarray(prog2(p, x))
    c = np.asarray(jitted(p, x))
    assert (a == b).all() and (a == c).all()     # loaded ≡ compiled ≡ jit
    assert prog1.program_sha == prog2.program_sha

    # environment drift: same program, different jax version → miss +
    # recompile + a structured event naming the drifted component
    events = []
    monkeypatch.setattr(runtime, 'event',
                        lambda *a, **kw: events.append((a, kw)))
    real_env = runtime.runtime_environment

    def skewed_env(devices):
        env = real_env(devices)
        env['jax'] = 'not-this-jax'
        return env

    monkeypatch.setattr(runtime, 'runtime_environment', skewed_env)
    prog3, path3 = runtime.ensure_program(store, 'toy', jitted, (p, x),
                                          lane='mesh1', feature_type='t')
    assert path3 == 'compiled'                   # silent miss, no raise
    assert (np.asarray(prog3(p, x)) == a).all()
    drift_events = [kw for _, kw in events if 'drift' in kw]
    assert drift_events and 'jax' in drift_events[0]['drift']
    assert store.puts == 2                       # republished under new key


def test_runtime_corrupt_payload_recompiles(tmp_path):
    """A payload that passes the size check but fails DESERIALIZE is
    evicted and recompiled — a poisoned entry must not fail every boot."""
    import jax
    import jax.numpy as jnp

    from video_features_tpu.aot import runtime

    jitted = jax.jit(lambda p, x: x * p)
    p = np.float32(2.0)
    x = np.arange(4, dtype=np.float32)
    store = _mkstore(tmp_path)
    _, path1 = runtime.ensure_program(store, 'toy', jitted, (p, x),
                                      lane='mesh1', feature_type='t')
    assert path1 == 'compiled'
    # same-size garbage: fetch serves it, deserialize must reject it
    digest = next(iter(store._index))
    size = store._index[digest]['size']
    Path(store._payload_path(digest)).write_bytes(b'\x00' * size)
    prog, path2 = runtime.ensure_program(store, 'toy', jitted, (p, x),
                                         lane='mesh1', feature_type='t')
    assert path2 == 'compiled'
    assert store.corrupt_evicted == 1
    assert (np.asarray(prog(p, x)) == np.asarray(jitted(p, x))).all()


def test_knob_classification_and_config_validation():
    """The aot_* knobs are classified (vft-lint: knob-classification):
    excluded from the cache fingerprint (outputs byte-identical by
    contract) but pool-key relevant (a worker consults the store it was
    built with); sanity_check validates the values."""
    from video_features_tpu.config import (
        AOT_DEFAULTS, KNOB_CLASSIFICATION, knob_exclude, load_config,
    )
    for knob in AOT_DEFAULTS:
        assert KNOB_CLASSIFICATION[knob] == 'pool_only'
        assert knob in knob_exclude('fingerprint')
        assert knob not in knob_exclude('pool_key')
    with pytest.raises(ValueError, match='aot_dir'):
        load_config('resnet', overrides={
            'video_paths': ['v.live'], 'device': 'cpu',
            'aot_enabled': True, 'aot_dir': None})
    with pytest.raises(ValueError, match='aot_max_bytes'):
        load_config('resnet', overrides={
            'video_paths': ['v.live'], 'device': 'cpu',
            'aot_max_bytes': -5})
    from video_features_tpu.config import split_serve_config
    with pytest.raises(ValueError, match='serve_prewarm'):
        split_serve_config({'serve_prewarm': ['nosuchfamily']})
    # known but NOT serveable (no packed/serving support): pre-warming
    # it would burn a pool slot no request can reach — fails the boot
    with pytest.raises(ValueError, match='unserveable'):
        split_serve_config({'serve_prewarm': ['vggish']})


# -- extractor round trip (ONE shared cold build publishes the store) ---------


RESNET_OVERRIDES = dict(
    device='cpu', model_name='resnet18', batch_size=4,
    allow_random_weights=True, on_extraction='save_numpy',
    pack_across_videos=True)


def _npy_bytes(root) -> dict:
    return {f.name: f.read_bytes() for f in sorted(Path(root).rglob('*.npy'))}


@pytest.fixture(scope='module')
def aot_clips(tmp_path_factory):
    vids = tmp_path_factory.mktemp('aot_vids')
    return [str(write_noise_clip(vids / f'c{i}.mp4', n, seed=i))
            for i, n in enumerate((6, 4))]


@pytest.fixture(scope='module')
def cold_run(tmp_path_factory, aot_clips):
    """THE one cold extractor build: packed resnet run that compiles and
    publishes the store every other extractor-level test loads from."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    td = tmp_path_factory.mktemp('aot_cold')
    store_dir = str(td / 'exec_store')
    args = load_config('resnet', overrides=dict(
        RESNET_OVERRIDES, video_paths=aot_clips,
        output_path=str(td / 'out'), tmp_path=str(td / 'tmp'),
        aot_enabled=True, aot_dir=store_dir))
    ex = create_extractor(args)
    ex.extract_packed(aot_clips)
    return {'ex': ex, 'store_dir': store_dir,
            'out': _npy_bytes(td / 'out')}


def test_cli_repeat_loads_and_is_byte_identical(tmp_path_factory,
                                                aot_clips, cold_run):
    """The compile-free CLI repeat: a SECOND build against the published
    store resolves its program by LOADING (zero compiles) and its
    features are byte-identical to the cold run's."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    assert cold_run['ex'].aot_stats['compiled'] >= 1
    assert cold_run['ex'].aot_stats['loaded'] == 0
    td = tmp_path_factory.mktemp('aot_warm')
    args = load_config('resnet', overrides=dict(
        RESNET_OVERRIDES, video_paths=aot_clips,
        output_path=str(td / 'out'), tmp_path=str(td / 'tmp'),
        aot_enabled=True, aot_dir=cold_run['store_dir']))
    ex = create_extractor(args)
    ex.extract_packed(aot_clips)
    assert ex.aot_stats['loaded'] >= 1, ex.aot_stats
    assert ex.aot_stats['compiled'] == 0, ex.aot_stats
    assert _npy_bytes(td / 'out') == cold_run['out']
    # the manifest-facing snapshot names the path each program took
    snap = ex.aot_snapshot()
    assert snap['enabled'] and snap['loaded'] >= 1
    assert all(p['path'] == 'loaded' for p in snap['programs'].values())


def test_serve_boot_compile_free_against_published_store(
        tmp_path_factory, aot_clips, cold_run):
    """The acceptance pin (ISSUE 14): on an unchanged program set, a
    serve boot pre-warming from the store is COMPILE-FREE —
    ``builds_loaded`` == entries pre-warmed, ``builds_compiled == 0``,
    visible in pool stats and the metrics document — and the features
    it serves are byte-identical to the cold CLI run's."""
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer

    td = tmp_path_factory.mktemp('aot_serve')
    server = ExtractionServer(
        base_overrides=dict(RESNET_OVERRIDES,
                            tmp_path=str(td / 'tmp'),
                            aot_enabled=True,
                            aot_dir=cold_run['store_dir']),
        queue_depth=8, pool_size=2).start()
    try:
        pre = server.prewarm(['resnet'])
        assert pre['entries'] == 1, pre
        assert pre['programs_compiled'] == 0, pre
        assert pre['programs_loaded'] >= 1, pre
        client = ServeClient(port=server.port)
        rid = client.submit('resnet', aot_clips,
                            overrides={'output_path': str(td / 'out')})
        assert client.wait(rid, timeout_s=300)['state'] == 'done'
        m = client.metrics()
        assert m['warm_pool']['builds_compiled'] == 0, m['warm_pool']
        assert m['warm_pool']['builds_loaded'] == pre['entries'] == 1
        # the pre-warmed entry answered the request (no second build)
        assert m['warm_pool']['hits'] == 1, m['warm_pool']
        assert m['aot']['programs_loaded'] >= 1
        assert m['aot']['programs_compiled'] == 0
    finally:
        server.drain(wait=True, grace_s=60)
    assert _npy_bytes(td / 'out') == cold_run['out']


# -- slow lane: multi-family store coverage -----------------------------------


@pytest.mark.slow
def test_multi_family_store_roundtrip(tmp_path_factory):
    """A stack family (r21d: raw decode-geometry windows, its own
    program shape) through the same store: cold build compiles +
    publishes, a fresh build LOADS with byte-identical packed outputs —
    the store generalizes beyond the framewise fixture family."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    vids = tmp_path_factory.mktemp('mf_vids')
    clips = [str(write_noise_clip(vids / f'm{i}.mp4', n, seed=10 + i))
             for i, n in enumerate((20, 18))]
    td = tmp_path_factory.mktemp('mf_store')
    store_dir = str(td / 'exec_store')

    def run(tag):
        args = load_config('r21d', overrides=dict(
            video_paths=clips, device='cpu',
            model_name='r2plus1d_18_16_kinetics', stack_size=4,
            step_size=4, batch_size=2, allow_random_weights=True,
            on_extraction='save_numpy', pack_across_videos=True,
            output_path=str(td / f'out_{tag}'),
            tmp_path=str(td / f'tmp_{tag}'),
            aot_enabled=True, aot_dir=store_dir))
        ex = create_extractor(args)
        ex.extract_packed(clips)
        return ex, _npy_bytes(td / f'out_{tag}')

    ex1, out1 = run('cold')
    assert ex1.aot_stats['compiled'] >= 1 and out1
    ex2, out2 = run('warm')
    assert ex2.aot_stats['loaded'] >= 1 and ex2.aot_stats['compiled'] == 0
    assert out1 == out2


@pytest.mark.slow
def test_manifest_records_aot_section(tmp_path_factory, aot_clips,
                                      cold_run):
    """A manifest-enabled run against the warm store records the 'aot'
    section: enabled, per-program 'loaded' paths, StableHLO identities."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    td = tmp_path_factory.mktemp('aot_manifest')
    manifest = str(td / 'manifest.json')
    args = load_config('resnet', overrides=dict(
        RESNET_OVERRIDES, video_paths=aot_clips,
        output_path=str(td / 'out'), tmp_path=str(td / 'tmp'),
        aot_enabled=True, aot_dir=cold_run['store_dir'],
        manifest_out=manifest))
    ex = create_extractor(args)
    ex.extract_packed(aot_clips)
    ex.finish_obs()
    man = json.loads(Path(manifest).read_text())
    assert man['aot']['enabled'] is True
    assert man['aot']['loaded'] >= 1 and man['aot']['compiled'] == 0
    progs = man['aot']['programs']
    assert progs and all(p['path'] == 'loaded' for p in progs.values())
    assert all(len(p['stablehlo_sha256']) == 64 for p in progs.values())
