"""The warm-pool extraction service (serve/): the long-running serving
layer must preserve every batch-path contract — byte-identical outputs vs
the one-shot CLI, per-video fault isolation inside shared batches, the
resume skip — while adding warmth (transplant+compile paid once across
requests), admission control, deadlines, and graceful drain.

Socket-level tests run a real server on an ephemeral loopback port with
resnet18 random weights on CPU (same fixture weight class as
tests/test_packing.py). Soak-style concurrency tests are ``slow``.
"""
import os
import signal
import time
from pathlib import Path

import numpy as np
import pytest

from video_features_tpu.utils.output import make_path


from tools.make_sample_video import write_noise_clip as _write_clip  # noqa: E402


@pytest.fixture(scope='module')
def serve_clips(tmp_path_factory):
    d = tmp_path_factory.mktemp('servevids')
    return [_write_clip(d / f'sv{i}.mp4', n, seed=i)
            for i, n in enumerate((9, 4))]


def _base_overrides(tmp_path):
    return {
        'device': 'cpu', 'model_name': 'resnet18', 'batch_size': 4,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'tmp_path': str(tmp_path / 'serve_tmp'),
    }


def _start_server(tmp_path, **kw):
    from video_features_tpu.serve.server import ExtractionServer
    opts = dict(base_overrides=_base_overrides(tmp_path), queue_depth=32,
                pool_size=2)
    opts.update(kw)
    return ExtractionServer(**opts).start()


RESNET_KEYS = ('resnet', 'fps', 'timestamps_ms')


# -- pure units (no server, no jax) ------------------------------------------


def test_check_version_minor_skew_accepted_major_rejected():
    """The MAJOR/MINOR compatibility contract behind WIRE.lock.json's
    bump semantics: VERSION is now '1.5' (1.1 covered PR 8's versioning
    + PR 11's trace surface; 1.2 added the additive `features` fused
    submit field; 1.3 adds the `search`/`index_status` feature-index
    surface; 1.4 adds the additive `code` error field the fleet
    router's failover keys on; 1.5 adds the additive fleet
    observability surface — scatter-gathered traces with `hosts`,
    aggregated `/metrics`, `vft_slo_*`), and a client speaking ANY
    unknown 1.x
    must keep working, while an unknown major gets the structured
    rejection echoing its request_id."""
    from video_features_tpu.serve import protocol

    assert protocol.VERSION == '1.5'
    assert protocol.MAJOR == 1
    # minor skew is additive-fields-only by contract: never rejected,
    # future minors included
    assert protocol.check_version({'v': '1.0'}) is None
    assert protocol.check_version({'v': '1.1'}) is None
    assert protocol.check_version({'v': '1.7'}) is None
    # pre-versioning clients (no v) keep working
    assert protocol.check_version({'cmd': 'ping'}) is None
    # unknown MAJOR: structured error naming both versions and echoing
    # the message's request_id for client-side correlation
    rej = protocol.check_version({'v': '2.0', 'request_id': 'r000042'})
    assert rej is not None and rej['ok'] is False
    assert '2.0' in rej['error'] and protocol.VERSION in rej['error']
    assert rej['v'] == protocol.VERSION
    assert rej['request_id'] == 'r000042'
    # malformed versions fail loudly too, not as a parse error
    assert protocol.check_version({'v': 'banana'})['ok'] is False

def test_warm_pool_lru_hit_rate_and_graceful_eviction():
    from video_features_tpu.serve.pool import WarmPool

    class FakeEntry:
        def __init__(self, busy=False):
            self.busy = busy
            self.closed = False

        def idle(self):
            return not self.busy

        def close(self):
            self.closed = True

    pool = WarmPool(2)
    a, b, c = FakeEntry(), FakeEntry(), FakeEntry()
    assert pool.get(('a',)) is None            # miss
    pool.put(('a',), a)
    pool.put(('b',), b)
    assert pool.get(('a',)) is a               # hit refreshes recency
    evicted = pool.put(('c',), c)              # b is now LRU → evicted
    assert evicted == [b] and b.closed
    st = pool.stats()
    assert st['size'] == 2 and st['evictions'] == 1
    assert st['hits'] == 1 and st['misses'] == 1 and st['hit_rate'] == 0.5

    # a busy LRU entry is passed over: pool runs over capacity rather
    # than stalling admission behind a drain
    a.busy = True
    c.busy = True
    d = FakeEntry()
    assert pool.put(('d',), d) == []
    assert pool.stats()['size'] == 3
    a.busy = False
    e = FakeEntry()
    # back under capacity: BOTH idle entries (a: LRU, d) evict; busy c
    # stays over-capacity until it goes idle
    assert set(pool.put(('e',), e)) == {a, d}
    assert pool.stats()['size'] == 2


def test_packed_batches_flush_sentinel():
    """FLUSH forces partial geometry pools out padded — the latency bound
    for a lone request during an arrival lull — and later windows of the
    same geometry pool afresh. Every FLUSH is followed by the batchless
    drain marker ``(None, [], 0)`` so the consumer also materializes its
    in-flight output queue (async device loop) on idle."""
    from video_features_tpu.parallel.packing import FLUSH, packed_batches

    w = np.zeros((2, 2), np.float32)

    def stream():
        yield ('t1', w, None)
        yield FLUSH
        yield FLUSH                            # idempotent on empty pools
        yield ('t2', w, None)
        yield ('t3', w, None)

    out = list(packed_batches(stream(), batch=2))
    markers = [item for item in out if item[0] is None]
    assert markers == [(None, [], 0)] * 2      # one drain marker per FLUSH
    batches = [item for item in out if item[0] is not None]
    assert [(v, [t for t, _ in prov]) for _, prov, v in batches] == \
        [(1, ['t1']), (2, ['t2', 't3'])]
    # the first FLUSH's flushed batch precedes its drain marker
    assert out[0][0] is not None and out[1][0] is None
    assert all(stacks.shape == (2, 2, 2) for stacks, _, _ in batches)


def test_packed_batches_pool_age_bound():
    """Under CONTINUOUS traffic the feed never idles (no FLUSH), but a
    partial pool older than max_pool_age_s must still flush as other
    geometries' windows keep flowing — the serve liveness bound."""
    import time as _t

    from video_features_tpu.parallel.packing import packed_batches

    odd = np.zeros((3, 3), np.float32)
    main = np.zeros((2, 2), np.float32)

    def stream():
        yield ('odd', odd, None)               # pools alone
        _t.sleep(0.06)
        for i in range(4):                     # other-geometry traffic
            yield (f'm{i}', main, None)

    out = list(packed_batches(stream(), batch=4, max_pool_age_s=0.05))
    # the odd window flushed (padded, valid=1) BEFORE the main batch
    # completed — it did not wait for stream end
    assert [(v, [t for t, _ in prov]) for _, prov, v in out] == \
        [(1, ['odd']), (4, ['m0', 'm1', 'm2', 'm3'])]


def test_atomic_writes_leave_no_partial_files(tmp_path):
    from video_features_tpu.utils.output import (
        load_numpy, load_pickle, write_numpy, write_pickle,
    )

    fp = str(tmp_path / 'a.npy')
    write_numpy(fp, np.arange(5))
    np.testing.assert_array_equal(load_numpy(fp), np.arange(5))
    pp = str(tmp_path / 'b.pkl')
    write_pickle(pp, {'x': 1})
    assert load_pickle(pp) == {'x': 1}

    # a crash mid-write must strand nothing at the final path and clean
    # its tmp; a previously published file must survive untouched
    class Dies:
        def __reduce__(self):
            raise RuntimeError('dies mid-pickle')

    with pytest.raises(RuntimeError):
        write_pickle(pp, Dies())
    assert load_pickle(pp) == {'x': 1}
    assert [f.name for f in tmp_path.iterdir()] != []
    assert not [f for f in tmp_path.iterdir() if f.suffix == '.tmp']


def test_split_serve_config_validates():
    from video_features_tpu.config import split_serve_config

    serve, base = split_serve_config({
        'serve_port': '8791', 'serve_queue_depth': 8,
        'device': 'cpu', 'batch_size': 4,
    })
    assert serve['serve_port'] == 8791 and serve['serve_queue_depth'] == 8
    assert serve['serve_warm_pool_size'] == 4        # default survives
    assert base == {'device': 'cpu', 'batch_size': 4}
    with pytest.raises(ValueError, match='serve_warm_pol'):
        split_serve_config({'serve_warm_pol_size': 2})   # typo'd knob
    with pytest.raises(ValueError, match='serve_queue_depth'):
        split_serve_config({'serve_queue_depth': 0})


def test_tracer_merge_reports():
    from video_features_tpu.utils.tracing import Tracer, merge_reports

    t1, t2 = Tracer(), Tracer()
    t1.add('model', 1.0)
    t1.add('model', 3.0)
    t1.add_occupancy('model', 3, 4)
    t2.add('model', 2.0)
    t2.add_occupancy('model', 4, 4)
    t2.add('decode', 5.0)
    m = merge_reports([t1.report(), t2.report()])
    assert m['model']['count'] == 3
    assert m['model']['total_s'] == pytest.approx(6.0)
    assert m['model']['max_s'] == pytest.approx(3.0)
    assert m['model']['occupancy'] == pytest.approx(7 / 8)
    assert m['decode']['count'] == 1


def test_device_placer_stacks_int8_quarter_size_entries():
    """The precision ladder's serve payoff, pinned with NO placer code
    change: int8 entries are ~quarter the fp32 params bytes, so the
    byte-first ranking stacks TWO int8 entries plus a bf16 entry on one
    chip before a second fp32 copy lands there — and the
    ``vft_device_resident_bytes`` gauges read the QUANTIZED residency,
    not a per-entry count."""
    import jax

    from video_features_tpu.serve.pool import DevicePlacer

    devices = jax.devices()[:2]
    placer = DevicePlacer()
    FP32, BF16, INT8 = 4000, 2000, 1000     # the ladder's byte ratios
    fp32_a = placer.assign(devices, 1, nbytes=FP32)
    int8_a = placer.assign(devices, 1, nbytes=INT8)
    int8_b = placer.assign(devices, 1, nbytes=INT8)
    bf16_a = placer.assign(devices, 1, nbytes=BF16)
    # the small-lane chip absorbs both int8 entries AND the bf16 entry
    # (1000+1000+2000 = 4000 bytes) before the fp32 chip takes anything
    # else — byte ranking, where entry-count ranking would have
    # alternated chips after the first int8 landed
    assert int8_a[0].id != fp32_a[0].id
    assert int8_b[0].id == int8_a[0].id
    assert bf16_a[0].id == int8_a[0].id
    by_bytes = placer.snapshot_bytes()
    assert by_bytes[f'd{fp32_a[0].id}'] == FP32
    assert by_bytes[f'd{int8_a[0].id}'] == 2 * INT8 + BF16
    # now the ledger is level (4000 vs 4000): the NEXT fp32 copy ties on
    # bytes, ties on nothing else but entry count (1 vs 3) — it lands on
    # the fp32 chip, keeping the quantized stack intact
    fp32_b = placer.assign(devices, 1, nbytes=FP32)
    assert fp32_b[0].id == fp32_a[0].id
    for entry, size in ((fp32_a, FP32), (fp32_b, FP32), (bf16_a, BF16),
                        (int8_a, INT8), (int8_b, INT8)):
        placer.release(entry, nbytes=size)
    assert set(placer.snapshot_bytes().values()) == {0}
    assert set(placer.snapshot().values()) == {0}


# -- the live server ---------------------------------------------------------

def test_serve_lifecycle_warm_parity_fault_sigterm_resume(
        serve_clips, tmp_path, monkeypatch):
    """The acceptance path, end to end over the real socket:

    1. a warm server extracts the same two-video worklist twice paying
       transplant exactly once (pool hit rate > 0, one extractor build);
    2. outputs are byte-identical to the one-shot CLI path;
    3. a mid-queue failing video fails alone — its batch-mates save;
    4. a real SIGTERM drains gracefully, losing no completed output;
    5. a restarted server resumes: completed videos skip.
    """
    import video_features_tpu.serve.server as server_mod
    from video_features_tpu.serve.client import ServeClient

    builds = []
    real_create = server_mod.create_extractor
    monkeypatch.setattr(server_mod, 'create_extractor',
                        lambda args: builds.append(args['feature_type'])
                        or real_create(args))

    server = _start_server(tmp_path)
    client = ServeClient(port=server.port)
    assert client.ping()

    # -- 1+2: two passes, one transplant, CLI-parity outputs
    out1, out2 = str(tmp_path / 'p1'), str(tmp_path / 'p2')
    for out_root in (out1, out2):
        rid = client.submit('resnet', serve_clips,
                            overrides={'output_path': out_root})
        st = client.wait(rid, timeout_s=180)
        assert st['state'] == 'done', st
        assert set(st['videos'].values()) == {'saved'}
    assert builds == ['resnet']                # warm: built exactly once
    m = client.metrics()
    assert m['warm_pool']['hit_rate'] > 0
    assert m['warm_pool']['misses'] == 1
    assert m['requests']['completed'] == 2
    assert m['latency']['p99_s'] is not None
    assert m['stages_merged']['model']['count'] > 0

    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor
    ref = create_extractor(load_config('resnet', overrides=dict(
        _base_overrides(tmp_path), video_paths=serve_clips,
        output_path=str(tmp_path / 'ref'),
        tmp_path=str(tmp_path / 'ref_tmp'))))
    for p in serve_clips:
        ref._extract(p)
    for p in serve_clips:
        for key in RESNET_KEYS:
            a = Path(make_path(ref.output_path, p, key, '.npy'))
            b = Path(make_path(os.path.join(out1, 'resnet', 'resnet18'),
                               p, key, '.npy'))
            assert a.read_bytes() == b.read_bytes(), (p, key)

    # -- 3: mid-queue failing video + 4: SIGTERM drain, in flight together
    old_term = signal.getsignal(signal.SIGTERM)
    old_int = signal.getsignal(signal.SIGINT)
    try:
        server.install_signal_handlers()
        bad = str(tmp_path / 'missing.mp4')    # never created
        out3 = str(tmp_path / 'p3')
        rid3 = client.submit(
            'resnet', [serve_clips[0], bad, serve_clips[1]],
            overrides={'output_path': out3})
        os.kill(os.getpid(), signal.SIGTERM)   # drain while rid3 queued
        deadline = time.monotonic() + 120
        while not server.drained and time.monotonic() < deadline:
            time.sleep(0.05)
        assert server.drained
    finally:
        signal.signal(signal.SIGTERM, old_term)
        signal.signal(signal.SIGINT, old_int)

    st3 = server.status(rid3)                  # in-process: socket is down
    assert st3['state'] == 'partial', st3
    assert st3['videos'][bad] == 'failed'
    out3_root = os.path.join(out3, 'resnet', 'resnet18')
    for p in serve_clips:                      # batch-mates survived drain
        assert st3['videos'][p] == 'saved'
        for key in RESNET_KEYS:
            assert Path(make_path(out3_root, p, key, '.npy')).exists()
    with pytest.raises(Exception):             # draining rejects admission
        client.submit('resnet', serve_clips,
                      overrides={'output_path': str(tmp_path / 'px')})

    # -- 5: restart + resume: completed outputs skip, nothing rewritten
    mtimes = {p: Path(make_path(out3_root, p, 'resnet', '.npy'))
              .stat().st_mtime_ns for p in serve_clips}
    server2 = _start_server(tmp_path)
    try:
        client2 = ServeClient(port=server2.port)
        rid4 = client2.submit('resnet', serve_clips,
                              overrides={'output_path': out3})
        st4 = client2.wait(rid4, timeout_s=180)
        assert st4['state'] == 'done'
        assert set(st4['videos'].values()) == {'skipped'}
        for p in serve_clips:
            assert Path(make_path(out3_root, p, 'resnet', '.npy')) \
                .stat().st_mtime_ns == mtimes[p]
    finally:
        server2.drain(wait=True, grace_s=60)


def test_serve_async_loop_parity_and_inflight_gauge(serve_clips, tmp_path):
    """The warm workers inherit the async device loop: a server pinned
    synchronous (inflight=1 base override) and one running the
    deferred-D2H loop (inflight=2) produce BYTE-identical outputs for
    the same request, and the metrics document carries the
    vft_inflight_batches gauge (0 once idle — every dispatched batch
    was materialized)."""
    from video_features_tpu.serve.client import ServeClient

    roots = {}
    for depth in (1, 2):
        server = _start_server(
            tmp_path, base_overrides=dict(_base_overrides(tmp_path),
                                          inflight=depth))
        try:
            client = ServeClient(port=server.port)
            out_root = str(tmp_path / f'async{depth}')
            rid = client.submit('resnet', serve_clips,
                                overrides={'output_path': out_root})
            st = client.wait(rid, timeout_s=180)
            assert st['state'] == 'done', st
            m = client.metrics()
            assert m['inflight_batches'] == 0   # drained back to idle
            prom = client.metrics_prom()
            assert 'vft_inflight_batches 0' in prom
            if depth == 2:
                # the wire's own drain command (the other server takes the
                # in-process call below; SIGTERM has the lifecycle test)
                client.drain()
                deadline = time.monotonic() + 60
                while not server.drained and time.monotonic() < deadline:
                    time.sleep(0.05)
                assert server.drained, 'drain over the socket never ended'
        finally:
            server.drain(wait=True, grace_s=60)
        roots[depth] = os.path.join(out_root, 'resnet', 'resnet18')

    compared = 0
    for p in serve_clips:
        for key in RESNET_KEYS:
            a = Path(make_path(roots[1], p, key, '.npy'))
            b = Path(make_path(roots[2], p, key, '.npy'))
            assert a.read_bytes() == b.read_bytes(), (p, key)
            compared += 1
    assert compared == len(serve_clips) * len(RESNET_KEYS)


def test_serve_admission_deadline_and_protocol_errors(
        serve_clips, tmp_path):
    from video_features_tpu.serve.client import ServeClient, ServeError

    server = _start_server(tmp_path, queue_depth=2)
    try:
        client = ServeClient(port=server.port)
        # backpressure: a request that would exceed queue depth is
        # REJECTED atomically (not partially admitted)
        with pytest.raises(ServeError, match='queue_full'):
            client.submit('resnet', [str(tmp_path / f'x{i}.mp4')
                                     for i in range(3)],
                          overrides={'output_path': str(tmp_path / 'o')})
        # duplicate paths would collapse in per-request accounting —
        # rejected even under `python -O` (where sanity_check's
        # unique-stem assert vanishes)
        with pytest.raises(ServeError, match='duplicate'):
            client.submit('resnet', [serve_clips[0], serve_clips[0]],
                          overrides={'output_path': str(tmp_path / 'o')})
        # no packed support → no serving support, rejected loudly
        with pytest.raises(ServeError, match='vggish'):
            client.submit('vggish', serve_clips,
                          overrides={'output_path': str(tmp_path / 'o')})
        # invalid per-request config surfaces the sanity_check reason
        with pytest.raises(ServeError, match='invalid request'):
            client.submit('resnet', serve_clips,
                          overrides={'output_path': str(tmp_path / 'same'),
                                     'tmp_path': str(tmp_path / 'same')})
        # an already-expired deadline: videos expire unstarted, the
        # request still reaches a terminal state
        rid = client.submit('resnet', serve_clips, timeout_s=0.0,
                            overrides={'output_path': str(tmp_path / 'od')})
        st = client.wait(rid, timeout_s=120)
        assert st['state'] == 'failed'
        assert set(st['videos'].values()) == {'expired'}
        m = client.metrics()
        assert m['requests']['expired_videos'] == len(serve_clips)
        assert m['requests']['rejected'] == 4
        # protocol-level garbage gets an error reply, not a hang
        with pytest.raises(ServeError, match='unknown cmd'):
            client._call({'cmd': 'frobnicate'})
        with pytest.raises(ServeError, match='unknown request_id'):
            client.status('r999999')
        with pytest.raises(ServeError, match='unknown submit fields'):
            client._call({'cmd': 'submit', 'feature_type': 'resnet',
                          'video_paths': serve_clips, 'surprise': 1})
    finally:
        server.drain(wait=True, grace_s=60)


@pytest.mark.slow
def test_serve_soak_concurrent_requests_and_metrics_file(
        serve_clips, tmp_path):
    """Soak: concurrent clients race submits through one warm worker;
    every request reaches a terminal state, outputs parity-match a clean
    packed run, and the metrics mirror file stays valid JSON."""
    import json
    import threading

    from video_features_tpu.serve.client import ServeClient

    metrics_path = str(tmp_path / 'metrics.json')
    server = _start_server(tmp_path, queue_depth=64,
                           metrics_path=metrics_path)
    try:
        results = {}

        def one_client(i):
            c = ServeClient(port=server.port)
            out_root = str(tmp_path / f'soak{i}')
            rid = c.submit('resnet', serve_clips,
                           overrides={'output_path': out_root})
            results[i] = (out_root, c.wait(rid, timeout_s=300))

        threads = [threading.Thread(target=one_client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert len(results) == 4
        first_root = None
        for i, (out_root, st) in sorted(results.items()):
            assert st['state'] == 'done', (i, st)
            root = os.path.join(out_root, 'resnet', 'resnet18')
            if first_root is None:
                first_root = root
                continue
            for p in serve_clips:
                for key in RESNET_KEYS:
                    a = Path(make_path(first_root, p, key, '.npy'))
                    b = Path(make_path(root, p, key, '.npy'))
                    assert a.read_bytes() == b.read_bytes(), (i, p, key)
        doc = json.loads(Path(metrics_path).read_text())
        assert doc['requests']['completed'] == 4
        # concurrent cold submits may each count a miss, but the per-key
        # build lock guarantees ONE transplant total (no aot store in
        # this config, so the build lands on the compiled counter)
        assert doc['warm_pool']['builds_compiled'] == 1
        assert doc['warm_pool']['builds_loaded'] == 0
    finally:
        server.drain(wait=True, grace_s=60)
