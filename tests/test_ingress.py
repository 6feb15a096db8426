"""The ingress (ingress/): network front door over the serve daemon.

Covers the four tentpole pieces — transport (framing bounds, chunked
streaming, drain/reap), tenancy (API keys, token-bucket + concurrency
quotas, priority shed), segment queries (range plumbed through the
windower + cache key; byte parity vs the loopback path; decode bounded
to the covered range, tracer-verified), live sessions (per-window
streamed chunks, duplicate-id rejection, drain reaping) — plus the
loopback satellites (protocol ``v`` versioning, client connect retry).

The e2e layer runs resnet18 random weights on CPU against noise-clip
fixtures, one shared server per module (same policy as test_serve.py).
"""
import io
import json
import socket
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from tools.make_sample_video import write_noise_clip as _write_clip
from video_features_tpu.utils.output import make_path

API_KEY = 'test-key-interactive'
BATCH_KEY = 'test-key-batch'
LIMITED_KEY = 'test-key-limited'


# -- pure units (no server, no jax) ------------------------------------------

def test_token_bucket_and_quota_manager():
    from video_features_tpu.ingress.auth import Tenant
    from video_features_tpu.ingress.quota import QuotaManager, TokenBucket

    assert TokenBucket(None, 1).try_acquire()       # unlimited

    q = QuotaManager()
    slow = Tenant('slow', rate_rps=0.001, burst=2)
    assert q.acquire(slow) == (True, None)
    assert q.acquire(slow) == (True, None)
    ok, reason = q.acquire(slow)
    assert (ok, reason) == (False, 'rate_limited')  # bucket dry

    one = Tenant('one', max_concurrent=1)
    assert q.acquire(one) == (True, None)
    assert q.acquire(one) == (False, 'concurrency')
    q.release('one')
    assert q.acquire(one) == (True, None)

    snap = q.snapshot()
    assert snap['slow']['shed'] == 1 and snap['one']['shed'] == 1
    assert snap['one']['inflight'] == 1


def test_auth_file_parsing_and_header_auth(tmp_path):
    from video_features_tpu.ingress.auth import ApiKeyAuth

    p = tmp_path / 'keys.json'
    p.write_text(json.dumps({'keys': {
        'k1': {'tenant': 'acme', 'priority': 'batch', 'rate_rps': 10},
        'k2': {'tenant': 'acme', 'priority': 'batch', 'rate_rps': 10},
        'k3': {'tenant': 'zeta', 'max_concurrent': 2},
    }}))
    auth = ApiKeyAuth.from_file(str(p))
    assert auth.n_tenants == 2                    # two keys share 'acme'
    t = auth.authenticate({'authorization': 'Bearer k1'})
    assert t.name == 'acme' and t.priority == 'batch'
    assert auth.authenticate({'x-api-key': 'k3'}).name == 'zeta'
    assert auth.authenticate({'authorization': 'Bearer nope'}) is None
    assert auth.authenticate({}) is None

    # keys sharing a tenant share its quota ledger: their policies must
    # agree, or the effective policy would be first-authenticated-wins
    bad = tmp_path / 'conflict.json'
    bad.write_text(json.dumps({'keys': {
        'kA': {'tenant': 'acme', 'rate_rps': 5},
        'kB': {'tenant': 'acme', 'rate_rps': 500},
    }}))
    with pytest.raises(ValueError, match='conflicting policies'):
        ApiKeyAuth.from_file(str(bad))

    bad = tmp_path / 'bad.json'
    bad.write_text(json.dumps({'keys': {'k': {'priority': 'interactive'}}}))
    with pytest.raises(ValueError, match='no tenant'):
        ApiKeyAuth.from_file(str(bad))
    bad.write_text(json.dumps(
        {'keys': {'k': {'tenant': 't', 'shoe_size': 9}}}))
    with pytest.raises(ValueError, match='unknown fields'):
        ApiKeyAuth.from_file(str(bad))


def test_http_oversized_body_is_structured_413():
    """An oversized DECLARED body must come back as a structured 413 —
    before a byte of the payload is read — and an oversized chunk must
    do the same mid-stream; neither may crash the reader."""
    from video_features_tpu.ingress.http import HttpError, HttpServer

    def handler(req, resp, conn):
        if req.chunked:
            for _ in req.iter_chunks(max_chunk_bytes=64):
                pass
            resp.send_json(200, {'ok': True})
        else:
            req.read_body(max_bytes=128)
            resp.send_json(200, {'ok': True})

    srv = HttpServer(handler).start()
    try:
        import http.client
        c = http.client.HTTPConnection('127.0.0.1', srv.port, timeout=10)
        c.request('POST', '/x', body=b'y' * 1024)
        r = c.getresponse()
        body = json.loads(r.read())
        assert r.status == 413 and body['error'] == 'body_too_large'
        assert body['max_bytes'] == 128 and body['got_bytes'] == 1024

        s = socket.create_connection(('127.0.0.1', srv.port), timeout=10)
        s.sendall(b'POST /x HTTP/1.1\r\nHost: a\r\n'
                  b'Transfer-Encoding: chunked\r\n\r\n')
        s.sendall(b'%x\r\n%s\r\n' % (4096, b'z' * 4096))
        resp = s.makefile('rb').read()
        assert b'413' in resp.split(b'\r\n', 1)[0]
        assert b'body_too_large' in resp

        # a NEGATIVE chunk size must be a structured 400, never an
        # unbounded read-to-EOF (int(_, 16) parses '-1'; rfile.read(-1)
        # would buffer everything the client cares to send)
        s2 = socket.create_connection(('127.0.0.1', srv.port), timeout=10)
        s2.sendall(b'POST /x HTTP/1.1\r\nHost: a\r\n'
                   b'Transfer-Encoding: chunked\r\n\r\n'
                   b'-1\r\n' + b'y' * 1024)
        resp2 = s2.makefile('rb').read()
        assert b'400' in resp2.split(b'\r\n', 1)[0]
        assert b'negative chunk size' in resp2
    finally:
        srv.begin_drain()
        srv.finish_drain(grace_s=1.0)


def test_segment_name_and_cache_key_distinctness(tmp_path):
    from video_features_tpu.cache.key import video_cache_key
    from video_features_tpu.parallel.packing import VideoTask, segment_name

    clip = tmp_path / 'a.mp4'
    clip.write_bytes(b'notavideo but hashable')
    assert segment_name(str(clip), None) == str(clip)
    named = segment_name(str(clip), (1.5, 3.0))
    assert named.endswith('a_seg1500-3000ms.mp4')
    # millisecond quantization: float jitter below 1ms can't fork names
    assert segment_name(str(clip), (1.5000001, 3.0)) == named

    full = video_cache_key(str(clip), 'fp')
    seg = video_cache_key(str(clip), 'fp', segment=(1.5, 3.0))
    seg2 = video_cache_key(str(clip), 'fp', segment=(1.5, 4.0))
    assert len({full, seg, seg2}) == 3   # never collide with full/other

    t = VideoTask(str(clip), segment=(1.5, 3.0))
    assert t.name_path == named
    assert VideoTask(str(clip)).name_path == str(clip)


def test_stream_windows_frame_range_bounds_decode():
    """The windower emits exactly the range-overlapping windows and
    stops PULLING decode batches past the range's end — the unit behind
    the 'decode proportional to the range' acceptance."""
    from video_features_tpu.extract.streaming import stream_windows

    frames = [np.full((2, 2), i, np.uint8) for i in range(100)]

    class Counting:
        def __init__(self):
            self.pulled = 0

        def __iter__(self):
            for i in range(0, 100, 8):
                self.pulled += 1
                yield frames[i:i + 8], None, None

    full_src = Counting()
    full = list(stream_windows(iter(full_src), 4, 2))
    assert len(full) == 49

    src = Counting()
    seg = list(stream_windows(iter(src), 4, 2, frame_range=(10, 20)))
    # windows overlapping [10, 20): starts 8..18
    assert [int(w[0, 0, 0]) for w in seg] == [8, 10, 12, 14, 16, 18]
    # byte-identical to the same windows of the full run
    for w in seg:
        assert np.array_equal(w, full[int(w[0, 0, 0]) // 2])
    # decode stopped early: batches pulled ∝ range end, not video length
    assert src.pulled < full_src.pulled
    assert src.pulled <= 3

    empty = Counting()
    assert list(stream_windows(iter(empty), 4, 2, frame_range=(5, 5))) == []


def test_live_session_windowing_unit():
    """LiveSession.windows replays stack windowing over pushed frames
    and yields FLUSH on arrival lulls."""
    from video_features_tpu.ingress.live import LiveSession
    from video_features_tpu.parallel.packing import FLUSH

    class StubEx:
        feature_type = 'stub'

        def live_window_spec(self):
            return (4, 2, None, False)

    s = LiveSession('s1', 'acme', fps=10.0, idle_flush_s=0.01)
    gen = s.windows(StubEx())
    # nothing pushed yet → the first item is a lull FLUSH
    assert next(gen) is FLUSH
    frames = np.arange(10, dtype=np.uint8).reshape(10, 1, 1, 1) * \
        np.ones((1, 2, 2, 3), np.uint8)
    s.push(frames[:6])
    s.push(frames[6:])
    s.end_input()
    got = [item for item in gen if item is not FLUSH]
    # starts 0,2,4,6 (win=4 over 10 frames)
    assert [int(w[0, 0, 0, 0]) for w, _ in got] == [0, 2, 4, 6]
    assert s.windows_in == 4

    # framewise spec: per-frame windows with synthesized timestamps
    class StubFrameEx:
        feature_type = 'stubf'

        def live_window_spec(self):
            return (1, 1, None, True)

    s2 = LiveSession('s2', 'acme', fps=10.0, idle_flush_s=0.01)
    s2.push(frames[:3])
    s2.end_input()
    got2 = [item for item in s2.windows(StubFrameEx())
            if item is not FLUSH]
    assert [m for _, m in got2] == [0.0, 100.0, 200.0]


def test_decode_frame_chunk_validation():
    from video_features_tpu.ingress.live import (
        LiveSessionError, decode_frame_chunk,
    )
    buf = io.BytesIO()
    np.save(buf, np.zeros((2, 4, 4, 3), np.uint8))
    assert decode_frame_chunk(buf.getvalue()).shape == (2, 4, 4, 3)
    buf = io.BytesIO()
    np.save(buf, np.zeros((4, 4, 3), np.uint8))      # single HWC frame
    assert decode_frame_chunk(buf.getvalue()).shape == (1, 4, 4, 3)
    with pytest.raises(LiveSessionError, match='undecodable'):
        decode_frame_chunk(b'not npy')
    buf = io.BytesIO()
    np.save(buf, np.zeros((4, 4, 3), np.float32))
    with pytest.raises(LiveSessionError, match='uint8'):
        decode_frame_chunk(buf.getvalue())


def test_protocol_version_check_unit():
    from video_features_tpu.serve import protocol

    assert protocol.check_version({'cmd': 'ping'}) is None
    assert protocol.check_version({'v': '1.0'}) is None
    assert protocol.check_version({'v': '1.7'}) is None   # minor skew ok
    err = protocol.check_version({'v': '99.0', 'request_id': 'r42'})
    assert err['ok'] is False and 'unsupported protocol' in err['error']
    assert err['request_id'] == 'r42' and err['v'] == protocol.VERSION
    err = protocol.check_version({'v': 'abc'})
    assert err['ok'] is False and 'malformed' in err['error']


def test_client_connect_retries_until_late_binding_listener():
    """A refused connect retries with backoff up to the deadline — a
    listener that binds 0.4s late is cured, a dead port still fails."""
    from video_features_tpu.serve import protocol
    from video_features_tpu.serve.client import ServeClient

    probe = socket.socket()
    probe.bind(('127.0.0.1', 0))
    port = probe.getsockname()[1]
    probe.close()                           # port now refuses connects

    def late_listener():
        time.sleep(0.4)
        srv = socket.socket()
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(('127.0.0.1', port))
        srv.listen(1)
        conn, _ = srv.accept()
        with conn, conn.makefile('rb') as rf:
            msg = protocol.decode(rf.readline())
            assert msg['cmd'] == 'ping' and msg['v'] == protocol.VERSION
            conn.sendall(protocol.encode(protocol.ok(draining=False)))
        srv.close()

    t = threading.Thread(target=late_listener, daemon=True)
    t.start()
    assert ServeClient(port, connect_timeout_s=10.0).ping()
    t.join(5.0)

    probe = socket.socket()
    probe.bind(('127.0.0.1', 0))
    dead = probe.getsockname()[1]
    probe.close()
    t0 = time.monotonic()
    with pytest.raises((ConnectionRefusedError, OSError)):
        ServeClient(dead, connect_timeout_s=0.3).ping()
    assert time.monotonic() - t0 < 5.0      # bounded, no infinite retry


# -- e2e: one shared server + gateway (resnet18 random weights, CPU) ---------

@pytest.fixture(scope='module')
def ingress_clips(tmp_path_factory):
    d = tmp_path_factory.mktemp('ingressvids')
    return [str(_write_clip(d / f'iv{i}.mp4', n, seed=10 + i))
            for i, n in enumerate((16, 6))]


def _base_overrides(root: Path):
    return {
        'device': 'cpu', 'model_name': 'resnet18', 'batch_size': 4,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'tmp_path': str(root / 'ing_tmp'),
        'output_path': str(root / 'ing_out_default'),
    }


def _make_auth():
    from video_features_tpu.ingress.auth import ApiKeyAuth, Tenant
    return ApiKeyAuth({
        API_KEY: Tenant('acme'),
        BATCH_KEY: Tenant('bulkco', priority='batch'),
        LIMITED_KEY: Tenant('capped', rate_rps=0.001, burst=1,
                            max_concurrent=1),
    })


@pytest.fixture(scope='module')
def gatewayed(tmp_path_factory):
    from video_features_tpu.ingress.gateway import IngressGateway
    from video_features_tpu.serve.server import ExtractionServer
    root = tmp_path_factory.mktemp('ingress_srv')
    server = ExtractionServer(base_overrides=_base_overrides(root),
                              queue_depth=8, pool_size=2,
                              batch_shed_fraction=0.5).start()
    gateway = IngressGateway(server, auth=_make_auth()).start()
    yield server, gateway, root
    server.drain(wait=True, grace_s=120)


def _api(gateway, method, path, body=None, key=API_KEY, timeout=180,
         headers=None):
    import http.client
    c = http.client.HTTPConnection('127.0.0.1', gateway.port,
                                   timeout=timeout)
    headers = dict(headers or {})
    if key:
        headers['Authorization'] = f'Bearer {key}'
    c.request(method, path,
              body=json.dumps(body) if body is not None else None,
              headers=headers)
    r = c.getresponse()
    raw = r.read()
    c.close()
    try:
        return r.status, json.loads(raw)
    except ValueError:
        return r.status, raw


def _wait_done(gateway, rid, key=API_KEY, timeout_s=180.0):
    deadline = time.monotonic() + timeout_s
    while True:
        st, doc = _api(gateway, 'GET', f'/v1/requests/{rid}', key=key)
        assert st == 200, doc
        if doc['state'] != 'running':
            return doc
        assert time.monotonic() < deadline, f'request {rid} stuck: {doc}'
        time.sleep(0.1)


def test_health_auth_and_metrics_surfaces(gatewayed):
    server, gateway, _ = gatewayed
    st, doc = _api(gateway, 'GET', '/healthz', key=None)
    assert st == 200 and doc['ok'] and doc['draining'] is False
    st, doc = _api(gateway, 'GET', '/v1/metrics', key='wrong-key')
    assert st == 401 and doc['error'] == 'unauthorized'
    st, doc = _api(gateway, 'GET', '/v1/metrics')
    assert st == 200 and doc['metrics']['ingress']['enabled'] is True
    st, text = _api(gateway, 'GET', '/metrics')
    assert st == 200 and b'vft_ingress_requests_total' in text
    st, doc = _api(gateway, 'GET', '/v1/nope')
    assert st == 404


def test_segment_query_parity_ingress_vs_loopback(gatewayed, ingress_clips):
    """The acceptance triangle: the same [0.2, 0.6) range over ingress
    and over the loopback socket produce byte-identical feature files,
    named so they can never collide with a full extraction."""
    from video_features_tpu.serve.client import ServeClient
    server, gateway, root = gatewayed
    clip = ingress_clips[0]
    seg = [0.2, 0.6]

    out_ing = str(root / 'seg_ing')
    st, doc = _api(gateway, 'POST', '/v1/extract', {
        'feature_type': 'resnet', 'video_paths': [clip], 'range': seg,
        'overrides': {'output_path': out_ing}})
    assert st == 200 and doc['tenant'] == 'acme', doc
    status = _wait_done(gateway, doc['request_id'])
    assert status['state'] == 'done' and status['range'] == seg
    assert status['tenant'] == 'acme'

    out_loop = str(root / 'seg_loop')
    client = ServeClient(port=server.port)
    rid = client.submit('resnet', [clip],
                        overrides={'output_path': out_loop}, range_s=seg)
    assert client.wait(rid, timeout_s=180)['state'] == 'done'

    stem = Path(clip).stem + '_seg200-600ms.mp4'
    for key_, ext in (('resnet', '.npy'), ('timestamps_ms', '.npy')):
        a = Path(make_path(str(Path(out_ing) / 'resnet' / 'resnet18'),
                           stem, key_, ext)).read_bytes()
        b = Path(make_path(str(Path(out_loop) / 'resnet' / 'resnet18'),
                           stem, key_, ext)).read_bytes()
        assert a == b, f'{key_} differs between ingress and loopback'
    ts = np.load(make_path(str(Path(out_ing) / 'resnet' / 'resnet18'),
                           stem, 'timestamps_ms', '.npy'))
    # 25 fps clip → frames 5..14 → timestamps 200..560 ms: the covered
    # range only, not the whole video
    assert ts.min() >= 200.0 - 1e-6 and ts.max() < 600.0
    assert 0 < len(ts) < 16


def test_trace_route_tenant_scoped_and_traceparent_adopted(gatewayed,
                                                           ingress_clips):
    """vft-flight over the front door: the caller's W3C traceparent is
    adopted end-to-end (echoed as trace_id), GET /v1/requests/<id>/trace
    answers the OWNING tenant, a FOREIGN tenant gets an explicit 403,
    and an unknown id stays 404."""
    server, gateway, root = gatewayed
    clip = ingress_clips[1]
    caller_trace = 'feedc0de' * 4
    st, doc = _api(gateway, 'POST', '/v1/extract', {
        'feature_type': 'resnet', 'video_paths': [clip],
        'overrides': {'output_path': str(root / 'trace_out_dir')}},
        headers={'traceparent':
                 f'00-{caller_trace}-00f067aa0ba902b7-01'})
    assert st == 200, doc
    assert doc['trace_id'] == caller_trace, doc
    rid = doc['request_id']
    assert _wait_done(gateway, rid)['state'] == 'done'

    # owner reads its trace (this server runs without trace_out, so the
    # assembled event list is empty — the scoping contract is the point)
    st, tr = _api(gateway, 'GET', f'/v1/requests/{rid}/trace')
    assert st == 200, tr
    assert tr['trace_id'] == caller_trace and tr['tenant'] == 'acme'
    assert tr['request_id'] == rid and isinstance(tr['events'], list)

    # a FOREIGN tenant gets an explicit 403 (not status's 404 ambiguity)
    st, err = _api(gateway, 'GET', f'/v1/requests/{rid}/trace',
                   key=BATCH_KEY)
    assert st == 403 and err['error'] == 'forbidden', err
    # ...while the same foreign tenant's STATUS read stays a 404
    st, err = _api(gateway, 'GET', f'/v1/requests/{rid}', key=BATCH_KEY)
    assert st == 404
    # unknown id: 404 for everyone
    st, err = _api(gateway, 'GET', '/v1/requests/r999999/trace')
    assert st == 404
    # a malformed traceparent degrades to a minted trace, never a reject
    st, doc2 = _api(gateway, 'POST', '/v1/extract', {
        'feature_type': 'resnet', 'video_paths': [clip],
        'overrides': {'output_path': str(root / 'trace_out_dir2')}},
        headers={'traceparent': 'garbage'})
    assert st == 200 and len(doc2['trace_id']) == 32
    assert doc2['trace_id'] != caller_trace
    _wait_done(gateway, doc2['request_id'])


def test_front_door_trace_spans_ingress_to_d2h_under_one_trace_id(
        ingress_clips, tmp_path):
    """vft-flight over the front door with the recorder ON (a server-wide
    ``trace_out``): one HTTP request carrying a caller traceparent yields
    ONE assembled trace whose ingress, admission, pack, model and d2h
    spans all belong to that trace_id; the armed watchdog stays quiet on
    the HTTP metrics surface; and the merged export written at drain
    passes tools/trace_view.py."""
    from tools.trace_view import main as trace_view_main
    from video_features_tpu.ingress.gateway import IngressGateway
    from video_features_tpu.serve.server import ExtractionServer

    trace_path = tmp_path / 'flight_trace.json'
    server = ExtractionServer(
        base_overrides=dict(_base_overrides(tmp_path),
                            trace_out=str(trace_path),
                            watchdog_stall_s=3600.0),   # armed, quiet
        queue_depth=8, pool_size=2).start()
    gateway = IngressGateway(server, auth=_make_auth()).start()
    caller_trace = 'f1e1d1c1' * 4
    try:
        st, doc = _api(gateway, 'POST', '/v1/extract', {
            'feature_type': 'resnet', 'video_paths': [ingress_clips[1]]},
            headers={'traceparent':
                     f'00-{caller_trace}-00f067aa0ba902b7-01'})
        assert st == 200 and doc['trace_id'] == caller_trace, doc
        rid = doc['request_id']
        assert _wait_done(gateway, rid)['state'] == 'done'

        st, tr = _api(gateway, 'GET', f'/v1/requests/{rid}/trace')
        assert st == 200 and tr['trace_id'] == caller_trace, tr
        names = {e['name'] for e in tr['events']}
        for stage in ('ingress', 'admission', 'pack', 'model', 'd2h'):
            assert stage in names, (stage, sorted(names))
        for e in tr['events']:
            args = e.get('args') or {}
            assert (args.get('trace_id') == caller_trace
                    or caller_trace in (args.get('trace_ids') or ())
                    or args.get('request_id') == rid), e
        st, m = _api(gateway, 'GET', '/v1/metrics')
        assert m['metrics']['watchdog']['enabled'] is True
        assert m['metrics']['watchdog']['stalls_total'] == 0
    finally:
        server.drain(wait=True, grace_s=60)
    assert trace_view_main([str(trace_path), '--quiet']) == 0


def test_segment_decode_is_tracer_bounded_to_range(ingress_clips,
                                                   tmp_path):
    """Tracer-verified acceptance: a packed segment run records decode
    spans proportional to the covered range, not the video length."""
    from video_features_tpu.config import load_config
    from video_features_tpu.obs.spans import SpanRecorder
    from video_features_tpu.parallel.packing import VideoTask
    from video_features_tpu.registry import create_extractor
    from video_features_tpu.utils.tracing import Tracer

    clip = ingress_clips[0]                      # 16 frames @ 25 fps
    args = load_config('resnet', overrides={
        'device': 'cpu', 'model_name': 'resnet18', 'batch_size': 4,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'video_paths': [clip],
        'output_path': str(tmp_path / 'out'),
        'tmp_path': str(tmp_path / 'tmp')})
    ex = create_extractor(args)

    def run(segment, tag):
        rec = SpanRecorder()
        ex.tracer = Tracer(enabled=True, recorder=rec)
        ex.profile = False
        task = VideoTask(clip, segment=segment)
        task.out_root = str(tmp_path / tag)
        ex.extract_packed([task])
        return sum(1 for ev in rec.snapshot()
                   if ev.get('name') == 'decode+preprocess')

    full = run(None, 'full')
    seg = run((0.0, 0.2), 'seg')                 # 5 of 16 frames
    assert full >= 16
    assert 0 < seg <= 6                          # ∝ the range, + slack
    assert seg < full / 2


def test_quota_exhausted_sheds_without_admission_slot(gatewayed,
                                                      ingress_clips):
    """The satellite: a quota-shed request returns a structured error
    carrying tenant + request id, never occupies an admission slot, and
    increments vft_ingress_shed_total."""
    server, gateway, root = gatewayed
    depth_before = server.metrics()['queue']['depth']

    # burst=1: the first request drains the bucket (and may also pin the
    # 1-concurrency budget); the second MUST shed at the quota gate
    st1, d1 = _api(gateway, 'POST', '/v1/extract', {
        'feature_type': 'resnet', 'video_paths': [ingress_clips[1]],
        'overrides': {'output_path': str(root / 'q_out')}},
        key=LIMITED_KEY)
    assert st1 == 200, d1
    st2, d2 = _api(gateway, 'POST', '/v1/extract', {
        'feature_type': 'resnet', 'video_paths': [ingress_clips[1]]},
        key=LIMITED_KEY)
    assert st2 == 429, d2
    assert d2['error'] in ('rate_limited', 'concurrency')
    assert d2['tenant'] == 'capped' and 'request_id' in d2

    # shed never touched admission: depth unchanged by the rejection
    m = server.metrics()
    assert m['queue']['depth'] <= depth_before + 1  # only the accepted one
    assert m['ingress']['tenants']['capped']['shed'] >= 1

    st, text = _api(gateway, 'GET', '/metrics')
    assert st == 200
    shed_lines = [ln for ln in text.decode().splitlines()
                  if ln.startswith('vft_ingress_shed_total{')
                  and 'tenant="capped"' in ln]
    assert shed_lines and any(
        'class="interactive"' in ln and not ln.endswith(' 0')
        for ln in shed_lines), shed_lines

    _wait_done(gateway, d1['request_id'], key=LIMITED_KEY)


def test_batch_priority_shed_before_interactive(gatewayed):
    """queue_depth=8, batch_shed_fraction=0.5 → the batch class sees a
    capacity of 4: a 5-video batch submit is shed (structured, never
    occupying a slot) while the same submit as interactive admits."""
    server, gateway, root = gatewayed
    fakes = [f'/nonexistent/batchvid{i}.mp4' for i in range(5)]

    st, doc = _api(gateway, 'POST', '/v1/extract', {
        'feature_type': 'resnet', 'video_paths': fakes},
        key=BATCH_KEY)                          # tenant priority: batch
    assert st == 503 and doc['error'] == 'queue_full', doc
    assert doc['priority'] == 'batch' and doc['capacity'] == 4
    assert doc['tenant'] == 'bulkco'
    assert server.metrics()['queue']['depth'] == 0  # never admitted

    st, text = _api(gateway, 'GET', '/metrics')
    assert any('class="batch"' in ln and 'reason="queue_full"' in ln
               for ln in text.decode().splitlines()
               if ln.startswith('vft_ingress_shed_total{'))

    # the key's class is a CAP: a batch-provisioned tenant can't claim
    # interactive to dodge the shed
    st, doc = _api(gateway, 'POST', '/v1/extract', {
        'feature_type': 'resnet', 'video_paths': fakes,
        'priority': 'interactive'}, key=BATCH_KEY)
    assert st == 403 and doc['error'] == 'priority_forbidden', doc
    assert doc['tenant'] == 'bulkco'

    # the SAME videos from an INTERACTIVE tenant fit (full capacity 8);
    # they fail fast per-video (nonexistent files) through the normal
    # contract
    st, doc = _api(gateway, 'POST', '/v1/extract', {
        'feature_type': 'resnet', 'video_paths': fakes,
        'priority': 'interactive',
        'overrides': {'output_path': str(root / 'b_out')}})
    assert st == 200, doc
    status = _wait_done(gateway, doc['request_id'])
    assert status['state'] == 'failed'
    assert set(status['videos'].values()) == {'failed'}


def test_deadline_expired_over_ingress(gatewayed, ingress_clips):
    """The satellite's other half: a deadline that passes before decode
    starts expires the videos; the ingress status names tenant + request
    id and the expired count lands in the metrics families."""
    server, gateway, root = gatewayed
    # a ZERO deadline is expired by construction (monotonic() >= now) —
    # a warm worker can dequeue within any positive epsilon, so this is
    # the only race-free way to pin the expiry path
    st, doc = _api(gateway, 'POST', '/v1/extract', {
        'feature_type': 'resnet', 'video_paths': [ingress_clips[0]],
        'timeout_s': 0.0,
        'overrides': {'output_path': str(root / 'dl_out')}})
    assert st == 200, doc
    status = _wait_done(gateway, doc['request_id'])
    assert status['state'] == 'failed'
    assert set(status['videos'].values()) == {'expired'}
    assert status['tenant'] == 'acme'
    assert status['request_id'] == doc['request_id']
    assert server.metrics()['requests']['expired_videos'] >= 1


def _live_connect(gateway, sid, key=API_KEY, timeout=180):
    s = socket.create_connection(('127.0.0.1', gateway.port),
                                 timeout=timeout)
    s.sendall(f'POST /v1/live/{sid} HTTP/1.1\r\nHost: t\r\n'
              f'Authorization: Bearer {key}\r\n'
              f'Transfer-Encoding: chunked\r\n\r\n'.encode())
    return s


def _send_chunk(s, payload: bytes):
    s.sendall(b'%x\r\n%s\r\n' % (len(payload), payload))


def _frames_chunk(rng, n=3, h=48, w=64):
    buf = io.BytesIO()
    np.save(buf, rng.integers(0, 255, (n, h, w, 3), dtype=np.uint8))
    return buf.getvalue()


class _ChunkReader:
    """Minimal chunked-response reader over a raw socket."""

    def __init__(self, s):
        self.rf = s.makefile('rb')

    def read_headers(self):
        line = self.rf.readline()
        status = int(line.split()[1])
        while self.rf.readline() not in (b'\r\n', b''):
            pass
        return status

    def read_chunk(self):
        size = int(self.rf.readline().split(b';')[0], 16)
        if size == 0:
            self.rf.readline()
            return None
        data = self.rf.read(size)
        self.rf.readline()
        return data


def test_live_session_streams_windows_before_final(gatewayed):
    """Acceptance: a live session streams >= 2 per-window feature chunks
    BEFORE the final done-line; window count matches the frames sent."""
    server, gateway, _ = gatewayed
    rng = np.random.default_rng(7)
    s = _live_connect(gateway, 'live-a')
    try:
        _send_chunk(s, json.dumps(
            {'feature_type': 'resnet', 'fps': 5.0}).encode())
        reader = _ChunkReader(s)
        assert reader.read_headers() == 200
        hello = json.loads(reader.read_chunk())
        assert hello['ok'] and hello['session'] == 'live-a'
        rid = hello['request_id']

        _send_chunk(s, _frames_chunk(rng, n=3))
        _send_chunk(s, _frames_chunk(rng, n=3))
        rows = []
        while len(rows) < 6:                    # resnet: 1 frame = 1 window
            row = json.loads(reader.read_chunk())
            assert 'window' in row and not row.get('done'), row
            rows.append(row)
        # >= 2 per-window chunks arrived BEFORE end-of-input, each with
        # a feature vector + the fps-derived timestamp
        assert len(rows) >= 2
        assert len(rows[0]['feats']['resnet']) == 512
        assert rows[1]['timestamp_ms'] == pytest.approx(200.0)

        s.sendall(b'0\r\n\r\n')                 # end of input
        final = json.loads(reader.read_chunk())
        while not final.get('done'):
            final = json.loads(reader.read_chunk())
        assert final['state'] == 'done' and final['windows'] == 6
        assert final['request_id'] == rid
    finally:
        s.close()


def test_live_session_tail_windows_survive_immediate_end(gatewayed):
    """Regression (review): a client that sends its terminator right
    after the last frames — no idle lull, nothing read yet — must still
    receive EVERY window and a 'done' final state. (End-of-input used to
    tear the session down via the windower's finally, so tail windows
    still pooled in the packer hit a dead send_window and the task was
    marked failed.)"""
    server, gateway, _ = gatewayed
    rng = np.random.default_rng(13)
    s = _live_connect(gateway, 'tail-sid')
    try:
        _send_chunk(s, json.dumps(
            {'feature_type': 'resnet', 'fps': 5.0}).encode())
        # 3 frames (< batch_size 4: they pool) then the terminator
        # immediately — before reading a single response chunk
        _send_chunk(s, _frames_chunk(rng, n=3))
        s.sendall(b'0\r\n\r\n')
        reader = _ChunkReader(s)
        assert reader.read_headers() == 200
        assert json.loads(reader.read_chunk())['ok']
        rows = []
        final = None
        while True:
            row = json.loads(reader.read_chunk())
            if row.get('done'):
                final = row
                break
            rows.append(row)
        assert len(rows) == 3, rows
        assert final['state'] == 'done' and final['windows'] == 3
    finally:
        s.close()


def test_range_validation_rejects_nonfinite_and_bad_order(gatewayed):
    """Structured 400s for malformed ranges — including JSON's 1e999 →
    inf, which must never reach the decode thread as an OverflowError."""
    server, gateway, _ = gatewayed
    for bad in ([1.0], [2.0, 1.0], [-1.0, 2.0], [0.0, 1e999],
                ['a', 'b']):
        st, doc = _api(gateway, 'POST', '/v1/extract', {
            'feature_type': 'resnet',
            'video_paths': ['/nonexistent/r.mp4'], 'range': bad})
        assert st == 400, (bad, st, doc)
        assert doc['tenant'] == 'acme'


def test_duplicate_live_session_id_rejected(gatewayed):
    """Bugfix satellite: two in-flight sessions must not share an id —
    the second gets a structured 409 while the first keeps streaming."""
    server, gateway, _ = gatewayed
    rng = np.random.default_rng(8)
    s1 = _live_connect(gateway, 'dup-sid')
    try:
        _send_chunk(s1, json.dumps(
            {'feature_type': 'resnet', 'fps': 5.0}).encode())
        r1 = _ChunkReader(s1)
        assert r1.read_headers() == 200
        assert json.loads(r1.read_chunk())['ok']

        s2 = _live_connect(gateway, 'dup-sid')
        try:
            _send_chunk(s2, json.dumps(
                {'feature_type': 'resnet', 'fps': 5.0}).encode())
            r2 = _ChunkReader(s2)
            assert r2.read_headers() == 409
        finally:
            s2.close()

        # first session is unharmed: frames still round-trip
        _send_chunk(s1, _frames_chunk(rng, n=2))
        row = json.loads(r1.read_chunk())
        assert 'window' in row
        s1.sendall(b'0\r\n\r\n')
        final = json.loads(r1.read_chunk())
        while not final.get('done'):
            final = json.loads(r1.read_chunk())
        assert final['state'] == 'done'
    finally:
        s1.close()

    # the id is reusable once its session ended
    st, _doc = _api(gateway, 'GET', '/v1/metrics')
    assert st == 200
    assert server.metrics()['ingress']['live_sessions'] == 0


def test_live_session_rejected_for_nonlive_family(monkeypatch):
    """LIVE_FEATURES gates sessions up front with a clear error (all
    packed families currently opt in, so the gate is pinned by shrinking
    the set)."""
    from video_features_tpu.serve import server as server_mod

    class FakeSession:
        pseudo_path = 'x.live'

        def bind(self, req):
            pass

    monkeypatch.setattr(server_mod, 'LIVE_FEATURES',
                        frozenset({'resnet'}))
    server = server_mod.ExtractionServer(base_overrides={'device': 'cpu'})
    out = server.submit_live('r21d', FakeSession())
    assert out['ok'] is False and 'live-session support' in out['error']


def test_protocol_version_rejected_over_socket(gatewayed):
    """Satellite: unknown major version → structured error with the
    echoed request_id, not a silent parse failure; current version ok."""
    from video_features_tpu.serve import protocol
    server, _, _ = gatewayed

    def roundtrip(msg):
        s = socket.create_connection(('127.0.0.1', server.port),
                                     timeout=30)
        with s, s.makefile('rb') as rf:
            s.sendall(protocol.encode(msg))
            return protocol.decode(rf.readline())

    bad = roundtrip({'cmd': 'status', 'request_id': 'r000001',
                     'v': '99.1'})
    assert bad['ok'] is False
    assert 'unsupported protocol' in bad['error']
    assert bad['request_id'] == 'r000001'
    assert bad['v'] == protocol.VERSION

    good = roundtrip({'cmd': 'ping', 'v': protocol.VERSION})
    assert good['ok'] is True


@pytest.mark.slow
def test_segment_parity_through_decode_farm(ingress_clips, tmp_path):
    """Farm recipes included (tentpole piece 3): the worker PROCESSES
    replay the same frame-range filter, byte-identically to in-process
    segment decode."""
    from video_features_tpu.config import load_config
    from video_features_tpu.parallel.packing import VideoTask
    from video_features_tpu.registry import create_extractor

    clip = ingress_clips[0]
    args = load_config('resnet', overrides={
        'device': 'cpu', 'model_name': 'resnet18', 'batch_size': 4,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'video_paths': [clip],
        'output_path': str(tmp_path / 'out'),
        'tmp_path': str(tmp_path / 'tmp')})
    ex = create_extractor(args)
    seg = (0.2, 0.6)

    def run(tag, workers):
        task = VideoTask(clip, segment=seg)
        task.out_root = str(tmp_path / tag)
        ex.extract_packed([task], decode_workers=workers)
        stem = Path(clip).stem + '_seg200-600ms.mp4'
        return Path(make_path(task.out_root, stem,
                              'resnet', '.npy')).read_bytes()

    assert run('inproc', 1) == run('farm', 2)


@pytest.mark.slow
def test_live_session_through_decode_farm(tmp_path):
    """A farm-backed warm worker (decode_workers=2) runs live sessions
    on a parent-side feeder — frames never ship to a worker process —
    with the same streamed-windows contract."""
    from video_features_tpu.ingress.gateway import IngressGateway
    from video_features_tpu.serve.server import ExtractionServer

    base = _base_overrides(tmp_path)
    base['decode_workers'] = 2
    server = ExtractionServer(base_overrides=base, queue_depth=8,
                              pool_size=2).start()
    gateway = IngressGateway(server, auth=_make_auth()).start()
    rng = np.random.default_rng(11)
    s = _live_connect(gateway, 'farm-live')
    try:
        _send_chunk(s, json.dumps(
            {'feature_type': 'resnet', 'fps': 5.0}).encode())
        reader = _ChunkReader(s)
        assert reader.read_headers() == 200
        assert json.loads(reader.read_chunk())['ok']
        _send_chunk(s, _frames_chunk(rng, n=3))
        _send_chunk(s, _frames_chunk(rng, n=2))
        rows = []
        while len(rows) < 5:
            row = json.loads(reader.read_chunk())
            assert 'window' in row, row
            rows.append(row)
        assert len(rows[0]['feats']['resnet']) == 512
        s.sendall(b'0\r\n\r\n')
        final = json.loads(reader.read_chunk())
        while not final.get('done'):
            final = json.loads(reader.read_chunk())
        assert final['state'] == 'done' and final['windows'] == 5
    finally:
        s.close()
        server.drain(wait=True, grace_s=120)


def test_drain_reaps_half_open_live_session(tmp_path):
    """Bugfix satellite: a live client that stops mid-stream must not
    block drain — begin_drain ends its input, finish_drain force-closes
    the connection, and the warm pool is released."""
    from video_features_tpu.ingress.gateway import IngressGateway
    from video_features_tpu.serve.server import ExtractionServer

    server = ExtractionServer(base_overrides=_base_overrides(tmp_path),
                              queue_depth=8, pool_size=2).start()
    gateway = IngressGateway(server, auth=_make_auth()).start()
    rng = np.random.default_rng(9)
    s = _live_connect(gateway, 'half-open')
    _send_chunk(s, json.dumps(
        {'feature_type': 'resnet', 'fps': 5.0}).encode())
    reader = _ChunkReader(s)
    assert reader.read_headers() == 200
    assert json.loads(reader.read_chunk())['ok']
    _send_chunk(s, _frames_chunk(rng, n=2))
    # ... and the client goes silent: no end chunk, connection held open

    t0 = time.monotonic()
    server.drain(wait=True, grace_s=60)
    assert server.drained
    # drain completed promptly — the half-open session did not pin a
    # worker for the LIVE_IDLE_TIMEOUT (minutes)
    assert time.monotonic() - t0 < 45
    # the reaped handler thread's cleanup runs just after the force-
    # close; give it a beat before asserting the connection table empty
    deadline = time.monotonic() + 10
    while gateway.http.open_connections and time.monotonic() < deadline:
        time.sleep(0.05)
    assert gateway.http.open_connections == 0
    assert server.metrics()['ingress']['live_sessions'] == 0
    s.close()
