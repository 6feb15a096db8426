"""The fleet tier (fleet/): multi-host front door + shared caches.

Three layers, cheapest first:

  * pure units — the consistent-hash ring's determinism/minimal-movement
    contract, structured error-code classification (the wire-1.4
    failover driver), fleet config splitting, and both shared tiers
    (feature cache L1+L2, AOT artifact store) over tmp dirs, no jax;
  * fake-backend router tests — tiny in-process threads speaking the
    loopback JSON-lines protocol with canned responses pin failover,
    proactive unhealthy-marking, drain-aware membership, and the
    mid-stream-kill semantics without ever building a model;
  * ONE real two-backend integration — two ExtractionServers sharing an
    L2 feature cache + artifact tier behind a router: the acceptance
    scenario (extract on the ring owner, kill it, the survivor serves
    the same video byte-identically from the shared cache without
    decoding, having cold-booted on a peer-compiled executable with
    ``builds_compiled == 0``).
"""
import os
import re
import socket
import threading
import time
from pathlib import Path

import pytest

from video_features_tpu.fleet.ring import HashRing
from video_features_tpu.serve import protocol

from tools.make_sample_video import write_noise_clip as _write_clip  # noqa: E402
from tools.trace_view import validate_events  # noqa: E402


# -- hash ring ---------------------------------------------------------------


def test_ring_determinism_and_failover_order():
    hosts = ['h0:1', 'h1:1', 'h2:1', 'h3:1']
    r1, r2 = HashRing(hosts), HashRing(list(reversed(hosts)))
    # duplicate entries collapse; host ORDER never affects placement
    assert HashRing(hosts + hosts).hosts == hosts
    keys = [f'video{i}' for i in range(500)]
    assert [r1.host_for(k) for k in keys] == [r2.host_for(k) for k in keys]
    for k in keys[:50]:
        order = r1.hosts_for(k)
        assert order[0] == r1.host_for(k)
        assert sorted(order) == sorted(hosts)      # every host, once


def test_ring_rebalance_moves_only_the_removed_hosts_keys():
    """The property the fleet's cache warmth rides on: dropping one of
    N hosts reassigns EXACTLY the keys it owned (~1/N of the space) —
    every other key keeps its backend, its L1 entries, and its warm
    pool."""
    hosts = [f'10.0.0.{i}:9300' for i in range(4)]
    ring = HashRing(hosts)
    keys = [f'sha256:{i:06d}' for i in range(4000)]
    before = {k: ring.host_for(k) for k in keys}
    victim = hosts[1]
    after = ring.without(victim)
    moved = [k for k in keys if before[k] != after.host_for(k)]
    owned = [k for k in keys if before[k] == victim]
    assert set(moved) == set(owned)
    # ~1/N with virtual-node variance: a generous band still catches a
    # broken ring (all keys moving, or none)
    assert 0.10 < len(moved) / len(keys) < 0.45
    # the eligibility FILTER (what the router actually uses mid-flight)
    # agrees with a rebuilt ring: same owners, no rebuild needed
    eligible = set(hosts) - {victim}
    for k in keys[:300]:
        assert ring.hosts_for(k, eligible=eligible)[0] == after.host_for(k)


# -- structured error codes (wire 1.4) ---------------------------------------


def test_error_code_classification_drives_retry():
    """Failover keys on ``ServeError.code``, never on message text: the
    retryable set is exactly {shed, connect_refused, deadline}, and the
    compat subclasses still satisfy the OS-exception types pre-1.4
    callers caught."""
    from video_features_tpu.serve.client import (
        ServeConnectError, ServeDeadlineError, ServeError,
    )
    for code in (protocol.ERR_SHED, protocol.ERR_CONNECT_REFUSED,
                 protocol.ERR_DEADLINE):
        assert ServeError('anything at all', code=code).retryable
    for code in (protocol.ERR_INVALID, protocol.ERR_UNSUPPORTED,
                 protocol.ERR_NOT_FOUND, protocol.ERR_INTERNAL, None):
        assert not ServeError('queue full', code=code).retryable
    assert isinstance(ServeConnectError('x'), ConnectionRefusedError)
    assert ServeConnectError('x').code == protocol.ERR_CONNECT_REFUSED
    assert isinstance(ServeDeadlineError('x'), TimeoutError)
    assert ServeDeadlineError('x').code == protocol.ERR_DEADLINE
    e = ServeError('shed', code=protocol.ERR_SHED,
                   extra={'queue_depth': 64})
    assert e.extra['queue_depth'] == 64


def test_split_fleet_config_validates():
    from video_features_tpu.config import parse_dotlist, split_fleet_config
    fleet, extra = split_fleet_config(parse_dotlist(
        ['fleet_hosts=[127.0.0.1:9301,127.0.0.1:9302]', 'fleet_port=0',
         'feature_type=resnet']))
    assert fleet['fleet_hosts'] == ['127.0.0.1:9301', '127.0.0.1:9302']
    assert fleet['fleet_port'] == 0 and fleet['fleet_max_attempts'] == 3
    assert dict(extra) == {'feature_type': 'resnet'}   # refused by main
    with pytest.raises(ValueError, match='Unknown fleet option'):
        split_fleet_config({'fleet_hots': '127.0.0.1:1'})
    with pytest.raises(ValueError, match='fleet_auth_file'):
        split_fleet_config({'fleet_hosts': ['127.0.0.1:1'],
                            'fleet_http_port': 8080})
    with pytest.raises(ValueError, match='fleet_probe_interval_s'):
        split_fleet_config({'fleet_hosts': ['127.0.0.1:1'],
                            'fleet_probe_interval_s': 0})


def test_l2_knobs_require_their_subsystems():
    from video_features_tpu.config import sanity_check
    base = {'feature_type': 'resnet', 'device': 'cpu',
            'on_extraction': 'save_numpy', 'output_path': '/tmp/o',
            'tmp_path': '/tmp/t'}
    with pytest.raises(ValueError, match='cache_l2_dir requires'):
        sanity_check(dict(base, cache_l2_dir='/tmp/l2'))
    with pytest.raises(ValueError, match='aot_l2_dir requires'):
        sanity_check(dict(base, aot_l2_dir='/tmp/l2'))


# -- shared feature-cache tier -----------------------------------------------


def _seed_entry(cache, tmp_path, key, payload: bytes):
    src = tmp_path / f'{key}.npy'
    src.write_bytes(payload)
    cache.put(key, {'resnet': (str(src), '.npy')}, meta={'n': 1})


def test_tiered_cache_peer_hit_promotes_and_publishes(tmp_path):
    """The two-host story in one process: host A's put lands in the
    shared L2; host B (empty L1, same L2) serves it byte-identically
    and promotes it into its own L1 so the NEXT hit is local."""
    from video_features_tpu.cache.store import FeatureCache
    from video_features_tpu.fleet.tier import TieredFeatureCache
    l2 = str(tmp_path / 'shared')
    a = TieredFeatureCache(str(tmp_path / 'a'), l2)
    b = TieredFeatureCache(str(tmp_path / 'b'), l2)
    payload = os.urandom(512)
    _seed_entry(a, tmp_path, 'k1', payload)
    assert a.stats()['l2_publishes'] == 1
    assert b.contains('k1')                     # union view: via L2

    out = tmp_path / 'out_b'
    assert b.fetch_to('k1', str(out), '/videos/clip.mp4')
    served = out / 'clip_resnet.npy'
    assert served.read_bytes() == payload       # byte-identical via L2
    st = b.stats()
    assert st['peer_hits'] == 1 and st['hits'] == 0
    # promoted: B's own L1 now holds the entry — the next fetch never
    # touches the L2
    assert FeatureCache.contains(b, 'k1')
    assert b.fetch_to('k1', str(tmp_path / 'out_b2'), '/videos/clip.mp4')
    assert b.stats()['peer_hits'] == 1 and b.stats()['hits'] >= 1


def test_tiered_cache_corrupt_l2_entry_is_a_miss(tmp_path):
    """Same integrity contract at both levels: a truncated shared entry
    is evicted, reads as a miss, and is never served."""
    from video_features_tpu.fleet.tier import TieredFeatureCache
    l2_dir = str(tmp_path / 'shared')
    a = TieredFeatureCache(str(tmp_path / 'a'), l2_dir)
    _seed_entry(a, tmp_path, 'k1', os.urandom(256))
    # truncate the SHARED copy only
    edir = Path(a.l2._entry_dir('k1'))
    victim = next(p for p in edir.iterdir() if p.suffix == '.npy')
    victim.write_bytes(b'torn')
    b = TieredFeatureCache(str(tmp_path / 'b'), l2_dir)
    assert not b.fetch_to('k1', str(tmp_path / 'o'), '/v/clip.mp4')
    assert b.stats()['peer_hits'] == 0
    assert b.stats()['l2']['corrupt_evicted'] == 1


def test_tiered_cache_get_pair_is_process_global(tmp_path):
    from video_features_tpu.fleet.tier import TieredFeatureCache
    p1 = TieredFeatureCache.get_pair(tmp_path / 'l1', tmp_path / 'l2')
    p2 = TieredFeatureCache.get_pair(tmp_path / 'l1', tmp_path / 'l2')
    assert p1 is p2
    assert TieredFeatureCache.get_pair(tmp_path / 'x', tmp_path / 'l2') \
        is not p1


# -- shared AOT artifact tier ------------------------------------------------


def test_tiered_exec_store_publish_pull_and_corrupt_purge(tmp_path):
    from video_features_tpu.fleet.artifacts import TieredExecStore
    shared = str(tmp_path / 'artifacts')
    a = TieredExecStore(str(tmp_path / 'aot_a'), shared)
    payload = os.urandom(1024)
    meta = {'program_sha': 'sha256:p1', 'lane': 'mesh1'}
    a.put('digest1', payload, meta)              # publish-on-compile
    assert a.stats()['published'] == 1

    b = TieredExecStore(str(tmp_path / 'aot_b'), shared)
    assert b.contains('digest1')                 # union view
    assert b.metas_for('sha256:p1')              # fleet-wide, not empty L1
    assert b.fetch('digest1') == payload         # pull-on-miss
    st = b.stats()
    assert st['pulled'] == 1
    # re-published locally: the next fetch is an L1 hit (no pull bump)
    assert b.fetch('digest1') == payload
    assert b.stats()['pulled'] == 1

    # a corrupt payload purges BOTH tiers — the shared copy must not
    # re-poison the next cold host
    b.evict_corrupt('digest1')
    assert not b.l2.contains('digest1')
    c = TieredExecStore(str(tmp_path / 'aot_c'), shared)
    assert c.fetch('digest1') is None            # structural miss now


# -- fake-backend router tests ----------------------------------------------


class _FakeBackend:
    """A thread speaking just enough of the loopback protocol: canned
    per-command responses, a call log, and a kill switch."""

    def __init__(self, respond):
        self.respond = respond
        self.calls = []
        self.sock = socket.socket()
        self.sock.bind(('127.0.0.1', 0))
        self.sock.listen(16)
        self.port = self.sock.getsockname()[1]
        self.addr = f'127.0.0.1:{self.port}'
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        try:
            with conn:
                rfile, wfile = conn.makefile('rb'), conn.makefile('wb')
                for line in rfile:
                    msg = protocol.decode(line)
                    self.calls.append(msg['cmd'])
                    wfile.write(protocol.encode(self.respond(msg)))
                    wfile.flush()
        except (OSError, ValueError):
            pass

    def kill(self):
        # shutdown BEFORE close: a bare close leaves the listener
        # half-alive in the kernel while the accept thread is blocked
        # on it, and exactly one more connection would sneak through
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()


def _healthy(msg, **submit_fields):
    if msg['cmd'] == protocol.CMD_PING:
        return protocol.ok(draining=False, v=protocol.VERSION)
    if msg['cmd'] == protocol.CMD_SUBMIT:
        return protocol.ok(request_id='r1', **submit_fields)
    if msg['cmd'] == protocol.CMD_STATUS:
        return protocol.ok(request_id=msg.get('request_id'), state='done')
    if msg['cmd'] == protocol.CMD_METRICS:
        return protocol.ok(metrics={'queue': {'depth': 2},
                                    'cache': {'hit_rate': 0.25},
                                    'warm_pool': {'builds_compiled': 1,
                                                  'builds_loaded': 0}})
    return protocol.error('unknown', code=protocol.ERR_INVALID)


def _router(hosts, **kw):
    from video_features_tpu.fleet.router import FleetRouter
    opts = dict(port=0, probe_interval_s=30.0, backoff_base_s=0.005,
                connect_timeout_s=0.5)
    opts.update(kw)
    return FleetRouter(hosts, **opts).start()


def test_router_sheds_failover_and_code_propagation():
    """One shedding backend + one healthy one: retryable codes walk the
    ring (counted), non-retryable codes propagate verbatim, and the
    router's own rejections are structured."""
    ok = _FakeBackend(_healthy)
    shed = _FakeBackend(lambda m: _healthy(m) if m['cmd'] != 'submit'
                        else protocol.error('queue full (64/64)',
                                            code=protocol.ERR_SHED))
    router = _router([shed.addr, ok.addr])
    try:
        from video_features_tpu.serve.client import ServeClient
        client = ServeClient(router.port)
        assert client.ping()
        for i in range(8):
            resp = client._call({'cmd': 'submit',
                                 'video_paths': [f'/v/{i}.mp4']})
            assert resp['ok'] and resp['backend'] == ok.addr
        fleet = client.metrics()['fleet']
        assert fleet['routed'][ok.addr] == 8
        assert fleet['routed'][shed.addr] == 0
        # some keys hash to the shedding backend first → failovers
        assert fleet['failovers'] > 0
        # status routes by the remembered request_id → backend binding
        assert client.status('r1')['state'] == 'done'
        from video_features_tpu.serve.client import ServeError
        with pytest.raises(ServeError) as ei:
            client.status('never')
        assert ei.value.code == protocol.ERR_NOT_FOUND
    finally:
        router.stop()
        ok.kill()
        shed.kill()


def test_router_invalid_request_never_retries():
    """A request the whole fleet would reject identically must fail
    ONCE — retrying an `invalid` N times would triple every bad
    request's latency and lie about the failure."""
    calls = []

    def invalid(msg):
        if msg['cmd'] == protocol.CMD_PING:
            return protocol.ok(draining=False)
        calls.append(msg['cmd'])
        return protocol.error('unknown feature_type zzz',
                              code=protocol.ERR_INVALID)
    b1, b2 = _FakeBackend(invalid), _FakeBackend(invalid)
    router = _router([b1.addr, b2.addr])
    try:
        from video_features_tpu.serve.client import ServeClient, ServeError
        with pytest.raises(ServeError) as ei:
            ServeClient(router.port).submit('zzz', ['/v/a.mp4'])
        assert ei.value.code == protocol.ERR_INVALID
        assert len(calls) == 1                   # no second backend tried
    finally:
        router.stop()
        b1.kill()
        b2.kill()


def test_router_kill_midstream_survivor_takes_over():
    """The acceptance semantics: killing a backend fails only what was
    in flight on it; the very next submit routes to the survivor
    (proactive unhealthy-marking on connect_refused, no probe wait),
    and the probe keeps it out of the eligible set."""
    b1, b2 = _FakeBackend(_healthy), _FakeBackend(_healthy)
    router = _router([b1.addr, b2.addr], max_attempts=2)
    try:
        from video_features_tpu.serve.client import ServeClient
        client = ServeClient(router.port)
        assert sorted(router.eligible()) == sorted([b1.addr, b2.addr])
        b1.kill()
        # every submit still lands (failover covers b1's keys)
        for i in range(8):
            resp = client._call({'cmd': 'submit',
                                 'video_paths': [f'/v/{i}.mp4']})
            assert resp['ok'] and resp['backend'] == b2.addr, resp
        assert router.eligible() == [b2.addr]    # marked without a probe
        table = router.probe()
        assert not table[b1.addr]['healthy']
        assert table[b2.addr]['healthy']
        # with BOTH dead the router sheds with a structured code
        b2.kill()
        router.probe()
        from video_features_tpu.serve.client import ServeError
        with pytest.raises(ServeError) as ei:
            client._call({'cmd': 'submit', 'video_paths': ['/v/z.mp4']})
        assert ei.value.code == protocol.ERR_SHED
        assert ei.value.retryable                # a later fleet may recover
    finally:
        router.stop()


def test_router_drain_aware_membership():
    """A DRAINING backend is alive (its ping answers) but leaves the
    eligible set — new work must not land on a host that is shutting
    down; it comes back when the drain flag clears."""
    state = {'draining': False}

    def drainable(msg):
        if msg['cmd'] == protocol.CMD_PING:
            return protocol.ok(draining=state['draining'])
        return _healthy(msg)
    d = _FakeBackend(drainable)
    ok = _FakeBackend(_healthy)
    router = _router([d.addr, ok.addr])
    try:
        assert sorted(router.eligible()) == sorted([d.addr, ok.addr])
        state['draining'] = True
        router.probe()
        assert router.eligible() == [ok.addr]
        from video_features_tpu.serve.client import ServeClient
        for i in range(4):
            resp = ServeClient(router.port)._call(
                {'cmd': 'submit', 'video_paths': [f'/v/{i}.mp4']})
            assert resp['ok'] and resp['backend'] == ok.addr
        state['draining'] = False                # drain cancelled
        router.probe()
        assert sorted(router.eligible()) == sorted([d.addr, ok.addr])
    finally:
        router.stop()
        d.kill()
        ok.kill()


def test_router_failover_yields_one_merged_trace():
    """Acceptance pin (vft-scope): a submit that fails over mid-walk
    yields ONE trace — the router's route/failover spans plus spans
    from BOTH attempted backends, merged ts-sorted under a single
    trace_id, every event stamped with its contributing host."""
    def traced(tag, captured, shed_submit=False):
        def respond(msg):
            if msg['cmd'] == protocol.CMD_PING:
                return protocol.ok(draining=False)
            if msg['cmd'] == protocol.CMD_SUBMIT:
                captured[tag] = msg.get('traceparent')
                if shed_submit:
                    return protocol.error('queue full (64/64)',
                                          code=protocol.ERR_SHED)
                return protocol.ok(request_id='r-trace')
            if msg['cmd'] == protocol.CMD_TRACE:
                tid = captured[tag].split('-')[1]
                return protocol.ok(
                    request_id=msg.get('request_id'), trace_id=tid,
                    state='done',
                    events=[{'name': f'{tag}_admission', 'ph': 'X',
                             'ts': 10.0 if shed_submit else 20.0,
                             'dur': 5.0, 'pid': 1, 'tid': 1,
                             'args': {'trace_id': tid}}])
            return protocol.error('unknown', code=protocol.ERR_INVALID)
        return respond

    captured = {}
    shed = _FakeBackend(traced('shed', captured, shed_submit=True))
    ok = _FakeBackend(traced('ok', captured))
    router = _router([shed.addr, ok.addr])
    try:
        from video_features_tpu.fleet.router import FleetRouter
        from video_features_tpu.serve.client import ServeClient
        # pick a key the SHEDDING backend owns, so the ring walk
        # attempts it first and fails over to the healthy one
        path = next(
            p for p in (f'/v/trace{i}.mp4' for i in range(200))
            if router.ring.host_for(FleetRouter.route_key(
                {'video_paths': [p]})) == shed.addr)
        client = ServeClient(router.port)
        resp = client._call({'cmd': 'submit', 'video_paths': [path]})
        assert resp['ok'] and resp['backend'] == ok.addr
        rid = resp['request_id']

        # the router minted ONE W3C traceparent and forwarded it to
        # BOTH attempted backends — same trace_id on each wire
        w3c = re.compile(r'^00-[0-9a-f]{32}-[0-9a-f]{16}-[0-9a-f]{2}$')
        assert w3c.match(captured['shed']), captured
        assert w3c.match(captured['ok']), captured
        tid = captured['ok'].split('-')[1]
        assert captured['shed'].split('-')[1] == tid

        trace = client.trace(rid)
        assert trace['trace_id'] == tid
        assert sorted(trace['hosts']) == sorted(
            ['router', shed.addr, ok.addr])
        spans = [e for e in trace['events'] if e.get('ph') != 'M']
        by_host = {}
        for e in spans:
            by_host.setdefault(e['args']['host'], []).append(e['name'])
        assert 'shed_admission' in by_host[shed.addr]
        assert 'ok_admission' in by_host[ok.addr]
        assert 'failover' in by_host['router']
        assert 'route' in by_host['router']
        assert by_host['router'].count('backend_call') == 2
        # merged presentation order: ts-sorted across all hosts
        ts = [e['ts'] for e in spans]
        assert ts == sorted(ts)
        assert client.metrics()['fleet']['failovers'] >= 1
    finally:
        router.stop()
        shed.kill()
        ok.kill()


def test_router_metrics_prom_aggregates_host_labeled_families():
    """The router's exposition is the FLEET's: every backend's families
    relabeled ``host=``, family headers emitted once, plus the router's
    own ``vft_fleet_*`` and ``vft_slo_*`` series."""
    def with_prom(msg):
        if msg['cmd'] == protocol.CMD_METRICS_PROM:
            return protocol.ok(text='# HELP vft_up liveness\n'
                                    '# TYPE vft_up gauge\n'
                                    'vft_up 1\n')
        return _healthy(msg)
    b1, b2 = _FakeBackend(with_prom), _FakeBackend(with_prom)
    router = _router([b1.addr, b2.addr])
    try:
        from video_features_tpu.serve.client import ServeClient
        client = ServeClient(router.port)
        resp = client._call({'cmd': 'submit', 'video_paths': ['/v/a.mp4']})
        assert resp['ok']
        text = client.metrics_prom()
        for addr in (b1.addr, b2.addr):
            assert f'vft_up{{host="{addr}"}} 1' in text, text
            assert f'vft_fleet_backend_up{{host="{addr}"}} 1' in text
            assert f'vft_fleet_probe_age_seconds{{host="{addr}"}}' in text
        # one merged family header despite two contributing hosts
        assert text.count('# TYPE vft_up gauge') == 1
        assert 'vft_fleet_routed_total{host=' in text
        assert 'vft_fleet_requests_total{outcome="completed"} 1' in text
        assert 'vft_slo_latency_burn_rate{window="5m"}' in text
        assert 'vft_slo_availability_burn_rate{window="1h"}' in text
        # a dead backend contributes nothing but stays visible as down
        b2.kill()
        router.probe()
        text = router.metrics_prom()
        assert f'vft_up{{host="{b2.addr}"}}' not in text
        assert f'vft_fleet_backend_up{{host="{b2.addr}"}} 0' in text
    finally:
        router.stop()
        b1.kill()
        b2.kill()


# -- real two-backend integration (the acceptance scenario) ------------------


@pytest.fixture(scope='module')
def fleet_clip(tmp_path_factory):
    d = tmp_path_factory.mktemp('fleetvids')
    return str(_write_clip(d / 'fv0.mp4', 6, seed=7))


def _fleet_overrides(tmp_path, host_tag, shared):
    return {
        'device': 'cpu', 'model_name': 'resnet18', 'batch_size': 4,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'tmp_path': str(tmp_path / f'{host_tag}_tmp'),
        'cache_enabled': True,
        'cache_dir': str(tmp_path / f'{host_tag}_cache'),
        'cache_l2_dir': str(shared / 'features'),
        'aot_enabled': True,
        'aot_dir': str(tmp_path / f'{host_tag}_aot'),
        'aot_l2_dir': str(shared / 'artifacts'),
        'trace_out': str(tmp_path / f'{host_tag}_trace.json'),
    }


def test_fleet_two_backends_cache_parity_and_cold_boot(
        fleet_clip, tmp_path):
    """Two real serve daemons sharing an L2 feature cache + artifact
    tier behind a router:

    1. the ring owner extracts the clip (compiles, publishes features
       to the L2 and executables to the artifact tier);
    2. the OTHER backend pre-warms compile-free off the peer's
       executables (``builds_compiled == 0``, ``builds_loaded >= 1``);
    3. the owner dies; the router routes the same video to the
       survivor, which serves it byte-identically from the shared
       cache WITHOUT decoding (admission-time 'cached' status — no
       extraction task, hence no decode, ever enqueued).
    """
    from video_features_tpu.fleet.router import FleetRouter
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer
    from video_features_tpu.utils.output import make_path

    shared = tmp_path / 'shared'
    servers = {}
    for tag in ('a', 'b'):
        servers[tag] = ExtractionServer(
            base_overrides=_fleet_overrides(tmp_path, tag, shared),
            queue_depth=16, pool_size=2).start()
    addr = {tag: f'127.0.0.1:{s.port}' for tag, s in servers.items()}
    router = FleetRouter(list(addr.values()), port=0,
                         probe_interval_s=30.0).start()
    try:
        client = ServeClient(router.port)
        owner_addr = router.ring.host_for(
            FleetRouter.route_key({'video_paths': [fleet_clip]}))
        owner = next(t for t in servers if addr[t] == owner_addr)
        other = 'b' if owner == 'a' else 'a'

        # 1: extract on the ring owner, through the router
        out1 = str(tmp_path / 'out1')
        rid = client.submit('resnet', [fleet_clip],
                            overrides={'output_path': out1})
        st = client.wait(rid, timeout_s=300)
        assert st['state'] == 'done' and st['videos'][fleet_clip] == 'saved'
        assert client.metrics()['fleet']['routed'][owner_addr] == 1

        # the router is the fleet's ONE scrape target: the aggregate of
        # two REAL backends parses line by line, host-relabeled
        prom = client.metrics_prom()
        assert prom.endswith('\n')
        for needle in (f'vft_fleet_routed_total{{host="{owner_addr}"}}',
                       f'vft_serve_queue_depth{{host="{owner_addr}"}}',
                       'vft_fleet_requests_total{outcome="completed"}'):
            assert needle in prom, needle
        sample = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? '
                            r'(NaN|[+-]?Inf|[-+0-9.eE]+)$')
        for line in prom.splitlines():
            if line and not line.startswith('#'):
                assert sample.match(line), f'bad sample: {line!r}'
        # ... and the routed request's scatter-gathered trace holds the
        # router's and the owner's spans and is a valid Chrome trace
        trace = client.trace(rid)
        assert trace['trace_id'] and 'router' in trace['hosts'], trace
        assert {'router', owner_addr} <= {
            (e.get('args') or {}).get('host') for e in trace['events']}
        assert 'route' in {e.get('name') for e in trace['events']}
        assert validate_events(trace['events']) == []

        # 2: cold boot on the survivor: its empty L1 pulls the peer's
        # executables from the shared artifact tier — zero compiles
        report = servers[other].prewarm(['resnet'])
        assert report['errors'] == []
        m_other = servers[other].metrics()['warm_pool']
        assert m_other['builds_compiled'] == 0, m_other
        assert m_other['builds_loaded'] >= 1, m_other

        # 3: the owner dies mid-fleet; the survivor serves the same
        # video from the shared cache, byte-identically, no decode
        servers[owner].drain(wait=True, grace_s=60)
        router.probe()
        assert router.eligible() == [addr[other]]
        out2 = str(tmp_path / 'out2')
        rid2 = client.submit('resnet', [fleet_clip],
                             overrides={'output_path': out2})
        st2 = client.wait(rid2, timeout_s=120)
        assert st2['state'] == 'done'
        assert st2['videos'][fleet_clip] == 'cached'    # admission hit
        for key in ('resnet', 'fps', 'timestamps_ms'):
            p1 = Path(make_path(os.path.join(out1, 'resnet', 'resnet18'),
                                fleet_clip, key, '.npy'))
            p2 = Path(make_path(os.path.join(out2, 'resnet', 'resnet18'),
                                fleet_clip, key, '.npy'))
            assert p1.read_bytes() == p2.read_bytes(), key
        m = servers[other].metrics()
        assert m['warm_pool']['builds_compiled'] == 0   # still never compiled
        assert m['requests']['cached_videos'] >= 1
        # the serve-side tier saw the peer hit (L2 → L1 promotion)
        assert m['cache']['peer_hits'] >= 1, m['cache']
    finally:
        router.stop()
        for s in servers.values():
            try:
                s.drain(wait=True, grace_s=30)
            except Exception:
                pass
