"""The flight recorder (obs/): span timeline, metrics registry,
Prometheus exposition, run manifest, structured error log — and the
contracts that pin their schemas.
"""
import json
import logging
import re
from pathlib import Path

import numpy as np
import pytest

from video_features_tpu.obs.metrics import (
    DEFAULT_BUCKETS, Histogram, MetricsRegistry,
)
from video_features_tpu.obs.spans import SpanRecorder
from video_features_tpu.utils.tracing import Tracer

from tools.make_sample_video import write_noise_clip as _write_clip  # noqa: E402
from tools.trace_view import validate_events  # noqa: E402


# -- span recorder -----------------------------------------------------------

def test_span_recorder_records_and_exports(tmp_path):
    rec = SpanRecorder(capacity=100)
    t0 = 1.0
    rec.span('decode', t0, t0 + 0.5, video='a.mp4')
    rec.instant('video_done', video='a.mp4', outcome='saved')
    events = rec.snapshot()
    spans = [e for e in events if e['ph'] == 'X']
    assert len(spans) == 1
    assert spans[0]['name'] == 'decode'
    assert spans[0]['args']['video'] == 'a.mp4'
    assert spans[0]['dur'] == pytest.approx(0.5e6)
    assert validate_events(events) == []

    out = tmp_path / 'trace.json'
    rec.export(str(out))
    doc = json.loads(out.read_text())
    assert isinstance(doc['traceEvents'], list)
    assert doc['otherData']['events_dropped'] == 0


def test_span_recorder_ring_buffer_drops_oldest():
    rec = SpanRecorder(capacity=4)
    for i in range(10):
        rec.span(f's{i}', float(i), float(i) + 0.1)
    assert rec.dropped == 6
    names = [e['name'] for e in rec.snapshot() if e['ph'] == 'X']
    assert names == ['s6', 's7', 's8', 's9']


def test_merge_traces_aligns_recorders_on_common_origin():
    """Recorders created at different times (serve workers built hours
    apart) share one CLOCK; the merged export must re-base everything to
    ONE origin so cross-worker ordering survives — each recorder's own
    snapshot re-bases to its own epoch."""
    from video_features_tpu.obs.spans import merge_traces
    a, b = SpanRecorder(capacity=8), SpanRecorder(capacity=8)
    a._t0, b._t0 = 100.0, 110.0            # b "built" 10s later
    a.span('a_span', 100.0, 100.5)
    b.span('b_span', 110.0, 110.5)
    # alone, each re-bases to its own epoch: both spans sit at ts=0
    assert [e['ts'] for e in a.snapshot() if e['ph'] == 'X'] == [0.0]
    assert [e['ts'] for e in b.snapshot() if e['ph'] == 'X'] == [0.0]
    merged = {e['name']: e for e in merge_traces([a, b])
              if e['ph'] == 'X'}
    assert merged['a_span']['ts'] == 0.0
    assert merged['b_span']['ts'] == pytest.approx(10e6)


def test_disabled_recorder_is_noop():
    rec = SpanRecorder(capacity=8, enabled=False)
    rec.span('x', 0.0, 1.0)
    rec.instant('y')
    assert [e for e in rec.snapshot() if e['ph'] != 'M'] == []


def test_tracer_feeds_recorder():
    """The stage table and the span timeline are two views over the SAME
    instrumentation sites: a tracer with a recorder attached both
    aggregates and appends span events, with attrs flowing through."""
    rec = SpanRecorder(capacity=100)
    t = Tracer(enabled=True, recorder=rec)
    with t.stage('model', video='v.mp4'):
        pass
    t.add('decode', 0.25, video='w.mp4')
    rep = t.report()
    assert rep['model']['count'] == 1 and rep['decode']['count'] == 1
    spans = {e['name']: e for e in rec.snapshot() if e['ph'] == 'X'}
    assert spans['model']['args']['video'] == 'v.mp4'
    assert spans['decode']['args']['video'] == 'w.mp4'
    assert spans['decode']['dur'] == pytest.approx(0.25e6, rel=1e-3)


def test_null_tracer_never_records():
    from video_features_tpu.utils.tracing import NULL_TRACER
    with NULL_TRACER.stage('x', video='v'):
        pass
    assert NULL_TRACER.report() == {}


# -- trace_view validation ---------------------------------------------------

def test_trace_view_rejects_violations(tmp_path):
    from tools.trace_view import main as trace_view_main
    bad = {'traceEvents': [
        {'name': 'a', 'ph': 'X', 'ts': 5.0, 'dur': 1.0, 'pid': 1, 'tid': 1},
        {'name': 'b', 'ph': 'X', 'ts': 2.0, 'dur': -1.0, 'pid': 1, 'tid': 1},
        {'name': 'c', 'ph': 'E', 'ts': 9.0, 'pid': 1, 'tid': 1},
        {'ph': 'X', 'ts': 1.0, 'pid': 1, 'tid': 1},
    ]}
    p = tmp_path / 'bad.json'
    p.write_text(json.dumps(bad))
    assert trace_view_main([str(p)]) == 1
    assert trace_view_main([str(tmp_path / 'missing.json')]) == 2


def test_trace_view_accepts_b_e_pairs(tmp_path):
    from tools.trace_view import main as trace_view_main
    good = {'traceEvents': [
        {'name': 'outer', 'ph': 'B', 'ts': 0.0, 'pid': 1, 'tid': 1},
        {'name': 'inner', 'ph': 'B', 'ts': 1.0, 'pid': 1, 'tid': 1},
        {'name': 'inner', 'ph': 'E', 'ts': 2.0, 'pid': 1, 'tid': 1},
        {'name': 'outer', 'ph': 'E', 'ts': 3.0, 'pid': 1, 'tid': 1},
    ]}
    p = tmp_path / 'good.json'
    p.write_text(json.dumps(good))
    assert trace_view_main([str(p), '--quiet']) == 0


# -- metrics registry + Prometheus exposition --------------------------------

_LABEL_VALUE = r'"(?:[^"\\]|\\.)*"'   # escaped \" \\ \n allowed inside
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*=' + _LABEL_VALUE +
    r'(,[a-zA-Z_][a-zA-Z0-9_]*=' + _LABEL_VALUE + r')*\})? '
    r'(NaN|[+-]?Inf|[-+0-9.eE]+)$')


def assert_valid_prometheus(text: str) -> None:
    """Line-grammar check for the text exposition format 0.0.4."""
    assert text.endswith('\n')
    seen_type = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith('# HELP ') or line.startswith('# TYPE '):
            parts = line.split(' ', 3)
            assert len(parts) >= 4 or parts[1] == 'TYPE', line
            if parts[1] == 'TYPE':
                seen_type[parts[2]] = parts[3]
            continue
        assert _SAMPLE_RE.match(line), f'bad sample line: {line!r}'
    assert seen_type, 'no TYPE lines'


def test_registry_counter_gauge_histogram_render():
    reg = MetricsRegistry()
    reg.counter('vft_requests_total', 'requests',
                labels={'outcome': 'completed'}).inc(3)
    reg.counter('vft_requests_total',
                labels={'outcome': 'failed'}).inc()
    reg.gauge('vft_queue_depth', 'queued videos').set(7)
    h = reg.histogram('vft_latency_seconds', 'latency',
                      buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    text = reg.render()
    assert_valid_prometheus(text)
    assert 'vft_requests_total{outcome="completed"} 3' in text
    assert 'vft_queue_depth 7' in text
    # cumulative buckets: 0.1→1, 1.0→2, 10→3, +Inf→4
    assert 'vft_latency_seconds_bucket{le="0.1"} 1' in text
    assert 'vft_latency_seconds_bucket{le="1"} 2' in text
    assert 'vft_latency_seconds_bucket{le="10"} 3' in text
    assert 'vft_latency_seconds_bucket{le="+Inf"} 4' in text
    assert 'vft_latency_seconds_count 4' in text
    assert 'vft_latency_seconds_sum 55.55' in text
    # re-registration returns the same series
    assert reg.gauge('vft_queue_depth').value == 7


def test_registry_rejects_type_conflicts_and_negative_inc():
    reg = MetricsRegistry()
    reg.counter('x_total')
    with pytest.raises(ValueError):
        reg.gauge('x_total')
    with pytest.raises(ValueError):
        reg.counter('y_total').inc(-1)


def test_prometheus_escaping_label_values_and_help():
    """Exposition-format escaping: label values escape backslash,
    double-quote, and newline; HELP text escapes backslash and newline
    (but NOT quotes — the 0.0.4 rules differ). Host labels injected by
    the fleet aggregator carry arbitrary operator strings, so a hostile
    value must not tear the line grammar."""
    reg = MetricsRegistry()
    reg.gauge('vft_up', 'backend "up"\nby host (C:\\fleet)',
              labels={'host': 'bad"host\\with\nnewline'}).set(1)
    text = reg.render()
    assert ('vft_up{host="bad\\"host\\\\with\\nnewline"} 1'
            in text.splitlines())
    # HELP: backslash and newline escaped, the quote left alone
    assert ('# HELP vft_up backend "up"\\nby host (C:\\\\fleet)'
            in text.splitlines())
    # no raw newline survived into the body of any line
    for line in text.splitlines():
        assert '\n' not in line
    assert_valid_prometheus(text)


def test_histogram_default_buckets_cover_latency_range():
    h = Histogram()
    assert h.buckets == tuple(sorted(DEFAULT_BUCKETS))
    h.observe(0.0)
    assert h.snapshot()['buckets'][0][1] == 1


def test_prometheus_from_serve_doc():
    """The serve metrics document renders to valid Prometheus text with
    the queue depth, pool hit rate, cache hits, and latency histogram
    the acceptance criteria name."""
    from video_features_tpu.obs.metrics import MetricsRegistry
    from video_features_tpu.serve import metrics as metrics_mod

    reg = MetricsRegistry()
    stats = metrics_mod.RequestStats(registry=reg)
    stats.bump('submitted')
    stats.bump('completed')
    stats.observe_latency(0.2)
    doc = metrics_mod.build_metrics(
        started_at=0.0, queue_depth=3, queue_capacity=64, draining=False,
        pool_stats={'size': 1, 'capacity': 4, 'hits': 5, 'misses': 1,
                    'hit_rate': 5 / 6, 'evictions': 0,
                    'builds_compiled': 1, 'builds_loaded': 0},
        request_stats=stats,
        stage_reports={'i3d': {'model': {
            'count': 4, 'total_s': 2.0, 'mean_s': 0.5, 'max_s': 0.9,
            'first_s': 0.9, 'occupancy': 0.75, 'occ_valid': 12,
            'occ_capacity': 16}}},
        cache_stats={'caches': 1, 'entries': 2, 'bytes': 10, 'hits': 7,
                     'misses': 3, 'hit_rate': 0.7, 'puts': 2,
                     'evictions': 0, 'corrupt_evicted': 0,
                     'bytes_saved': 123})
    text = metrics_mod.prometheus_text(doc, reg)
    assert_valid_prometheus(text)
    for needle in ('vft_serve_queue_depth 3',
                   'vft_warm_pool_hit_rate',
                   'vft_cache_hits 7',
                   'vft_serve_request_latency_seconds_bucket',
                   'vft_serve_requests_total{outcome="completed"} 1',
                   'vft_stage_seconds{stage="model"} 2',
                   'vft_stage_occupancy{stage="model"} 0.75'):
        assert needle in text, f'{needle!r} missing from:\n{text}'


# -- SLO burn-rate evaluation (obs/slo.py) -----------------------------------

def test_slo_burn_rate_trips_on_latency_spike():
    """Satellite/acceptance pin: an injected latency spike drives the
    burn rate over the 14.4x threshold in BOTH windows, fires the
    alert (gauges + alerts_total + WARNING event), and a recovery
    phase resolves it WITHOUT another FIRING transition."""
    from video_features_tpu.obs.events import event_counts
    from video_features_tpu.obs.slo import SloEvaluator

    clock = {'t': 1000.0}
    reg = MetricsRegistry()
    slo = SloEvaluator(reg, latency_p99_s=1.0,
                       clock=lambda: clock['t'])
    h = reg.histogram('vft_serve_request_latency_seconds')
    warn0 = event_counts().get(('WARNING', 'slo'), 0)

    slo.tick()                               # baseline sample
    for _ in range(100):
        h.observe(0.01)                      # clean traffic
    clock['t'] += 30
    doc = slo.tick()
    assert doc['enabled'] is True
    assert doc['alerts'] == {'latency_p99': False}
    assert all(v == 0.0 for v in doc['burn_rates']['latency'].values())

    for _ in range(50):
        h.observe(5.0)                       # the spike: 50 over 1.0s
    clock['t'] += 30
    doc = slo.tick()
    # 50/150 over threshold → frac 1/3 → burn ~33x against the 1%
    # budget, in both windows (both baselines predate the spike)
    assert doc['alerts'] == {'latency_p99': True}
    assert doc['alerts_firing'] == 1
    assert doc['alerts_total'] == 1
    for burn in doc['burn_rates']['latency'].values():
        assert burn > 14.4
    assert event_counts().get(('WARNING', 'slo'), 0) == warn0 + 1
    text = reg.render()
    assert_valid_prometheus(text)
    assert 'vft_slo_latency_burn_rate{window="5m"}' in text
    assert 'vft_slo_alert{slo="latency_p99"} 1' in text
    assert 'vft_slo_latency_threshold_seconds 1' in text

    # recovery: enough clean traffic that the 5m window's baseline
    # moves past the spike → short-window burn drops → alert resolves
    for _ in range(2000):
        h.observe(0.01)
    clock['t'] += 400
    doc = slo.tick()
    assert doc['alerts'] == {'latency_p99': False}
    assert doc['alerts_firing'] == 0
    assert doc['alerts_total'] == 1          # FIRING transitions only
    assert 'vft_slo_alert{slo="latency_p99"} 0' in reg.render()


def test_slo_availability_burn_rate():
    """The availability objective burns on the failed-request fraction:
    10% failures against a 99.9% target is a 100x burn."""
    from video_features_tpu.obs.slo import SloEvaluator

    clock = {'t': 0.0}
    reg = MetricsRegistry()
    slo = SloEvaluator(reg, availability=0.999,
                       clock=lambda: clock['t'])
    slo.tick()
    reg.counter('vft_serve_requests_total',
                labels={'outcome': 'completed'}).inc(90)
    reg.counter('vft_serve_requests_total',
                labels={'outcome': 'failed'}).inc(10)
    clock['t'] += 60
    doc = slo.tick()
    for burn in doc['burn_rates']['availability'].values():
        assert burn == pytest.approx(100.0)
    assert doc['alerts'] == {'availability': True}
    assert 'vft_slo_availability_burn_rate{window="1h"}' in reg.render()


def test_slo_evaluator_rejects_bad_objectives():
    from video_features_tpu.obs.slo import SloEvaluator, disabled_stats
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        SloEvaluator(reg)                    # no objective at all
    with pytest.raises(ValueError):
        SloEvaluator(reg, latency_p99_s=0.0)
    with pytest.raises(ValueError):
        SloEvaluator(reg, availability=1.5)
    # the disabled shape carries the same keys as a live evaluation
    live = SloEvaluator(reg, latency_p99_s=1.0, clock=lambda: 0.0).tick()
    assert set(disabled_stats()) <= set(live)


# -- structured event log ----------------------------------------------------

def _make_stub(tmp_path, on_extraction, fail=True):
    from video_features_tpu.extract.base import BaseExtractor

    class Stub(BaseExtractor):
        output_feat_keys = ['rgb']

        def extract(self, video_path):
            if fail:
                raise RuntimeError('decode exploded')
            return {'rgb': np.ones((2, 3), np.float32)}

    return Stub('stub', on_extraction, str(tmp_path / 'tmp'),
                str(tmp_path / 'out'), keep_tmp_files=False, device='cpu')


def test_error_log_keeps_print_mode_stdout_clean(tmp_path, capsys, caplog):
    """The fault-isolation error report must never interleave with the
    feature stream: stdout stays byte-clean, the structured record (video
    path + traceback) lands on the logging channel → stderr."""
    ex = _make_stub(tmp_path, 'print')
    with caplog.at_level(logging.WARNING, logger='video_features_tpu'):
        ex._extract('/videos/bad.mp4')          # must not raise
    captured = capsys.readouterr()
    assert captured.out == ''                   # byte-clean feature stream
    assert 'bad.mp4' in captured.err
    assert 'RuntimeError' in captured.err       # full traceback, stderr
    rec = next(r for r in caplog.records if getattr(r, 'video', None))
    assert rec.levelno == logging.WARNING
    assert rec.video == '/videos/bad.mp4'
    assert rec.exc_info is not None


def test_packed_device_step_error_goes_to_logger(tmp_path, capsys, caplog):
    """parallel/packing.py's device-step fault isolation reports through
    the same structured channel — batch videos named, stdout untouched."""
    from video_features_tpu.obs.events import log_batch_error
    with caplog.at_level(logging.WARNING, logger='video_features_tpu'):
        try:
            raise RuntimeError('geometry will not compile')
        except RuntimeError:
            log_batch_error(['a.mp4', 'b.mp4'], valid=3, batch=4)
    captured = capsys.readouterr()
    assert captured.out == ''
    assert 'a.mp4' in captured.err and 'geometry will not compile' in captured.err
    rec = next(r for r in caplog.records if getattr(r, 'videos', None))
    assert rec.valid == 3 and rec.batch == 4


# -- the packed CLI run: trace + manifest end to end -------------------------

@pytest.fixture(scope='module')
def obs_worklist(tmp_path_factory):
    d = tmp_path_factory.mktemp('obsvids')
    return [str(_write_clip(d / f'v{i}.mp4', n, seed=10 + i))
            for i, n in enumerate((6, 9))]


def test_packed_cli_trace_out_covers_every_video(obs_worklist, tmp_path,
                                                 capsys):
    """Acceptance: one packed CLI run with trace_out yields a Chrome
    trace whose spans cover decode/pack/device-step/save for EVERY video
    in the worklist, and tools/trace_view.py validates it."""
    from tools.trace_view import main as trace_view_main
    from video_features_tpu.cli import main

    trace = tmp_path / 'trace.json'
    manifest = tmp_path / 'manifest.json'
    rc = main([
        'feature_type=resnet', 'model_name=resnet18', 'device=cpu',
        f'video_paths=[{",".join(obs_worklist)}]',
        'pack_across_videos=true', 'batch_size=4',
        'allow_random_weights=true', 'on_extraction=save_numpy',
        f'output_path={tmp_path / "out"}', f'tmp_path={tmp_path / "tmp"}',
        f'trace_out={trace}', f'manifest_out={manifest}'])
    assert rc == 0
    capsys.readouterr()

    doc = json.loads(trace.read_text())
    events = doc['traceEvents']
    assert validate_events(events) == []
    spans = [e for e in events if e['ph'] == 'X']
    by_name = {}
    for e in spans:
        by_name.setdefault(e['name'], []).append(e)
    for path in obs_worklist:
        assert any(e['args'].get('video') == path
                   for e in by_name.get('decode+preprocess', [])
                   if 'args' in e), f'no decode span for {path}'
        assert any(path in e['args'].get('videos', [])
                   for e in by_name.get('pack', []) if 'args' in e), \
            f'no pack span for {path}'
        assert any(path in e['args'].get('videos', [])
                   for e in by_name.get('model', []) if 'args' in e), \
            f'no device-step span for {path}'
        # the deferred readback is its own stage with the same
        # provenance/occupancy attrs — the timeline must show model
        # (dispatch+compute) and d2h (readback) as DISTINCT spans
        assert any(path in e['args'].get('videos', [])
                   and e['args'].get('capacity')
                   for e in by_name.get('d2h', []) if 'args' in e), \
            f'no d2h span for {path}'
        assert any(e['args'].get('video') == path
                   for e in by_name.get('save', []) if 'args' in e), \
            f'no save span for {path}'
    # no time lost or double-counted: every dispatched batch has exactly
    # one model span and one d2h span
    assert len(by_name.get('d2h', [])) == len(by_name.get('model', []))
    # every model/d2h span names the precision lane that computed it
    # (compute_dtype — the bf16 fast lane's post-hoc attribution hook);
    # this run is the default lane, so every span says float32
    for name in ('model', 'd2h'):
        assert all(e['args'].get('compute_dtype') == 'float32'
                   for e in by_name.get(name, []) if 'args' in e), name
    # vft-flight: a packed CLI run is ONE request — every trace-tagged
    # span shares the run's single trace_id (per-video child span_ids
    # under it), so --trace-id filtering works on CLI traces too
    run_tids = {e['args']['trace_id'] for e in spans
                if 'args' in e and 'trace_id' in e['args']}
    assert len(run_tids) == 1, run_tids
    assert all('span_id' in e['args'] for e in spans
               if 'args' in e and 'trace_id' in e['args'])
    # the validator tool accepts the real artifact (tier-1 exercise)
    assert trace_view_main([str(trace), '--quiet']) == 0
    capsys.readouterr()

    # -- run manifest: fingerprints + outcomes + stages ----------------------
    man = json.loads(manifest.read_text())
    assert man['schema'] == 'video_features_tpu.run_manifest/1'
    assert man['fingerprints']['run']
    assert man['fingerprints']['config']
    assert set(man['videos']) == set(obs_worklist)
    assert all(v['outcome'] == 'saved' for v in man['videos'].values())
    assert man['outcomes'] == {'saved': len(obs_worklist)}
    assert 'model' in man['stages'] and man['stages']['model']['count'] > 0
    assert man['config']['feature_type'] == 'resnet'
    # outputs written normally alongside the telemetry
    from video_features_tpu.utils.output import make_path
    for p in obs_worklist:
        arr = np.load(make_path(str(tmp_path / 'out' / 'resnet' /
                                    'resnet18'), p, 'resnet', '.npy'))
        assert arr.shape[1] == 512


def test_one_shot_cli_trace_and_manifest(obs_worklist, tmp_path, capsys):
    """The per-video loop records the same telemetry: a video span per
    clip plus the stage spans, and a manifest with per-video outcomes."""
    from video_features_tpu.cli import main

    trace = tmp_path / 'trace.json'
    manifest = tmp_path / 'manifest.json'
    rc = main([
        'feature_type=resnet', 'model_name=resnet18', 'device=cpu',
        f'video_paths=[{",".join(obs_worklist)}]', 'batch_size=4',
        'allow_random_weights=true', 'on_extraction=save_numpy',
        f'output_path={tmp_path / "out"}', f'tmp_path={tmp_path / "tmp"}',
        f'trace_out={trace}', f'manifest_out={manifest}'])
    assert rc == 0
    capsys.readouterr()
    events = json.loads(trace.read_text())['traceEvents']
    assert validate_events(events) == []
    vids = [e for e in events if e['ph'] == 'X' and e['name'] == 'video']
    assert {e['args']['video'] for e in vids} == set(obs_worklist)
    assert all(e['args']['outcome'] == 'saved' for e in vids)
    man = json.loads(manifest.read_text())
    assert man['outcomes'] == {'saved': len(obs_worklist)}
    assert man['stages']                       # folded across the reset


# -- serve: Prometheus endpoint + file mirror --------------------------------

def test_serve_prometheus_endpoint_and_mirror(tmp_path):
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer

    metrics_path = str(tmp_path / 'metrics.json')
    server = ExtractionServer(metrics_path=metrics_path).start()
    try:
        client = ServeClient(port=server.port)
        text = client.metrics_prom()
        assert_valid_prometheus(text)
        for needle in ('vft_serve_queue_depth 0',
                       'vft_serve_queue_capacity 64',
                       'vft_warm_pool_hit_rate',
                       'vft_cache_hits',
                       'vft_inflight_batches 0',
                       'vft_serve_request_latency_seconds_count',
                       'vft_serve_uptime_seconds'):
            assert needle in text, f'{needle!r} missing from:\n{text}'
    finally:
        server.drain(wait=True, grace_s=30)
    # the atomic mirror wrote BOTH formats on drain
    doc = json.loads(Path(metrics_path).read_text())
    assert 'queue' in doc
    prom = Path(metrics_path + '.prom').read_text()
    assert_valid_prometheus(prom)
    assert 'vft_serve_draining 1' in prom


def test_serve_drain_exports_merged_trace(obs_worklist, tmp_path):
    """A server-wide trace_out base override stitches EVERY worker's
    recorder into one Chrome trace at drain — spans from a real request
    (decode/pack/model/save, request ids) survive the merge and the
    export validates. vft-flight acceptance rides the same request: the
    caller's traceparent is adopted, the live ``trace`` command
    assembles admission/pack/model/d2h/save spans sharing that one
    trace_id (farm decode spans are exercised in tests/test_farm.py),
    and the ids survive into the merged export."""
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer

    trace = tmp_path / 'serve_trace.json'
    server = ExtractionServer(base_overrides={
        'device': 'cpu', 'model_name': 'resnet18', 'batch_size': 4,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'tmp_path': str(tmp_path / 'serve_tmp'),
        'output_path': str(tmp_path / 'serve_out'),
        'trace_out': str(trace),
    }, queue_depth=8, pool_size=2).start()
    caller_trace = 'c0ffee5e1f00d5c0ffee5e1f00d5c0ff'
    try:
        client = ServeClient(port=server.port)
        rid = client.submit(
            'resnet', [obs_worklist[0]],
            traceparent=f'00-{caller_trace}-00f067aa0ba902b7-01')
        st = client.wait(rid, timeout_s=300)
        assert st['state'] == 'done', st
        # the caller's trace id was ADOPTED, not re-minted
        assert st['trace_id'] == caller_trace, st
        # the live /trace assembly: one request's spans, one trace_id,
        # covering admission + pack + model + d2h + save
        tr = client.trace(rid)
        assert tr['trace_id'] == caller_trace
        names = {e['name'] for e in tr['events']}
        for stage in ('admission', 'pack', 'model', 'd2h', 'save'):
            assert stage in names, (stage, sorted(names))
        for e in tr['events']:
            args = e.get('args') or {}
            assert (args.get('trace_id') == caller_trace
                    or caller_trace in (args.get('trace_ids') or ())
                    or args.get('request_id') == rid), e
        # ts-sorted (the route contract)
        ts = [e['ts'] for e in tr['events']]
        assert ts == sorted(ts)
        # ANOTHER request must not leak into this one's trace
        rid2 = client.submit('resnet', [obs_worklist[1]])
        client.wait(rid2, timeout_s=300)
        tr2 = client.trace(rid2)
        assert tr2['trace_id'] != caller_trace
        assert all((e.get('args') or {}).get('video') != obs_worklist[0]
                   for e in tr2['events'])
    finally:
        server.drain(wait=True, grace_s=120)

    doc = json.loads(trace.read_text())
    events = doc['traceEvents']
    assert validate_events(events) == []
    assert doc['otherData']['recorders_merged'] >= 1
    spans = [e for e in events if e['ph'] == 'X' and 'args' in e]
    assert any(e['name'] == 'model' for e in spans)
    assert any(e['name'] == 'save'
               and e['args'].get('video') == obs_worklist[0]
               and e['args'].get('request_id') == rid for e in spans)
    # the trace ids survive the merged export too
    assert any(e['args'].get('trace_id') == caller_trace for e in spans)


# -- schema contracts --------------------------------------------------------

TRACER_RECORD_KEYS = {'count', 'total_s', 'mean_s', 'max_s', 'first_s',
                      'ramp', 'occupancy', 'occ_valid', 'occ_capacity',
                      # mesh-sharded batches: per-device slot ledger
                      'occ_device'}
METRICS_DOC_KEYS = {'uptime_s', 'queue', 'warm_pool', 'cache', 'farm',
                    'requests', 'latency', 'stages', 'stages_merged',
                    'inflight_batches',
                    # persistent executable store (aot/): merged store
                    # counters + programs_loaded/programs_compiled —
                    # the zero-cold-start audit pair (all-zero without
                    # aot_enabled)
                    'aot',
                    # sharded feature index (index/): rows/shards/
                    # ingest-lag + query counters, {'enabled': False}
                    # without index_enabled
                    'index',
                    # network front door (ingress/): per-tenant view,
                    # {'enabled': False, ...} on loopback-only servers
                    'ingress',
                    # vft-flight: structured-event counts (the
                    # vft_events_total mirror's source), span-ring view
                    # (recorders + events_dropped), and the stall
                    # watchdog's progress ledger ({'enabled': False}
                    # without watchdog_stall_s)
                    'events', 'trace', 'watchdog',
                    # vft-scope: SLO burn-rate evaluation (obs/slo.py),
                    # {'enabled': False, ...} without slo_* knobs
                    'slo'}
TRACE_EVENT_KEYS = {'name', 'ph', 'ts', 'dur', 'pid', 'tid', 'args', 's'}
MANIFEST_KEYS = {'schema', 'version', 'started_at_unix_s', 'wall_s',
                 'config', 'fingerprints', 'videos', 'outcomes', 'stages',
                 'compile', 'executables', 'farm', 'mesh', 'ingress',
                 'programs_lock', 'aot', 'index', 'slo',
                 # in-process decode lanes of packed runs
                 # (extract/streaming.py): the lane plan + per-lane
                 # counters, {} on farm-backed and per-video runs
                 'decode',
                 # which path each call site with a choice compiled to
                 # ({'causal_attention': 'kernel' | 'xla'} or {'retention':
                 # 'kernel' | 'state', 'retention_chunk': n} on lm
                 # runs), {} where a family has no such choice
                 'kernels'}


CANONICAL_STAGES = {'decode', 'decode+preprocess', 'audio_dsp',
                    'queue_idle', 'pack', 'h2d', 'input_wait', 'model',
                    'device_wait', 'd2h', 'save', 'cache_lookup',
                    'cache_publish',
                    # PR 27, the lm family: its tokeniser's span and the
                    # routing counters of its expert layers
                    'tokenise', 'moe_route', 'moe_held',
                    # PR 31, the lm family's retention trunk: positions
                    # mixed through the carried state
                    'retention_scan',
                    # PR 32: of those, through the state-product kernels
                    'retention_kernel',
                    # PR 33, the lm family's hybrid trunk: the expert
                    # walk's held assignments over the rows it computed
                    'moe_walk',
                    # the packed path's batches built in a recycled host
                    # buffer
                    'pack_recycled',
                    # the hybrid trunk's Mamba-2 mixers: positions through
                    # the SSD scan, and chunks of it through the kernel
                    'ssd_scan', 'ssd_kernel',
                    # the sparse latent trunk's indexer: query blocks scored
                    # through its kernel, and its rows whose threshold the
                    # tie rule settled
                    'index_kernel', 'topk_ties'}


def test_stage_vocabulary_contract():
    """Pin the canonical stage names (utils.tracing.STAGES): dashboards
    key vft_stage_* families and bench stage_reports on them — renaming
    or dropping one (e.g. folding d2h back into model) must be an
    intentional, test-visible event."""
    from video_features_tpu.utils.tracing import STAGES
    assert set(STAGES) == CANONICAL_STAGES
    assert 'model' in STAGES and 'd2h' in STAGES    # split, not aliased


def test_merge_reports_keeps_model_and_d2h_distinct():
    """Fleet-wide merges (serve metrics, retired-worker history) must
    keep the dispatch and readback stages separate — their shares sum to
    the old all-in 'model' share, so folding them would re-launder
    readback into compute."""
    from video_features_tpu.utils.tracing import merge_reports
    a = {'model': {'count': 2, 'total_s': 1.0, 'max_s': 0.6,
                   'first_s': 0.6},
         'd2h': {'count': 2, 'total_s': 0.5, 'max_s': 0.3, 'first_s': 0.3,
                 'occ_valid': 6, 'occ_capacity': 8}}
    b = {'model': {'count': 1, 'total_s': 0.4, 'max_s': 0.4,
                   'first_s': 0.4},
         'd2h': {'count': 1, 'total_s': 0.1, 'max_s': 0.1, 'first_s': 0.1,
                 'occ_valid': 4, 'occ_capacity': 4}}
    merged = merge_reports([a, b])
    assert merged['model']['total_s'] == pytest.approx(1.4)
    assert merged['d2h']['total_s'] == pytest.approx(0.6)
    assert merged['d2h']['occupancy'] == pytest.approx(10 / 12)


def test_schema_contract_key_sets(tmp_path):
    """Pin the three export schemas: a key rename is an intentional,
    test-visible event — scrapers and dashboards depend on these."""
    # tracer report records
    t = Tracer()
    with t.stage('a'):
        pass
    with t.stage('a'):
        pass
    t.add_occupancy('a', 3, 4)
    rec = t.report()['a']
    assert set(rec) <= TRACER_RECORD_KEYS
    assert {'count', 'total_s', 'mean_s', 'max_s', 'first_s'} <= set(rec)

    # serve metrics document
    from video_features_tpu.serve import metrics as metrics_mod
    doc = metrics_mod.build_metrics(
        started_at=0.0, queue_depth=0, queue_capacity=1, draining=False,
        pool_stats={}, request_stats=metrics_mod.RequestStats(),
        stage_reports={})
    assert set(doc) == METRICS_DOC_KEYS
    assert set(doc['requests']) == {'submitted', 'completed', 'failed',
                                    'rejected', 'expired_videos',
                                    'cached_videos'}

    # trace events
    sr = SpanRecorder(capacity=8)
    sr.span('s', 0.0, 1.0, video='v')
    sr.instant('i')
    for ev in sr.snapshot():
        assert set(ev) <= TRACE_EVENT_KEYS, ev

    # run manifest
    from video_features_tpu.obs.manifest import RunManifest
    man = RunManifest({'feature_type': 'resnet'}).document()
    assert set(man) == MANIFEST_KEYS


# -- vft-flight: trace context ------------------------------------------------


def test_trace_context_mint_parse_roundtrip():
    from video_features_tpu.obs.context import (
        TraceContext, accept_traceparent, mint, parse_traceparent,
    )
    ctx = mint()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    # wire form round-trips: same trace, NEW span per hop
    hop = parse_traceparent(ctx.traceparent())
    assert hop.trace_id == ctx.trace_id
    assert hop.span_id != ctx.span_id
    # children stay under the parent's trace
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.span_id != ctx.span_id
    assert set(ctx.attrs()) == {'trace_id', 'span_id'}
    # malformed / absent / all-zero headers degrade to None (and
    # accept_traceparent to a fresh mint), never to garbage ids
    for bad in (None, '', 'not-a-traceparent',
                '00-' + '0' * 32 + '-00f067aa0ba902b7-01',
                '00-' + 'a' * 32 + '-' + '0' * 16 + '-01',
                'ff-' + 'a' * 32 + '-00f067aa0ba902b7-01',
                '00-a' * 20):
        assert parse_traceparent(bad) is None, bad
        assert isinstance(accept_traceparent(bad), TraceContext)
    # uppercase hex normalizes (the W3C header is case-insensitive)
    up = parse_traceparent('00-' + 'A' * 32 + '-00F067AA0BA902B7-01')
    assert up is not None and up.trace_id == 'a' * 32


def test_trace_attrs_helper_tolerates_legacy_tasks():
    from video_features_tpu.obs.context import mint, trace_attrs
    from video_features_tpu.parallel.packing import VideoTask
    assert trace_attrs(VideoTask('a.mp4')) == {}
    assert trace_attrs(object()) == {}
    ctx = mint()
    t = VideoTask('a.mp4', trace=ctx)
    assert trace_attrs(t) == ctx.attrs()


# -- vft-flight: spans bugfixes (bytes rendering, bounded snapshot) ----------


def test_jsonable_renders_bytes_ascii_safely_with_cap():
    from video_features_tpu.obs.spans import _jsonable
    assert _jsonable(b'hello') == 'hello'
    assert "b'" not in _jsonable(b'hello')        # the old str() bug
    # non-ASCII bytes escape instead of raising (ASCII-safe contract)
    out = _jsonable(b'\xff\x00ok')
    assert isinstance(out, str) and 'ok' in out
    out.encode('ascii')                            # must be pure ASCII
    # length cap: a stray frame buffer must not balloon the export
    big = _jsonable(b'x' * 10_000)
    assert len(big) < 1_000 and '(+' in big
    json.dumps({'v': _jsonable(b'\xff' * 300)})    # always JSON-safe


def test_snapshot_limit_bounds_events():
    rec = SpanRecorder(capacity=1000)
    for i in range(100):
        rec.span(f's{i}', float(i), float(i) + 0.5)
    full = [e for e in rec.snapshot() if e['ph'] == 'X']
    assert len(full) == 100
    tail = [e for e in rec.snapshot(limit=10) if e['ph'] == 'X']
    assert len(tail) == 10
    # MOST RECENT events, still ts-sorted, same origin semantics
    assert [e['name'] for e in tail] == [f's{i}' for i in range(90, 100)]
    assert validate_events(rec.snapshot(limit=10)) == []
    # limit >= len is the full snapshot
    assert len([e for e in rec.snapshot(limit=500)
                if e['ph'] == 'X']) == 100


def test_span_pid_tid_override_for_cross_process_spans():
    """Farm decode spans are recorded by the parent but MEASURED in the
    worker: pid/tid overrides put them in the worker's own lane."""
    rec = SpanRecorder(capacity=16)
    rec.span('decode', 1.0, 1.5, pid=4242, tid=7, video='v.mp4')
    rec.span('local', 2.0, 2.5)
    import os as _os
    by_name = {e['name']: e for e in rec.snapshot() if e['ph'] == 'X'}
    assert by_name['decode']['pid'] == 4242
    assert by_name['decode']['tid'] == 7
    assert by_name['local']['pid'] == _os.getpid()
    assert validate_events(rec.snapshot()) == []


# -- vft-flight: event counters + tail ---------------------------------------


def test_event_counts_and_tail_feed_metrics_and_blackbox(caplog):
    from video_features_tpu.obs.events import (
        event, event_counts, events_tail,
    )
    before = event_counts().get(('WARNING', 'testsub'), 0)
    with caplog.at_level(logging.WARNING, logger='video_features_tpu'):
        event(logging.WARNING, 'something odd', subsystem='testsub',
              video='v.mp4', request_id='r1')
    counts = event_counts()
    assert counts[('WARNING', 'testsub')] == before + 1
    tail = events_tail()
    rec = next(r for r in reversed(tail)
               if r.get('subsystem') == 'testsub')
    assert rec['level'] == 'WARNING' and rec['msg'] == 'something odd'
    assert rec['fields'] == {'video': 'v.mp4', 'request_id': 'r1'}
    # exc_info captures the traceback text for the black box
    with caplog.at_level(logging.WARNING, logger='video_features_tpu'):
        try:
            raise RuntimeError('boom for tail')
        except RuntimeError:
            event(logging.ERROR, 'it died', subsystem='testsub',
                  exc_info=True)
    rec = events_tail()[-1]
    assert 'boom for tail' in rec.get('exc', '')


def test_prometheus_mirrors_events_and_trace_dropped():
    """vft_events_total{level,subsystem} and
    vft_trace_events_dropped_total are COUNTERS mirrored by delta —
    repeated renders never double-count, and a recorder aging out of
    the bounded deque (sum dips) never decrements."""
    import logging as _logging

    from video_features_tpu.obs.events import event
    from video_features_tpu.obs.metrics import MetricsRegistry
    from video_features_tpu.serve import metrics as metrics_mod
    event(_logging.WARNING, 'mirror me', subsystem='mirrorsub')
    reg = MetricsRegistry()
    stats = metrics_mod.RequestStats(registry=reg)

    def render(dropped):
        doc = metrics_mod.build_metrics(
            started_at=0.0, queue_depth=0, queue_capacity=1,
            draining=False, pool_stats={}, request_stats=stats,
            stage_reports={},
            trace_stats={'recorders': 2, 'events_dropped': dropped})
        assert set(doc['trace']) == {'recorders', 'events_dropped'}
        assert doc['events']['total'] >= 1
        return metrics_mod.prometheus_text(doc, reg)

    text = render(7)
    assert_valid_prometheus(text)
    assert ('vft_events_total{level="WARNING",subsystem="mirrorsub"}'
            in text)
    assert 'vft_trace_events_dropped_total 7' in text
    # stable under re-render; a DIP (recorder eviction) never decrements
    assert 'vft_trace_events_dropped_total 7' in render(7)
    assert 'vft_trace_events_dropped_total 7' in render(3)
    assert 'vft_trace_events_dropped_total 9' in render(9)
    assert 'vft_watchdog_enabled 0' in text


# -- vft-flight: stall watchdog ----------------------------------------------


def _fake_clock(start=1000.0):
    state = {'t': start}

    def clock():
        return state['t']

    return clock, state


def test_watchdog_fires_on_stall_quiet_on_empty_queue():
    from video_features_tpu.obs.metrics import MetricsRegistry
    from video_features_tpu.obs.watchdog import StallWatchdog
    clock, state = _fake_clock()
    stalls = []
    reg = MetricsRegistry()
    wd = StallWatchdog(5.0, on_stall=stalls.append, registry=reg,
                       clock=clock)
    # idle-but-EMPTY: no pending work → silence forever
    wd.advance('w0', 'model')
    state['t'] += 1000
    assert wd.check() == []
    # pending work + advances → quiet
    wd.set_pending('w0', 3)
    wd.advance('w0', 'decode')
    state['t'] += 4.0
    wd.advance('w0', 'model')
    state['t'] += 4.0
    assert wd.check() == []
    # pending work + NO advance past the deadline → one trip, attributed
    # to the last stage that advanced
    state['t'] += 6.0
    fired = wd.check()
    assert len(fired) == 1 and fired[0]['worker'] == 'w0'
    assert fired[0]['stage'] == 'model' and fired[0]['pending'] == 3
    assert stalls == fired
    # a tripped worker does NOT re-trip until it advances again
    state['t'] += 100.0
    assert wd.check() == []
    wd.advance('w0', 'd2h')
    state['t'] += 6.0
    assert len(wd.check()) == 1
    assert wd.stalls_total == 2
    # the counter family carries the stage label
    text = reg.render()
    assert 'vft_watchdog_stalls_total{stage="model"} 1' in text
    assert 'vft_watchdog_stalls_total{stage="d2h"} 1' in text
    snap = wd.snapshot()
    assert snap['enabled'] and snap['stalls_total'] == 2
    assert snap['workers']['w0']['pending'] == 3


def test_watchdog_new_work_resets_clock_and_never_started_stage():
    from video_features_tpu.obs.watchdog import (
        STAGE_NOT_STARTED, StallWatchdog,
    )
    clock, state = _fake_clock()
    wd = StallWatchdog(5.0, clock=clock)
    wd.set_pending('w1', 1)
    state['t'] += 3.0
    wd.set_pending('w1', 0)          # drained before the deadline
    state['t'] += 100.0
    assert wd.check() == []          # long-idle, empty: quiet
    wd.set_pending('w1', 2)          # NEW work: full stall_s restarts
    state['t'] += 4.0
    assert wd.check() == []
    state['t'] += 2.0
    fired = wd.check()
    # queued work that never started attributes to 'admission'
    assert len(fired) == 1 and fired[0]['stage'] == STAGE_NOT_STARTED
    wd.forget('w1')
    assert wd.snapshot()['workers'] == {}


def test_watchdog_rides_tracer_progress_hook():
    """The ledger feeds off the SAME instrumentation sites as the stage
    table: a Tracer with a progress hook advances the ledger on every
    add/stage, with farm-worker attribution via the worker attr."""
    from video_features_tpu.obs.watchdog import StallWatchdog
    clock, state = _fake_clock()
    wd = StallWatchdog(5.0, clock=clock)
    t = Tracer(enabled=True)
    t.progress = lambda stage, worker=None: (
        wd.advance('lbl', stage),
        wd.advance(f'lbl/farm-w{worker}', stage)
        if worker is not None else None)
    with t.stage('model'):
        pass
    t.add('decode', 0.1, worker=3)
    snap = wd.snapshot()['workers']
    assert snap['lbl']['stage'] == 'decode'
    assert snap['lbl/farm-w3']['stage'] == 'decode'


# -- vft-flight: black box ---------------------------------------------------


def _make_blackbox(tmp_path, **kw):
    from video_features_tpu.obs.blackbox import BlackBox
    rec = SpanRecorder(capacity=64)
    rec.span('model', 1.0, 2.0, video='v.mp4')
    kw.setdefault('recorders', lambda: [rec])
    kw.setdefault('min_interval_s', 0.0)
    return BlackBox(str(tmp_path / 'postmortem'), **kw), rec


def test_blackbox_bundle_layout_and_validation(tmp_path):
    from video_features_tpu.obs.blackbox import validate_bundle
    from video_features_tpu.obs.events import event
    event(logging.WARNING, 'pre-crash breadcrumb', subsystem='obs')
    bb, _ = _make_blackbox(
        tmp_path,
        metrics_fn=lambda: {'queue': {'depth': 1}},
        prom_fn=lambda: 'vft_x 1\n',
        manifest_fn=lambda: {'schema': 'frag', 'videos': {}})
    bundle = bb.dump('worker_crash', label='resnet/resnet18')
    assert bundle is not None
    assert validate_bundle(bundle) == []
    meta = json.loads((Path(bundle) / 'meta.json').read_text())
    assert meta['reason'] == 'worker_crash'
    assert meta['extra']['label'] == 'resnet/resnet18'
    assert meta['sections'] == {'spans': True, 'events': True,
                                'metrics': True, 'manifest': True}
    spans_doc = json.loads((Path(bundle) / 'spans.json').read_text())
    assert validate_events(spans_doc['traceEvents']) == []
    assert any(e.get('name') == 'model'
               for e in spans_doc['traceEvents'])
    lines = (Path(bundle) / 'events.jsonl').read_text().splitlines()
    assert any('pre-crash breadcrumb' in ln for ln in lines)
    assert json.loads((Path(bundle) / 'metrics.json').read_text()
                      )['queue']['depth'] == 1
    assert (Path(bundle) / 'metrics.prom').read_text() == 'vft_x 1\n'
    # broken collectors degrade to missing sections, never to a raise
    bb2, _ = _make_blackbox(
        tmp_path / 'b2',
        metrics_fn=lambda: (_ for _ in ()).throw(RuntimeError('wedged')))
    bundle2 = bb2.dump('watchdog_stall')
    assert bundle2 is not None and validate_bundle(bundle2) == []
    meta2 = json.loads((Path(bundle2) / 'meta.json').read_text())
    assert meta2['sections']['metrics'] is False


def test_blackbox_gc_keeps_newest_under_cap_and_rate_limits(tmp_path):
    bb, rec = _make_blackbox(tmp_path)
    # every bundle carries the same ~payload; cap to roughly 2 bundles
    first = bb.dump('r0')
    size = sum(f.stat().st_size
               for f in Path(first).rglob('*') if f.is_file())
    bb.max_bytes = int(size * 2.5)
    for i in range(1, 6):
        assert bb.dump(f'r{i}') is not None
    bundles = sorted(p.name for p in (tmp_path / 'postmortem').iterdir())
    total = sum(f.stat().st_size
                for f in (tmp_path / 'postmortem').rglob('*')
                if f.is_file())
    assert total <= bb.max_bytes
    assert any(b.endswith('-r5') for b in bundles)   # newest survives
    assert not any(b.endswith('-r0') for b in bundles)  # oldest GC'd
    # rate limit: back-to-back dumps collapse (r5 just fired)
    bb.min_interval_s = 60.0
    assert bb.dump('r6') is None
    assert bb.suppressed == 1
    bb._last_dump_t = 0.0            # interval elapsed → dumps resume
    assert bb.dump('r7') is not None


def test_serve_worker_crash_dumps_blackbox(tmp_path):
    """An induced serve-worker crash walks the REAL crash path: the
    entry retires, and a post-mortem bundle appears (after the recovery,
    never instead of it)."""
    from video_features_tpu.obs.blackbox import validate_bundle
    from video_features_tpu.serve.server import ExtractionServer, _Worker
    from video_features_tpu.utils.tracing import NULL_TRACER

    pm = tmp_path / 'postmortem'
    server = ExtractionServer(base_overrides={
        'postmortem_dir': str(pm),
        'watchdog_stall_s': 3600.0,      # armed, but must stay quiet
    })
    assert server.blackbox is not None and server.watchdog is not None
    try:
        class BoomEx:
            trace_out = None
            tracer = NULL_TRACER

            def extract_packed(self, feed, **kw):
                raise RuntimeError('scheduler-level boom')

            def finish_obs(self, export_trace=True):
                pass

        w = _Worker(server, key=('boom',), label='boom', extractor=BoomEx(),
                    idle_flush_s=0.01)
        w.start()
        w.thread.join(30)
        assert not w.thread.is_alive() and w.crashed
        bundles = list(pm.iterdir())
        assert len(bundles) == 1
        assert validate_bundle(str(bundles[0])) == []
        meta = json.loads((bundles[0] / 'meta.json').read_text())
        assert meta['reason'] == 'serve_worker_crash'
        assert meta['extra']['label'] == 'boom'
        # the armed-but-quiet watchdog ledger rides along in the bundle
        assert meta['extra']['watchdog']['enabled'] is True
        # the metrics document names the watchdog + events + trace view
        doc = server.metrics()
        assert doc['watchdog']['enabled'] is True
        assert doc['watchdog']['stalls_total'] == 0
        prom = server._prometheus(doc)
        assert 'vft_watchdog_enabled 1' in prom
        assert 'vft_events_total' in prom
    finally:
        server.drain(wait=True, grace_s=30)


# -- vft-flight: trace_view upgrades -----------------------------------------


def _flight_trace(tmp_path):
    """A two-trace document: trace A's chain (ingress→model overlapped
    by d2h), trace B a lone span, plus shared-batch trace_ids."""
    tid_a, tid_b = 'a' * 32, 'b' * 32
    events = [
        {'name': 'ingress', 'ph': 'X', 'ts': 0.0, 'dur': 100.0,
         'pid': 1, 'tid': 1,
         'args': {'trace_id': tid_a, 'span_id': '1' * 16}},
        {'name': 'model', 'ph': 'X', 'ts': 120.0, 'dur': 200.0,
         'pid': 1, 'tid': 1,
         'args': {'trace_ids': [tid_a, tid_b], 'videos': ['v']}},
        {'name': 'd2h', 'ph': 'X', 'ts': 200.0, 'dur': 60.0,
         'pid': 1, 'tid': 2,
         'args': {'trace_ids': [tid_a]}},      # overlaps model
        {'name': 'save', 'ph': 'X', 'ts': 340.0, 'dur': 50.0,
         'pid': 1, 'tid': 1,
         'args': {'trace_id': tid_a, 'span_id': '2' * 16}},
        {'name': 'other', 'ph': 'X', 'ts': 400.0, 'dur': 10.0,
         'pid': 1, 'tid': 1},
    ]
    p = tmp_path / 'flight.json'
    p.write_text(json.dumps({'traceEvents': events}))
    return p, tid_a, tid_b


def test_trace_view_trace_id_filter_and_critical_path(tmp_path, capsys):
    from tools.trace_view import critical_path, main as trace_view_main
    p, tid_a, tid_b = _flight_trace(tmp_path)
    assert trace_view_main([str(p)]) == 0
    out = capsys.readouterr().out
    # per-trace critical-path summaries appear for both traces
    assert f'trace {tid_a}:' in out and f'trace {tid_b}:' in out
    # filter: only trace A's events counted
    assert trace_view_main([str(p), '--trace-id', tid_a]) == 0
    out = capsys.readouterr().out
    assert '4/5 events' in out
    assert f'trace {tid_b}:' not in out
    # unknown id: valid document, empty filter, exit 0
    assert trace_view_main([str(p), '--trace-id', 'f' * 32]) == 0
    # critical path: ingress(100) + model(200) + save(50) — d2h overlaps
    # model and must NOT be double-counted into the chain
    events = json.loads(p.read_text())['traceEvents']
    spans_a = [e for e in events if (e.get('args') or {}).get('trace_id')
               == tid_a or tid_a in ((e.get('args') or {}
                                      ).get('trace_ids') or ())]
    total, chain = critical_path(spans_a)
    assert total == pytest.approx(350.0)
    assert [e['name'] for e in chain] == ['ingress', 'model', 'save']


def test_trace_view_rejects_trace_id_without_span_id(tmp_path, capsys):
    from tools.trace_view import main as trace_view_main
    bad = {'traceEvents': [
        {'name': 'x', 'ph': 'X', 'ts': 0.0, 'dur': 1.0, 'pid': 1,
         'tid': 1, 'args': {'trace_id': 'a' * 32}},   # no span_id
    ]}
    p = tmp_path / 'unpaired.json'
    p.write_text(json.dumps(bad))
    assert trace_view_main([str(p), '--quiet']) == 1
    assert 'trace_id without span_id' in capsys.readouterr().err
    # batch-level trace_ids (shared work) are exempt by design
    ok = {'traceEvents': [
        {'name': 'model', 'ph': 'X', 'ts': 0.0, 'dur': 1.0, 'pid': 1,
         'tid': 1, 'args': {'trace_ids': ['a' * 32]}},
    ]}
    p2 = tmp_path / 'paired.json'
    p2.write_text(json.dumps(ok))
    assert trace_view_main([str(p2), '--quiet']) == 0
