"""``readers/idle_by_span.py`` on synthetic traces and timelines, no chip: the
clock join by step ordinal, the blocking chain, and every refusal.

The scene (host seconds; the device's clock is ``OFFSET`` behind and counts
nanoseconds from a large base). One warm-up step before the trace, then
three steps of ``jit_p``; 2 and 3 run back to back, then the device idles
0.4 s while the host saves, waits for input and dispatches step 4::

    dispatch thread (tid 1), inside `video` [0.9, 2.5]:
      model#2 [1.0000,1.0010]  model#3 [1.0500,1.0510]
      device_wait#2 [1.0510,1.3001]  d2h [1.3001,1.3100]
      device_wait#3 [1.3100,1.6001]  d2h [1.6001,1.6100]  save [1.61,1.70]
      input_wait [1.70,1.99]  model#4 [1.9999,2.0010]
      device_wait#4 [2.0010,2.3001]
    producer thread (tid 2):
      decode+preprocess [1.65,1.80]  pack [1.80,1.90]  h2d [1.90,1.95]
    device: #2 [1.0001,1.3]  #3 [1.3,1.6]  #4 [2.0,2.3]   (host clock)
"""
import copy

import pytest

import loader

OFFSET = 3.25                      # host = device + OFFSET
BASE_NS = 5_000_000_000_000.0      # the device clock's zero is far away
PROGRAM = 'jit_p'
# the reader counts device time from the trace's first event, so the offset
# it brackets is that event's time on the host clock
T0_HOST = 1.0001


@pytest.fixture(scope='module')
def reader():
    return loader.load_module('readers', 'idle_by_span')


def span(name, a, b, tid=1, **args):
    e = {'name': name, 'ph': 'X', 'pid': 1, 'tid': tid,
         'ts': round(a * 1e6, 3), 'dur': round((b - a) * 1e6, 3)}
    if args:
        e['args'] = args
    return e


def step(n, disp, disp_end, wait_from, ready):
    return [span('model', disp, disp_end, step=n, program=PROGRAM, valid=8,
                 capacity=8),
            span('device_wait', wait_from, ready, step=n, program=PROGRAM)]


def module(a, b, name=f'{PROGRAM}(123456789)'):
    return (name, BASE_NS + (a - OFFSET) * 1e9, (b - a) * 1e9)


def scene():
    host = [{'name': 'thread_name', 'ph': 'M', 'ts': 0, 'pid': 1, 'tid': 1,
             'args': {'name': 'MainThread'}},
            span('video', 0.05, 0.25), span('video', 0.9, 2.5),
            span('d2h', 1.3001, 1.31), span('d2h', 1.6001, 1.61),
            span('save', 1.61, 1.70, video='a.mp4'),
            span('input_wait', 1.70, 1.99),
            span('decode+preprocess', 1.65, 1.80, tid=2),
            span('pack', 1.80, 1.90, tid=2), span('h2d', 1.90, 1.95, tid=2)]
    host += step(1, 0.10, 0.101, 0.101, 0.2001)            # warm-up
    host += step(2, 1.0, 1.001, 1.051, 1.3001)
    host += step(3, 1.05, 1.051, 1.31, 1.6001)
    host += step(4, 1.9999, 2.001, 2.001, 2.3001)
    modules = [module(1.0001, 1.3), module(1.3, 1.6), module(2.0, 2.3),
               module(1.6, 1.6001, 'jit_convert_element_type(7)')]
    ops = [('%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop', s,
            d * 0.9) for _, s, d in modules]
    trace = {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Modules', 'events': modules},
        {'name': 'XLA Ops', 'events': ops}]}]}
    return trace, host


def test_the_offset_is_recovered_inside_the_bracket(reader):
    trace, host = scene()
    got = reader.attribute(trace, host)
    assert 'refused' not in got
    assert got['steps'] == 3                 # the warm-up step is not one
    assert got['lo'] <= T0_HOST <= got['hi']
    assert got['lo'] == pytest.approx(T0_HOST - 1e-4, abs=1e-7)
    assert got['hi'] == pytest.approx(T0_HOST + 1e-4, abs=1e-7)
    assert got['trace_span_s'] == pytest.approx(1.2999, abs=1e-7)
    assert got['in_program_s'] == pytest.approx(0.1 * 0.9, abs=1e-6)


def test_the_chain_puts_each_part_of_the_gap_under_its_name(reader):
    trace, host = scene()
    seconds = reader.attribute(trace, host)['seconds']
    want = {'d2h': 1.61 - 1.6001,            # the dispatch thread's own
            'save': 0.09,
            'video': 1.9999 - 1.99,          # between input_wait and model
            'model': 2.0 - 1.9999,
            'decode+preprocess': 0.10,       # input_wait, decode covers
            'pack': 0.10, 'h2d': 0.05,       # input_wait, pack/h2d cover
            'unexplained': 1.99 - 1.95}      # input_wait, nothing covers
    # (but for a rounding sliver where a span ends as the gap begins)
    assert {n for n, s in seconds.items() if s > 1e-6} == set(want)
    for name, s in want.items():
        assert seconds[name] == pytest.approx(s, abs=2e-6), name
    # 0.3999 s between programs in all (the sliver program in it is not idle)
    assert sum(seconds.values()) == pytest.approx(0.3999, abs=1e-6)


def test_more_warm_up_steps_before_the_trace_change_nothing(reader):
    trace, host = scene()
    base = reader.attribute(trace, host)
    for e in host:                           # renumber: three more warm-ups
        if 'step' in (e.get('args') or {}):
            e['args']['step'] += 3
    host += step(1, 0.01, 0.011, 0.011, 0.02) + step(2, 0.02, 0.021, 0.021,
                                                     0.03)
    host += step(3, 0.03, 0.031, 0.031, 0.04)
    again = reader.attribute(trace, host)
    assert again['steps'] == 3 and again['seconds'] == base['seconds']


def drop_device_event(trace, host, k):
    del trace['planes'][0]['lines'][0]['events'][k]


def drop_host_step(trace, host, n):
    host[:] = [e for e in host if (e.get('args') or {}).get('step') != n]


def early_ready(trace, host, by_us=40e3):
    for e in host:
        if e['name'] == 'device_wait' and e['args']['step'] == 3:
            e['dur'] -= by_us                # seen ready before its end


def slow_dispatch(trace, host):
    for e in host:                           # every step starts 30 ms late
        if e['name'] == 'model':
            e['ts'] -= 30e3


def other_program(trace, host):
    for e in host:
        if (e.get('args') or {}).get('step') == 3:
            e['args']['program'] = 'jit_q'
    events = trace['planes'][0]['lines'][0]['events']
    events[1] = ('jit_q(5)',) + events[1][1:]
    events[1], events[2] = events[2], events[1]   # q's event sorts by start


@pytest.mark.parametrize('fault, why', [
    (lambda t, h: drop_device_event(t, h, 1), 'missing on the device'),
    (lambda t, h: drop_host_step(t, h, 3), 'missing on the host'),
    (lambda t, h: drop_host_step(t, h, 1) or drop_host_step(t, h, 2),
     '3 steps on the device, 2 on the host'),
    (early_ready, '-39.800 ms apart'),
    (slow_dispatch, '30.200 ms apart'),
    (lambda t, h: h.clear(), 'no model/device_wait pair'),
], ids=['device-step-missing', 'host-step-missing', 'fewer-host-steps',
        '40ms-conflict', '30ms-bracket', 'no-ordinals'])
def test_no_number_when_the_join_cannot_be_trusted(reader, fault, why):
    trace, host = scene()
    fault(trace, host)
    got = reader.attribute(trace, host)
    assert why in got.get('refused', ''), got


def test_a_small_conflict_of_the_bounds_is_the_joins_error(reader,
                                                           monkeypatch):
    """A step seen ready 0.5 ms before its device event ends: the bounds
    conflict by 0.3 ms (seen on the chip: 0.3 and 4.0 ms). That is an error of
    the join like a wide bracket is, not a refusal; the log says so."""
    trace, host = scene()
    early_ready(trace, host, by_us=500)
    got = reader.attribute(trace, host)
    assert got['lo'] - got['hi'] == pytest.approx(3e-4, abs=1e-7)
    assert got['seconds']['save'] == pytest.approx(0.09, abs=1e-5)
    monkeypatch.setattr(reader, '_newest_recorder',
                        lambda: FakeRecorder(host))
    logs = []
    assert reader.read(ctx_for(trace, ['save'], logs)) == \
        pytest.approx(4.5, abs=1e-3)
    assert 'the bounds CONFLICT by 300.0 us' in '\n'.join(logs)


def test_no_number_when_the_recorder_dropped_events(reader):
    trace, host = scene()
    assert 'dropped 3' in reader.attribute(trace, host, dropped=3)['refused']


def test_a_step_still_running_when_the_trace_began_is_a_missing_step(reader):
    """A host step left over by the tail match must have ended before the
    trace's first event; one that ends inside it has lost its device event."""
    trace, host = scene()
    host += step(0, 0.99, 0.991, 0.991, 1.2)      # ends inside step 2
    got = reader.attribute(trace, host)
    assert 'missing on the device' in got['refused']


def test_steps_of_two_programs_join_in_one_sequence(reader):
    trace, host = scene()
    other_program(trace, host)
    got = reader.attribute(trace, host)
    assert got['steps'] == 3 and got['lo'] <= T0_HOST <= got['hi']
    # and a name that differs across the two sides is refused
    host[:] = [dict(e, args=dict(e['args'], program='jit_p'))
               if (e.get('args') or {}).get('program') == 'jit_q' else e
               for e in host]
    assert 'refused' in reader.attribute(trace, host)


class FakeRecorder:
    def __init__(self, events, dropped=0):
        self.events, self.dropped = events, dropped

    def snapshot(self):
        return copy.deepcopy(self.events)


def ctx_for(trace, spans, logs):
    return {'trace': trace, 'metric': {'spans': spans}, 'log': logs.append,
            'reduced': {'window_s': 2.0, 'busy_s': 0.81}}


def test_read_gives_shares_of_the_window_and_logs_the_table(reader,
                                                            monkeypatch):
    trace, host = scene()
    monkeypatch.setattr(reader, '_newest_recorder',
                        lambda: FakeRecorder(host))
    logs = []
    assert reader.read(ctx_for(trace, ['decode+preprocess'], logs)) == \
        pytest.approx(100 * 0.10 / 2.0, abs=1e-3)
    assert reader.read(ctx_for(trace, ['pack', 'h2d'], logs)) == \
        pytest.approx(100 * 0.15 / 2.0, abs=1e-3)
    assert reader.read(ctx_for(trace, [], logs)) == \
        pytest.approx(100 * 0.04 / 2.0, abs=1e-3)
    text = '\n'.join(logs)
    assert text.count('steps matched') == 1      # one table for the metrics
    assert '3 steps matched' in text and 'width 200.0 us' in text
    assert 'window edges' in text and 'inside a program' in text
    # the rows account for the run's device_idle: 2.0 - 0.81 s
    assert 'rows sum to 1.1900 s' in text and 'is 1.1900 s' in text


def test_read_says_nothing_without_a_recorder_or_after_a_refusal(
        reader, monkeypatch):
    trace, host = scene()
    logs = []
    monkeypatch.setattr(reader, '_newest_recorder', lambda: None)
    assert reader.read(ctx_for(trace, [], logs)) is None
    trace2, _ = scene()
    monkeypatch.setattr(reader, '_newest_recorder',
                        lambda: FakeRecorder(host, dropped=1))
    assert reader.read(ctx_for(trace2, [], logs)) is None
    assert 'no span recorder' in logs[0] and 'dropped 1' in logs[-1]


def test_the_reader_finds_the_programs_newest_attached_recorder(
        reader, monkeypatch):
    from video_features_tpu.obs import spans
    monkeypatch.setattr(spans, '_ATTACHED', type(spans._ATTACHED)(maxlen=4))
    assert reader._newest_recorder() is None
    old, new = spans.SpanRecorder(), spans.SpanRecorder()
    spans.attach(old)
    spans.attach(new)
    assert reader._newest_recorder() is new
    monkeypatch.delattr(spans, 'attached')       # a parent commit has none
    assert reader._newest_recorder() is None
