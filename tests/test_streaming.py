"""Unit tests for the shared streaming helpers: window batching semantics
(tail padding, valid counts, window indices), the producer-thread
transfer pipeline (ordering, keep_host, passthrough put), and the
deferred-D2H fetch window (overlap_fetch)."""
import numpy as np

from video_features_tpu.extract.streaming import (
    iter_batched_windows, overlap_fetch, stream_windows, transfer_batches,
)


def test_overlap_fetch_defers_by_depth_and_preserves_order():
    """At depth k the oldest dispatch is fetched only once k items are in
    flight; results come back in dispatch order with meta intact, and
    the tail drains at stream end. depth=1 is strictly alternating
    (synchronous)."""
    events = []

    def dispatched(n):
        for i in range(n):
            events.append(('dispatch', i))
            yield f'dev{i}', i * 10

    def fetch(dev):
        i = int(dev[3:])
        events.append(('fetch', i))
        return f'host{i}'

    out = list(overlap_fetch(dispatched(4), fetch, depth=2))
    assert out == [(f'host{i}', i * 10) for i in range(4)]
    # fetch(0) happens only after dispatch(1); fetch(3) after the stream
    assert events.index(('fetch', 0)) > events.index(('dispatch', 1))
    assert events[-1] == ('fetch', 3)

    events.clear()
    list(overlap_fetch(dispatched(3), fetch, depth=1))
    assert events == [('dispatch', 0), ('fetch', 0), ('dispatch', 1),
                      ('fetch', 1), ('dispatch', 2), ('fetch', 2)]


def test_overlap_fetch_records_d2h_stage():
    from video_features_tpu.utils.tracing import Tracer
    t = Tracer(enabled=True)
    out = list(overlap_fetch(((x,) for x in 'ab'), lambda x: x.upper(),
                             depth=3, tracer=t))
    assert out == [('A',), ('B',)]
    assert t.report()['d2h']['count'] == 2


def _windows(n, shape=(2, 3)):
    return [np.full(shape, i, np.float32) for i in range(n)]


def test_iter_batched_windows_exact_multiple():
    out = list(iter_batched_windows(iter(_windows(6)), batch=3))
    assert [(v, i) for _, v, i in out] == [(3, 0), (3, 3)]
    for stacks, _, start in out:
        assert stacks.shape == (3, 2, 3)
        np.testing.assert_array_equal(stacks[:, 0, 0],
                                      np.arange(start, start + 3))


def test_iter_batched_windows_tail_padding():
    out = list(iter_batched_windows(iter(_windows(5)), batch=3))
    assert [(v, i) for _, v, i in out] == [(3, 0), (2, 3)]
    tail = out[-1][0]
    # tail padded by repeating the last window; mask with [:valid]
    np.testing.assert_array_equal(tail[:, 0, 0], [3.0, 4.0, 4.0])


def test_iter_batched_windows_empty_and_single():
    assert list(iter_batched_windows(iter([]), batch=4)) == []
    out = list(iter_batched_windows(iter(_windows(1)), batch=4))
    assert len(out) == 1
    stacks, valid, idx = out[0]
    assert (valid, idx) == (1, 0)
    assert stacks.shape == (4, 2, 3)


def test_transfer_batches_order_and_meta():
    items = [(np.full((2,), i, np.float32), 10 * i, f'm{i}')
             for i in range(7)]
    seen_by_put = []

    def put(batch):
        seen_by_put.append(float(batch[0]))
        return batch + 1000.0  # stand-in for a device placement

    out = list(transfer_batches(iter(items), put))
    assert seen_by_put == [float(i) for i in range(7)]  # producer order
    for i, (dev, host, meta1, meta2) in enumerate(out):
        assert float(dev[0]) == 1000.0 + i
        assert host is None
        assert (meta1, meta2) == (10 * i, f'm{i}')


def test_transfer_batches_keep_host():
    items = [(np.full((2,), i, np.float32), i) for i in range(3)]
    out = list(transfer_batches(iter(items), put=lambda b: b * 0, keep_host=True))
    for i, (dev, host, meta) in enumerate(out):
        assert float(host[0]) == float(i)   # untouched host array
        assert float(dev[0]) == 0.0
        assert meta == i


def test_stream_windows_overlapping_steps():
    """step < win: overlapping windows, matching form_slices semantics."""
    frames = [np.full((1,), i, np.float32) for i in range(10)]
    batches = iter([(frames[:4], None, None), (frames[4:], None, None)])
    wins = list(stream_windows(batches, win=4, step=2))
    # starts at 0, 2, 4, 6; start 8 would need frame 11 -> dropped
    assert [int(w[0, 0]) for w in wins] == [0, 2, 4, 6]
    assert all(w.shape == (4, 1) for w in wins)


# -- the waiting spans: input_wait, device_wait, and the step ordinal --------

def _traced():
    from video_features_tpu.obs.spans import SpanRecorder
    from video_features_tpu.utils.tracing import Tracer
    return Tracer(enabled=True, recorder=SpanRecorder())


def _spans(tracer, name):
    return [e for e in tracer.recorder.snapshot()
            if e['ph'] == 'X' and e['name'] == name]


def test_input_wait_is_the_consumers_wait_for_a_slow_producer():
    """``input_wait`` is recorded on the thread that consumes
    ``transfer_batches`` and is about as long as the producer slept; the
    producer's own ``h2d`` spans sit on another thread."""
    import threading
    import time
    tracer = _traced()
    nap, n = 0.03, 4

    def slow_items():
        for i in range(n):
            time.sleep(nap)
            yield np.full((2,), i), i

    out = [meta for _, _, meta in
           transfer_batches(slow_items(), lambda b: b, tracer=tracer)]
    assert out == list(range(n))
    waits, puts = _spans(tracer, 'input_wait'), _spans(tracer, 'h2d')
    assert len(waits) == n + 1               # the last next() finds the end
    assert {e['tid'] for e in waits} == {threading.get_ident()}
    assert threading.get_ident() not in {e['tid'] for e in puts}
    waited = tracer.report()['input_wait']['total_s']
    assert 0.8 * n * nap <= waited <= n * nap + 0.25


def test_disabled_tracer_fetches_once_and_never_syncs(monkeypatch):
    """Tracing off: ``fetch_step`` is the one ``fetch`` call of before — no
    ``block_until_ready`` on the hot path — and ``transfer_batches`` hands
    back the prefetch iterator itself, so nothing is recorded anywhere."""
    import jax

    from video_features_tpu.extract.streaming import fetch_step
    from video_features_tpu.utils.tracing import NULL_TRACER
    synced, fetched = [], []
    monkeypatch.setattr(jax, 'block_until_ready', synced.append)
    out = list(overlap_fetch(
        ((f'dev{i}', i) for i in range(3)),
        lambda dev: fetched.append(dev) or dev.upper(), depth=2,
        step_of=lambda: {'step': 1, 'program': 'jit_x'}))
    assert out == [(f'DEV{i}', i) for i in range(3)]
    assert fetched == ['dev0', 'dev1', 'dev2'] and synced == []
    assert fetch_step(lambda o: o + 1, 41) == 42 and synced == []
    batches = transfer_batches(iter([(np.zeros(1), 0)]), lambda b: b)
    assert batches.gi_code.co_name == 'prefetch'     # not wrapped
    assert len(list(batches)) == 1 and NULL_TRACER.report() == {}


def test_device_wait_and_d2h_tile_the_old_d2h_interval():
    """Tracing on: the wait (``block_until_ready``) and the copy (``fetch``)
    are two spans, back to back, that together cover what ``d2h`` used to;
    the batch's provenance rides on both, the ordinal on the wait."""
    import time

    from video_features_tpu.extract.streaming import fetch_step
    tracer = _traced()

    class Out:                       # a leaf jax.block_until_ready blocks on
        def block_until_ready(self):
            time.sleep(0.03)
            return self

    def fetch(out):
        time.sleep(0.01)
        return 'host'

    t0 = time.perf_counter()
    assert fetch_step(fetch, Out(), tracer,
                      {'step': 7, 'program': 'jit_x'}, valid=3) == 'host'
    whole = (time.perf_counter() - t0) * 1e6
    (wait,), (copy,) = _spans(tracer, 'device_wait'), _spans(tracer, 'd2h')
    assert wait['args'] == {'valid': 3, 'step': 7, 'program': 'jit_x'}
    assert copy['args'] == {'valid': 3}
    assert wait['dur'] >= 0.03e6 and copy['dur'] >= 0.01e6
    # the copy follows the wait, and nothing of the fetch lies outside them
    # (bounds loose enough for a loaded test host)
    gap = copy['ts'] - (wait['ts'] + wait['dur'])
    assert 0 <= gap < 50e3
    assert copy['ts'] + copy['dur'] - wait['ts'] <= whole


def test_step_ordinals_are_consecutive_and_shared_by_each_pair(tmp_path):
    """Every ``model`` span and the ``device_wait`` span of the same step
    carry one ordinal and the program's name; ordinals count on across
    videos (the tracer is reset a video, the counter is not)."""
    from video_features_tpu.extract.base import BaseExtractor

    class Stub(BaseExtractor):
        pass

    ex = Stub('stub', 'print', str(tmp_path), str(tmp_path), False, 'cpu')
    assert ex.step_attrs(3, 4) == {} and ex.last_step() is None   # off
    ex.tracer = _traced()

    def video(n):
        def dispatched():
            for i in range(n):
                with ex.tracer.stage('model', **ex.step_attrs(3, 4)):
                    out = f'dev{i}'
                yield out, i
        return list(overlap_fetch(dispatched(), str.upper, 2, ex.tracer,
                                  ex.last_step))

    assert [m for _, m in video(3)] == [0, 1, 2]
    ex.tracer.reset()
    video(2)
    models, waits = _spans(ex.tracer, 'model'), _spans(ex.tracer,
                                                       'device_wait')
    assert [e['args']['step'] for e in models] == [1, 2, 3, 4, 5]
    assert [e['args']['step'] for e in waits] == [1, 2, 3, 4, 5]
    assert {e['args']['program'] for e in models + waits} == {'jit_stub_step'}
    assert models[0]['args']['valid'] == 3
    assert models[0]['args']['capacity'] == 4
    for m, w in zip(models, waits):          # a step is waited for after it
        assert w['ts'] >= m['ts'] + m['dur']  # was dispatched


def test_h2d_span_is_the_transfer_not_its_enqueue():
    """Tracing on: the ``h2d`` span, on the producer thread, lasts until the
    batch has landed (``block_until_ready``), so no step is dispatched ahead
    of its own input; tracing off, ``put`` is called and nothing waits (the
    disabled-tracer test above spies on that)."""
    import time
    tracer = _traced()

    class Landing:
        def block_until_ready(self):
            time.sleep(0.03)
            return self

    out = list(transfer_batches(iter([(np.zeros(1), 7)]),
                                lambda b: Landing(), tracer=tracer))
    assert isinstance(out[0][0], Landing) and out[0][2] == 7
    (put,) = _spans(tracer, 'h2d')
    assert put['dur'] >= 0.03e6 and put['args'] == {'staged': True}
