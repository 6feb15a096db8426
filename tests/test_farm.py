"""Decode farm (farm/): N decoder worker PROCESSES feeding the packed
scheduler over bounded shared-memory rings must be externally
indistinguishable from in-process decode — byte-identical outputs across
the CLI, packed, and serve paths at any worker count — while surviving
worker crashes with the per-video fault contract (one casualty, siblings
complete, the worker respawns).

The recipe classes used for fault injection / transport tests live at
module level: spawn'd workers unpickle them by reference, so they must
be importable (``tests.test_farm``) from the child process.
"""
import os
import signal
import time
from pathlib import Path

import numpy as np
import pytest

from video_features_tpu.config import load_config
from video_features_tpu.registry import create_extractor
from video_features_tpu.utils.output import make_path

from tools.make_sample_video import write_noise_clip as _write_clip  # noqa: E402


# -- shared-memory ring: pure units (no processes, no jax) -------------------


def _make_ring(capacity):
    from video_features_tpu.farm.ring import RingProducer
    buf = memoryview(bytearray(capacity))
    return RingProducer(buf, capacity), buf


def test_ring_roundtrip_with_wraps():
    """Windows written through the producer come back byte-exact through
    ``read_window`` across many arena wraps, including the skipped-tail
    case (a region never straddles the wrap)."""
    from video_features_tpu.farm.ring import read_window
    ring, buf = _make_ring(1 << 12)           # 4 KiB arena
    rng = np.random.RandomState(0)
    inflight = []                             # (offset, adv, expected)
    freed = []

    def wait_free():
        assert inflight, 'alloc blocked with nothing to free: deadlock'
        off, adv, expect = inflight.pop(0)
        got = read_window(buf, off, expect.shape, expect.dtype.str)
        np.testing.assert_array_equal(got, expect)
        ring.freed(adv)
        freed.append(adv)

    for i in range(64):
        # odd sizes force misaligned offsets and frequent wraps
        arr = rng.randint(0, 255, size=(rng.randint(200, 600),),
                          ).astype(np.uint8)
        region = ring.alloc(arr.nbytes, wait_free)
        assert region is not None
        off, adv = region
        assert adv >= arr.nbytes              # adv folds any skipped tail
        assert off + arr.nbytes <= ring.capacity   # contiguous region
        ring.write(off, arr)
        inflight.append((off, adv, arr))
    while inflight:
        wait_free()
    # both sides agree on total advance: frees reported verbatim
    assert ring.write_pos == ring.read_pos == sum(freed)


def test_ring_oversized_window_takes_queue_fallback():
    """A window over half the arena can never be satisfied by freeing
    (its wrap skip could exceed capacity) — alloc must return None (the
    worker then ships bytes through the message queue) instead of
    deadlocking in wait_free."""
    ring, _ = _make_ring(1 << 10)
    assert ring.alloc((1 << 9) + 1) is None
    # exactly half still fits
    assert ring.alloc(1 << 9) is not None


def test_ring_backpressure_blocks_until_freed():
    """When the arena is full the producer spins in ``wait_free`` — a
    slow consumer stalls decode instead of growing memory."""
    from video_features_tpu.farm.ring import RingFull
    ring, _ = _make_ring(1 << 10)
    a = ring.alloc(400)
    b = ring.alloc(400)
    assert a is not None and b is not None
    # no free callback → RingFull, proving alloc would have to wait
    with pytest.raises(RingFull):
        ring.alloc(400)
    calls = []

    def wait_free():
        ring.freed(a[1])                     # consumer frees the oldest
        calls.append(1)

    c = ring.alloc(400, wait_free)
    assert c is not None and calls           # it blocked, then proceeded


# -- picklable transport/fault recipes (unpickled inside spawn'd workers) ----


class SyntheticRecipe:
    """Deterministic windows derived from the path — no video decode, so
    transport tests isolate the SHM ring + queue machinery."""

    def __init__(self, n_windows=24, nbytes=300_000):
        self.n_windows = n_windows
        self.nbytes = nbytes

    def open(self, path):
        # crc32, not hash(): PYTHONHASHSEED differs across spawned
        # processes, and the parent recomputes these seeds to verify
        import zlib
        seed = zlib.crc32(os.path.basename(path).encode()) % (2 ** 31)

        def windows():
            for i in range(self.n_windows):
                rng = np.random.RandomState(seed + i)
                yield rng.randint(0, 255, size=(self.nbytes,)
                                  ).astype(np.uint8), i

        return {'seed': seed}, windows()


def expected_window(path, i, nbytes=300_000):
    import zlib
    seed = zlib.crc32(os.path.basename(path).encode()) % (2 ** 31)
    return np.random.RandomState(seed + i).randint(
        0, 255, size=(nbytes,)).astype(np.uint8)


class CrashRecipe(SyntheticRecipe):
    """SIGKILLs its own worker process mid-video for paths containing
    'CRASH' — the closest harness-reachable stand-in for a decoder
    segfault (no Python teardown, no 'err' message, just a dead pid)."""

    def open(self, path):
        info, windows = super().open(path)
        if 'CRASH' not in os.path.basename(path):
            return info, windows

        def crashing():
            it = iter(windows)
            yield next(it)                    # one window escapes first
            os.kill(os.getpid(), signal.SIGKILL)

        return info, crashing()


class CrashingRealRecipe(CrashRecipe):
    """Module-level (spawn unpickles recipes by reference): decode real
    clips via the extractor's own recipe, but SIGKILL the worker on the
    marked one (``CrashRecipe.open`` handles the marker)."""

    def __init__(self, inner):
        super().__init__(n_windows=4)
        self.inner = inner

    def open(self, path):
        if 'CRASH' in os.path.basename(path):
            return super().open(path)
        return self.inner.open(path)


def _tasks(paths):
    from video_features_tpu.parallel.packing import VideoTask
    return [VideoTask(str(p)) for p in paths]


def _drain_farm(farm, tasks):
    """Consume a farm stream to completion; returns {path: [windows]}."""
    from video_features_tpu.parallel.packing import FLUSH, NUDGE
    got = {str(t.path): [] for t in tasks}
    for item in farm.stream(iter(tasks), lambda t: True):
        if item is FLUSH or item is NUDGE:
            continue
        task, window, meta = item
        got[str(task.path)].append((meta, window))
    return got


# -- farm transport: integrity, backpressure, fallback (no jax) --------------


def test_farm_ships_windows_byte_exact_across_workers(tmp_path):
    """Every window of every video arrives exactly once, in order, with
    the exact bytes the worker produced — through rings small enough to
    wrap and backpressure many times per video."""
    from video_features_tpu.farm import DecodeFarm
    paths = [tmp_path / f'v{i}.bin' for i in range(4)]
    tasks = _tasks(paths)
    farm = DecodeFarm(SyntheticRecipe(), workers=2,
                      ring_bytes=1 << 20)     # ~3 windows per ring
    got = _drain_farm(farm, tasks)
    for t in tasks:
        assert not t.failed and t.exhausted
        assert t.emitted == 24
        wins = got[str(t.path)]
        assert [m for m, _ in wins] == list(range(24))   # in order
        for i, (_, w) in enumerate(wins):
            np.testing.assert_array_equal(w, expected_window(t.path, i))
    st = farm.stats()
    assert st['windows'] == 4 * 24
    assert st['queue_fallback'] == 0
    assert st['videos_failed'] == 0 and st['respawns'] == 0


def test_farm_slow_consumer_backpressures_not_balloons(tmp_path):
    """With a consumer slower than decode, producer-side ring occupancy
    is the only buffer: the run completes, every byte intact, and the
    reported in-flight ring bytes never exceed ring capacity."""
    from video_features_tpu.farm import DecodeFarm
    from video_features_tpu.parallel.packing import FLUSH, NUDGE
    paths = [tmp_path / 'slow0.bin', tmp_path / 'slow1.bin']
    tasks = _tasks(paths)
    ring_bytes = 1 << 20
    farm = DecodeFarm(SyntheticRecipe(n_windows=12), workers=2,
                      ring_bytes=ring_bytes)
    seen = 0
    for item in farm.stream(iter(tasks), lambda t: True):
        if item is FLUSH or item is NUDGE:
            continue
        task, window, meta = item
        np.testing.assert_array_equal(
            window, expected_window(task.path, meta))
        seen += 1
        for w in farm._workers:               # producer-reported usage
            assert w.ring_used <= ring_bytes
        time.sleep(0.02)                      # slower than decode
    assert seen == 2 * 12


def test_farm_oversized_windows_fall_back_to_queue(tmp_path):
    """Windows larger than half a ring take the message-queue fallback —
    slower, but never wrong and never deadlocked."""
    from video_features_tpu.farm import DecodeFarm
    paths = [tmp_path / 'big.bin']
    tasks = _tasks(paths)
    farm = DecodeFarm(SyntheticRecipe(n_windows=5, nbytes=400_000),
                      workers=1, ring_bytes=1 << 19)   # windows > ring/2
    got = _drain_farm(farm, tasks)
    wins = got[str(paths[0])]
    assert len(wins) == 5
    for i, (_, w) in enumerate(wins):
        np.testing.assert_array_equal(
            w, expected_window(paths[0], i, nbytes=400_000))
    assert farm.stats()['queue_fallback'] == 5


def test_farm_oversized_fallback_backpressures(tmp_path):
    """Queue-transport windows are credit-bounded (MAX_UNACKED_WINQ,
    acked by the consumer per consumed window): a slow consumer stalls
    decode instead of growing the parent's message queue without bound
    — the fallback path honors the same memory contract as the ring."""
    from video_features_tpu.farm import DecodeFarm
    from video_features_tpu.farm.worker import MAX_UNACKED_WINQ
    from video_features_tpu.parallel.packing import FLUSH, NUDGE
    paths = [tmp_path / 'big.bin']
    tasks = _tasks(paths)
    farm = DecodeFarm(SyntheticRecipe(n_windows=12, nbytes=400_000),
                      workers=1, ring_bytes=1 << 19)   # all > ring/2
    seen = 0
    for item in farm.stream(iter(tasks), lambda t: True):
        if item is FLUSH or item is NUDGE:
            continue
        task, window, meta = item
        np.testing.assert_array_equal(
            window, expected_window(task.path, meta, nbytes=400_000))
        seen += 1
        time.sleep(0.05)                      # much slower than decode
        for w in farm._workers:
            try:                              # queued = unacked ≤ cap
                backlog = w.out_q.qsize()
            except NotImplementedError:       # macOS qsize — skip bound
                backlog = 0
            # slack beyond the winq credit cap: the start/end markers
            # plus at most two tiny clock-calibration replies (startup
            # + the min-RTT refinement, which stops once tight) — all
            # O(bytes) control messages, not window payloads, so the
            # memory contract this test pins is untouched
            assert backlog <= MAX_UNACKED_WINQ + 3
    assert seen == 12
    assert farm.stats()['queue_fallback'] == 12


def test_farm_worker_crash_fails_one_video_and_respawns(tmp_path):
    """A worker SIGKILLed mid-video fails exactly that video; its
    queued siblings re-dispatch to the respawned worker and complete
    byte-exact; the farm records the respawn."""
    from video_features_tpu.farm import DecodeFarm
    paths = [tmp_path / 'a.bin', tmp_path / 'CRASH.bin',
             tmp_path / 'b.bin', tmp_path / 'c.bin', tmp_path / 'd.bin']
    tasks = _tasks(paths)
    farm = DecodeFarm(CrashRecipe(n_windows=8), workers=2,
                      ring_bytes=1 << 20)
    got = _drain_farm(farm, tasks)

    by_path = {str(t.path): t for t in tasks}
    victim = by_path[str(tmp_path / 'CRASH.bin')]
    assert victim.failed and victim.exhausted
    for t in tasks:
        if t is victim:
            continue
        assert not t.failed, t.path
        wins = got[str(t.path)]
        assert len(wins) == 8, t.path
        for i, (_, w) in enumerate(wins):
            np.testing.assert_array_equal(w, expected_window(t.path, i))
    st = farm.stats()
    assert st['respawns'] >= 1
    assert st['videos_failed'] == 1


def test_farm_worker_death_leaves_a_valid_postmortem_bundle(tmp_path):
    """A worker SIGKILLed mid-video dumps ONE black-box bundle naming the
    death (worker, victim video, requeued count), valid by the bundle's
    own validator and inside the byte cap — the crash path's evidence,
    written after the recovery and never instead of it."""
    import json

    from video_features_tpu.farm import DecodeFarm
    from video_features_tpu.obs.blackbox import BlackBox, validate_bundle
    from video_features_tpu.obs.spans import SpanRecorder
    from video_features_tpu.utils.tracing import Tracer

    paths = [tmp_path / 'a.bin', tmp_path / 'CRASH.bin', tmp_path / 'b.bin']
    tasks = _tasks(paths)
    rec = SpanRecorder(capacity=4096)
    max_bytes = 8 * (1 << 20)
    pm = tmp_path / 'postmortem'
    farm = DecodeFarm(CrashRecipe(n_windows=4), workers=2,
                      ring_bytes=1 << 20,
                      tracer=Tracer(enabled=True, recorder=rec),
                      blackbox=BlackBox(str(pm), max_bytes=max_bytes,
                                        recorders=lambda: [rec]))
    _drain_farm(farm, tasks)
    assert farm.stats()['videos_failed'] == 1

    bundles = sorted(pm.iterdir())
    assert len(bundles) == 1, bundles
    assert validate_bundle(str(bundles[0])) == []
    meta = json.loads((bundles[0] / 'meta.json').read_text())
    assert meta['reason'] == 'farm_worker_death', meta
    assert meta['extra']['victim'] == str(tmp_path / 'CRASH.bin')
    total = sum(f.stat().st_size for f in pm.rglob('*') if f.is_file())
    assert 0 < total <= max_bytes


def test_farm_worker_spans_land_under_worker_pid_calibrated(tmp_path):
    """vft-flight cross-process span round-trip: decode spans are
    MEASURED in the worker and shipped on the result channel; the
    parent records them under the worker's own pid with the
    clock-calibration offset applied, tagged with the task's trace
    context — so the merged timeline shows true in-worker decode time,
    not parent-side drain time."""
    from tools.trace_view import validate_events

    from video_features_tpu.farm import DecodeFarm
    from video_features_tpu.obs.context import mint
    from video_features_tpu.obs.spans import SpanRecorder
    from video_features_tpu.utils.tracing import Tracer

    paths = [tmp_path / 'sa.bin', tmp_path / 'sb.bin']
    tasks = _tasks(paths)
    ctx = mint()
    for t in tasks:
        t.trace = ctx.child()
    rec = SpanRecorder(capacity=4096)
    farm = DecodeFarm(SyntheticRecipe(n_windows=6), workers=2,
                      ring_bytes=1 << 20,
                      tracer=Tracer(enabled=True, recorder=rec))
    worker_pids = []
    got = {str(p): 0 for p in paths}
    from video_features_tpu.parallel.packing import FLUSH, NUDGE
    for item in farm.stream(iter(tasks), lambda t: True):
        if not worker_pids:
            worker_pids = [w.proc.pid for w in farm._workers
                           if w.proc is not None]
            # calibration sanity: perf_counter is process-shared on
            # Linux, so the midpoint offset must be tiny — a huge value
            # means the handshake mixed up its operands
            assert all(abs(w.clock_offset) < 60.0
                       for w in farm._workers)
        if item is FLUSH or item is NUDGE:
            continue
        got[str(item[0].path)] += 1
    events = rec.snapshot()
    assert validate_events(events) == []
    decode = [e for e in events
              if e['ph'] == 'X' and e['name'] == 'decode']
    # one in-worker span per shipped window, every one under a WORKER
    # pid (never the parent's), per-video ordering intact
    assert len(decode) == sum(got.values()) == 2 * 6
    assert all(e['pid'] in worker_pids for e in decode)
    assert all(e['pid'] != os.getpid() for e in decode)
    for p in paths:
        vid_spans = [e for e in decode
                     if e['args']['video'] == str(p)]
        assert len(vid_spans) == 6
        # calibrated offsets: in-worker spans sit on the parent
        # timeline (non-negative, ts-ordered per video)
        ts = [e['ts'] for e in vid_spans]
        assert ts == sorted(ts) and ts[0] >= 0
        # trace context crossed the process boundary
        assert all(e['args']['trace_id'] == ctx.trace_id
                   for e in vid_spans)
        assert all(e['args'].get('span_id') for e in vid_spans)
        assert all(e['tid'] == e['args']['worker'] for e in vid_spans)


def test_farm_clock_calibration_keeps_min_rtt_measurement():
    """The offset error is bounded by half the exchange's round trip,
    so only the tightest exchange ever seen may update the offset: the
    startup handshake (round trip spans process SPAWN — its midpoint
    would shift spans by ~spawn/2) only seeds it, and a tight in-decode
    re-sync replaces it; later coarse replies never regress it."""
    from video_features_tpu.farm import DecodeFarm
    from video_features_tpu.farm.farm import _Worker
    farm = DecodeFarm(SyntheticRecipe(), workers=1)   # never started
    w = _Worker(0, 0)
    t = time.perf_counter()
    # startup-grade exchange: 1s round trip (spawn) → coarse seed
    farm._handle(w, ('clock', 0, 0, t - 1.0, t - 0.2))
    assert w.clock_rtt >= 1.0
    assert abs(w.clock_offset + 0.3) < 0.05           # ≈ -(spawn)/2 bias
    # tight in-decode refinement: ~2ms round trip → replaces the seed
    t2 = time.perf_counter()
    farm._handle(w, ('clock', 0, 0, t2 - 0.002, t2 - 0.001))
    assert w.clock_rtt < 0.05
    tight = w.clock_offset
    assert abs(tight) < 0.05          # shared clock ⇒ true offset ≈ 0
    # a later COARSE reply must never regress the calibration
    t3 = time.perf_counter()
    farm._handle(w, ('clock', 0, 0, t3 - 2.0, t3 - 1.0))
    assert w.clock_offset == tight and w.clock_rtt < 0.05


def test_farm_pending_cb_mirrors_backlog_and_zeroes_on_shutdown(tmp_path):
    """The stall-watchdog feed: the farm mirrors each worker's
    assignment backlog through pending_cb, and shutdown zeroes the rows
    so a retired farm can never read as a stall."""
    from video_features_tpu.farm import DecodeFarm
    calls = []
    paths = [tmp_path / f'pb{i}.bin' for i in range(3)]
    tasks = _tasks(paths)
    farm = DecodeFarm(SyntheticRecipe(n_windows=6), workers=2,
                      ring_bytes=1 << 20,
                      pending_cb=lambda idx, n: calls.append((idx, n)))
    _drain_farm(farm, tasks)
    assert calls, 'pending_cb never fired'
    last = {}
    for idx, n in calls:
        last[idx] = n
    assert set(last) == {0, 1}
    assert all(n == 0 for n in last.values())   # zeroed at shutdown


def test_farm_sigkill_loses_at_most_inflight_spans(tmp_path):
    """A SIGKILLed worker loses at most its in-flight video's unsent
    spans: every window that reached the parent has its span, siblings
    keep a full per-window span ledger, and the victim's spans stop at
    what it shipped before dying."""
    from video_features_tpu.farm import DecodeFarm
    from video_features_tpu.obs.spans import SpanRecorder
    from video_features_tpu.utils.tracing import Tracer

    paths = [tmp_path / 'ka.bin', tmp_path / 'CRASH.bin',
             tmp_path / 'kb.bin']
    tasks = _tasks(paths)
    rec = SpanRecorder(capacity=4096)
    farm = DecodeFarm(CrashRecipe(n_windows=8), workers=2,
                      ring_bytes=1 << 20,
                      tracer=Tracer(enabled=True, recorder=rec))
    got = _drain_farm(farm, tasks)
    decode = [e for e in rec.snapshot()
              if e['ph'] == 'X' and e['name'] == 'decode']
    by_video = {}
    for e in decode:
        by_video.setdefault(e['args']['video'], []).append(e)
    for p in (paths[0], paths[2]):
        assert len(by_video[str(p)]) == 8 == len(got[str(p)])
    victim_spans = by_video.get(str(paths[1]), [])
    # exactly the windows that escaped before the SIGKILL (one), no
    # phantom spans for windows that never reached the parent
    assert len(victim_spans) == len(got[str(paths[1])]) <= 1


def test_farm_unparks_duplicate_while_stream_stays_open(tmp_path):
    """Serve regression: a duplicate parked behind a mid-decode twin
    must resolve as soon as the twin FINALIZES — not when the task
    stream ends, because a serve feed never ends until server drain. The
    drain loop's supervise tick owns the unpark."""
    import threading

    from video_features_tpu.farm import DecodeFarm
    from video_features_tpu.parallel.packing import FLUSH, NUDGE
    a, b = _tasks([tmp_path / 'dup_a.bin', tmp_path / 'dup_b.bin'])
    stop = threading.Event()
    feed_timed_out = []

    def feed():
        yield a
        yield b                               # same key, twin mid-decode
        # serve-style: the stream stays open until told otherwise,
        # punctuated by idle FLUSHes (packed_batches' lull behavior) —
        # the unpark must happen while the stream is still live
        deadline = time.monotonic() + 20
        while not stop.is_set():
            if time.monotonic() > deadline:
                feed_timed_out.append(True)
                return
            time.sleep(0.05)
            yield FLUSH

    def admit(t):
        # the cache seam: misses while the twin is mid-decode (so B gets
        # gated through to the dedupe park), hits once it published (so
        # B's re-gate is terminal without decoding)
        return t is a or not a.finalized

    farm = DecodeFarm(SyntheticRecipe(n_windows=12), workers=2,
                      ring_bytes=1 << 20,
                      cache_key_fn=lambda p: 'same-content')
    for item in farm.stream(feed(), admit):
        if item is not FLUSH and item is not NUDGE:
            task, window, meta = item
            np.testing.assert_array_equal(
                window, expected_window(task.path, meta))
        if a.exhausted and not a.finalized:
            a.finalized = True                # run_packed's finalize()
        if b.exhausted:
            stop.set()                        # only now may the feed end
    assert not feed_timed_out, \
        'duplicate stayed parked until the stream ended'
    assert a.emitted == 12 and not a.failed
    assert b.exhausted and not b.failed
    assert b.emitted == 0                     # never decoded
    assert farm.stats()['deduped'] == 1


# -- the workers' real import footprint ---------------------------------------


def test_worker_side_decode_never_imports_jax(tmp_path):
    """What a decode worker RUNS — unpickle a recipe, open a video, drain
    its windows — must not import jax: workers are started after the
    parent touched jax, and a TPU belongs to one process. The static
    spawn-purity rule cannot see lazy imports below the root modules
    (``stream_windows`` used to import ``parallel.packing``, whose package
    __init__ is the whole jax stack — found on the chip, PR 21), so this
    replays every recipe kind in a fresh interpreter and looks."""
    import pickle
    import subprocess
    import sys

    from video_features_tpu.farm.recipes import (
        FramewiseRecipe, FusedRecipe, StackRecipe,
    )
    clip = _write_clip(tmp_path / 'fp.mp4', 9, seed=5)
    common = dict(batch_size=4, fps=None, total=None,
                  tmp_path=str(tmp_path / 'tmp'), keep_tmp=False,
                  backend='auto')
    resize = ('edge_resize', 32, 'bilinear')
    recipes = [StackRecipe(win=4, step=4, transform=resize, **common),
               FramewiseRecipe(transform=resize, **common),
               FusedRecipe(transforms={'a': resize, 'b': None}, **common)]
    blob = tmp_path / 'recipes.pkl'
    blob.write_bytes(pickle.dumps(recipes))
    child = (
        'import pickle, sys\n'
        'import video_features_tpu.farm.worker\n'
        f'for recipe in pickle.load(open({str(blob)!r}, "rb")):\n'
        f'    info, windows = recipe.open({clip!r})\n'
        '    assert sum(1 for _ in windows) > 0, recipe\n'
        'print(sorted(m for m in sys.modules '
        'if m.split(".")[0] in ("jax", "jaxlib", "flax")))\n')
    proc = subprocess.run([sys.executable, '-c', child], text=True,
                          capture_output=True, timeout=120,
                          cwd=str(Path(__file__).resolve().parents[1]))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == '[]', proc.stdout


# -- packed-path parity: byte-identical to decode_workers=1 ------------------


@pytest.fixture(scope='module')
def farm_worklist(tmp_path_factory):
    """Mixed-length clips: windows straddle batch boundaries and workers
    finish out of order, so interleaving is actually exercised."""
    d = tmp_path_factory.mktemp('farmvids')
    return [_write_clip(d / f'fv{i}.mp4', n, seed=i)
            for i, n in enumerate((11, 4, 16))]


def _resnet_args(paths, out, tmp, **kw):
    over = dict(video_paths=paths, device='cpu', model_name='resnet18',
                batch_size=4, allow_random_weights=True,
                on_extraction='save_numpy', output_path=str(out),
                tmp_path=str(tmp))
    over.update(kw)
    return load_config('resnet', overrides=over)


RESNET_KEYS = ('resnet', 'fps', 'timestamps_ms')


def _assert_outputs_identical(root_a, root_b, paths, keys=RESNET_KEYS):
    compared = 0
    for p in paths:
        for k in keys:
            a = Path(make_path(str(root_a), p, k, '.npy'))
            b = Path(make_path(str(root_b), p, k, '.npy'))
            assert a.read_bytes() == b.read_bytes(), (p, k)
            compared += 1
    assert compared == len(paths) * len(keys)


def test_packed_farm_byte_identity_framewise(farm_worklist, tmp_path):
    """resnet (FramewiseRecipe: per-frame edge-resize + crop in the
    worker) — packed outputs at decode_workers=2 are byte-identical to
    decode_workers=1, and the farm actually ran."""
    # ONE extractor, both decode paths via the run-level decode_workers
    # override with per-task out_roots (the serve warm-reuse pattern) —
    # halves this tier-1 test's transplant+compile cost
    from video_features_tpu.parallel.packing import VideoTask
    ex = create_extractor(_resnet_args(
        farm_worklist, tmp_path / 'w1', tmp_path / 't1',
        pack_across_videos=True, decode_workers=1))
    ex.extract_packed(farm_worklist)
    assert ex._farm is None                    # 1 ≡ in-process path

    farm_root = str(tmp_path / 'w2')
    ex.extract_packed([VideoTask(p, out_root=farm_root)
                       for p in farm_worklist], decode_workers=2)
    assert ex._farm is not None
    st = ex._farm.stats()
    assert st['videos_assigned'] == len(farm_worklist)
    assert st['windows'] > 0 and st['videos_failed'] == 0

    _assert_outputs_identical(ex.output_path, farm_root, farm_worklist)


def test_packed_farm_byte_identity_stacks(farm_worklist, tmp_path):
    """r21d (StackRecipe: raw-frame stack windows off the worker's
    decoder) — byte-identical at any worker count."""
    # ONE extractor, in-process then farm decode (run-level override +
    # per-task out_roots) — same parity contract, half the build cost
    from video_features_tpu.parallel.packing import VideoTask
    args = load_config('r21d', overrides=dict(
        video_paths=farm_worklist, device='cpu',
        model_name='r2plus1d_18_16_kinetics', stack_size=8,
        step_size=8, batch_size=2, allow_random_weights=True,
        on_extraction='save_numpy',
        output_path=str(tmp_path / 's1' / 'out'),
        tmp_path=str(tmp_path / 's1' / 'tmp'),
        pack_across_videos=True, decode_workers=1))
    ex = create_extractor(args)
    ex.extract_packed(farm_worklist)
    farm_root = str(tmp_path / 's2' / 'out')
    ex.extract_packed([VideoTask(p, out_root=farm_root)
                       for p in farm_worklist], decode_workers=2)
    _assert_outputs_identical(ex.output_path, farm_root,
                              farm_worklist, keys=('r21d',))


def test_packed_farm_crash_spares_siblings_end_to_end(farm_worklist,
                                                     tmp_path):
    """The whole stack under a worker kill: a crashing recipe injected
    into a real resnet packed run fails only the marked video — the
    siblings' saved features are byte-identical to a clean farm run."""
    # ONE extractor: clean farm pass, then the crash pass through the
    # same warm build (per-task out_roots keep the trees apart) — half
    # the transplant+compile cost, same end-to-end contract
    from video_features_tpu.parallel.packing import VideoTask
    ex = create_extractor(_resnet_args(
        farm_worklist, tmp_path / 'clean', tmp_path / 'tc',
        pack_across_videos=True, decode_workers=2))
    ex.extract_packed(farm_worklist)
    clean_root = str(ex.output_path)

    crash_clip = str(Path(farm_worklist[0]).parent / 'CRASH_e2e.mp4')
    if not os.path.exists(crash_clip):
        _write_clip(crash_clip, 8, seed=99)
    worklist = farm_worklist[:1] + [crash_clip] + farm_worklist[1:]

    hurt_root = str(tmp_path / 'hurt')
    real = ex.farm_recipe()
    ex.farm_recipe = lambda: CrashingRealRecipe(real)
    ex.extract_packed([VideoTask(str(p), out_root=hurt_root)
                       for p in worklist])

    assert ex._farm.stats()['respawns'] >= 1
    # the victim has no outputs; every sibling is byte-identical
    assert not Path(make_path(hurt_root, crash_clip, 'resnet',
                              '.npy')).exists()
    _assert_outputs_identical(clean_root, hurt_root, farm_worklist)


def test_packed_farm_cache_dedupe_decodes_shared_content_once(
        farm_worklist, tmp_path):
    """Two worklist entries with IDENTICAL content (different names):
    the farm consults the content-addressed cache key before assigning,
    parks the duplicate while its twin decodes, and serves it from the
    cache once the twin publishes — one decode, two complete outputs."""
    import shutil
    twin_dir = tmp_path / 'twins'
    twin_dir.mkdir()
    a = str(twin_dir / 'orig.mp4')
    b = str(twin_dir / 'copy.mp4')
    shutil.copyfile(farm_worklist[0], a)
    shutil.copyfile(farm_worklist[0], b)

    ex = create_extractor(_resnet_args(
        [a, b], tmp_path / 'dd', tmp_path / 'td',
        pack_across_videos=True, decode_workers=2,
        cache_enabled=True, cache_dir=str(tmp_path / 'cache')))
    ex.extract_packed([a, b])

    st = ex._farm.stats()
    assert st['videos_assigned'] == 1          # one decode for two tasks
    assert st['deduped'] == 1
    for p in (a, b):
        for k in RESNET_KEYS:
            assert Path(make_path(str(ex.output_path), p, k,
                                  '.npy')).exists(), (p, k)
    # the copy's features are byte-identical to the original's
    for k in RESNET_KEYS:
        fa = Path(make_path(str(ex.output_path), a, k, '.npy'))
        fb = Path(make_path(str(ex.output_path), b, k, '.npy'))
        assert fa.read_bytes() == fb.read_bytes(), k


def test_packed_farm_fallback_without_recipe(farm_worklist, tmp_path,
                                             capsys):
    """decode_workers>1 on an extractor that publishes no recipe must
    degrade to in-process decode with a structured warning — outputs
    complete, no farm."""
    ex = create_extractor(_resnet_args(
        farm_worklist, tmp_path / 'fb', tmp_path / 'tf',
        pack_across_videos=True, decode_workers=2))
    ex.farm_recipe = lambda: None
    ex.extract_packed(farm_worklist)
    assert ex._farm is None
    err = capsys.readouterr().err
    assert 'decode_workers=2' in err and 'in-process' in err
    for p in farm_worklist:
        assert Path(make_path(str(ex.output_path), p, 'resnet',
                              '.npy')).exists()


# -- CLI + serve paths -------------------------------------------------------


def test_cli_farm_byte_identity(farm_worklist, tmp_path, capsys):
    """The full CLI entry (cli.main) with pack_across_videos=true +
    decode_workers=2 writes byte-identical features to the
    decode_workers=1 run."""
    from video_features_tpu.cli import main as cli_main
    roots = {}
    for workers in (1, 2):
        out = tmp_path / f'cli{workers}'
        rc = cli_main([
            'feature_type=resnet', 'model_name=resnet18', 'device=cpu',
            'batch_size=4', 'allow_random_weights=true',
            'on_extraction=save_numpy', 'pack_across_videos=true',
            f'decode_workers={workers}',
            f'output_path={out}', f'tmp_path={tmp_path / "ctmp"}',
            # YAML flow-list syntax: a bare comma-joined string would
            # parse as ONE path
            'video_paths=[' + ','.join(str(p) for p in farm_worklist) + ']',
        ])
        assert rc == 0
        roots[workers] = os.path.join(str(out), 'resnet', 'resnet18')
    capsys.readouterr()
    _assert_outputs_identical(roots[1], roots[2], farm_worklist)


def test_serve_farm_parity_and_metrics(farm_worklist, tmp_path):
    """A farm-backed server (decode_workers=2 base override) answers a
    request byte-identically to the in-process server, and the metrics
    document's 'farm' section + vft_farm_* families report the workers
    that ran it."""
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer

    def base(workers):
        return {
            'device': 'cpu', 'model_name': 'resnet18', 'batch_size': 4,
            'allow_random_weights': True, 'on_extraction': 'save_numpy',
            'tmp_path': str(tmp_path / f'stmp{workers}'),
            'decode_workers': workers,
        }

    roots = {}
    for workers in (1, 2):
        server = ExtractionServer(base_overrides=base(workers),
                                  queue_depth=32, pool_size=2).start()
        try:
            client = ServeClient(port=server.port)
            out_root = str(tmp_path / f'serve{workers}')
            rid = client.submit('resnet', farm_worklist,
                                overrides={'output_path': out_root})
            st = client.wait(rid, timeout_s=300)
            assert st['state'] == 'done', st
            m = client.metrics()
            assert 'farm' in m
            if workers > 1:
                assert m['farm']['decode_workers'] >= 2
                assert m['farm']['windows'] > 0
                prom = client.metrics_prom()
                assert 'vft_farm_windows' in prom
            else:
                assert m['farm']['windows'] == 0
        finally:
            server.drain(wait=True, grace_s=60)
        roots[workers] = os.path.join(out_root, 'resnet', 'resnet18')
    _assert_outputs_identical(roots[1], roots[2], farm_worklist)
