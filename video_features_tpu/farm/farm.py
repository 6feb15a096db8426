"""DecodeFarm: dispatcher + supervisor + the scheduler-facing stream.

The farm sits where ``extract.streaming.stream_windows_across_videos``
sits in the in-process pipeline: it consumes the scheduler's (possibly
blocking, FLUSH-punctuated) task stream and yields the same
``(task, window, meta)`` items — but decode runs in N worker PROCESSES
(``farm/worker.py``), each shipping windows through its own bounded
shared-memory ring (``farm/ring.py``).

Threading model (all in the parent):

  * the DISPATCHER thread consumes the task stream: runs the admission
    gate (resume skip / cache hit) per video, dedupes in-flight content
    (two tasks whose cache keys match decode ONCE — the second parks
    until the first finalizes and then re-runs the gate, which hits),
    and assigns videos to the least-loaded worker under a bounded
    runahead, preserving the lazy-resume-check property of the
    in-process path (never an up-front O(corpus) ``is_already_exist``
    scan);
  * the caller's thread (the packed scheduler's prefetch producer) runs
    :meth:`stream`'s drain loop: multiplexes every worker's message
    queue (``connection.wait`` over the queue pipes), copies windows out
    of SHM (freeing ring space immediately — the copy is ~1000× cheaper
    than the decode it replaces), maintains
    ``task.emitted/exhausted/failed`` and the FLUSH/NUDGE sentinel
    contract, and supervises workers: a dead process fails ONLY its
    in-flight video, its queued videos re-dispatch, and the worker
    respawns with a fresh ring epoch.

Fault model matches the per-video error contract everywhere: a decode
error or worker crash dooms exactly one video; the farm (and the
worklist) keep going. Only a systemic crash loop (``RESPAWN_LIMIT``
exceeded with no workers left) surfaces as a scheduler-level error,
which the serve layer already isolates per warm worker.
"""
from __future__ import annotations

import logging
import queue as queue_mod
import threading
import time
from collections import deque
from typing import Callable, Dict, Iterable, Iterator, List, Optional

import numpy as np

from video_features_tpu.obs.context import trace_attrs
from video_features_tpu.utils.tracing import NULL_TRACER, Tracer

# total across the farm's lifetime; generous vs any real transient (a
# poison video costs at most 2: one mid-decode kill + one retry kill)
RESPAWN_LIMIT = 8

_MB = 1 << 20

# the vft_farm_* gauges are process-global while farms are per-run: a
# serve process can have several warm-pool entries each running a farm
# concurrently, so every gauge write must be an aggregate over the LIVE
# farms, not one instance's view (else last-writer-wins and a retiring
# entry zeroes a sibling's live workers out of the scrape)
_LIVE_FARMS: set = set()
_LIVE_LOCK = threading.Lock()
# thread-discipline declaration (vft-lint): module-level mutables and
# the lock that guards every access to them
_LOCKED_BY = {'_LIVE_FARMS': '_LIVE_LOCK'}


class FarmUnavailable(RuntimeError):
    """The host can't run the farm (no spawn context / SHM support)."""


def farm_available() -> bool:
    """Best-effort capability probe (import-level only — actual spawn
    failures still degrade gracefully at start())."""
    try:
        import multiprocessing.shared_memory  # noqa: F401
        import multiprocessing

        multiprocessing.get_context('spawn')
        return True
    except (ImportError, ValueError):
        return False


def _request_id(task) -> Optional[str]:
    req = getattr(task, 'request', None)
    return getattr(req, 'id', None)


class _Worker:
    __slots__ = ('idx', 'epoch', 'proc', 'shm', 'task_q', 'out_q',
                 'free_q', 'ctrl_q', 'pending', 'started', 'ring_used',
                 'aborted', 'clock_offset', 'clock_rtt', 'clock_asked')

    def __init__(self, idx: int, epoch: int) -> None:
        self.idx = idx
        self.epoch = epoch
        self.proc = None
        self.shm = None
        self.task_q = None
        self.out_q = None
        self.free_q = None
        self.ctrl_q = None
        self.pending: 'deque[int]' = deque()   # seqs assigned, FIFO
        self.started: set = set()              # seqs whose 'start' arrived
        self.aborted: set = set()              # seqs already sent an abort
        self.ring_used = 0                     # last-reported ring bytes
        # worker-clock → parent-clock offset ('clock' handshake; 0.0
        # until calibrated): added to every in-worker span timestamp so
        # the merged timeline shows true in-worker decode time. The
        # parent keeps the MINIMUM-round-trip measurement (NTP-style):
        # the startup exchange's round trip spans process spawn (its
        # midpoint would shift spans by ~spawn/2), so it only seeds the
        # offset until a tight in-decode refinement replaces it.
        self.clock_offset = 0.0
        self.clock_rtt = float('inf')          # best RTT seen (seconds)
        self.clock_asked = 0.0                 # last re-sync request t


class DecodeFarm:
    """N decode worker processes behind one cross-video window stream."""

    def __init__(self, recipe, workers: int = 2,
                 ring_bytes: int = 64 * _MB,
                 tracer: Tracer = NULL_TRACER,
                 cache_key_fn: Optional[Callable] = None,
                 respawn_limit: int = RESPAWN_LIMIT,
                 live_open: Optional[Callable] = None,
                 blackbox=None,
                 pending_cb: Optional[Callable] = None) -> None:
        import multiprocessing
        self.recipe = recipe
        # post-mortem dump target (obs/blackbox.BlackBox or None): a
        # dead worker process dumps a bundle alongside the respawn
        self._blackbox = blackbox
        # stall-watchdog feed (serve): ``pending_cb(worker_idx,
        # n_queued)`` mirrors each worker's assignment backlog so a
        # single wedged decode worker trips its own watchdog row even
        # while its siblings keep the serve-level row advancing
        self._pending_cb = pending_cb
        self.n_workers = max(int(workers), 1)
        self.ring_bytes = max(int(ring_bytes), _MB // 4)
        self.tracer = tracer
        self.cache_key_fn = cache_key_fn
        self.respawn_limit = int(respawn_limit)
        self._ctx = multiprocessing.get_context('spawn')
        self._lock = threading.Lock()
        self._ctrl: 'deque' = deque()          # FLUSH/NUDGE markers
        self._tasks: Dict[int, object] = {}    # seq → VideoTask
        self._next_seq = 0
        self._outstanding = 0                  # assigned, not yet ended
        self._unfinished: set = set()          # seqs assigned, not ended
        self._runahead = max(2 * self.n_workers, 4)
        self._inflight_keys: Dict[str, object] = {}
        self._parked: Dict[str, List] = {}
        self._retried: set = set()             # seqs given a post-crash retry
        self._respawns = 0
        self._stats = {'windows': 0, 'bytes': 0, 'queue_fallback': 0,
                       'videos_assigned': 0, 'videos_done': 0,
                       'videos_failed': 0, 'deduped': 0}
        self._workers: List[_Worker] = []
        # live tasks (ingress live sessions): windows arrive over the
        # network in the PARENT, so they never ship to a worker process.
        # ``live_open(task)`` returns the task's window iterator; each
        # live task gets a feeder thread appending to _live_out, which
        # the drain loop yields alongside worker windows.
        self._live_open = live_open
        self._live_out: 'deque' = deque()
        self._live_threads: List[threading.Thread] = []
        self._admit: Optional[Callable] = None
        self._dispatch_done = False
        self._dispatch_error: Optional[BaseException] = None
        self._stopping = False
        self._started = False
        from video_features_tpu.obs.metrics import REGISTRY
        self._g_workers = REGISTRY.gauge(
            'vft_farm_workers', 'decode farm worker processes alive')
        self._g_busy = REGISTRY.gauge(
            'vft_farm_busy_workers',
            'decode farm workers with videos assigned')
        self._g_ring = REGISTRY.gauge(
            'vft_farm_ring_bytes',
            'decoded bytes resident in the farm SHM rings')
        self._c_respawns = REGISTRY.counter(
            'vft_farm_respawns_total', 'decode farm worker respawns')

    # -- lifecycle -----------------------------------------------------------

    @staticmethod
    def _task_msg(seq: int, task) -> tuple:
        """The ONE builder of worker task messages — ('video', seq, path
        [, segment[, select]]). ``task.farm_select`` (fused worklists:
        the family subset still wanting this video's shared decode) is
        appended only when set, so plain recipes keep receiving the
        message shape they've always parsed."""
        select = getattr(task, 'farm_select', None)
        if select is not None:
            return ('video', seq, str(task.path),
                    getattr(task, 'segment', None), tuple(select))
        return ('video', seq, str(task.path),
                getattr(task, 'segment', None))

    def _spawn(self, idx: int, epoch: int,
               requeue: Iterable[int] = ()) -> _Worker:
        from multiprocessing import shared_memory

        from video_features_tpu.farm.worker import worker_main
        w = _Worker(idx, epoch)
        w.shm = shared_memory.SharedMemory(create=True,
                                           size=self.ring_bytes)
        w.task_q = self._ctx.Queue()
        w.out_q = self._ctx.Queue()
        w.free_q = self._ctx.Queue()
        w.ctrl_q = self._ctx.Queue()
        w.proc = self._ctx.Process(
            target=worker_main,
            args=(idx, epoch, self.recipe, w.shm.name, self.ring_bytes,
                  w.task_q, w.out_q, w.free_q, w.ctrl_q),
            daemon=True, name=f'vft-decode-{idx}')
        w.proc.start()
        # clock-calibration handshake: the worker reads this first (see
        # farm/worker.py) and answers with ('clock', ...) carrying its
        # own perf_counter reading — _handle computes the offset
        w.ctrl_q.put(('sync', time.perf_counter()))
        for seq in requeue:
            task = self._tasks[seq]
            w.pending.append(seq)
            w.task_q.put(self._task_msg(seq, task))
        return w

    def start(self) -> 'DecodeFarm':
        if self._started:
            return self
        try:
            self._workers = [self._spawn(i, 0)
                             for i in range(self.n_workers)]
        except Exception as e:
            self.shutdown()
            raise FarmUnavailable(f'decode farm failed to start: {e}')
        self._started = True
        with _LIVE_LOCK:
            _LIVE_FARMS.add(self)
        self._update_gauges()
        return self

    def shutdown(self) -> None:
        """Idempotent teardown: stop workers, reap processes, unlink SHM."""
        self._stopping = True
        for w in self._workers:
            if w.proc is not None and w.proc.is_alive():
                try:
                    w.task_q.put(('stop',))
                except Exception:
                    # vft-lint: ok=swallowed-exception — best-effort stop
                    # to a possibly-dead child; join below bounds teardown
                    pass
        deadline = time.monotonic() + 5.0
        for w in self._workers:
            if w.proc is not None:
                w.proc.join(max(0.0, deadline - time.monotonic()))
                if w.proc.is_alive():
                    w.proc.terminate()
                    w.proc.join(1.0)
        for w in self._workers:
            self._close_ring(w)
        if self._pending_cb is not None:
            # zero the watchdog rows: a retired farm's stale backlog
            # must not read as a stall after the run ends. The pending
            # deques are CLEARED first — _update_gauges below mirrors
            # len(w.pending) through the same callback, and republishing
            # a dead worker's backlog would undo this zeroing
            for w in self._workers:
                with self._lock:
                    w.pending.clear()
                try:
                    self._pending_cb(w.idx, 0)
                except Exception:
                    # vft-lint: ok=swallowed-exception — teardown-path
                    # liveness hook; the forget on worker retirement
                    # clears the rows regardless
                    pass
        with _LIVE_LOCK:
            _LIVE_FARMS.discard(self)
        self._update_gauges()

    @staticmethod
    def _close_ring(w: _Worker) -> None:
        w.ring_used = 0
        if w.shm is not None:
            try:
                w.shm.close()
                w.shm.unlink()
            except Exception:
                # vft-lint: ok=swallowed-exception — idempotent teardown:
                # a ring already unlinked by a respawn raises harmlessly
                pass
            w.shm = None

    # -- stats ---------------------------------------------------------------

    def stats(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self._stats)
            out['decode_workers'] = self.n_workers
            out['alive_workers'] = sum(
                1 for w in self._workers
                if w.proc is not None and w.proc.is_alive())
            out['busy_workers'] = sum(1 for w in self._workers if w.pending)
            out['ring_bytes_in_use'] = sum(w.ring_used
                                           for w in self._workers)
            out['respawns'] = self._respawns
            out['ring_bytes_capacity'] = self.ring_bytes * self.n_workers
        return out

    def _update_gauges(self) -> None:
        with _LIVE_LOCK:
            farms = list(_LIVE_FARMS)
        self._g_workers.set(sum(
            1 for f in farms for w in f._workers
            if w.proc is not None and w.proc.is_alive()))
        self._g_busy.set(sum(
            1 for f in farms for w in f._workers if w.pending))
        self._g_ring.set(sum(
            w.ring_used for f in farms for w in f._workers))
        if self._pending_cb is not None:
            with self._lock:
                backlog = [(w.idx, len(w.pending)) for w in self._workers]
            for idx, n in backlog:
                try:
                    self._pending_cb(idx, n)
                except Exception:
                    # vft-lint: ok=swallowed-exception — a broken
                    # liveness hook must not take down the drain loop
                    pass

    # -- dispatcher ----------------------------------------------------------

    def _dispatch(self, tasks: Iterable, admit: Callable) -> None:
        from video_features_tpu.parallel.packing import FLUSH
        try:
            for item in tasks:
                if item is FLUSH:
                    self._append_flush()
                    continue
                task = item
                if getattr(task, 'windows_override', None) is not None \
                        and self._live_open is not None:
                    # live session: no file to decode — run its window
                    # source on a parent-side feeder thread
                    self._start_live(task)
                    continue
                if not self._gate(task, admit):
                    continue
                key = None
                if self.cache_key_fn is not None:
                    seg = getattr(task, 'segment', None)
                    try:
                        # segment passed only when set: a range task must
                        # never dedupe against its full-video twin, and
                        # pre-segment key fns keep working for whole
                        # videos
                        key = (self.cache_key_fn(str(task.path), seg)
                               if seg is not None
                               else self.cache_key_fn(str(task.path)))
                    except Exception:
                        # vft-lint: ok=swallowed-exception — fallback by
                        # design: an unhashable video skips dedupe and
                        # decodes normally (its own failure reports there)
                        key = None             # unhashable → no dedupe
                with self._lock:
                    twin = (self._inflight_keys.get(key)
                            if key is not None else None)
                    if twin is not None and not getattr(twin, 'finalized',
                                                        False):
                        # same content is decoding right now (another
                        # request, a duplicate worklist entry): park
                        # until the twin publishes, then the gate's
                        # cache consult answers this one for free
                        self._parked.setdefault(key, []).append(task)
                        self._stats['deduped'] += 1
                        continue
                    if key is not None:
                        self._inflight_keys[key] = task
                self._assign(task)
            # resolve parked duplicates + wait for the field to clear
            last_flush = 0.0
            while not self._stopping:
                self._resolve_parked(admit)
                with self._lock:
                    busy = (self._outstanding > 0
                            or any(self._parked.values())
                            or any(t.is_alive()
                                   for t in self._live_threads))
                if not busy:
                    break
                if any(self._parked.values()) \
                        and time.monotonic() - last_flush > 0.05:
                    # a parked twin may be waiting on a tail pool: force
                    # the packer to flush so the twin can finalize
                    self._append_flush()
                    last_flush = time.monotonic()
                time.sleep(0.02)
        # vft-lint: ok=swallowed-exception — stored, not swallowed: the
        # drain loop re-raises _dispatch_error to the caller
        except BaseException as e:            # surfaced by the drain loop
            self._dispatch_error = e
        finally:
            self._dispatch_done = True

    def _prune_live(self) -> None:
        """Drop finished feeder threads — a serve farm lives for the
        server's lifetime, so an append-only list would retain a dead
        Thread (and an is_alive scan) per live session forever."""
        with self._lock:
            self._live_threads = [t for t in self._live_threads
                                  if t.is_alive()]

    def _start_live(self, task) -> None:
        t = threading.Thread(target=self._feed_live, args=(task,),
                             daemon=True, name='vft-farm-live')
        self._prune_live()
        with self._lock:
            self._live_threads.append(t)
        t.start()

    def _feed_live(self, task) -> None:
        """Feeder thread for one live task: runs its window source (the
        session's network-fed windower) and hands windows to the drain
        loop via ``_live_out``, bounded so a stalled consumer
        backpressures the session instead of growing parent memory. The
        per-video error contract holds: a feeder failure dooms exactly
        this task."""
        from video_features_tpu.extract.base import log_extraction_error
        from video_features_tpu.parallel.packing import FLUSH
        try:
            for item in self._live_open(task):
                if self._stopping or task.failed:
                    break
                if item is FLUSH:
                    # arrival lull: flush partial pools so computed
                    # windows stream back (watermarked like any FLUSH,
                    # so it never overtakes windows still decoding)
                    self._append_flush()
                    continue
                window, meta = item
                task.emitted += 1
                while len(self._live_out) >= 64 and not self._stopping:
                    time.sleep(0.005)
                self._live_out.append((task, window, meta))
        except Exception:
            task.failed = True
            log_extraction_error(task.path, stage='decode',
                                 request_id=_request_id(task))
        finally:
            task.exhausted = True
            if task.emitted == 0:
                self._ctrl.append(('nudge', task))
            else:
                # flush the session's tail windows out of the pools now —
                # the stream may not see another FLUSH for a long time
                self._append_flush()

    def _append_flush(self) -> None:
        """Queue a FLUSH marker with a watermark: the in-process windower
        yields FLUSH only AFTER the windows of every task that preceded
        it in the stream, so the farm must not let a FLUSH overtake
        windows still decoding in the workers — a serve feed that goes
        idle right after its last FLUSH would otherwise leave the late
        windows pooled in the packer forever. The drain loop holds the
        marker until every seq assigned before it has ended."""
        with self._lock:
            self._ctrl.append(('flush', self._next_seq))

    def _gate(self, task, admit: Callable) -> bool:
        """Admission gate (resume skip / cache hit / gate failure) —
        False means the video is terminal without decoding (NUDGE)."""
        from video_features_tpu.extract.base import log_extraction_error
        try:
            go = admit(task)
        except KeyboardInterrupt:
            raise
        except Exception:
            task.failed = True
            log_extraction_error(task.path, stage='decode',
                                 request_id=_request_id(task))
            go = False
        if not go:
            task.exhausted = True
            self._ctrl.append(('nudge', task))
            return False
        return True

    def _pick_worker(self) -> Optional[_Worker]:
        """Least-loaded alive worker, or None. Caller holds the lock."""
        alive = [w for w in self._workers
                 if w.proc is not None and w.proc.is_alive()]
        return min(alive, key=lambda w: len(w.pending)) if alive else None

    def _assign(self, task, block: bool = True) -> bool:
        """Hand the video to a worker. ``block=False`` (drain-thread
        callers only) returns False instead of waiting when the runahead
        window is full — the drain thread is the one that shrinks
        ``_outstanding``, so blocking there would deadlock the farm."""
        while not self._stopping:
            with self._lock:
                if self._outstanding < self._runahead:
                    self._outstanding += 1
                    break
            if not block:
                return False
            time.sleep(0.01)
        if self._stopping:
            return True
        with self._lock:
            target = self._pick_worker()
            if target is None:
                # systemic: no workers left (respawn budget burned) —
                # fail the video through the normal per-video contract
                task.failed = True
                task.exhausted = True
                self._outstanding -= 1
                # videos_done counts every ENDED video, failures
                # included (videos_failed ⊆ videos_done — serving.md
                # documents backlog math on that invariant)
                self._stats['videos_done'] += 1
                self._stats['videos_failed'] += 1
                self._ctrl.append(('nudge', task))
                return True
            seq = self._next_seq
            self._next_seq += 1
            self._tasks[seq] = task
            self._unfinished.add(seq)
            target.pending.append(seq)
            self._stats['videos_assigned'] += 1
        target.task_q.put(self._task_msg(seq, task))
        return True

    def _resolve_parked(self, admit: Callable,
                        block: bool = True) -> None:
        """Unpark duplicates whose twin has finalized. Runs on BOTH
        threads: the dispatcher's post-source loop (``block=True``), and
        the drain loop's supervise tick (``block=False``) — the latter
        is what keeps a serve feed honest, where the task stream never
        ends and a concurrent-duplicate request would otherwise stay
        parked until server drain."""
        with self._lock:
            ready = [key for key, twin in self._inflight_keys.items()
                     if getattr(twin, 'finalized', False)]
            # keys parked with NO inflight twin (a failed non-blocking
            # assign below re-parks this way) are ready by definition
            ready += [key for key in self._parked
                      if key not in self._inflight_keys]
        for key in ready:
            with self._lock:
                waiters = self._parked.pop(key, [])
                self._inflight_keys.pop(key, None)
            for task in waiters:
                # the gate re-runs: if the twin published, the cache
                # consult materializes this video without a decode
                if not self._gate(task, admit):
                    continue
                with self._lock:
                    twin = self._inflight_keys.get(key)
                    if twin is not None and not getattr(
                            twin, 'finalized', False):
                        self._parked.setdefault(key, []).append(task)
                        continue
                    self._inflight_keys[key] = task
                if not self._assign(task, block=block):
                    # runahead full (non-blocking caller): put it back
                    # exactly as it was and retry on a later tick
                    with self._lock:
                        if self._inflight_keys.get(key) is task:
                            del self._inflight_keys[key]
                        self._parked.setdefault(key, []).append(task)

    # -- the scheduler-facing stream -----------------------------------------

    def stream(self, tasks: Iterable, admit: Callable) -> Iterator:
        """Yield ``(task, window, meta)`` / FLUSH / NUDGE across the
        whole task stream — the drop-in replacement for
        ``stream_windows_across_videos`` (the scheduler packs the windows
        into batches on the thread that drains this, ahead of the
        device)."""
        self.start()
        self._admit = admit
        dispatcher = threading.Thread(
            target=self._dispatch, args=(tasks, admit),
            daemon=True, name='vft-farm-dispatch')
        dispatcher.start()
        try:
            yield from self._drain()
            if self._dispatch_error is not None:
                raise self._dispatch_error
        finally:
            self.shutdown()

    def _drain(self) -> Iterator:
        from multiprocessing.connection import wait as conn_wait

        from video_features_tpu.parallel.packing import FLUSH, NUDGE
        last_supervise = 0.0
        while True:
            # live-session windows first: produced parent-side, they
            # should reach the packer before any lull FLUSH queued after
            # them flushes the pools
            while self._live_out:
                yield self._live_out.popleft()
            while self._ctrl:
                marker = self._ctrl[0]
                if marker[0] == 'flush':
                    # ordering barrier (see _append_flush): hold the
                    # FLUSH — and, to keep marker FIFO, everything
                    # behind it — until every seq assigned before the
                    # marker has ended
                    watermark = marker[1]
                    with self._lock:
                        blocked = any(s < watermark
                                      for s in self._unfinished)
                    if blocked:
                        break
                    self._ctrl.popleft()
                    yield FLUSH
                else:
                    self._ctrl.popleft()
                    yield NUDGE
            with self._lock:
                drained = (self._dispatch_done and self._outstanding == 0
                           and not self._ctrl and not self._live_out
                           and not any(t.is_alive()
                                       for t in self._live_threads))
            if drained and not self._ctrl:
                if self._dispatch_error is None:
                    # surface any last accounting before ending
                    pass
                return
            # Queue._reader is CPython-private (the queue's underlying
            # read Connection) — the only handle connection.wait can
            # multiplex on. Guarded: a runtime without it just degrades
            # to the 20ms poll below, never an AttributeError.
            readers = [r for w in self._workers if w.proc is not None
                       for r in (getattr(w.out_q, '_reader', None),)
                       if r is not None]
            if readers:
                try:
                    conn_wait(readers, timeout=0.05)
                except OSError:
                    time.sleep(0.02)
            else:
                time.sleep(0.02)
            for w in list(self._workers):
                yield from self._drain_worker(w)
            now = time.monotonic()
            if now - last_supervise >= 0.2:
                last_supervise = now
                yield from self._supervise()
                # unpark duplicates whose twin finalized — on the DRAIN
                # thread because a serve feed never ends, so the
                # dispatcher's post-source resolve loop never runs there
                # (non-blocking: this thread must never wait on the
                # runahead window it is responsible for shrinking)
                self._resolve_parked(self._admit, block=False)
                self._prune_live()
                self._update_gauges()

    def _drain_worker(self, w: _Worker) -> Iterator:
        while True:
            try:
                msg = w.out_q.get_nowait()
            except queue_mod.Empty:
                return
            except (OSError, EOFError):
                return                        # feeder died mid-message
            item = self._handle(w, msg)
            if item is not None:
                yield item

    def _handle(self, w: _Worker, msg: tuple):
        """Process one worker message; returns a stream item or None."""
        from video_features_tpu.farm.ring import read_window
        from video_features_tpu.parallel.packing import NUDGE
        kind, widx, epoch = msg[0], msg[1], msg[2]
        if epoch != w.epoch:
            return None                       # stale pre-respawn message
        if kind == 'clock':
            # calibration reply (midpoint method, minimum-RTT filtered):
            # the worker echoed our t_parent0 with its own clock; the
            # midpoint's error is bounded by HALF THE ROUND TRIP, so
            # only the tightest exchange ever seen updates the offset —
            # the startup exchange (whose round trip spans process
            # spawn) seeds it, and the first in-decode re-sync (the
            # worker polls ctrl every window) replaces it with a
            # millisecond-grade measurement. Spans recorded before any
            # reply stay at offset 0 — perf_counter is process-shared
            # on Linux, so that degradation is benign.
            t_parent0, t_worker = msg[3], msg[4]
            rtt = time.perf_counter() - t_parent0
            if rtt < w.clock_rtt:
                w.clock_rtt = rtt
                w.clock_offset = ((t_parent0 + time.perf_counter()) / 2.0
                                  - t_worker)
            return None
        if kind == 'start':
            seq, info = msg[3], msg[4]
            task = self._tasks.get(seq)
            w.started.add(seq)
            if task is not None and info:
                task.info.update(info)
            return None
        if kind in ('win', 'winq'):
            if kind == 'win':
                seq, off, adv, shape, dtype, meta, t0, dt, used = msg[3:]
                window = read_window(w.shm.buf, off, shape, dtype)
                w.free_q.put(adv)
                w.ring_used = used            # producer-reported occupancy
                with self._lock:
                    self._stats['bytes'] += window.nbytes
            else:
                seq, payload, shape, dtype, meta, t0, dt = msg[3:]
                window = np.frombuffer(
                    payload, dtype=np.dtype(dtype)).reshape(shape)
                try:
                    # credit the queue-transport slot back (see
                    # MAX_UNACKED_WINQ in farm/worker.py) — sent for
                    # every consumed 'winq' regardless of task state,
                    # it is transport accounting, not video accounting
                    w.ctrl_q.put(('winq_ack',))
                except Exception:
                    # vft-lint: ok=swallowed-exception — ack to a dead
                    # worker; the supervisor reaps it on the next tick
                    pass
                with self._lock:
                    self._stats['queue_fallback'] += 1
                    self._stats['bytes'] += window.nbytes
            if w.clock_rtt > 0.05 \
                    and time.monotonic() - w.clock_asked > 0.5:
                # calibration still coarse (the startup exchange spans
                # spawn): re-sync NOW, while the worker is provably in
                # its decode loop polling ctrl every window — this
                # round trip is tight, and min-RTT filtering keeps it
                w.clock_asked = time.monotonic()
                try:
                    w.ctrl_q.put(('sync', time.perf_counter()))
                except Exception:
                    # vft-lint: ok=swallowed-exception — re-sync to a
                    # dying worker; supervision reaps it, spans keep
                    # the seed offset
                    pass
            task = self._tasks.get(seq)
            if task is None:
                return None
            if task.failed:
                # device-side fault mid-video: stop paying decode for
                # the rest of it (same early-stop the in-process
                # windower applies), drop the window
                if seq not in w.aborted:
                    w.aborted.add(seq)
                    try:
                        w.ctrl_q.put(('abort', seq))
                    except Exception:
                        # vft-lint: ok=swallowed-exception — abort to a
                        # dead worker; supervision handles the corpse
                        pass
                return None
            task.emitted += 1
            with self._lock:
                self._stats['windows'] += 1
            if self.tracer.enabled:
                # per-worker provenance + transport occupancy: which
                # process decoded this window and how full its SHM ring
                # ran (ring_used ≈ capacity ⇒ the consumer is the wall,
                # not decode). The span is placed at the WORKER's
                # clock-calibrated start and attributed to the worker's
                # own pid/lane — the merged timeline shows true
                # in-worker decode time, not parent-side drain time.
                # Fused recipes tag each window with its family
                # (recipe.family_of) so the SHARED decode span set still
                # answers "which family was this window for".
                fam_attr = {}
                fam_of = getattr(self.recipe, 'family_of', None)
                if fam_of is not None:
                    fam = fam_of(meta)
                    if fam is not None:
                        fam_attr['family'] = fam
                self.tracer.add('decode', dt,
                                **fam_attr,
                                t0=t0 + w.clock_offset,
                                span_pid=(w.proc.pid
                                          if w.proc is not None else None),
                                span_tid=widx,
                                video=str(task.path), worker=widx,
                                ring_used=w.ring_used,
                                ring_capacity=self.ring_bytes,
                                request_id=_request_id(task),
                                **trace_attrs(task))
            return task, window, meta
        if kind in ('end', 'err'):
            seq = msg[3]
            task = self._tasks.get(seq)
            self._finish_seq(w, seq)
            if task is None:
                return None
            if kind == 'err':
                task.failed = True
                self._report_decode_error(task, msg[4])
            task.exhausted = True
            with self._lock:
                self._stats['videos_done'] += 1
                if task.failed:
                    self._stats['videos_failed'] += 1
            if task.emitted == 0:
                return NUDGE
            return None
        return None

    def _finish_seq(self, w: _Worker, seq: int) -> None:
        with self._lock:
            try:
                w.pending.remove(seq)
            except ValueError:
                pass
            w.started.discard(seq)
            w.aborted.discard(seq)
            self._unfinished.discard(seq)
            self._retried.discard(seq)
            # drop the task ref: on a serve farm (one run for the
            # server's lifetime) seq→task entries would otherwise
            # accumulate per request forever. Callers that need the task
            # fetch it BEFORE finishing the seq; late messages from the
            # same epoch can't reference an ended seq (per-video 'end'
            # is the worker's last message for it), and stale-epoch
            # messages are dropped before task lookup.
            self._tasks.pop(seq, None)
            self._outstanding -= 1

    def _report_decode_error(self, task, tb_text: str) -> None:
        from video_features_tpu.obs.events import event
        event(logging.WARNING,
              f'decode farm worker failed {task.path}:\n{tb_text}',
              video=str(task.path), stage='decode',
              request_id=_request_id(task))

    # -- supervision ---------------------------------------------------------

    def _supervise(self) -> Iterator:
        """Detect dead workers; fail their in-flight video, re-dispatch
        their queue, respawn under the budget."""
        from video_features_tpu.parallel.packing import NUDGE
        for i, w in enumerate(list(self._workers)):
            if w.proc is None or w.proc.is_alive() or self._stopping:
                continue
            # drain every message it managed to send before dying
            yield from self._drain_worker(w)
            with self._lock:
                pending = list(w.pending)
            victim_seq = None
            requeue: List[int] = []
            if pending:
                oldest = pending[0]
                if oldest in w.started or oldest in self._retried:
                    # mid-decode (or burned its one retry): this video
                    # dies, the per-video contract's single casualty
                    victim_seq = oldest
                    requeue = pending[1:]
                else:
                    # can't prove it ever started — give it ONE retry so
                    # a queued-but-untouched video isn't lost, while a
                    # poison video still fails on its second crash
                    self._retried.add(oldest)
                    requeue = pending
            from video_features_tpu.obs.events import event
            event(logging.WARNING,
                  f'decode farm worker {w.idx} died '
                  f'(exitcode {w.proc.exitcode}); '
                  f'{"failing " + str(self._tasks[victim_seq].path) if victim_seq is not None else "no video in flight"}'
                  f'; respawning with {len(requeue)} queued video(s)',
                  subsystem='farm')
            if self._blackbox is not None:
                # post-mortem bundle for the dead worker: the spans it
                # shipped before dying are already in the ring (at most
                # its in-flight video's tail is lost), the event above
                # is in the tail — dump both. Never raises, never on
                # the request hot path (supervise tick only).
                self._blackbox.dump(
                    'farm_worker_death', worker=w.idx,
                    exitcode=w.proc.exitcode,
                    victim=(str(self._tasks[victim_seq].path)
                            if victim_seq is not None else None),
                    requeued=len(requeue))
            if victim_seq is not None:
                task = self._tasks[victim_seq]
                task.failed = True
                task.exhausted = True
                self._finish_seq(w, victim_seq)
                with self._lock:
                    self._stats['videos_done'] += 1
                    self._stats['videos_failed'] += 1
                if task.emitted == 0:
                    yield NUDGE
            self._close_ring(w)
            with self._lock:
                over_budget = self._respawns >= self.respawn_limit
                if not over_budget:
                    # counted only when a respawn actually happens —
                    # retired-past-budget workers must not inflate
                    # vft_farm_respawns_total during the very crash
                    # loop it exists to diagnose
                    self._respawns += 1
                w.pending.clear()
                w.started.clear()
            # requeued videos STAY outstanding throughout — they were
            # assigned, they remain assigned, only the queue they sit in
            # changes; accounting moves only for the failed victim(s)
            if not over_budget:
                self._c_respawns.inc()
                self._workers[i] = self._spawn(w.idx, w.epoch + 1,
                                               requeue=requeue)
            else:
                event(logging.WARNING,
                      f'decode farm respawn budget exhausted '
                      f'({self.respawn_limit}); worker {w.idx} stays down',
                      subsystem='farm')
                # reap the corpse and retire the slot — proc=None takes
                # this worker out of every alive/reader scan, so the
                # next supervise tick doesn't re-enter the dead-worker
                # path (and re-count a respawn) every 0.2s forever
                try:
                    w.proc.join(0.1)
                except Exception:
                    # vft-lint: ok=swallowed-exception — reaping a corpse;
                    # the retirement below is what matters
                    pass
                w.proc = None
                # re-dispatch its queue to surviving workers (or fail)
                for seq in requeue:
                    task = self._tasks[seq]
                    with self._lock:
                        target = self._pick_worker()
                        if target is not None:
                            target.pending.append(seq)
                    if target is not None:
                        target.task_q.put(self._task_msg(seq, task))
                    else:
                        task.failed = True
                        task.exhausted = True
                        with self._lock:
                            self._outstanding -= 1
                            self._unfinished.discard(seq)
                            self._retried.discard(seq)
                            self._tasks.pop(seq, None)
                            self._stats['videos_done'] += 1
                            self._stats['videos_failed'] += 1
                        if task.emitted == 0:
                            yield NUDGE
            self._update_gauges()


def merge_farm_stats(stats: Iterable[Dict[str, float]]) -> Dict[str, float]:
    """Sum farm stats dicts across serve workers (the serve metrics
    document's ``farm`` section); always returns the full key set so
    scrapers see zeros before the first farm-enabled request."""
    out: Dict[str, float] = {
        'decode_workers': 0, 'alive_workers': 0, 'busy_workers': 0,
        'ring_bytes_in_use': 0, 'ring_bytes_capacity': 0, 'respawns': 0,
        'windows': 0, 'bytes': 0, 'queue_fallback': 0,
        'videos_assigned': 0, 'videos_done': 0, 'videos_failed': 0,
        'deduped': 0}
    for s in stats:
        if not s:
            continue
        for k in out:
            out[k] += int(s.get(k, 0))
    return out
