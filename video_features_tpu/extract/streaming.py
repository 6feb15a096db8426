"""Streaming stack-window assembly shared by the stack-based extractors.

The reference loads entire videos into RAM before slicing stacks
(reference extract_r21d.py:72-74 — "could run out of memory"; the i3d loop
holds every decoded frame too). Here frames stream off the decoder through
a bounded ring buffer and windows are emitted as soon as they complete, so
memory is O(window) and — wrapped in ``io.video.prefetch`` — decode overlaps
device compute.

Windowing semantics are exactly ``utils.slicing.form_slices``: window k
starts at ``k·step``; only full windows are emitted (partial final stacks
are dropped, like the reference, extract_i3d.py:126-129).

``stream_windows_across_videos`` extends the windower across video
boundaries for the packed corpus mode (``parallel.packing``): one
fault-isolated stream over the whole worklist, so device batches can fill
with windows from several videos instead of padding at every video's tail.
``stream_windows_across_lanes`` is the same stream with up to K videos
decoding at once, each on its own thread (a decode lane);
``decode_lane_plan`` derives K from the cores and the worklist.
"""
from __future__ import annotations

import math
import os
import queue
import threading
import time
from collections import deque
from typing import (
    Callable, Dict, Iterable, Iterator, List, Optional, Tuple,
)

import numpy as np

from video_features_tpu.utils.tracing import NULL_TRACER, Tracer

# The stream sentinels are defined HERE, not in parallel/packing.py (which
# re-exports them): the windowers below run inside decode-farm worker
# processes, and importing anything under ``parallel`` executes that
# package's __init__ — the whole jax stack — in a child that must stay
# jax-free (a TPU belongs to one process; tests/test_farm.py pins the
# workers' real import footprint). Identity-compared everywhere.
#
# FLUSH: "no more input for now — flush partial pools". Yielded by dynamic
# sources (the serve request feed) between arrival bursts; passes through
# the windower/prefetch layers untouched and is consumed by
# ``packed_batches``.
FLUSH = object()
# NUDGE: "a video exhausted without emitting any window" (resume skip,
# zero-window clip, failed open). It must REACH the consumer — all
# finalization runs on the consumer thread, and with no batch to carry the
# news a dynamic stream would otherwise not finalize such videos until
# drain (an all-skip request would hang). ``packed_batches`` forwards it
# as a batchless ``(None, [], 0)`` item that triggers a sweep.
NUDGE = object()


def iter_batched_windows(windows: Iterable[np.ndarray], batch: int,
                         tracer: Tracer = NULL_TRACER) -> Iterator[tuple]:
    """Group streamed windows into fixed-size ``(stacks, valid, window_idx)``
    batches: a (batch, ...) array whose tail is padded by repeating the last
    window (mask with ``[:valid]``) plus the absolute index of the batch's
    first window. Generator form so a caller can map a device transfer over
    it inside ``io.video.prefetch`` — batch assembly AND host→device copy
    then run on the producer thread, overlapped with device compute. The
    assembly copy is timed as the ``pack`` stage, like the packed
    scheduler's (35 MB a batch in the i3d loop: with ``stream_windows``'
    window copies, 60 ms of every video start that no span covered; my
    chip run, PR 25).
    """
    pending: List[np.ndarray] = []
    window_idx = 0

    def flush():
        valid = len(pending)
        with tracer.stage('pack', valid=valid, capacity=batch):
            while len(pending) < batch:
                pending.append(pending[-1])
            out = (np.stack(pending), valid, window_idx)
        pending.clear()
        return out, valid

    for window in windows:
        pending.append(window)
        if len(pending) == batch:
            out, valid = flush()
            yield out
            window_idx += valid
    if pending:
        yield flush()[0]


def transfer_batches(items: Iterable[tuple], put, keep_host: bool = False,
                     tracer: Tracer = NULL_TRACER,
                     depth: int = 2) -> Iterator[tuple]:
    """Overlap host→device input transfer with device compute.

    ``items`` yields ``(host_batch, *meta)``; ``put`` places one batch on
    the device(s) (``BaseExtractor.put_input``). Returns a prefetched
    iterator of ``(device_batch, host_batch | None, *meta)`` where the
    async copy of batch k+1 starts on the producer thread while the
    consumer runs batch k. ``depth`` (default 2) is how many transferred
    batches the producer thread STAGES ahead of the consumer: at 2 the
    next batch's ``device_put`` is always already issued while the
    current batch runs, so the transfer never lands on the dispatch
    critical path even when the consumer momentarily outruns the
    producer. Each staged unit keeps one more input batch resident on
    device; ``depth=1`` restores the minimal single-buffer overlap.
    ``keep_host=True`` carries the host array alongside (debug surfaces
    like show_pred read pixels without paying a D2H round trip). The
    single home for this transfer policy — every batched extractor
    drives its device loop through here. ``tracer`` attributes the
    producer-thread transfer time to the ``h2d`` stage (``put_traced``;
    it runs outside the extract loop, so without this it would be
    invisible in the profile table); the span's ``staged`` attr records
    whether the transfer was issued ahead of need (depth > 1) or on
    demand. The
    CONSUMER side of the same queue is the ``input_wait`` stage: each
    ``next()`` of the returned iterator is timed on the thread that
    calls it (the dispatch thread), so the profile says not only that
    decode/pack/h2d were busy but how long the device loop stood
    waiting for them. With a disabled tracer the prefetch iterator is
    returned as is.
    """
    from video_features_tpu.io.video import prefetch

    depth = max(int(depth or 1), 1)
    staged = depth > 1

    def to_device(item):
        batch = item[0]
        if batch is None:
            # batchless scheduler marker (packed NUDGE): nothing to copy
            return (None, None) + tuple(item[1:])
        host = batch if keep_host else None
        dev = put_traced(put, batch, tracer, staged=staged)
        return (dev, host) + tuple(item[1:])

    ready = prefetch(map(to_device, items), depth=depth)
    if not tracer.enabled:
        return ready
    return tracer.wrap_iter('input_wait', ready)


def put_traced(put, batch, tracer: Tracer = NULL_TRACER, **attrs):
    """Place one host batch on the device under the ``h2d`` stage. With a
    disabled tracer this is the bare ``put(batch)``: ``device_put`` only
    ENQUEUES the copy and returns. With tracing on the span waits for
    the copy (``jax.block_until_ready``), so ``h2d`` is the transfer and
    not its enqueue: a 35 MB i3d batch is enqueued in 2 ms and lands 27
    ms later, during which the step that needs it cannot start (my chip
    run, PR 25) — time no span covered. It also means a step is never
    dispatched ahead of its own input, which is what makes "a step
    cannot start before it is dispatched" a tight bound for joining the
    timeline to the device trace. The wait is on the producer thread,
    not the dispatch thread."""
    if not tracer.enabled:
        return put(batch)
    import jax
    with tracer.stage('h2d', **attrs):
        return jax.block_until_ready(put(batch))


def fetch_step(fetch, out, tracer: Tracer = NULL_TRACER,
               step: Optional[dict] = None, **attrs):
    """Materialize one dispatched step's outputs on the host — the one
    home of the sync point, shared by ``overlap_fetch`` and the packed
    schedulers. With a disabled tracer this is the single ``fetch(out)``
    call (no extra sync on the hot path). With tracing on, the wait and
    the copy are told apart: ``jax.block_until_ready`` under
    ``device_wait`` (carrying ``step``: the ordinal and program name of
    the step's ``model`` span), then ``fetch`` under ``d2h``, which then
    only copies. ``attrs`` (batch provenance) ride on both spans. An
    asynchronously raised execution error surfaces from either call, so
    callers keep their fault isolation around this one."""
    if not tracer.enabled:
        return fetch(out)
    import jax
    with tracer.stage('device_wait', **attrs, **(step or {})):
        jax.block_until_ready(out)
    with tracer.stage('d2h', **attrs):
        return fetch(out)


def overlap_fetch(dispatched: Iterable[tuple], fetch, depth: int,
                  tracer: Tracer = NULL_TRACER,
                  step_of: Optional[Callable] = None) -> Iterator[tuple]:
    """Defer device→host readback ``depth`` dispatches behind compute.

    ``dispatched`` yields ``(device_out, *meta)`` where ``device_out``
    is a just-dispatched step's output (device arrays — no forced
    readback yet); items queue until ``depth`` of them are in flight,
    then the OLDEST is materialized with ``fetch`` (through
    ``fetch_step``: the ``device_wait`` + ``d2h`` stages) and yielded as
    ``(host_out, *meta)`` — so on async backends
    the readback + whatever the consumer does with the results (feature
    append, save) overlap the device computing the next batches.
    ``step_of()`` gives the span attrs of the step just dispatched
    (``BaseExtractor.last_step``), so a step's ``device_wait`` span
    carries the ordinal of its ``model`` span.
    ``depth=1`` is the old synchronous order: every dispatch is
    immediately followed by its fetch. Results always come back in
    dispatch order, so consumers are unchanged beyond the deferral.
    The per-video extract loops drive their device steps through here;
    the packed scheduler (``parallel.packing.run_packed``) implements
    the same policy inline because its sync point also owns scatter and
    fault isolation.
    """
    from collections import deque
    depth = max(int(depth or 1), 1)
    pending: 'deque' = deque()

    def materialize():
        item, step = pending.popleft()
        host = fetch_step(fetch, item[0], tracer, step)
        return (host,) + tuple(item[1:])

    for item in dispatched:
        pending.append((item, step_of() if step_of is not None else None))
        if len(pending) >= depth:
            yield materialize()
    while pending:
        yield materialize()


def segment_frame_range(segment, fps) -> Optional[Tuple[int, int]]:
    """Map a ``(start_s, end_s)`` time range onto retimed frame indices.

    The half-open frame range ``[start_f, end_f)`` covers every frame
    whose timestamp falls inside the segment at the loader's OUTPUT
    frame rate (post-retiming — the timebase ``timestamps_ms`` and the
    windower both live in). Conservative rounding (floor start, ceil
    end) so a window that merely touches the boundary is still covered.
    """
    if segment is None:
        return None
    start_s, end_s = float(segment[0]), float(segment[1])
    fps = float(fps)
    return (int(math.floor(start_s * fps)),
            max(int(math.ceil(end_s * fps)), 0))


def framewise_segment_windows(batches: Iterable,
                              frame_range: Optional[Tuple[int, int]],
                              ) -> Iterator[tuple]:
    """Per-frame ``(frame, t_ms)`` windows from a loader's batch stream,
    honoring an optional half-open frame range with early decode stop —
    the ONE home for the frame-wise segment filter, shared by
    ``BaseFrameWiseExtractor.packed_windows`` and the farm's
    ``FramewiseRecipe`` so the in-process and worker-process paths can
    never diverge on the boundary rule (byte-parity is tested, but only
    a shared implementation makes it structural)."""
    for batch, times, indices in batches:
        for frame, t_ms, idx in zip(batch, times, indices):
            if frame_range is not None:
                if idx < frame_range[0]:
                    continue                  # before the range: drop
                if idx >= frame_range[1]:
                    return                    # past it: stop decoding
            yield np.asarray(frame), t_ms


def _fail_decode(task) -> None:
    """A video failed to open or decode (call inside the ``except``): its
    task fails, and the structured fault report carries the serve request
    id (None for CLI tasks) and the stage that died."""
    from video_features_tpu.extract.base import log_extraction_error
    task.failed = True
    log_extraction_error(
        task.path, stage='decode',
        request_id=getattr(getattr(task, 'request', None), 'id', None))


def stream_windows_across_videos(tasks: Iterable,
                                 open_windows: Callable) -> Iterator[tuple]:
    """The corpus-mode windower: yield ``(task, window, meta)`` across video
    boundaries so a downstream packer can fill device batches from the whole
    worklist instead of draining one video at a time.

    ``tasks`` iterates scheduler tasks (``parallel.packing.VideoTask``);
    ``open_windows(task)`` returns that video's ``(window, meta)`` iterator
    (an extractor's ``packed_windows`` hook). Videos are drained in order —
    the tail windows of video k and the head windows of video k+1 land in
    the same stream, which is exactly what lets the packed batch stay full
    at boundaries.

    Per-video fault isolation matches ``BaseExtractor._extract``: an
    exception while opening or decoding one video marks that task failed
    (its partial windows may still flow through a shared batch — harmless,
    they are never saved) and the stream continues with the next video; one
    bad file never kills the worklist nor the batches it shares
    (KeyboardInterrupt re-raises). ``task.emitted``/``task.exhausted`` are
    maintained here — the scatter side uses them to decide when a video's
    features are complete.

    The ``parallel.packing.FLUSH`` sentinel (dynamic sources: the serve
    request feed marks an arrival lull) passes straight through to the
    downstream packer, which flushes its partial geometry pools.
    """
    for task in tasks:
        if task is FLUSH:
            yield FLUSH
            continue
        try:
            for item in open_windows(task):
                if item is FLUSH:
                    # a LIVE window source (ingress live sessions) marks
                    # an arrival lull mid-video: pass it through so the
                    # packer flushes partial pools and the async loop
                    # materializes — already-computed windows stream back
                    # to the client instead of waiting on future frames
                    yield FLUSH
                    continue
                window, meta = item
                if task.failed:
                    # the consumer failed this video mid-run (device-step
                    # fault): stop decoding the rest of it — only the few
                    # windows already buffered/pooled still flow through
                    # (and are dropped at scatter), instead of the whole
                    # remainder of the video burning decode + device time
                    break
                task.emitted += 1
                yield task, window, meta
        except KeyboardInterrupt:
            raise
        except Exception:
            _fail_decode(task)
        finally:
            task.exhausted = True
        if task.emitted == 0:
            # no batch will ever carry this video's completion (resume
            # skip / too-short clip / failed open): NUDGE the consumer so
            # it finalizes NOW — a dynamic stream may not end for hours
            yield NUDGE


# -- decode lanes: several videos of the worklist at once --------------------
#
# One video's decode + host preprocess on one thread bounded the packed
# resnet50 cell at 1,524.7 frames/s with the device idle 65 % of the window
# (ledger, PR 26, the parent's side). The ctypes call into libvfdecode and
# Pillow's resize release the GIL, so K videos on K threads scale almost
# linearly to four (ISSUE 26's host probe); threads start in microseconds
# and copy nothing, where the farm's processes need seconds to their first
# window.

MAX_LANES = 4
# A lane hands its windows over in CHUNKS: a hand-over a frame made lock
# convoys on the queue that collapsed at six threads (ISSUE 26's probe).
# Bounded in windows for frames (32 × 150 KB) and in bytes for stacks (an
# i3d window of 17 × 256 × 344 × 3 is 4.5 MB: two a chunk).
CHUNK_WINDOWS = 32
CHUNK_BYTES = 8 << 20
_POLL_S = 0.1       # how often a blocked lane looks at the stop flag
_JOIN_S = 5.0       # how long closing the stream waits for a thread
_CHUNK, _FLUSH_MARK, _END, _ERROR = object(), object(), object(), object()


def decode_lane_plan(decode_workers: Optional[int],
                     videos: Optional[int] = None,
                     cores: Optional[int] = None) -> Dict:
    """How the packed path's input side runs, from what the code can see.

    ``decode_workers`` unset (``None``, the shipped default of the
    framewise ymls): in-process decode lanes, half the usable cores
    (``os.sched_getaffinity``), at most ``MAX_LANES``, at least 1, and
    never more than the ``videos`` at hand (``None``: a dynamic source,
    not known). Explicit 1: the serial windower. Explicit N > 1: the
    decode farm's N worker processes (``farm_workers``), as before.
    Returns ``{'lanes', 'farm_workers', 'decode_workers', 'cores',
    'videos', 'why'}`` — the run manifest's ``decode`` section."""
    if cores is None:
        cores = (len(os.sched_getaffinity(0))
                 if hasattr(os, 'sched_getaffinity')
                 else os.cpu_count() or 1)
    plan = {'lanes': 1, 'farm_workers': 0,
            'decode_workers': decode_workers, 'cores': int(cores),
            'videos': videos}
    if decode_workers is None:
        lanes = max(1, min(MAX_LANES, int(cores) // 2))
        plan['why'] = f'unset: {cores} cores halved, at most {MAX_LANES}'
        if videos is not None and videos < lanes:
            lanes = max(1, int(videos))
            plan['why'] += f', {videos} video(s) at hand'
        plan['lanes'] = lanes
    elif int(decode_workers) <= 1:
        plan['why'] = 'explicit 1: the serial windower'
    else:
        plan['farm_workers'] = int(decode_workers)
        plan['why'] = f'explicit {int(decode_workers)}: the decode farm'
    return plan


def stream_windows_across_lanes(tasks: Iterable, open_windows: Callable,
                                lanes: int,
                                tracer: Tracer = NULL_TRACER,
                                span_attrs: Optional[Callable] = None,
                                stats: Optional[List[Dict]] = None,
                                ) -> Iterator[tuple]:
    """``stream_windows_across_videos`` with up to ``lanes`` videos of the
    worklist decoding at once, each on its own thread, merged into the one
    stream the packer reads. Same items (``(task, window, meta)``, ``FLUSH``,
    ``NUDGE``), same per-video fault isolation.

      * One video is one lane's from open to close, so its windows stay in
        order. A dispatcher thread pulls the next task from ``tasks`` only
        when a lane is idle, so videos start in worklist order and a source
        that blocks in ``next()`` (serve) holds back nothing of the videos
        already running.
      * Lanes hand over LISTS of windows (``CHUNK_WINDOWS`` / ``CHUNK_BYTES``)
        through one queue bounded at ``lanes`` chunks; a full queue blocks
        the lane (back-pressure). Beside the prefetch buffer downstream,
        at most ``lanes`` chunks queued and one in each lane's hands exist.
      * This generator, on the caller's thread (the prefetch producer),
        re-yields single items and is the ONE place that writes
        ``task.emitted`` / ``task.exhausted`` and yields ``NUDGE``.
      * A ``FLUSH`` of the task source is held until every video dispatched
        before it has ended — it must not overtake windows still decoding,
        or a feed that goes idle right after it would leave them pooled (as
        ``farm._append_flush``). A ``FLUSH`` of a live window source rides
        in its lane's chunk, behind that video's own windows, and hands the
        chunk over at once.
      * A video that fails to open or decode fails its own task and its
        lane takes the next; ``task.failed`` set by the consumer stops that
        video's lane. Closing the generator stops and joins every lane and
        closes every window source (``packed_windows`` closes its loader).

    With the tracer on each lane records one ``decode+preprocess`` span a
    CHUNK under ``span_tid`` = lane (``span_attrs(task)`` ride on it), the
    wait that ends in a ``FLUSH`` as ``queue_idle``, and ``stats`` (one dict
    a lane: ``videos``, ``windows``, ``chunks`` and, tracer on, ``busy_s``,
    ``blocked_s``: seconds blocked on the full hand-over queue, and
    ``first_chunk_s``: seconds from opening a video to handing over its
    first chunk, summed over the lane's videos) is filled in place. Tracer
    off: no clock is read.
    """
    lanes = max(int(lanes), 1)
    timed = tracer.enabled
    out: 'queue.Queue' = queue.Queue(maxsize=lanes)
    todo: 'queue.SimpleQueue' = queue.SimpleQueue()
    idle = threading.Semaphore(lanes)
    stop = threading.Event()
    if stats is None:
        stats = []
    stats[:] = [{'videos': 0, 'windows': 0, 'chunks': 0, 'busy_s': 0.0,
                 'blocked_s': 0.0, 'first_chunk_s': 0.0}
                for _ in range(lanes)]

    def hand_over(msg) -> bool:
        """Blocking put that gives up once the stream is closed."""
        while not stop.is_set():
            try:
                out.put(msg, timeout=_POLL_S)
                return True
            except queue.Full:
                continue
        return False

    def dispatch() -> None:
        n = 0
        try:
            it = iter(tasks)
            while True:
                # a lane takes the next task when it finishes one: the
                # source is not read ahead of an idle lane
                while not idle.acquire(timeout=_POLL_S):
                    if stop.is_set():
                        return
                while True:
                    t0 = time.perf_counter() if timed else 0.0
                    item = next(it, _END)
                    if item is not FLUSH:
                        break
                    if timed:
                        tracer.add('queue_idle', time.perf_counter() - t0,
                                   t0=t0)
                    if not hand_over((_FLUSH_MARK, n)):
                        return
                if item is _END or stop.is_set():
                    break
                todo.put((n, item))
                n += 1
            hand_over((_END, n))
        # vft-lint: ok=swallowed-exception — shipped, not swallowed: the
        # merging generator re-raises what a thread posts
        except BaseException as e:
            hand_over((_ERROR, e))
        finally:
            for _ in range(lanes):
                todo.put(None)

    def run_video(lane: int, seq: int, task) -> None:
        st = stats[lane]
        st['videos'] += 1
        attrs = span_attrs(task) if timed and span_attrs is not None else {}
        chunk: list = []
        nbytes = 0
        first = True
        t_chunk = t_prev = time.perf_counter() if timed else 0.0

        def send(ended: bool, t_end: float) -> None:
            nonlocal chunk, nbytes, t_chunk, t_prev, first
            if timed:
                dt = t_end - t_chunk
                st['busy_s'] += dt
                if first:
                    # the first chunk's span starts where the video opens
                    st['first_chunk_s'] += dt
                first = False
                tracer.add('decode+preprocess', dt, t0=t_chunk,
                           span_tid=lane, lane=lane,
                           windows=len(chunk) - (chunk[-1:] == [FLUSH]),
                           **attrs)
                t_put = time.perf_counter()
            st['chunks'] += 1
            hand_over((_CHUNK, seq, task, chunk, ended))
            chunk, nbytes = [], 0
            if timed:
                t_chunk = t_prev = time.perf_counter()
                st['blocked_s'] += t_chunk - t_put

        windows = None
        try:
            windows = open_windows(task)
            for item in windows:
                now = time.perf_counter() if timed else 0.0
                if stop.is_set() or task.failed:
                    # closed, or the consumer failed this video mid-run
                    # (device-step fault): stop decoding the rest of it
                    break
                if item is FLUSH:
                    # a LIVE window source marks an arrival lull: the
                    # wait was the session's, not decode, and the chunk
                    # must not sit in this lane until the next frame
                    chunk.append(FLUSH)
                    idle_from = t_prev
                    send(False, idle_from)
                    if timed:
                        tracer.add('queue_idle', now - idle_from,
                                   t0=idle_from, span_tid=lane)
                    continue
                # the stream's item is built here, not on the one
                # thread that merges
                chunk.append((task, item[0], item[1]))
                st['windows'] += 1
                nbytes += getattr(item[0], 'nbytes', 0)
                t_prev = now
                if len(chunk) >= CHUNK_WINDOWS or nbytes >= CHUNK_BYTES:
                    send(False, now)
        except Exception:
            _fail_decode(task)
        finally:
            try:
                close = getattr(windows, 'close', None)
                if close is not None:
                    close()       # the window source closes its loader
            finally:
                # always: only the merging generator may end the task
                send(True, time.perf_counter() if timed else 0.0)

    def lane_loop(lane: int) -> None:
        try:
            while True:
                job = todo.get()
                if job is None or stop.is_set():
                    return
                run_video(lane, *job)
                idle.release()
        # vft-lint: ok=swallowed-exception — shipped, not swallowed
        except BaseException as e:
            hand_over((_ERROR, e))

    threads = [threading.Thread(target=dispatch, daemon=True,
                                name='vft-decode-dispatch')]
    threads += [threading.Thread(target=lane_loop, args=(i,), daemon=True,
                                 name=f'vft-decode-lane-{i}')
                for i in range(lanes)]
    for t in threads:
        t.start()
    try:
        total = None            # videos dispatched, once the source ended
        ended = 0
        low = 0                 # every video with seq < low has ended
        done: set = set()
        held: 'deque' = deque()     # FLUSH watermarks not yet passed
        while total is None or ended < total:
            msg = out.get()
            kind = msg[0]
            if kind is _ERROR:
                raise msg[1]
            if kind is _END:
                total = msg[1]
                continue
            if kind is _FLUSH_MARK:
                if not held and msg[1] <= low:
                    yield FLUSH
                else:
                    held.append(msg[1])
                continue
            _, seq, task, chunk, last = msg
            for item in chunk:
                if item is FLUSH:
                    yield FLUSH
                elif not task.failed:
                    task.emitted += 1
                    yield item
            if last:
                task.exhausted = True
                if task.emitted == 0:
                    # no batch will ever carry this video's completion
                    yield NUDGE
                ended += 1
                done.add(seq)
                while low in done:
                    done.remove(low)
                    low += 1
                while held and held[0] <= low:
                    held.popleft()
                    yield FLUSH
    finally:
        stop.set()
        for _ in range(lanes):
            todo.put(None)      # wake lanes waiting for a task
        # a lane inside one long decode call, or the dispatcher inside a
        # source's next() that nobody can interrupt (an idle serve feed),
        # is abandoned after _JOIN_S: both are daemons
        for t in threads:
            t.join(_JOIN_S)


def stream_windows(batches: Iterable, win: int, step: int,
                   tracer: Tracer = NULL_TRACER,
                   stage: str = 'decode',
                   frame_range: Optional[Tuple[int, int]] = None,
                   ) -> Iterator[np.ndarray]:
    """Yield (win, ...)-shaped frame windows from a loader's batch stream.

    ``batches`` iterates ``(batch, times, indices)`` tuples (the VideoLoader
    protocol); decode work inside ``next()`` is timed under ``stage``, the
    copy that assembles each window under ``pack``.

    ``frame_range`` (segment queries) restricts the emitted windows to
    those OVERLAPPING the half-open frame range ``[start_f, end_f)``:
    window k spans frames ``[k·step, k·step + win)``, and the first /
    last covered k follow from that. The iterator stops pulling decode
    batches as soon as the last covered window completes, so decode cost
    is proportional to the covered range's END, never the whole video
    (sequential decoders can't seek, so frames BEFORE the range still
    decode but are dropped without stacking).

    A bare ``parallel.packing.FLUSH`` item in ``batches`` passes through
    untouched (live sessions mark arrival lulls mid-stream) — this is
    what lets the live-session layer run its network frames through THIS
    windower, so live and file-backed windowing can never diverge.
    """
    buf: List[np.ndarray] = []
    offset = 0          # absolute frame index of buf[0]
    next_start = 0      # absolute start of the next window
    end_f = None
    if frame_range is not None:
        start_f, end_f = frame_range
        if start_f >= end_f:
            return          # empty range: no window overlaps it
        # first window whose span reaches into the range:
        # k·step + win > start_f
        k_min = max(0, (start_f - win) // step + 1)
        next_start = k_min * step
        if next_start >= end_f:
            return
    for item in tracer.wrap_iter(stage, batches):
        if item is FLUSH:
            yield FLUSH
            continue
        batch = item[0]
        buf.extend(batch)
        # drop frames the next window can no longer touch
        d = min(next_start - offset, len(buf))
        if d > 0:
            del buf[:d]
            offset += d
        while next_start + win <= offset + len(buf):
            s = next_start - offset
            with tracer.stage('pack'):
                window = np.stack(buf[s:s + win])
            yield window
            next_start += step
            if end_f is not None and next_start >= end_f:
                return      # past the range: stop decoding the tail
            d = min(next_start - offset, len(buf))
            if d > 0:
                del buf[:d]
                offset += d
