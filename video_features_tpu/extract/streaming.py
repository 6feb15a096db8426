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
"""
from __future__ import annotations

import math
from typing import Callable, Iterable, Iterator, List, Optional, Tuple

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
    producer (h2d was a 6–11.5% share serialized before dispatch in
    BENCH_r05). Each staged unit keeps one more input batch resident on
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
    from video_features_tpu.extract.base import log_extraction_error
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
            task.failed = True
            # structured fault report: the serve request id (None for CLI
            # tasks) and the stage that died ride on the log record
            log_extraction_error(
                task.path, stage='decode',
                request_id=getattr(getattr(task, 'request', None), 'id',
                                   None))
        finally:
            task.exhausted = True
        if task.emitted == 0:
            # no batch will ever carry this video's completion (resume
            # skip / too-short clip / failed open): NUDGE the consumer so
            # it finalizes NOW — a dynamic stream may not end for hours
            yield NUDGE


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
