"""Corpus-level packed execution: batch-major scheduling across videos.

The reference (and, until this module, this framework) runs a video-major
outer loop: every video separately streams its windows into the compiled
device step, so at corpus shapes (K400: a handful of stack windows per
clip) the last batch of every video runs mostly padded and every video
pays the pipeline ramp (prefetch fill, cache warm, H2D latency) again.

This module inverts the loop — batch-major over the whole worklist:

  * a cross-video window stream drains clip stacks / frames from the
    worklist with per-video fault isolation: up to K videos at once on
    in-process decode lanes (``extract.streaming.
    stream_windows_across_lanes``; K from the cores and the worklist,
    ``decode_lane_plan``), one video after another at K = 1
    (``stream_windows_across_videos``), or the decode farm's worker
    processes (``farm/``) at an explicit ``decode_workers`` > 1;
  * the packer fills every device batch to capacity with
    (video, window_idx) provenance, grouping by window geometry so mixed
    corpora still feed fixed-shape executables. It runs on the thread
    that drains the window stream (``io.video.prefetch``, a lookahead of
    ``decode_ahead`` finished batches, so the decoder keeps working
    across video boundaries) and copies each window into a recycled host
    batch buffer (``BatchBuffers``) as it arrives; the transfer thread
    only places finished batches on the device;
  * the device loop is asynchronous on BOTH sides: ``packed_step`` only
    DISPATCHES (device arrays out, no forced readback), and a bounded
    in-flight queue (the ``inflight`` knob, default 2; 1 = synchronous)
    defers each batch's D2H readback until the next batch has
    dispatched — so readback, row scatter, and output writes overlap
    device compute instead of stalling it;
  * features scatter back into per-video accumulators that flush as each
    video completes (NOT necessarily in worklist order — a video whose
    geometry pool can't fill must not block videos behind it) through the
    UNCHANGED per-video output contract (``is_already_exist`` skip,
    idempotent ``action_on_extraction`` writes, identical filenames) —
    the same files as the per-video loop, except the chip stays fed.

Composition: batches go through ``BaseExtractor.put_input``, so
``data_parallel=true`` sharding works unchanged; the worklist arrives
already sharded per host in multihost runs (``cli.py``), so packing is a
per-host concern and needs no cross-host coordination.

Since the serving layer (``serve/``) the worklist no longer has to be a
static list: ``run_packed`` consumes its ``video_paths`` iterable lazily
(it may block — e.g. on a request queue) and accepts pre-built
``VideoTask`` objects, so dynamically arriving requests pack into the
same device batches as a static corpus. The ``FLUSH`` sentinel bounds
latency under dynamic arrivals: when the source momentarily runs dry it
can push ``FLUSH`` through the stream to force the partial geometry
pools out as padded batches instead of holding a lone request's windows
hostage until the next request happens to share its geometry.
"""
from __future__ import annotations

import logging as _logging
import sys
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

# the stream sentinels live with the windowers that pass them through
# (a jax-free module: decode-farm workers run it); re-exported here, where
# the scheduler consumes them and most callers import them from
from video_features_tpu.extract.streaming import FLUSH, NUDGE  # noqa: F401
from video_features_tpu.obs.context import trace_attrs, trace_ids_of
from video_features_tpu.obs.events import event
from video_features_tpu.utils.tracing import NULL_TRACER, Tracer


def _request_id(task) -> Optional[str]:
    """The originating request id of a serve task (None for CLI tasks) —
    threaded onto span/instant events so a Perfetto timeline groups by
    request as well as by video."""
    req = getattr(task, 'request', None)
    return getattr(req, 'id', None)


def segment_name(path: str, segment) -> str:
    """Output-naming path for a ``(start_s, end_s)`` segment extraction:
    the video's stem gains a ``_seg<start>-<end>ms`` suffix (millisecond
    ints — dots in a stem would truncate under ``Path(...).stem``), so a
    partial-range extraction NEVER collides with the full video's output
    files (or another range's) in a shared output root. The same
    quantization keys the cache (``cache.key.video_cache_key``)."""
    if segment is None:
        return str(path)
    from pathlib import Path as _Path
    p = _Path(path)
    start_ms = int(round(float(segment[0]) * 1000))
    end_ms = int(round(float(segment[1]) * 1000))
    return str(p.with_name(f'{p.stem}_seg{start_ms}-{end_ms}ms{p.suffix}'))


class VideoTask:
    """Per-video scheduling + scatter-back state for the packed pipeline.

    ``emitted`` counts windows the decode side yielded, ``done`` counts
    windows whose features have scattered back; the video is complete when
    ``exhausted and done == emitted``. ``skipped`` (resume hit) and
    ``failed`` both finalize without writing. ``rows``/``meta_rows`` accumulate
    the scattered per-window feature rows (in window order — the packer
    preserves per-video FIFO because a video's windows share one geometry
    pool); ``info`` carries video-level metadata (e.g. fps) set by the
    extractor's window stream. ``out_root`` (None for CLI worklists)
    overrides the extractor's ``output_path`` for this one video — the
    serving layer routes concurrent requests with different output roots
    through one shared warm extractor.
    """

    __slots__ = ('path', 'video_id', 'rows', 'meta_rows', 'info',
                 'emitted', 'done', 'exhausted', 'failed', 'skipped',
                 'cached', 'out_root', 'finalized', 'segment', 'trace')

    def __init__(self, path: str, video_id: int = -1,
                 out_root: Optional[str] = None,
                 segment: Optional[tuple] = None,
                 trace=None) -> None:
        self.path = path
        self.video_id = video_id
        self.out_root = out_root
        # request-scoped trace context (obs/context.TraceContext, or
        # None for legacy CLI tasks): every span/instant this task's
        # work produces carries its trace_id/span_id, so one request's
        # timeline is a single filter over the merged export
        self.trace = trace
        # optional (start_s, end_s) time range (segment queries): the
        # windower decodes/extracts only the covered windows, outputs
        # are named via name_path, and the cache keys on the range.
        # Quantized to MILLISECONDS here — the one choke point — so the
        # frame filter, the output name, and the cache key all derive
        # from the same value: two sub-ms-different ranges must never
        # share a cache key while selecting different frames.
        if segment is not None:
            segment = (round(float(segment[0]), 3),
                       round(float(segment[1]), 3))
        self.segment = segment
        self.rows: Dict[str, List[np.ndarray]] = {}
        self.meta_rows: List = []
        self.info: Dict = {}
        self.emitted = 0
        self.done = 0
        self.exhausted = False
        self.failed = False
        self.skipped = False
        # skipped via a content-addressed cache hit (outputs materialized
        # from the cache rather than found on disk) — consumers that care
        # about the difference (serve per-video states, metrics) read it
        self.cached = False
        # terminal: finalize() ran (saved/failed/skipped, cache published,
        # on_video_done fired). The decode farm's dedupe reads it — a
        # parked duplicate waits for its twin's publish, never a
        # mid-flight state.
        self.finalized = False

    @property
    def name_path(self) -> str:
        """The path output files are NAMED after: the real path, or the
        segment-suffixed pseudo-path for a range extraction (so partial
        and full outputs never collide in one root). Decode and content
        hashing always use the real ``path``."""
        return segment_name(self.path, self.segment)


class FusedTask(VideoTask):
    """One video inside a fused multi-family run: the CARRIER the shared
    decode stream flows through, plus one per-family subtask.

    The carrier owns everything the decode side touches (``emitted`` /
    ``exhausted`` / ``failed`` / ``info`` — the farm and the in-process
    windower keep their bookkeeping unchanged on it); each family's
    scatter-back, fault isolation, and finalization state lives on its
    SUBTASK, a plain :class:`VideoTask` that the family's unchanged
    save/cache/finalize path consumes. A family's device-step fault
    fails only its subtask — the shared decode keeps feeding the
    healthy siblings; a DECODE fault fails the carrier, which fails
    every still-active subtask at finalize.

    ``active`` is the family subset still wanting this video after
    per-family admission (resume skips / cache hits drop out);
    ``farm_select`` mirrors it onto the farm task message so skipped
    families also drop out of the worker's transform fan-out.
    """

    __slots__ = ('subtasks', 'active', 'farm_select')

    def __init__(self, path: str, families: Iterable[str],
                 video_id: int = -1,
                 segment: Optional[tuple] = None, trace=None) -> None:
        super().__init__(path, video_id=video_id, segment=segment,
                         trace=trace)
        self.subtasks: Dict[str, VideoTask] = {
            fam: VideoTask(path, video_id=video_id, segment=segment,
                           trace=trace)
            for fam in families}
        self.active: List[str] = list(self.subtasks)
        self.farm_select = None


def _geometry(shape: tuple, dtype) -> str:
    """A batch buffer's geometry as the manifest names it:
    ``'1024x224x224x3 uint8'``."""
    return f'{"x".join(str(n) for n in shape)} {np.dtype(dtype).name}'


class BatchBuffers:
    """Host batch buffers of the packed path, recycled per geometry.

    ``packed_batches`` takes one buffer of ``(capacity, *window_shape)``
    for each batch it starts and copies windows into it as they arrive;
    the device loop gives the buffer back once the step that read it has
    been fetched — never when ``device_put`` returns: on a TPU the copy
    is still running then, and the CPU backend may alias the host array
    outright. A take finds a given-back buffer of its geometry, or
    allocates a new one, so nothing ever waits here and the count per
    geometry settles at what the pipeline holds at once (the lookahead,
    the staged transfers, the steps in flight and the batch filling).

    The extractor owns one (``BaseExtractor._packed_setup``), so idle
    buffers outlive a run and the next run recycles them: host memory
    stays held between runs, the count × the batch's bytes per geometry
    (5 × 154 MB in ``resnet50.corpus``). Idle buffers are kept for the
    ``MAX_IDLE_GEOMETRIES`` geometries taken last and dropped for older
    ones, so a long-lived daemon that sees many geometries holds a bounded
    number. Thread-safe: takes run on the packer's thread, gives back on
    the dispatch thread. ``stats()`` per geometry: ``buffers`` allocated,
    ``batches`` started, ``recycled`` of them in a given-back buffer, and
    ``pack_recycled`` = recycled ÷ batches.
    """

    MAX_IDLE_GEOMETRIES = 4

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._idle: 'OrderedDict[tuple, List[np.ndarray]]' = OrderedDict()
        self._stats: Dict[tuple, Dict[str, int]] = {}

    def take(self, shape: tuple, dtype) -> Tuple[np.ndarray, bool]:
        """A buffer of ``shape``/``dtype`` and whether it was recycled."""
        key = (tuple(shape), np.dtype(dtype).str)
        with self._lock:
            st = self._stats.setdefault(
                key, {'buffers': 0, 'batches': 0, 'recycled': 0})
            st['batches'] += 1
            idle = self._idle.setdefault(key, [])
            self._idle.move_to_end(key)
            while len(self._idle) > self.MAX_IDLE_GEOMETRIES:
                self._idle.popitem(last=False)
            if idle:
                st['recycled'] += 1
                return idle.pop(), True
            st['buffers'] += 1
        return np.empty(shape, dtype), False

    def give_back(self, buf: np.ndarray) -> None:
        """Return a buffer whose step has been fetched: nothing reads it
        any more."""
        key = (buf.shape, buf.dtype.str)
        with self._lock:
            idle = self._idle.get(key)
            if idle is not None:
                idle.append(buf)

    def counts(self) -> Dict[tuple, Dict[str, int]]:
        """The raw counts so far, for ``stats(since=)``."""
        with self._lock:
            return {key: dict(st) for key, st in self._stats.items()}

    def stats(self, since: Optional[Dict] = None) -> Dict[str, Dict]:
        """Per geometry; with ``since`` (an earlier ``counts()``), the
        batches and recycled ones that came after it — one run's — beside
        every buffer the geometry was given so far."""
        since = since or {}
        out = {}
        for key, st in self.counts().items():
            before = since.get(key, {})
            st = dict(st, batches=st['batches'] - before.get('batches', 0),
                      recycled=st['recycled'] - before.get('recycled', 0))
            if st['batches']:
                out[_geometry(*key)] = dict(
                    st, pack_recycled=st['recycled'] / st['batches'])
        return out


# A run of copies shares one ``pack`` span while each window arrives
# within this many seconds of the last copy's end: the packer's own
# bookkeeping between the windows of one hand-over chunk. A longer gap is a
# wait (for a lane's next chunk, for decode) and ends the span before it.
PACK_SPAN_GAP_S = 50e-6


def packed_batches(windows: Iterable[tuple], batch: int,
                   max_pool_age_s: Optional[float] = None,
                   tracer: Tracer = NULL_TRACER,
                   family_of: Optional[Callable] = None,
                   family_batch: Optional[Dict] = None,
                   buffers: Optional[BatchBuffers] = None,
                   ) -> Iterator[Tuple[np.ndarray, list, int]]:
    """Group a cross-video ``(task, window, meta)`` stream into full
    fixed-size batches: ``(stacks, provenance, valid)`` where provenance is
    the per-slot ``(task, meta)`` list for the ``valid`` real slots.

    Windows pool per geometry (shape, dtype) so a mixed-resolution corpus
    still feeds fixed-shape compiled steps — a batch only ever mixes
    windows of identical geometry, and each geometry's pool holds at most
    ``batch - 1`` windows (memory stays bounded by the number of DISTINCT
    geometries in flight, not by corpus size).

    ``family_of`` (fused worklists) extends the pool key with the window
    meta's FAMILY, so a fused stream where two families share a geometry
    (resnet and clip both emit 224×224×3 uint8) still never mixes
    families in one batch — each family's batches must feed that
    family's own compiled program. ``family_batch`` (family → capacity)
    then lets each family's pools fill/pad at ITS packed batch size, so
    a fused run dispatches the exact per-family programs a sequential
    run compiles (no new program identities, no AOT-store misses). Tail pools flush padded
    (repeating the last window, masked via ``valid``) only once the whole
    worklist is drained — that final partial batch per geometry is the only
    padding the corpus pays, vs one per video in the per-video loop.

    A ``FLUSH`` item in the stream forces that tail flush early, for
    dynamic sources whose "worklist" has momentarily run dry: a serving
    queue must bound a lone request's latency by batch-padding now rather
    than waiting for future arrivals to fill the pool. Every ``FLUSH``
    (and every ``NUDGE``) is forwarded as the batchless drain marker
    ``(None, [], 0)`` after its pools flush, telling the consumer to
    materialize its in-flight output queue too — the async device loop
    defers D2H until the NEXT dispatch, and on an idle dynamic source
    that next dispatch may be hours away.

    ``max_pool_age_s`` (serving: ``serve_max_batch_wait_s``) additionally
    ages pools OUT-OF-BAND of the source: any pool whose oldest window
    has waited that long flushes padded as the next window — of ANY
    geometry — arrives. This is what bounds a lone odd-geometry request
    under CONTINUOUS traffic, where the upstream feed is never idle (and
    so never emits FLUSH) but other geometries' windows keep flowing.

    Each pool fills one host buffer of ``(capacity, *shape)`` from
    ``buffers`` (None: a private ``BatchBuffers`` that nothing is given
    back to, so every batch gets a new buffer): a window is copied into
    the next free slot as it arrives, and a flush hands the buffer over
    whole, its tail slots filled with the last window — the bytes
    ``np.stack`` of the pooled windows would give. The copies are the
    ``pack`` stage: one span a run of back-to-back copies (one a lane's
    hand-over chunk; ``PACK_SPAN_GAP_S``), never the wait for the next
    window, and one a flush (the tail fill, carrying the batch's videos
    and trace ids). ``pack_recycled`` counts, a batch, whether its buffer
    was a recycled one. Closing the generator closes ``windows``.
    """
    if buffers is None:
        buffers = BatchBuffers()
    timed = tracer.enabled
    # key → [buffer or None, [(task, meta)], recycled]; a key stays once
    # seen, so FLUSH and the drain visit geometries in first-seen order
    pools: Dict[tuple, list] = {}
    ages: Dict[tuple, float] = {}      # key → oldest pooled window's time
    run = [0.0, 0.0, 0]                # the open pack span: t0, end, copies

    def end_run() -> None:
        if run[2]:
            tracer.add('pack', run[1] - run[0], t0=run[0], windows=run[2])
            run[2] = 0

    def cap_of(key) -> int:
        # fused pools are keyed (family, shape, dtype) and fill at that
        # family's own packed batch size
        if family_batch is not None:
            return int(family_batch[key[0]])
        return batch

    def flush(key):
        buf, prov, recycled = pools[key]
        pools[key] = [None, [], False]
        ages.pop(key, None)
        valid = len(prov)
        # the span attrs (videos in the batch) are built ONLY when
        # tracing is on, so the default hot loop stays allocation-free.
        # getattr, not t.path: unit tests drive the packer with plain
        # task tokens.
        attrs = ({'videos': sorted({str(getattr(t, 'path', t))
                                    for t, _ in prov}),
                  'valid': valid, 'capacity': len(buf)}
                 if timed else {})
        if timed:
            # batch spans serve several requests at once: carry the SET
            # of trace ids so a per-request trace filter still finds the
            # shared pack/model/d2h work it rode on
            tids = trace_ids_of(t for t, _ in prov)
            if tids:
                attrs['trace_ids'] = tids
        end_run()
        with tracer.stage('pack', **attrs):
            buf[valid:] = buf[valid - 1]
        tracer.add_occupancy('pack_recycled', int(recycled), 1)
        return buf, prov, valid

    try:
        for item in windows:
            if item is FLUSH:
                for key in list(pools):
                    if pools[key][1]:
                        yield flush(key)
                # always follow with the batchless drain marker: the
                # source is momentarily idle, so the consumer must ALSO
                # materialize its in-flight output queue (async device
                # loop) — without this, a lone request's LAST dispatched
                # batch would wait on future traffic to push it through
                # the deferred-D2H window
                end_run()
                yield None, [], 0
                continue
            if item is NUDGE:
                # batchless marker: lets the consumer sweep for
                # zero-window videos without waiting for a real batch (or
                # stream end)
                end_run()
                yield None, [], 0
                continue
            task, window, meta = item
            window = np.asarray(window)
            key = (window.shape, window.dtype.str)
            if family_of is not None:
                key = (family_of(meta),) + key
            pool = pools.setdefault(key, [None, [], False])
            prov = pool[1]
            if not prov:
                ages[key] = time.monotonic()
                pool[0], pool[2] = buffers.take(
                    (cap_of(key),) + window.shape, window.dtype)
            if timed:
                t0 = time.perf_counter()
                pool[0][len(prov)] = window
                t1 = time.perf_counter()
                if run[2] and t0 - run[1] <= PACK_SPAN_GAP_S:
                    run[1] = t1
                    run[2] += 1
                else:
                    end_run()
                    run[:] = [t0, t1, 1]
            else:
                pool[0][len(prov)] = window
            prov.append((task, meta))
            if len(prov) == cap_of(key):
                yield flush(key)
            if max_pool_age_s is not None:
                now = time.monotonic()
                for k in list(pools):
                    if pools[k][1] and now - ages[k] >= max_pool_age_s:
                        yield flush(k)
        for key in list(pools):
            if pools[key][1]:
                yield flush(key)
        end_run()
    finally:
        close = getattr(windows, 'close', None)
        if close is not None:
            close()         # the lane windower joins its decode threads


def _admit_task(ex, task: VideoTask) -> bool:
    """The per-video admission gate, shared by the single-family and
    fused packed drivers (fused runs it once per (family, video) against
    that family's extractor — resume skips and cache hits stay
    per-family). False means the video is terminal for ``ex`` without
    decoding; ``task.skipped``/``task.cached`` say why."""
    # ephemeral tasks (ingress live sessions) have no file behind
    # them: nothing to resume, nothing to content-hash — always run
    if getattr(task, 'ephemeral', False):
        return True
    # The resume check runs here — lazily, as the decode side reaches
    # each video — NOT as an up-front scan: is_already_exist loads
    # every output file, and an eager pass over a mostly-done 20K
    # worklist would block for minutes before the first batch packs.
    # Amortized across the run it costs what the per-video loop paid.
    # (The farm's dispatcher keeps the same property via its bounded
    # assignment runahead.)
    # the output_path kwarg is passed only when a task carries a
    # per-request root: hooks monkeypatched/overridden with the
    # classic (self, video_path) signature keep working for CLI runs.
    # name_path (== path unless the task carries a segment range)
    # keys both resume and the cache materialization target, so a
    # range extraction never reuses — or clobbers — full outputs.
    name = task.name_path
    exists = (ex.is_already_exist(name, output_path=task.out_root)
              if task.out_root is not None
              else ex.is_already_exist(name))
    if exists:
        task.skipped = True
        return False
    # content-addressed cache: a hit materializes this video's outputs
    # right here and drops it from batch planning entirely — it never
    # decodes, never occupies batch slots, and finalizes through the
    # same sweep/on_video_done path as a resume skip
    if getattr(ex, 'cache', None) is not None and \
            ex.cache_fetch(task.path, output_path=task.out_root,
                           segment=task.segment, name_path=name):
        task.skipped = True
        task.cached = True
        return False
    return True


def _finalize_task(ex, t: VideoTask, recorder=None, manifest=None,
                   on_video_done: Optional[Callable] = None) -> None:
    """Finalize one (family, video): save/publish (unless skipped or
    failed), free its rows, stamp the outcome on the recorder/manifest,
    fire ``on_video_done``. Shared by the single-family driver's sweep
    and the fused driver's per-family fan-out — the fused path MUST go
    through the identical save/cache code for its byte-identity
    contract."""
    from video_features_tpu.extract.base import log_extraction_error
    try:
        if not (t.failed or t.skipped
                or getattr(t, 'stream_only', False)):
            # stream_only (live sessions) already delivered every
            # window through on_window — nothing to save or publish
            feats_dict = ex._maybe_concat_streams(ex.packed_result(t))
            with ex.tracer.stage('save', video=str(t.path),
                                 request_id=_request_id(t),
                                 **trace_attrs(t)):
                if t.out_root is not None:
                    ex.action_on_extraction(feats_dict, t.name_path,
                                            output_path=t.out_root)
                else:
                    ex.action_on_extraction(feats_dict, t.name_path)
            if getattr(ex, 'cache', None) is not None:
                with ex.tracer.stage('cache_publish',
                                     video=str(t.path)):
                    ex.cache_publish(t.path, output_path=t.out_root,
                                     segment=t.segment,
                                     name_path=t.name_path)
    except KeyboardInterrupt:
        raise
    except Exception:
        t.failed = True           # a failed save IS a failed video
        log_extraction_error(t.path, request_id=_request_id(t),
                             stage='save')
    finally:
        t.rows = {}               # free feature memory as we go
        t.finalized = True        # the farm's dedupe unparks twins now
        from video_features_tpu.utils.output import ACTION_TO_EXT
        outcome = ('failed' if t.failed else 'cached' if t.cached
                   else 'skipped' if t.skipped
                   else 'saved' if ex.on_extraction in ACTION_TO_EXT
                   else 'printed')
        if t.failed:
            ex.failed_videos += 1     # read by cli.main for the exit code
        if recorder is not None:
            recorder.instant('video_done', video=str(t.path),
                             outcome=outcome,
                             request_id=_request_id(t),
                             **trace_attrs(t))
        if manifest is not None:
            manifest.video_done(t.path, outcome)
        if on_video_done is not None:
            on_video_done(t)


def _decode_plan(ex, decode_workers: Optional[int], video_paths) -> Dict:
    """The input side's plan for one packed run
    (``streaming.decode_lane_plan``): the run-level ``decode_workers``
    wins over the extractor's (a directly constructed extractor without
    the attribute keeps the serial default); the videos at hand are
    known only for a sized worklist, not for a serve feed."""
    from video_features_tpu.extract.streaming import decode_lane_plan
    if decode_workers is None:
        decode_workers = getattr(ex, 'decode_workers', 1)
    return decode_lane_plan(
        decode_workers,
        videos=len(video_paths) if hasattr(video_paths, '__len__')
        else None)


def _without_farm(plan: Dict) -> Dict:
    """The plan of a run whose farm could not be had (no recipe, no
    shared memory): the serial windower, and the manifest says so."""
    if not plan['farm_workers']:
        return plan
    return dict(plan, farm_workers=0,
                why=plan['why'] + ', not to be had: the serial windower')


def _decode_note(plan: Dict, farm) -> str:
    """The stage-table header's word on the input side."""
    if farm is not None:
        return f'{farm.n_workers} decode-farm workers'
    return (f'{plan["lanes"]} decode lane'
            + ('s' if plan['lanes'] != 1 else ''))


def _in_process_windows(tasks: Iterator, open_windows: Callable,
                        plan: Dict, tracer: Tracer,
                        decode_attrs: Callable,
                        lane_stats: List[Dict]) -> Iterator:
    """The in-process window stream of both packed drivers, as the plan
    has it: ``plan['lanes']`` videos at once on decode lanes (which time
    their own work, a span a chunk), or the serial windower — under a
    consumer-side timing wrapper when the tracer is on."""
    from video_features_tpu.extract.streaming import (
        stream_windows_across_lanes, stream_windows_across_videos,
    )
    if plan['lanes'] > 1:
        return stream_windows_across_lanes(
            tasks, open_windows, plan['lanes'], tracer=tracer,
            span_attrs=decode_attrs, stats=lane_stats)
    source = stream_windows_across_videos(tasks, open_windows)
    if not tracer.enabled:
        return source

    def timed_source():
        # decode (and host preprocessing) runs on the prefetch producer
        # thread, ahead of the device across video boundaries; timed here
        # (inside the prefetch) so decode cost lands on the thread that
        # spends it. A dynamic source (serve) also BLOCKS in next() while
        # its request queue is idle — those spans surface as FLUSH items
        # and are attributed to a separate ``queue_idle`` stage, not
        # laundered into decode time. (Under lanes this wrapper would
        # time waits on the hand-over queue and call them decode, and
        # the farm traces its workers' own timings: neither uses it.)
        it = iter(source)
        while True:
            t0 = time.perf_counter()
            try:
                item = next(it)
            except StopIteration:
                return
            dt = time.perf_counter() - t0
            if item is FLUSH:
                tracer.add('queue_idle', dt, t0=t0)
            elif item is NUDGE:
                tracer.add('decode+preprocess', dt, t0=t0)
            else:
                tracer.add('decode+preprocess', dt, t0=t0,
                           **decode_attrs(item[0], item[2]))
            yield item

    return timed_source()


def run_packed(ex, video_paths: Iterable,
               batch_size: Optional[int] = None,
               decode_ahead: int = 2,
               on_video_done: Optional[Callable] = None,
               max_pool_age_s: Optional[float] = None,
               inflight: Optional[int] = None,
               decode_workers: Optional[int] = None) -> None:
    """Drive one extractor over the whole worklist, batch-major.

    ``video_paths`` yields ``str`` paths, pre-built :class:`VideoTask`
    objects (dynamic sources attach request state / ``out_root``), or the
    ``FLUSH`` sentinel; it is consumed LAZILY on the decode thread and may
    block — a serving queue feeds the packer exactly like a static
    worklist, the stream simply ends when the source drains.
    ``on_video_done(task)`` (if given) fires after each video finalizes —
    saved, skipped, failed, or empty — which is how the serving layer maps
    scattered videos back to request completions.

    Preserves every externally observable per-video contract:

      * resume — ``is_already_exist`` is checked as the decode side
        reaches each video (same skip message, amortized like the
        per-video loop — never an up-front O(corpus) scan) and re-checked
        by ``action_on_extraction`` right before writing, so concurrent
        workers still collide benignly;
      * outputs — identical filenames and array contents flow through the
        same ``_maybe_concat_streams`` + ``action_on_extraction`` path;
      * fault isolation — a video that fails to decode, compute, or save
        prints the same error and the worklist continues; windows it
        contributed to shared batches are computed but never saved, and a
        device-step failure (e.g. a geometry that won't compile) fails
        only the videos in that batch — one bad video cannot poison the
        batch it shares, nor abort the worklist.

    ``decode_ahead`` bounds the cross-video decode lookahead at
    ``decode_ahead`` finished batches: ``packed_batches`` runs on the
    thread that drains the window stream (``io.video.prefetch``), copying
    each window into a recycled host buffer (``ex.batch_buffers``) as it
    arrives, and ``transfer_batches``' thread only places finished
    batches on the device — the copy of batch k+1 overlaps the transfer
    of batch k. A buffer goes back for reuse once the step that read it
    has been fetched, so a geometry holds at most ``decode_ahead`` + 1
    (the lookahead and the batch filling or handed over) + 3 (the
    transfer thread's two staged batches and the one it is placing) +
    ``inflight`` buffers; the run manifest says how many it took.

    ``batch_size`` (default: the extractor's ``packed_batch_size``) is
    the PER-DEVICE capacity. With ``mesh_devices > 1`` the loop is
    mesh-sharded: batches plan at ``capacity × ndev``, ``put_input``
    shards each stacked batch over the data axis of the extractor's
    mesh (params replicated per chip — ``_ensure_packed_mesh``), and
    every device runs the family's unchanged packed program at its
    single-chip batch shape, so outputs are byte-identical at any
    device count. Uneven tails pad (and mask at scatter-back) exactly
    like single-device tails — a lone window never stalls the batch —
    and fault isolation is untouched: a poisoned window fails its
    video, not its shard. The ``model``/``d2h`` spans carry
    ``mesh_devices`` + per-shard valid counts, occupancy records both
    the global aggregate and each device's share, and the run manifest
    records the mesh shape.

    ``inflight`` (default: the extractor's ``inflight`` attribute, 2) is
    the OUTPUT-side pipelining depth: ``packed_step`` only dispatches
    (it returns device arrays), and the loop keeps up to ``inflight``
    dispatched batches queued before materializing the oldest one's
    results with ``ex.fetch_outputs`` — so the D2H readback, row
    scatter, ``sweep()`` finalization, and output writes of batch k-1
    all overlap the device computing batch k. ``inflight=1`` is exactly
    the old synchronous loop (dispatch, then immediately fetch), and
    outputs are byte-identical at any depth. Cost: each extra unit keeps
    one more output batch (B × feat_dim per stream) resident on device.
    Fault isolation covers BOTH failure sites — a dispatch-time error
    (e.g. a geometry that won't compile) and a sync-time error (an
    asynchronously raised execution fault surfacing in ``fetch_outputs``)
    each doom exactly the videos of the batch that produced them.

    ``decode_workers`` (default: the extractor's ``decode_workers``
    attribute) selects the INPUT side's parallelism
    (``streaming.decode_lane_plan``). Unset (``None``, the framewise
    ymls' default): in-process decode LANES — up to K videos of the
    worklist decode at once, each on its own thread
    (``stream_windows_across_lanes``), K from the usable cores (halved,
    at most 4) and never more than the videos of a sized worklist; K = 1
    is the serial windower. ``1`` is the serial in-process windower
    exactly as before; ``>1`` routes
    decode through the multi-process decode farm (``farm/``) — N worker
    processes running the extractor's published decode recipe, feeding
    this scheduler over shared-memory rings with the same stream
    contract, per-video fault isolation, and byte-identical outputs.
    Falls back to serial in-process decode (with a structured warning)
    when the extractor has no farm recipe or the host can't spawn
    workers. Outputs are byte-identical on every route: only which
    video's window sits in which batch slot changes.
    """
    from video_features_tpu.extract.streaming import (
        fetch_step, transfer_batches,
    )
    from video_features_tpu.io.video import prefetch

    ex._packed_setup()
    # mesh-sharded execution (mesh_devices > 1): the device loop plans
    # batches at capacity × ndev, put_input shards each stacked batch
    # over the data axis of the extractor's mesh (params replicated per
    # chip), and the in-flight queue / scatter-back below run UNCHANGED —
    # fetch_outputs gathers the sharded output, each row scatters to its
    # video, and a poisoned window still fails only its video. Per-shard
    # capacity equals the single-chip batch, so every device runs the
    # exact program the family was tuned for and outputs stay
    # byte-identical at any device count.
    ndev = ex._ensure_packed_mesh()
    capacity = int(batch_size or ex.packed_batch_size())
    if ndev > 1:
        from video_features_tpu.parallel.mesh import plan_device_batch
        batch = plan_device_batch(capacity, ex._mesh)
    else:
        batch = capacity

    def shard_valids(valid: int) -> list:
        """Per-device valid-slot counts for a ``valid``-row global batch:
        shard i holds rows [i·capacity, (i+1)·capacity) — uneven tails
        leave later shards partially (or fully) padded, masked at
        scatter-back like any other padding."""
        return [max(0, min(valid - i * capacity, capacity))
                for i in range(ndev)]

    # per-device telemetry labels ('d<jax device id>'), data-axis order
    dev_labels = ([f'd{d.id}' for d in ex._mesh.devices.flat]
                  if ndev > 1 else [])

    # which precision lane computed every model/d2h span of this run
    # (ops/precision.py): a trace or crash bundle must say which lane
    # produced it — an fp32-vs-bf16 perf or drift question is otherwise
    # unanswerable post-hoc
    compute_dtype = str(getattr(ex, 'compute_dtype', 'float32'))

    def mesh_attrs(valid: int) -> Dict:
        """Extra span attrs for mesh-sharded model/d2h stages: the mesh
        width and each shard's valid-slot count (empty single-device),
        plus the compute_dtype lane on every packed run."""
        if not ex.tracer.enabled:
            return {}
        attrs: Dict = {'compute_dtype': compute_dtype}
        if ndev > 1:
            attrs.update(mesh_devices=ndev,
                         shard_valid=shard_valids(valid))
        return attrs

    def record_occupancy(name: str, valid: int) -> None:
        """Aggregate occupancy at the GLOBAL capacity plus — on a mesh —
        one record per device shard at the per-device capacity; the two
        views never double-count (tracing.add_occupancy)."""
        ex.tracer.add_occupancy(name, valid, batch)
        if ndev > 1:
            for label, v in zip(dev_labels, shard_valids(valid)):
                ex.tracer.add_occupancy(name, v, capacity, device=label)

    recorder = getattr(ex.tracer, 'recorder', None)
    manifest = getattr(ex, 'manifest', None)

    # open_q doubles as the lazy task registry: the decode thread appends
    # each task as the source yields it (list.append is atomic; only the
    # consumer thread deletes), so a blocking dynamic source needs no
    # up-front worklist materialization.
    open_q: List[VideoTask] = []
    n_started = [0]

    # the extractor's run-level trace context (CLI runs with trace_out:
    # configure_obs mints one — "a CLI run is one request"): bare paths
    # wrap into tasks carrying a child span under it, so the packed
    # path's spans are trace-filterable exactly like serve requests'.
    # Pre-built tasks (serve) already carry their request's context.
    run_ctx = getattr(ex, 'trace_ctx', None)

    def task_stream() -> Iterator:
        for item in video_paths:
            if item is FLUSH:
                yield FLUSH
                continue
            task = (item if isinstance(item, VideoTask)
                    else VideoTask(item,
                                   trace=(run_ctx.child()
                                          if run_ctx is not None
                                          else None)))
            task.video_id = n_started[0]
            n_started[0] += 1
            open_q.append(task)
            if recorder is not None:
                recorder.instant('video_start', video=str(task.path),
                                 request_id=_request_id(task),
                                 **trace_attrs(task))
            yield task

    def admit(task: VideoTask) -> bool:
        return _admit_task(ex, task)

    def open_windows(task: VideoTask):
        if not admit(task):
            return iter(())
        # live tasks (ingress live sessions) carry their own window
        # source — frames arriving over the network, windowed to the
        # extractor's geometry — instead of decoding task.path
        override = getattr(task, 'windows_override', None)
        if override is not None:
            return override(ex)
        return ex.packed_windows(task)

    # flush each video as soon as its last window's features land. NOT
    # strictly in worklist order: a video whose geometry pool can't fill
    # (e.g. the lone odd-resolution clip in a mixed corpus — its tail
    # windows sit pooled until the final drain) must not hold up every
    # video behind it, or their accumulated rows pin O(corpus) host RAM
    # and a crash loses outputs that were long since computed. The scan
    # stops at the first video the decode side hasn't reached (videos
    # start strictly in worklist order), so each sweep touches only the
    # small in-flight window, not the whole worklist.

    def finalize(t: VideoTask) -> None:
        _finalize_task(ex, t, recorder=recorder, manifest=manifest,
                       on_video_done=on_video_done)

    def sweep(final: bool = False) -> None:
        i = 0
        while i < len(open_q):
            t = open_q[i]
            if not t.exhausted and t.emitted == 0:
                break                 # decode hasn't reached this video yet
            if t.exhausted and t.done >= t.emitted:
                del open_q[i]
                finalize(t)
            else:
                i += 1
        if final and open_q:
            # the stream is fully drained; every task must be ready
            t = open_q[0]
            raise AssertionError(
                f'packed scheduler lost windows for {t.path}: '
                f'{t.done}/{t.emitted} scattered, exhausted={t.exhausted}')

    # -- input side: in-process windower, or the decode farm ----------------
    # decode_workers > 1 routes the decode+preprocess work through N
    # worker PROCESSES (farm/) feeding this scheduler over shared-memory
    # rings — same stream contract ((task, window, meta) + FLUSH/NUDGE,
    # per-video fault isolation, task accounting), so everything below
    # this point is identical on both paths and outputs stay
    # byte-identical at any worker count.
    plan = _decode_plan(ex, decode_workers, video_paths)
    n_decode = plan['farm_workers']
    farm = None
    if n_decode > 1:
        from video_features_tpu.farm import farm_available
        recipe = None
        recipe_err: Optional[BaseException] = None
        try:
            recipe = ex.farm_recipe()
        # vft-lint: ok=swallowed-exception — stored, not swallowed: the
        # structured recipe-failure warning below reports recipe_err
        except Exception as e:
            recipe_err = e                     # a BROKEN recipe, not a
            recipe = None                      # family without one
        if recipe is None or not farm_available():
            import logging as _logging

            from video_features_tpu.obs.events import event
            event(_logging.WARNING,
                  f'decode_workers={n_decode} requested but '
                  + (f'building its decode recipe failed '
                     f'({type(recipe_err).__name__}: {recipe_err})'
                     if recipe_err is not None else
                     'this extractor publishes no decode recipe'
                     if recipe is None else
                     'the host cannot spawn shared-memory workers')
                  + ' — running in-process decode', subsystem='farm')
        else:
            from video_features_tpu.farm import DecodeFarm, FarmUnavailable
            ring_mb = int(getattr(ex, 'decode_farm_ring_mb', 64) or 64)
            farm = DecodeFarm(
                recipe, workers=n_decode,
                ring_bytes=ring_mb * (1 << 20), tracer=ex.tracer,
                # post-mortem target (obs/blackbox.py): a dead decode
                # worker dumps a bundle alongside the respawn
                blackbox=getattr(ex, 'blackbox', None),
                # stall-watchdog feed (obs/watchdog.py): per-worker
                # assignment backlog, mirrored on the supervise tick
                pending_cb=getattr(ex, 'watchdog_pending', None),
                cache_key_fn=(ex._video_cache_key
                              if getattr(ex, 'cache', None) is not None
                              else None),
                # live tasks (windows_override) never ship to a worker
                # process — their frames arrive over the network in the
                # parent; the farm runs them on a feeder thread instead
                live_open=lambda task: task.windows_override(ex))
            # start eagerly: a RUNTIME start failure (SHM creation on a
            # full /dev/shm, a spawn refused by the container) must
            # degrade to in-process decode like every other farm
            # unavailability, not abort the whole worklist run
            try:
                farm.start()
            except FarmUnavailable as e:
                import logging as _logging

                from video_features_tpu.obs.events import event
                event(_logging.WARNING,
                      f'decode_workers={n_decode} requested but {e} '
                      '— running in-process decode', subsystem='farm')
                farm = None
            else:
                # live handle for the serve metrics surface (vft_farm_*);
                # stats stay readable after the run ends
                ex._farm = farm

    # span provenance: the video (and serve request + trace) a decode
    # slice worked for
    def decode_attrs(task: VideoTask, meta=None) -> Dict:
        return dict(video=str(task.path), request_id=_request_id(task),
                    **trace_attrs(task))

    lane_stats: List[Dict] = []
    if farm is not None:
        source = farm.stream(task_stream(), admit)
    else:
        plan = _without_farm(plan)
        source = _in_process_windows(task_stream(), open_windows, plan,
                                     ex.tracer, decode_attrs, lane_stats)
    buffers = ex.batch_buffers
    counts0 = buffers.counts()
    ahead = prefetch(packed_batches(source, batch,
                                    max_pool_age_s=max_pool_age_s,
                                    tracer=ex.tracer, buffers=buffers),
                     depth=max(int(decode_ahead), 1))

    # the in-flight queue: dispatched-but-unmaterialized batches, oldest
    # first. ``depth=1`` degenerates to the old synchronous loop (every
    # dispatch is immediately followed by its fetch); deeper queues let
    # the D2H readback + scatter + save of batch k-1 overlap the device
    # computing batch k. ``ex._inflight_now`` mirrors the live depth for
    # the serve metrics gauge (vft_inflight_batches) — a plain attribute
    # store, no locking needed for a monitoring read.
    from collections import deque
    depth = max(int(inflight if inflight is not None
                    else getattr(ex, 'inflight', 1) or 1), 1)
    # (out_dev, host buffer, prov, valid, batch_videos, batch_traces,
    #  step attrs)
    pending: 'deque' = deque()
    ex._inflight_now = 0

    def batch_trace_ids(prov) -> Optional[list]:
        """Distinct trace ids riding this batch (tracing on only) — the
        model/d2h spans carry them so a per-request trace filter finds
        the shared device work too."""
        if not ex.tracer.enabled:
            return None
        return trace_ids_of(t for t, _ in prov) or None

    def doom_batch(prov, batch_videos, valid, stage):
        # fault isolation (shared by the dispatch and sync sites): a
        # failing batch fails exactly the videos it carries (the
        # per-video loop would likewise lose only them) and the worklist
        # continues; their accounting still advances so the sweep never
        # stalls
        from video_features_tpu.obs.events import log_batch_error
        log_batch_error(batch_videos if batch_videos is not None
                        else sorted({str(t.path) for t, _ in prov}),
                        valid, batch, stage=stage)
        for task, _ in prov:
            task.failed = True
            task.done += 1

    def sync_oldest() -> None:
        """Materialize the OLDEST in-flight batch: the wait for the
        step and the deferred D2H (``fetch_step``: the ``device_wait``
        and ``d2h`` stages — neither may launder into compute time) plus
        row scatter; asynchronously raised execution faults surface here
        and doom only this batch's videos. Only a fetched step's input
        buffer goes back for reuse (a failed one is dropped)."""
        out_dev, host, prov, valid, batch_videos, batch_traces, step = \
            pending.popleft()
        ex._inflight_now = len(pending)
        try:
            out = fetch_step(
                ex.fetch_outputs, out_dev, ex.tracer, step,
                videos=batch_videos, valid=valid, capacity=batch,
                **({'trace_ids': batch_traces} if batch_traces else {}),
                **mesh_attrs(valid))
        except KeyboardInterrupt:
            raise
        except Exception:
            doom_batch(prov, batch_videos, valid, 'd2h')
            sweep()
            return
        buffers.give_back(host)
        record_occupancy('d2h', valid)
        for i, (task, meta) in enumerate(prov):
            task.done += 1
            if task.failed:       # already doomed: don't grow its rows
                continue
            on_window = getattr(task, 'on_window', None)
            if on_window is not None:
                # per-window streaming (live sessions): deliver this
                # row NOW instead of waiting for the video to finalize.
                # A delivery failure (client hung up) fails the task —
                # which also tells the decode side to stop feeding it.
                try:
                    on_window({key: arr[i] for key, arr in out.items()},
                              meta)
                except Exception:
                    task.failed = True
                    # a one-line event, not log_extraction_error: the
                    # vanished client is the CAUSE, the task failure is
                    # the effect — but it must not be silent (a leaked
                    # quota unit / session would be invisible otherwise)
                    event(_logging.WARNING,
                          'per-window delivery failed; failing the '
                          'live task', exc_info=True,
                          video=str(task.path), stage='d2h')
                    continue
            if getattr(task, 'stream_only', False):
                continue          # don't pin a live session's rows in RAM
            for key, arr in out.items():
                task.rows.setdefault(key, []).append(arr[i])
            task.meta_rows.append(meta)
        sweep()

    with ex.precision_scope():
        # batch assembly (on the window stream's thread) and H2D of the
        # next batches overlap the device running this one; the host
        # buffer rides along until its step is fetched
        for dev, host, prov, valid in transfer_batches(
                ahead, ex.put_input, keep_host=True, tracer=ex.tracer):
            if dev is None:
                # batchless drain marker (NUDGE / post-FLUSH): the source
                # is idle or a video finished without windows — finalize
                # everything finishable NOW. That means materializing the
                # whole in-flight queue first (a dynamic source may not
                # dispatch another batch for hours, and a deferred batch
                # must not hold its requests' completions hostage).
                while pending:
                    sync_oldest()
                sweep()
                continue
            # span provenance only when tracing is on (hot-loop hygiene);
            # the error path below rebuilds the list lazily if needed
            batch_videos = (sorted({str(t.path) for t, _ in prov})
                            if ex.tracer.enabled else None)
            batch_traces = batch_trace_ids(prov)
            try:
                # 'model' times dispatch + any compute the backend runs
                # synchronously; the wait-for-results tail lands on the
                # 'device_wait' stage at the sync point, under the same
                # step ordinal (model + device_wait + d2h shares sum to
                # the old all-in 'model' share)
                step = ex.step_attrs()
                with ex.tracer.stage(
                        'model', videos=batch_videos, valid=valid,
                        capacity=batch,
                        **({'trace_ids': batch_traces} if batch_traces
                           else {}),
                        **mesh_attrs(valid), **step):
                    out = ex.packed_step(dev)
            except KeyboardInterrupt:
                raise
            except Exception:
                # dispatch-time fault (e.g. a geometry that won't
                # compile/fit): in-flight predecessors are unaffected
                doom_batch(prov, batch_videos, valid, 'model')
                sweep()
                continue
            record_occupancy('model', valid)
            pending.append((out, host, prov, valid, batch_videos,
                            batch_traces, step))
            ex._inflight_now = len(pending)
            while len(pending) >= depth:
                sync_oldest()
        while pending:            # stream drained: materialize the tail
            sync_oldest()
    ex._inflight_now = 0
    sweep(final=True)

    if manifest is not None:
        # deferred XLA cost analysis and scope map of each executable
        # the dispatch seam saw (BaseExtractor.aot_call remembered the
        # jit, the shapes, the statics and the precision really run):
        # lowered now that the worklist is done, off the device loop,
        # and once: an identity the manifest holds is skipped, so a
        # second extract_packed call (a benchmark's next pass, a serve
        # worker's next wave) lowers nothing
        ex.note_executables()

    if manifest is not None and ndev > 1:
        # the run manifest names the mesh that produced these numbers:
        # device count, (data, time) shape, and the per-device labels the
        # stage table / metrics key their occupancy records on
        manifest.note_mesh({
            'mesh_devices': ndev,
            'shape': {str(k): int(v) for k, v in ex._mesh.shape.items()},
            'devices': dev_labels,
            'capacity_per_device': capacity,
            'global_batch': batch,
            # which precision lane this mesh's programs computed in —
            # a bf16 entry is a different compiled program at the same
            # width, and the manifest must say which one ran
            'compute_dtype': compute_dtype})

    # this run's host batch buffers per geometry: buffers, batches,
    # recycled, pack_recycled
    buffer_note = {'batch_buffers': buffers.stats(since=counts0)}
    if farm is not None and manifest is not None:
        # farm config + lifetime stats land in the run manifest (the
        # 'farm' section) so a farm-backed BENCH/run record names the
        # decode parallelism that produced it
        manifest.note_farm({'decode_workers': farm.n_workers,
                            'ring_bytes_per_worker': farm.ring_bytes,
                            'stats': farm.stats(), **buffer_note})
    elif manifest is not None:
        # ... and an in-process run its lanes (the 'decode' section)
        manifest.note_decode(dict(plan, **buffer_note), lane_stats)

    if ex.tracer.enabled and ex.tracer.report():
        if manifest is not None:
            # fold BEFORE the reset: the manifest keeps the run aggregate
            manifest.fold_stages(ex.tracer.report())
        if getattr(ex, 'profile', True):
            mesh_note = (f' = {capacity} x {ndev} devices'
                         if ndev > 1 else '')
            # stderr: the stage table is a diagnostic, and with
            # on_extraction=print stdout carries features
            print(f'--- stage timing: packed worklist ({n_started[0]} '
                  f'videos, batch {batch}{mesh_note}, '
                  f'{_decode_note(plan, farm)})', file=sys.stderr)
            print(ex.tracer.summary(), file=sys.stderr)
        ex.tracer.reset()


# -- fused multi-family worklists: decode once, extract many ----------------


def build_fused_recipe(exs: Dict):
    """One :class:`farm.recipes.FusedRecipe` for a family→extractor map
    whose ``fused_decode_signature()`` values all match: the shared
    decode geometry comes from the lead (first) family — the signature
    equality the caller established means every family would have built
    the identical loader — and the per-family branch transforms are each
    family's own published ``host_transform_spec()``."""
    from video_features_tpu.extract.streaming import CHUNK_WINDOWS
    from video_features_tpu.farm.recipes import FusedRecipe
    lead = next(iter(exs.values()))
    # the loader batch is the lanes' chunk, as every packed framewise
    # loader's (``extract/framewise.py``): frames go over as they decode
    return FusedRecipe(
        batch_size=CHUNK_WINDOWS, fps=lead.extraction_fps,
        total=lead.extraction_total, tmp_path=lead.tmp_path,
        keep_tmp=lead.keep_tmp_files, backend=lead.decode_backend,
        transforms={fam: ex.host_transform_spec()
                    for fam, ex in exs.items()})


def run_packed_fused(exs: Dict, video_paths: Iterable,
                     batch_size: Optional[int] = None,
                     decode_ahead: int = 2,
                     on_video_done: Optional[Callable] = None,
                     max_pool_age_s: Optional[float] = None,
                     inflight: Optional[int] = None,
                     decode_workers: Optional[int] = None) -> None:
    """Drive N same-decode-signature extractors over ONE worklist with
    ONE decode pass per video.

    ``exs`` maps family name → warm extractor; every extractor must
    publish the same ``fused_decode_signature()`` (the caller groups by
    it — ``cli.py``). Per video, the shared raw frame stream is decoded
    once and branched through each family's named host transform
    (``FusedRecipe``), each window arrives tagged ``meta=(family,
    t_ms)``, and the packer pools per ``(family, geometry)`` at that
    family's own packed batch size — so the device sees the exact
    per-family programs a sequential run compiles (no new program
    identities, no AOT-store misses) and every family's outputs are
    byte-identical to its solo run.

    Scheduling state is a :class:`FusedTask` CARRIER per video (the
    decode side's bookkeeping object) plus per-family subtasks that own
    scatter-back, fault isolation, and finalization:

      * admission runs per (family, video) through the shared
        ``_admit_task`` gate — resume skips and cache hits stay
        per-family, and a video every family skips never decodes;
        families that drop out at admission are excluded from the
        decode fan-out (``farm_select`` on the farm task message, the
        ``select`` arg in-process), so a mostly-cached family costs no
        transform work either;
      * the video's content hash is computed ONCE (``cache.key``'s
        stat-memoized ``hash_file``) and reused by every family's cache
        key — the fused run's cache keys are identical to sequential
        runs';
      * a family's device-step fault fails only that family's subtask —
        the shared decode keeps feeding the healthy siblings; a DECODE
        fault fails the carrier, and with it every still-active
        subtask;
      * finalization fans each subtask through the shared
        ``_finalize_task`` (identical save/publish code), then fires
        ``on_video_done(carrier)`` once per video.

    ``decode_workers > 1`` ships the fused recipe to the decode farm
    unchanged — one worker decode per video, N tagged window streams
    back over the ring. The D2H side keeps a per-family in-flight queue
    at each family's ``inflight`` depth. Simplification vs
    ``run_packed``: H2D runs inline per batch (its own ``h2d`` stage)
    rather than through ``transfer_batches`` — with N families
    interleaving on one device loop there is no single "next batch" to
    overlap against (so ``input_wait`` is recorded here, on the lead
    tracer, around the batch prefetch). Batches are assembled as in
    ``run_packed``: on the window stream's thread, in the lead's recycled
    buffers, each given back once its family's step is fetched.
    """
    from video_features_tpu.extract.streaming import fetch_step, put_traced
    from video_features_tpu.io.video import prefetch

    if not exs:
        raise ValueError('run_packed_fused needs at least one family')
    sigs = {fam: ex.fused_decode_signature() for fam, ex in exs.items()}
    if None in sigs.values() or len(set(sigs.values())) != 1:
        raise ValueError(
            f'families cannot share one decode pass — fused decode '
            f'signatures differ or are unfusable: {sigs}')

    fams = list(exs)
    lead = exs[fams[0]]

    # per-family device setup + batch plan: each family keeps ITS packed
    # batch size (and mesh plan), so fused batches feed the family's own
    # compiled programs
    fam_batch: Dict[str, int] = {}
    for fam, ex in exs.items():
        ex._packed_setup()
        ndev = ex._ensure_packed_mesh()
        capacity = int(batch_size or ex.packed_batch_size())
        if ndev > 1:
            from video_features_tpu.parallel.mesh import plan_device_batch
            fam_batch[fam] = plan_device_batch(capacity, ex._mesh)
        else:
            fam_batch[fam] = capacity
        ex._inflight_now = 0
    max_batch = max(fam_batch.values())

    recorders = {fam: getattr(ex.tracer, 'recorder', None)
                 for fam, ex in exs.items()}
    manifests = {fam: getattr(ex, 'manifest', None)
                 for fam, ex in exs.items()}
    lead_recorder = recorders[fams[0]]
    run_ctx = getattr(lead, 'trace_ctx', None)

    open_q: List[FusedTask] = []
    n_started = [0]

    def task_stream() -> Iterator:
        for item in video_paths:
            if item is FLUSH:
                yield FLUSH
                continue
            c = (item if isinstance(item, FusedTask)
                 else FusedTask(item, fams,
                                trace=(run_ctx.child()
                                       if run_ctx is not None
                                       else None)))
            c.video_id = n_started[0]
            n_started[0] += 1
            open_q.append(c)
            if lead_recorder is not None:
                lead_recorder.instant('video_start', video=str(c.path),
                                      **trace_attrs(c))
            yield c

    def admit_fused(c: FusedTask) -> bool:
        """Per-family admission over the shared carrier: families whose
        subtask resolves at admit (resume skip / cache hit) drop out of
        the decode fan-out; the video decodes only if someone still
        wants it. Emits the ``decode_pass`` instant exactly once per
        video that will decode — the observable the fused amortization
        guard (tests) asserts on."""
        active = []
        for fam in c.subtasks:
            sub = c.subtasks[fam]
            if _admit_task(exs[fam], sub):
                active.append(fam)
            else:
                sub.exhausted = True   # terminal now; finalized with the
                #                        carrier so outcomes record once
        c.active = active
        c.farm_select = (tuple(active)
                         if active and len(active) < len(c.subtasks)
                         else None)
        if active and lead_recorder is not None:
            lead_recorder.instant('decode_pass', video=str(c.path),
                                  families=list(active),
                                  **trace_attrs(c))
        return bool(active)

    # -- input side: one shared decode, farm or in-process ------------------
    plan = _decode_plan(lead, decode_workers, video_paths)
    n_decode = plan['farm_workers']
    farm = None
    if n_decode > 1:
        from video_features_tpu.farm import farm_available
        if farm_available():
            from video_features_tpu.farm import DecodeFarm, FarmUnavailable
            ring_mb = int(getattr(lead, 'decode_farm_ring_mb', 64) or 64)
            farm = DecodeFarm(
                build_fused_recipe(exs), workers=n_decode,
                ring_bytes=ring_mb * (1 << 20), tracer=lead.tracer,
                blackbox=getattr(lead, 'blackbox', None),
                pending_cb=getattr(lead, 'watchdog_pending', None),
                # content-keyed dedupe stays off: per-family cache keys
                # diverge, so a carrier-level key could merge videos one
                # family still needs separately
                cache_key_fn=None)
            try:
                farm.start()
            except FarmUnavailable as e:
                event(_logging.WARNING,
                      f'decode_workers={n_decode} requested but {e} '
                      '— running in-process decode', subsystem='farm')
                farm = None
            else:
                lead._farm = farm
        else:
            event(_logging.WARNING,
                  f'decode_workers={n_decode} requested but the host '
                  'cannot spawn shared-memory workers — running '
                  'in-process decode', subsystem='farm')

    lane_stats: List[Dict] = []
    if farm is not None:
        source = farm.stream(task_stream(), admit_fused)
    else:
        plan = _without_farm(plan)
        recipe = build_fused_recipe(exs)

        def fused_open_windows(c: FusedTask):
            if not admit_fused(c):
                return iter(())
            kw = {}
            if c.segment is not None:
                kw['segment'] = c.segment
            if c.farm_select is not None:
                kw['select'] = c.farm_select
            info, windows = recipe.open(c.path, **kw)
            c.info.update(info)
            return windows

        # in-process decode+branch cost on the lead tracer: per family
        # window from the serial windower, per chunk (all families of
        # one decode) from a lane; the farm traces in-worker spans
        def decode_attrs(c: FusedTask, meta=None) -> Dict:
            fam = {'family': meta[0]} if meta is not None else {}
            return dict(video=str(c.path), **fam, **trace_attrs(c))

        source = _in_process_windows(task_stream(), fused_open_windows,
                                     plan, lead.tracer, decode_attrs,
                                     lane_stats)

    def counted(src):
        # PRODUCER-side per-family emit accounting: runs between the
        # windower (which counts the carrier) and the prefetch buffer,
        # so by the time the consumer can observe ``carrier.exhausted``
        # every subtask's ``emitted`` is final — the sweep's readiness
        # check (done >= emitted per active family) cannot fire early
        try:
            for item in src:
                if item is not FLUSH and item is not NUDGE:
                    sub = item[0].subtasks.get(item[2][0])
                    if sub is not None:
                        sub.emitted += 1
                yield item
        finally:
            src.close()       # an abandoned run joins its decode lanes

    # batches are assembled on the window stream's thread, in the lead's
    # recycled buffers, as in run_packed
    buffers = lead.batch_buffers
    counts0 = buffers.counts()
    ahead = prefetch(packed_batches(
        counted(source), max_batch, max_pool_age_s=max_pool_age_s,
        tracer=lead.tracer, family_of=lambda m: m[0],
        family_batch=fam_batch, buffers=buffers),
        depth=max(int(decode_ahead), 1))
    if lead.tracer.enabled:
        # the consumer side of the batch queue: this loop transfers
        # inline (no transfer_batches), so the dispatch thread's wait for
        # input is its next() on the batch prefetch
        ahead = lead.tracer.wrap_iter('input_wait', ahead)

    from collections import deque
    depth = {fam: max(int(inflight if inflight is not None
                          else getattr(ex, 'inflight', 1) or 1), 1)
             for fam, ex in exs.items()}
    pending: Dict[str, deque] = {fam: deque() for fam in fams}

    def finalize_carrier(c: FusedTask) -> None:
        for fam, sub in c.subtasks.items():
            for k, v in c.info.items():
                sub.info.setdefault(k, v)
            if c.failed and not sub.skipped:
                sub.failed = True    # decode fault fails every family
            sub.exhausted = True
            _finalize_task(exs[fam], sub, recorder=recorders[fam],
                           manifest=manifests[fam])
        c.rows = {}
        c.finalized = True
        if on_video_done is not None:
            on_video_done(c)

    def sweep(final: bool = False) -> None:
        i = 0
        while i < len(open_q):
            c = open_q[i]
            if not c.exhausted and c.emitted == 0:
                break             # decode hasn't reached this video yet
            if c.exhausted and all(c.subtasks[f].done
                                   >= c.subtasks[f].emitted
                                   for f in c.active):
                del open_q[i]
                finalize_carrier(c)
            else:
                i += 1
        if final and open_q:
            c = open_q[0]
            counts = {f: (c.subtasks[f].done, c.subtasks[f].emitted)
                      for f in c.active}
            raise AssertionError(
                f'fused scheduler lost windows for {c.path}: '
                f'{counts} (done, emitted) per family, '
                f'exhausted={c.exhausted}')

    def doom(fam: str, prov, valid: int, stage: str) -> None:
        # a family's device fault fails ITS subtasks only — the shared
        # decode keeps feeding the other families
        from video_features_tpu.obs.events import log_batch_error
        log_batch_error(sorted({str(c.path) for c, _ in prov}), valid,
                        fam_batch[fam], stage=f'{stage}:{fam}')
        for c, _ in prov:
            sub = c.subtasks[fam]
            sub.failed = True
            sub.done += 1

    def sync_oldest(fam: str) -> None:
        ex = exs[fam]
        out_dev, host, prov, valid, batch_videos, step = \
            pending[fam].popleft()
        ex._inflight_now = len(pending[fam])
        try:
            out = fetch_step(ex.fetch_outputs, out_dev, ex.tracer, step,
                             videos=batch_videos, valid=valid,
                             capacity=fam_batch[fam], family=fam)
        except KeyboardInterrupt:
            raise
        except Exception:
            doom(fam, prov, valid, 'd2h')
            sweep()
            return
        buffers.give_back(host)     # its step is fetched: reuse it
        ex.tracer.add_occupancy('d2h', valid, fam_batch[fam])
        for i, (c, meta) in enumerate(prov):
            f2, t_ms = meta
            sub = c.subtasks[f2]
            sub.done += 1
            if sub.failed or c.failed:
                continue
            for key, arr in out.items():
                sub.rows.setdefault(key, []).append(arr[i])
            sub.meta_rows.append(t_ms)
        sweep()

    def drain_all() -> None:
        for fam in fams:
            while pending[fam]:
                sync_oldest(fam)

    for stacked, prov, valid in ahead:
        if stacked is None:
            # batchless drain marker (NUDGE / post-FLUSH): materialize
            # every family's in-flight queue, then finalize
            drain_all()
            sweep()
            continue
        fam = prov[0][1][0]
        ex = exs[fam]
        batch_videos = (sorted({str(c.path) for c, _ in prov})
                        if ex.tracer.enabled else None)
        try:
            # per-batch precision scope: adjacent batches may belong to
            # families on different precision lanes
            with ex.precision_scope():
                dev = put_traced(ex.put_input, stacked, ex.tracer,
                                 videos=batch_videos, valid=valid,
                                 capacity=fam_batch[fam], family=fam)
                step = ex.step_attrs()
                with ex.tracer.stage('model', videos=batch_videos,
                                     valid=valid,
                                     capacity=fam_batch[fam],
                                     family=fam, **step):
                    out = ex.packed_step(dev)
        except KeyboardInterrupt:
            raise
        except Exception:
            doom(fam, prov, valid, 'model')
            sweep()
            continue
        ex.tracer.add_occupancy('model', valid, fam_batch[fam])
        pending[fam].append((out, stacked, prov, valid, batch_videos, step))
        ex._inflight_now = len(pending[fam])
        while len(pending[fam]) >= depth[fam]:
            sync_oldest(fam)
    drain_all()
    for ex in exs.values():
        ex._inflight_now = 0
    sweep(final=True)

    buffer_note = {'batch_buffers': buffers.stats(since=counts0)}
    for fam, ex in exs.items():
        manifest = manifests[fam]
        if manifest is not None:
            ex.note_executables()    # once per identity (run_packed)
        if farm is not None and manifest is not None:
            manifest.note_farm({'decode_workers': farm.n_workers,
                                'ring_bytes_per_worker': farm.ring_bytes,
                                'stats': farm.stats(),
                                'fused_families': fams, **buffer_note})
        elif manifest is not None:
            manifest.note_decode(dict(plan, fused_families=fams,
                                      **buffer_note), lane_stats)
        if ex.tracer.enabled and ex.tracer.report():
            if manifest is not None:
                manifest.fold_stages(ex.tracer.report())
            if getattr(ex, 'profile', True):
                print(f'--- stage timing: fused worklist '
                      f'[{fam}] ({n_started[0]} videos, batch '
                      f'{fam_batch[fam]}, {_decode_note(plan, farm)})',
                      file=sys.stderr)
                print(ex.tracer.summary(), file=sys.stderr)
            if ex is not lead:
                ex.tracer.reset()
    if lead.tracer.enabled:
        lead.tracer.reset()
