"""Span timeline: a low-overhead ring buffer of trace events + Perfetto export.

The per-stage :class:`utils.tracing.Tracer` answers "where does wall
time go in aggregate"; this module answers "what happened WHEN" — the
question that aggregate tables cannot: did decode stall behind a cold
geometry pool, did one request's save serialize behind another's device
step, how long did the lone odd-geometry window sit pooled before the
age-out flushed it. Every ``Tracer.stage``/``add`` call forwards its
(start, duration, attrs) here when a recorder is attached, so the stage
table and the timeline are two views over the SAME instrumentation
sites — there is no second set of probes to drift out of sync.

Recording is a bounded ``deque`` append under one lock (no allocation
beyond the event tuple, no I/O, no string formatting): cheap enough to
leave on for whole packed worklists and serve sessions. When the buffer
wraps, the OLDEST events drop and ``dropped`` counts them — a flight
recorder keeps the most recent window, and the export stamps how much
history was lost rather than silently truncating.

Export is Chrome trace-event JSON (the ``traceEvents`` array format):
load it at https://ui.perfetto.dev or ``chrome://tracing``. Complete
events (``ph='X'``) carry ``ts``/``dur`` in microseconds; instant events
(``ph='i'``) mark lifecycle points (video start/done, request admitted);
metadata events name the recording threads. ``tools/trace_view.py``
validates an export and prints a per-span summary.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional

# one clock for every span so cross-thread timelines line up; the same
# clock Tracer uses, so durations agree with the stage table
CLOCK = time.perf_counter

# ring-buffer default: ~200K events ≈ a few tens of MB resident and far
# beyond a worklist run; serve daemons wrap and keep the recent window
DEFAULT_CAPACITY = 200_000


class SpanRecorder:
    """Thread-safe bounded recorder of span / instant trace events."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 enabled: bool = True) -> None:
        self.enabled = enabled
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        # (ph, name, t_start_s, dur_s, tid, attrs|None, pid|None)
        # pid/tid overrides carry CROSS-PROCESS spans (decode-farm
        # workers): the worker measures, the parent records, and the
        # export shows the span under the worker's own pid lane
        self._events: 'deque' = deque(maxlen=self.capacity)
        self._appended = 0
        self._thread_names: Dict[int, str] = {}
        # epoch: perf_counter origin for ts=0 plus the wall clock at that
        # origin, so exports can be correlated with log timestamps
        self._t0 = CLOCK()
        self._wall0 = time.time()
        # incremental minimum of every start timestamp ever appended:
        # origin() must be O(1) — the /trace route calls it per recorder
        # on a request path, and a full O(capacity) ring scan under the
        # lock would stall the hot span-append path. Never reset on
        # ring eviction: a conservatively-old origin only shifts ts
        # later, it can never go negative.
        self._min_ts = self._t0

    # -- recording -----------------------------------------------------------

    def span(self, name: str, t_start: float, t_end: float,
             pid: Optional[int] = None, tid: Optional[int] = None,
             **attrs: Any) -> None:
        """Record one complete ('X') span. ``t_start``/``t_end`` are
        ``CLOCK()`` readings; ``attrs`` become the event's ``args``
        (video path, request id, trace/span ids, batch occupancy, ...).
        ``pid``/``tid`` override the recording process/thread identity —
        the decode farm records spans its WORKER processes measured
        (clock-calibrated), and the export must show them under the
        worker's own lane, not the parent drain thread's."""
        if not self.enabled:
            return
        own_thread = tid is None
        if own_thread:
            tid = threading.get_ident()
        with self._lock:
            if own_thread and tid not in self._thread_names:
                self._thread_names[tid] = threading.current_thread().name
            if t_start < self._min_ts:
                self._min_ts = t_start
            self._events.append(('X', name, t_start, t_end - t_start,
                                 int(tid), attrs or None, pid))
            self._appended += 1

    def instant(self, name: str, **attrs: Any) -> None:
        """Record an instant ('i') lifecycle marker at now."""
        if not self.enabled:
            return
        tid = threading.get_ident()
        with self._lock:
            if tid not in self._thread_names:
                self._thread_names[tid] = threading.current_thread().name
            self._events.append(('i', name, CLOCK(), 0.0, tid,
                                 attrs or None, None))
            self._appended += 1

    # -- export --------------------------------------------------------------

    @property
    def dropped(self) -> int:
        """Events lost to ring-buffer wrap (oldest-first)."""
        with self._lock:
            return max(0, self._appended - len(self._events))

    def origin(self) -> float:
        """This recorder's ts=0 reference: its epoch or the earliest
        start ever recorded, whichever is older — a span timed just
        before the recorder attached must not export a negative
        timestamp. O(1): the minimum is tracked at append time (the
        /trace route calls this per recorder on a request path)."""
        with self._lock:
            return min(self._t0, self._min_ts)

    def snapshot(self, origin: Optional[float] = None,
                 limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """The buffered events as Chrome trace-event dicts, ts-sorted.

        ``origin`` overrides the ts=0 reference — multi-recorder merges
        (``merge_traces``) pass one common origin so recorders created
        at different times stay aligned on one timeline (CLOCK is the
        shared process-wide ``perf_counter``).

        ``limit`` bounds the snapshot to the MOST RECENT ``limit``
        events: on-demand consumers (the serve ``/trace`` route, the
        black-box dumper) must never serialize the full 200K-event ring
        under the recorder lock on a request path."""
        with self._lock:
            if limit is not None and limit < len(self._events):
                from itertools import islice
                events = list(islice(self._events,
                                     len(self._events) - int(limit),
                                     len(self._events)))
            else:
                events = list(self._events)
            names = dict(self._thread_names)
            if origin is None:
                origin = min(self._t0, self._min_ts)
        own_pid = os.getpid()
        out: List[Dict[str, Any]] = []
        for tid, tname in sorted(names.items()):
            out.append({'name': 'thread_name', 'ph': 'M', 'ts': 0,
                        'pid': own_pid, 'tid': tid,
                        'args': {'name': tname}})
        body = []
        for ph, name, ts, dur, tid, attrs, pid in events:
            ev: Dict[str, Any] = {
                'name': name, 'ph': ph,
                'pid': pid if pid is not None else own_pid, 'tid': tid,
                'ts': round((ts - origin) * 1e6, 3),
            }
            if ph == 'X':
                ev['dur'] = round(dur * 1e6, 3)
            else:
                ev['s'] = 't'           # instant scope: this thread
            if attrs:
                ev['args'] = {k: _jsonable(v) for k, v in attrs.items()}
            body.append(ev)
        # viewers tolerate unsorted events but the validator contract is
        # monotonic timestamps; one sort at export keeps recording cheap
        body.sort(key=lambda e: e['ts'])
        return out + body

    def export(self, path: str) -> str:
        """Atomically write the Chrome trace JSON document to ``path``."""
        from video_features_tpu.utils.output import atomic_write
        doc = {
            'traceEvents': self.snapshot(),
            'displayTimeUnit': 'ms',
            'otherData': {
                'tool': 'video_features_tpu',
                'wall_epoch_s': self._wall0,
                'events_dropped': self.dropped,
            },
        }
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        atomic_write(path, lambda f: f.write(
            json.dumps(doc).encode('utf-8')))
        return path


# bytes attrs render at most this many bytes: a span arg is provenance,
# not payload — an accidental frame buffer must not balloon the export
_BYTES_RENDER_CAP = 256


def _jsonable(v: Any) -> Any:
    """JSON-safe projection shared by span args and the run manifest
    (obs/manifest imports this — one implementation to drift)."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (bytes, bytearray)):
        # ASCII-safe decode, NOT str(): repr would export "b'...'"
        # wrappers into traces/manifests, and a stray binary blob would
        # export escape soup of unbounded size — cap and say so
        head = bytes(v[:_BYTES_RENDER_CAP])
        text = head.decode('ascii', 'backslashreplace')
        if len(v) > _BYTES_RENDER_CAP:
            text += f'...(+{len(v) - _BYTES_RENDER_CAP} bytes)'
        return text
    if isinstance(v, (list, tuple, set, frozenset)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return str(v)


#: disabled singleton — instrumentation sites can hold it unconditionally
NULL_RECORDER = SpanRecorder(capacity=1, enabled=False)


# the recorders extractors attached in this process (configure_obs),
# newest last. Strong references on purpose: a same-process reader (the
# benchmark's idle_by_span) asks AFTER the extractor was freed. Bounded,
# so a process that builds extractors all day keeps four rings, not all.
_ATTACHED: 'deque' = deque(maxlen=4)


def attach(recorder: SpanRecorder) -> SpanRecorder:
    """Register ``recorder`` for :func:`attached` and return it."""
    _ATTACHED.append(recorder)
    return recorder


def attached() -> List[SpanRecorder]:
    """The recorders attached in this process, newest last (at most the
    last four) — the one door for a reader in the same process. The
    black box and serve's ``/trace`` route keep their own lists."""
    return list(_ATTACHED)


def merge_traces(recorders: Iterable[SpanRecorder],
                 limit: Optional[int] = None) -> List[Dict[str, Any]]:
    """One ts-sorted event list over several recorders (the serve daemon
    stitches every warm-pool worker's recorder into one drain export —
    ``export_merged`` below). All recorders share CLOCK, so one common
    origin (the oldest) keeps workers created hours apart correctly
    offset on the merged timeline instead of each re-basing to 0.
    ``limit`` bounds each recorder's contribution to its most recent
    events (request-path consumers: the ``/trace`` route, black-box
    dumps)."""
    recorders = list(recorders)
    if not recorders:
        return []
    origin = min(rec.origin() for rec in recorders)
    events: List[Dict[str, Any]] = []
    for rec in recorders:
        events.extend(rec.snapshot(origin=origin, limit=limit))
    events.sort(key=lambda e: (e['ph'] != 'M', e['ts']))
    return events


def export_merged(recorders: Iterable[SpanRecorder], path: str) -> str:
    """Atomically write one Chrome trace document stitching several
    recorders (serve drain: a shared ``trace_out`` base override must
    carry EVERY worker's spans, not whichever worker exported last)."""
    from video_features_tpu.utils.output import atomic_write
    recorders = [r for r in recorders if r is not None]
    doc = {
        'traceEvents': merge_traces(recorders),
        'displayTimeUnit': 'ms',
        'otherData': {
            'tool': 'video_features_tpu',
            'recorders_merged': len(recorders),
            'events_dropped': sum(r.dropped for r in recorders),
        },
    }
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    atomic_write(path, lambda f: f.write(json.dumps(doc).encode('utf-8')))
    return path
