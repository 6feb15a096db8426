"""Per-stage timing and JAX profiler hooks.

The reference has no tracing/profiling surface at all — only tqdm progress
and prints (SURVEY.md §5.1; reference main.py:2,47). On TPU the pipeline is
host-decode-bound long before it is FLOPs-bound, so knowing how wall time
splits across decode / preprocess / host→device+model / save is the first
profiling question. This module provides:

  * ``Tracer`` — a thread-safe accumulator of named stage timings. Stages
    are timed with ``with tracer.stage('decode'): ...`` or by wrapping an
    iterator (``tracer.wrap_iter('decode', loader)`` times each ``next()``
    call, which is where streaming decode work actually happens — including
    on the prefetch producer thread).
  * ``NULL_TRACER`` — a disabled singleton; instrumentation sites cost two
    attribute loads and a truthiness check when profiling is off.
  * ``jax_profiler_trace(dir)`` — context manager around
    ``jax.profiler.trace`` for XLA/TPU-level traces viewable in
    TensorBoard/Perfetto, gated so importing this module never imports jax.

Enable per-run with the ``profile: true`` config key (any extractor); each
video then prints a stage table after extraction. ``profile_dir`` addition-
ally captures a jax profiler trace of the whole run.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterable, Iterator, List, Optional

# Canonical pipeline stage names — the shared vocabulary across the stage
# table, the span timeline (obs/spans), the serve metrics families
# (vft_stage_*), and bench stage_reports. A stage either appears under
# one of these names everywhere or under its own new name everywhere.
# Each device step is three spans on the dispatch thread: `model` is
# DISPATCH only (plus whatever the backend computes synchronously),
# `device_wait` is the host blocked until the step's outputs are ready,
# and `d2h` is the device→host copy of outputs that are ready (split
# out so neither the wait nor the readback launders into compute time —
# the async device loop, parallel/packing.py). `input_wait` is the
# consumer side of the transfer queue: the dispatch thread blocked for
# the next batch, where `decode+preprocess`, `pack` and `h2d` time the
# producer side of the same queue (time busy, not time waited for).
# Pinned by tests/test_obs.py.
STAGES = (
    'decode',             # raw decode (stack families without preprocess)
    'decode+preprocess',  # decode + host transform on the prefetch thread
    'tokenise',           # lm: decoded frames → token ids, on the host
    'audio_dsp',          # vggish: host-side mel/log-mel DSP on the wav
    'queue_idle',         # serve: blocking waits on an idle request feed
    'pack',               # batch assembly copies (window and batch np.stack)
    'pack_recycled',      # counter: packed batches built in a reused buffer
    'h2d',                # host→device input transfer, until it has landed
    'input_wait',         # dispatch thread blocked for the next batch
    'model',              # device-step dispatch (step=, program= attrs)
    'device_wait',        # host blocked until that step's outputs are ready
    'd2h',                # device→host copy of a finished step's outputs
    'save',               # output materialization (.npy/.pkl writes)
    'cache_lookup',       # content-addressed cache consult
    'cache_publish',      # content-addressed cache publish
    # counters only (add_occupancy; no time), from the second output the
    # lm step itself returns: the expert trunk's routing ...
    'moe_route',          # mean ÷ largest load on one held expert
    'moe_held',           # assignments on held experts ÷ all assignments
    'moe_walk',           # held assignments ÷ rows the block walk computed
    # ... the retention trunk's mixer
    'retention_scan',     # positions × layers through the carried state ÷ all
    'retention_kernel',   # of those, through the state-product kernels
    # ... the hybrid trunk's Mamba-2 mixers
    'ssd_scan',           # positions × Mamba layers through the SSD scan ÷ all
    'ssd_kernel',         # chunks scanned through the ssd_scan kernel ÷ all
    # ... and the lightning indexer of the sparse latent trunk
    'index_kernel',       # query blocks scored by the index_scores kernel ÷ all
    'topk_ties',          # scored rows whose threshold the tie rule settled ÷ all
)


class _StageStat:
    __slots__ = ('count', 'total_s', 'max_s', 'first_s',
                 'occ_valid', 'occ_capacity', 'occ_device')

    def __init__(self) -> None:
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0
        # first-call wall time: the pipeline-ramp term (compile + cache
        # warm + prefetch fill) that a batch-major corpus loop pays once
        # instead of once per video
        self.first_s = 0.0
        # batch-slot accounting (add_occupancy): how full the compiled
        # batch actually ran — padded tail slots burn the same device time
        # as real work
        self.occ_valid = 0
        self.occ_capacity = 0
        # per-DEVICE slot accounting for mesh-sharded batches
        # (add_occupancy(..., device=)): device label → [valid, capacity]
        # raw counts, kept SEPARATE from the aggregate above so the two
        # views never double-count (the aggregate is recorded once per
        # batch at the global capacity; each shard's slice lands here)
        self.occ_device: Optional[Dict[str, list]] = None

    def add(self, dt: float) -> None:
        if self.count == 0:
            self.first_s = dt
        self.count += 1
        self.total_s += dt
        if dt > self.max_s:
            self.max_s = dt

    def ramp(self) -> Optional[float]:
        """first-call time over the steady-state mean (None until 2 calls).

        ~1.0 = no ramp; large values = a compile/warm-up wall that a
        longer run (or cross-video packing) amortizes away.
        """
        if self.count < 2:
            return None
        steady = (self.total_s - self.first_s) / (self.count - 1)
        return self.first_s / steady if steady > 0 else None

    def occupancy(self) -> Optional[float]:
        """valid-slot fraction of all batch slots (None if never recorded)."""
        if self.occ_capacity <= 0:
            return None
        return self.occ_valid / self.occ_capacity


class Tracer:
    """Thread-safe named-stage wall-time accumulator.

    With a ``recorder`` (``obs.spans.SpanRecorder``) attached, every
    timed stage ALSO lands as a span event on the flight-recorder
    timeline — the aggregate table and the Perfetto trace are two views
    over the same instrumentation sites. ``attrs`` passed to
    ``stage``/``add`` (video path, request id, batch occupancy) ride on
    the span's ``args``; the aggregate ignores them.
    """

    def __init__(self, enabled: bool = True, recorder=None) -> None:
        self.enabled = enabled
        self.recorder = recorder
        # liveness hook (obs/watchdog.py): called with (stage, worker)
        # on every recorded stage — the stall watchdog's progress ledger
        # rides the SAME instrumentation sites as the table/timeline.
        # ``worker`` is the farm worker index when the site carries one
        # (the ``worker=`` span attr), else None.
        self.progress = None
        self._lock = threading.Lock()
        self._stats: Dict[str, _StageStat] = {}
        self._order: List[str] = []

    # -- recording -----------------------------------------------------------

    def add(self, name: str, dt: float, t0: Optional[float] = None,
            span_pid: Optional[int] = None, span_tid: Optional[int] = None,
            **attrs) -> None:
        """Record ``dt`` seconds under ``name``. ``t0`` (the stage's
        ``time.perf_counter`` start, when the caller knows it) places the
        span on the timeline; without it the span is back-dated from
        now. ``span_pid``/``span_tid`` override the span's recorded
        process/thread identity (cross-process sites: the decode farm
        records spans its workers measured)."""
        if not self.enabled:
            return
        rec = self.recorder
        if rec is not None and rec.enabled:
            if t0 is None:
                t0 = time.perf_counter() - dt
            rec.span(name, t0, t0 + dt, pid=span_pid, tid=span_tid,
                     **attrs)
        progress = self.progress
        if progress is not None:
            try:
                progress(name, attrs.get('worker'))
            except Exception:
                # vft-lint: ok=swallowed-exception — a broken liveness
                # hook must not fail the hot loop it observes
                pass
        with self._lock:
            stat = self._stats.get(name)
            if stat is None:
                stat = self._stats[name] = _StageStat()
                self._order.append(name)
            stat.add(dt)

    def add_occupancy(self, name: str, valid: int, capacity: int,
                      device: Optional[str] = None) -> None:
        """Record that a ``capacity``-slot batch under ``name`` carried
        ``valid`` real items (the rest was padding). The summary table then
        reports the stage's aggregate batch occupancy — the fraction of
        compiled-step slots that did useful work.

        With ``device`` given (mesh-sharded packed batches), the counts
        land in the stage's PER-DEVICE map instead of the aggregate: the
        device loop records the aggregate once per batch at the global
        capacity and each shard's slice under its device label, so neither
        view double-counts the other (see ``merge_reports``)."""
        if not self.enabled:
            return
        with self._lock:
            stat = self._stats.get(name)
            if stat is None:
                stat = self._stats[name] = _StageStat()
                self._order.append(name)
            if device is not None:
                if stat.occ_device is None:
                    stat.occ_device = {}
                rec = stat.occ_device.setdefault(str(device), [0, 0])
                rec[0] += int(valid)
                rec[1] += int(capacity)
            else:
                stat.occ_valid += int(valid)
                stat.occ_capacity += int(capacity)

    @contextmanager
    def stage(self, name: str, **attrs):
        """Time a block under ``name`` (no-op when disabled). ``attrs``
        annotate the span on an attached recorder (the aggregate table
        ignores them)."""
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0, t0=t0, **attrs)

    def wrap_iter(self, name: str, iterable: Iterable) -> Iterator:
        """Yield from ``iterable``, timing each ``next()`` under ``name``.

        Streaming decoders do their work inside ``next()``; wrapping the
        iterator (before any prefetch thread) therefore times decode on the
        thread that actually runs it.
        """
        if not self.enabled:
            yield from iterable
            return
        it = iter(iterable)
        while True:
            t0 = time.perf_counter()
            try:
                item = next(it)
            except StopIteration:
                return
            finally:
                self.add(name, time.perf_counter() - t0, t0=t0)
            yield item

    # -- reporting -----------------------------------------------------------

    @staticmethod
    def _stat_record(s: '_StageStat') -> Dict[str, float]:
        rec = {'count': s.count, 'total_s': s.total_s,
               'mean_s': s.total_s / max(s.count, 1), 'max_s': s.max_s,
               'first_s': s.first_s}
        ramp = s.ramp()
        if ramp is not None:
            rec['ramp'] = ramp
        occ = s.occupancy()
        if occ is not None:
            rec['occupancy'] = occ
            # raw slot counts ride along so reports stay mergeable
            # (merge_reports recomputes aggregate occupancy from these —
            # averaging the derived ratios would weight batches wrongly)
            rec['occ_valid'] = s.occ_valid
            rec['occ_capacity'] = s.occ_capacity
        if s.occ_device:
            # mesh-sharded batches: each device's slot accounting, raw
            # counts + derived ratio (the serve metrics surface renders
            # these as vft_stage_occupancy{device=...})
            rec['occ_device'] = {
                dev: {'occ_valid': v, 'occ_capacity': c,
                      'occupancy': (v / c) if c else 0.0}
                for dev, (v, c) in s.occ_device.items()}
        return rec

    def report(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {name: self._stat_record(s)
                    for name, s in self._stats.items()}

    def summary(self) -> str:
        """Human-readable stage table, ordered by first occurrence.

        Beyond the wall-time split, two pipeline-health columns:
        ``occ%`` — aggregate batch occupancy (valid slots / all slots) where
        the stage recorded it (the compiled device step under packed or
        batched loops); ``ramp`` — first-call time over the steady-state
        mean, i.e. the compile/warm-up wall the run amortizes (≈1 = none).
        """
        # one lock acquisition for both stats and order: a concurrent add()
        # (e.g. a lingering prefetch thread) must not desync them
        with self._lock:
            order = list(self._order)
            rep = {name: self._stat_record(s)
                   for name, s in self._stats.items()}
        if not rep:
            return '(no stages recorded)'
        total = sum(r['total_s'] for r in rep.values())
        width = max(len(n) for n in order)
        lines = [f'{"stage".ljust(width)} | count |  total s |   mean ms '
                 f'| share |  occ% |   ramp']
        for name in order:
            r = rep[name]
            share = r['total_s'] / total * 100 if total else 0.0
            occ = (f'{r["occupancy"] * 100:5.1f}'
                   if 'occupancy' in r else '    -')
            ramp = f'{r["ramp"]:6.1f}' if 'ramp' in r else '     -'
            lines.append(
                f'{name.ljust(width)} | {r["count"]:5d} | {r["total_s"]:8.3f} '
                f'| {r["mean_s"] * 1e3:9.2f} | {share:4.1f}% | {occ} | {ramp}')
        return '\n'.join(lines)

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()
            self._order.clear()


NULL_TRACER = Tracer(enabled=False)


def merge_reports(reports: Iterable[Dict[str, Dict[str, float]]]
                  ) -> Dict[str, Dict[str, float]]:
    """Combine several ``Tracer.report()`` dicts into one aggregate table.

    The serve metrics endpoint exposes one fleet-wide stage view across
    every warm-pool entry's tracer: counts/totals sum, ``max_s`` maxes,
    ``first_s`` keeps the worst cold-start, occupancy recombines from the
    raw slot counts. ``ramp`` is per-tracer by construction (first call vs
    ITS steady state) and is dropped rather than faked.

    Per-device slot accounting (``occ_device`` — mesh-sharded packed
    batches) merges DEVICE-WISE, each device's raw counts summing with
    the same device's counts from other reports. The per-device counts
    are deliberately NEVER folded into the flat ``occ_valid`` /
    ``occ_capacity``: the aggregate is already recorded once per batch
    at the global capacity, so adding the shard slices again would
    double-count valid slots against per-shard capacities and push the
    merged occupancy past 100% (regression-pinned by
    tests/test_mesh_packed.py).
    """
    merged: Dict[str, Dict[str, float]] = {}
    for rep in reports:
        for name, r in rep.items():
            m = merged.setdefault(name, {
                'count': 0, 'total_s': 0.0, 'max_s': 0.0, 'first_s': 0.0,
            })
            m['count'] += r.get('count', 0)
            m['total_s'] += r.get('total_s', 0.0)
            m['max_s'] = max(m['max_s'], r.get('max_s', 0.0))
            m['first_s'] = max(m['first_s'], r.get('first_s', 0.0))
            if 'occ_capacity' in r:
                m['occ_valid'] = m.get('occ_valid', 0) + r['occ_valid']
                m['occ_capacity'] = (m.get('occ_capacity', 0)
                                     + r['occ_capacity'])
            for dev, d in (r.get('occ_device') or {}).items():
                by_dev = m.setdefault('occ_device', {})
                md = by_dev.setdefault(dev, {'occ_valid': 0,
                                             'occ_capacity': 0})
                md['occ_valid'] += d.get('occ_valid', 0)
                md['occ_capacity'] += d.get('occ_capacity', 0)
    for m in merged.values():
        m['mean_s'] = m['total_s'] / max(m['count'], 1)
        if m.get('occ_capacity'):
            m['occupancy'] = m['occ_valid'] / m['occ_capacity']
        for md in (m.get('occ_device') or {}).values():
            md['occupancy'] = (md['occ_valid'] / md['occ_capacity']
                               if md['occ_capacity'] else 0.0)
    return merged


def round_report(report: Dict[str, Dict[str, float]],
                 ndigits: int = 6) -> Dict[str, Dict[str, float]]:
    """A ``Tracer.report()`` with floats rounded for compact JSON
    embedding (bench ``stage_reports``, worklist records) — one
    serializer so every embedded report rounds identically."""
    def _round(v):
        if isinstance(v, float):
            return round(v, ndigits)
        if isinstance(v, dict):             # occ_device's nested records
            return {k: _round(x) for k, x in v.items()}
        return v

    return {name: {k: _round(v) for k, v in rec.items()}
            for name, rec in report.items()}


@contextmanager
def jax_profiler_trace(log_dir: Optional[str]):
    """Capture a jax/XLA profiler trace to ``log_dir`` (None → no-op).

    The trace includes device-side timelines (TPU step traces, XLA op
    breakdowns) viewable with TensorBoard's profile plugin or Perfetto.
    """
    if not log_dir:
        yield
        return
    import jax
    with jax.profiler.trace(str(log_dir)):
        yield
