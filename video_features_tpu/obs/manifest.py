"""Per-run JSON run manifest: what ran, on what, and what it cost.

A benchmark number without its recipe is a rumor. The manifest is the
CLI's durable run record (``manifest_out=<path>``): one JSON document
carrying

  * the merged **config** plus the config / weights / run
    **fingerprints** (``cache/key.py`` — the same identities that key
    the content-addressed cache and config-aware resume, so a manifest
    provably names the recipe that produced a directory of features);
  * the aggregate per-**stage** table (``Tracer.report`` folded across
    every video with ``merge_reports`` — identical semantics to the
    serve metrics fleet view);
  * per-**video outcomes** (saved / skipped / cached / failed /
    printed), the honest completion record a 20K-video run needs;
  * **compile** wall time, captured from ``jax.monitoring``'s
    backend-compile duration events (the real XLA compile cost, not a
    first-call-minus-steady estimate);
  * **executables**: per executable identity (feature family × input
    geometry × dtype), from ONE compile of the step as it was really
    dispatched (statics, shardings and matmul precision included): the
    XLA ``cost_analysis`` FLOPs / bytes-accessed, and ``scopes``, the map
    from HLO instruction to ``jax.named_scope`` path (``obs/scopes.py``)
    that turns a device trace's op events into time by the program's own
    names. The FLOPs are XLA's count of the optimised module with every
    ``while`` body counted ONCE (``loops_counted_once`` says when the
    module has one): for a scanned step (i3d's 20 RAFT updates, the lm
    trunks' block loops) a floor, NOT the denominator of an MFU — the
    benchmark takes model FLOPs from its plain references.

Collection is push-based: the extraction loops call ``video_done`` /
``fold_stages`` / ``note_executable`` as they go; ``write`` publishes
atomically. Every collector degrades to a no-op on failure — telemetry
must never fail a run.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Mapping, Optional

from video_features_tpu.obs.spans import _jsonable
from video_features_tpu.utils.tracing import merge_reports

# jax.monitoring event keys that measure XLA compilation; matched by
# substring so minor renames across jax versions degrade to "unattributed"
# rather than KeyError
_COMPILE_EVENT_MARKERS = ('compile',)

_listener_lock = threading.Lock()
_listener_installed = False
_compile_events: Dict[str, Dict[str, float]] = {}


def _on_event_duration(name: str, secs: float, **kwargs) -> None:
    if not any(m in name for m in _COMPILE_EVENT_MARKERS):
        return
    with _listener_lock:
        rec = _compile_events.setdefault(name, {'count': 0, 'total_s': 0.0})
        rec['count'] += 1
        rec['total_s'] += float(secs)


def _install_compile_listener() -> None:
    """Register the jax.monitoring duration listener once per process.
    Listeners cannot be unregistered individually, so the manifest reads
    deltas against the snapshot taken at its construction."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        _listener_installed = True
    try:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration)
    except Exception:
        # vft-lint: ok=swallowed-exception — telemetry never fails the
        # run: the manifest carries an empty compile section on runtimes
        # without jax.monitoring
        pass


def _compile_snapshot() -> Dict[str, Dict[str, float]]:
    with _listener_lock:
        return {k: dict(v) for k, v in _compile_events.items()}


def xla_cost_analysis(jitted, *args, **kwargs) -> Optional[Dict[str, Any]]:
    """Best-effort report on one compiled executable, from ONE compile:
    ``flops`` / ``bytes_accessed`` (XLA's ``cost_analysis()`` of the
    optimised module; a ``while`` body is counted once, and
    ``loops_counted_once`` is set where the module has one) and
    ``scopes``, the instruction → ``jax.named_scope`` map of the same
    compiled object (``obs.scopes.compiled_scopes``).

    AOT-lowers ``jitted`` at the given abstract shapes — through the ONE
    ``jitted.lower(...)`` seam shared with the vft-programs contract
    checker (``analysis.programs.abstract_lowering``). With the persistent
    compilation cache on (``enable_compilation_cache``) the second
    compile is a cache read, not a recompile. Returns None when the
    backend/step doesn't support it — an optimization report, never a
    requirement."""
    try:
        from video_features_tpu.analysis.programs import abstract_lowering
        lowered = abstract_lowering(jitted, *args, **kwargs)
        compiled = lowered.compile()
    except Exception:
        # vft-lint: ok=swallowed-exception — cost analysis is an
        # optimization report, never a requirement (docstring contract)
        return None
    out: Dict[str, Any] = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        for key in ('flops', 'bytes accessed'):
            if cost and key in cost:
                out[key.replace(' ', '_')] = float(cost[key])
    except Exception:
        # vft-lint: ok=swallowed-exception — as above
        pass
    try:
        from video_features_tpu.obs import scopes
        text = compiled.as_text()
        if ' while(' in text:
            out['loops_counted_once'] = True
        out['scopes'] = scopes.compiled_scopes(
            lowered.as_text(debug_info=True), text)
    except Exception:
        # vft-lint: ok=swallowed-exception — as above
        pass
    return out or None


class RunManifest:
    """Accumulates one run's outcomes/stages/costs; writes atomic JSON."""

    def __init__(self, args: Mapping[str, Any]) -> None:
        self._lock = threading.Lock()
        self._t0 = time.time()
        self._t0_perf = time.perf_counter()
        self.config: Dict[str, Any] = {k: _jsonable(v)
                                       for k, v in dict(args).items()}
        self.fingerprints = self._fingerprints(args)
        self.videos: Dict[str, Dict[str, Any]] = {}
        self.stages: Dict[str, Dict[str, float]] = {}
        self.executables: Dict[str, Dict[str, Any]] = {}
        self.farm: Dict[str, Any] = {}
        self.decode: Dict[str, Any] = {}
        self.mesh: Dict[str, Any] = {}
        self.kernels: Dict[str, Any] = {}
        self.ingress: Dict[str, Any] = {}
        self.programs_lock: Dict[str, Any] = {}
        self.aot: Dict[str, Any] = {}
        self.index: Dict[str, Any] = {}
        self.slo: Dict[str, Any] = {}
        self._compile0 = _compile_snapshot()
        _install_compile_listener()

    @staticmethod
    def _fingerprints(args: Mapping[str, Any]) -> Dict[str, Optional[str]]:
        """The same identities the cache and config-aware resume key on;
        each is best-effort (e.g. an unreadable checkpoint path must not
        fail the manifest — the build itself reports that error)."""
        out: Dict[str, Optional[str]] = {
            'config': None, 'weights': None, 'run': None}
        from video_features_tpu.cache.key import (
            config_fingerprint, run_fingerprint, weights_fingerprint,
        )
        for name, fn in (('config', config_fingerprint),
                         ('weights', weights_fingerprint),
                         ('run', run_fingerprint)):
            try:
                out[name] = fn(args)
            except Exception:
                # vft-lint: ok=swallowed-exception — best-effort identity:
                # an unreadable checkpoint fails the BUILD with its own
                # error; the manifest records null rather than masking it
                pass
        return out

    # -- collectors (called from the extraction loops) -----------------------

    def video_done(self, video_path: str, outcome: str) -> None:
        """Record one video's terminal state (saved / skipped / cached /
        failed / printed / expired)."""
        with self._lock:
            self.videos[str(video_path)] = {'outcome': outcome}

    def fold_stages(self, report: Dict[str, Dict[str, float]]) -> None:
        """Merge one ``Tracer.report()`` into the run-wide stage table
        (the per-video loop resets its tracer per video; the manifest
        keeps the whole-run aggregate)."""
        if not report:
            return
        with self._lock:
            self.stages = merge_reports([self.stages, report])

    def note_executable(self, identity: str,
                        info: Dict[str, Any]) -> None:
        """Attach cost/compile info for one executable identity (feature
        family × batch geometry × dtype). Later notes for the same
        identity merge over earlier ones. ``info['scopes']`` (the
        instruction → scope map, ``obs.scopes.compiled_scopes``) is also
        kept for a reader in this process (``obs.scopes.noted()``)."""
        scopes_record = info.get('scopes')
        if scopes_record:
            # the same-process door: a reader of the device trace (the
            # benchmark's scope_time) asks obs.scopes.noted()
            from video_features_tpu.obs import scopes
            scopes.note(scopes_record.get('program'), scopes_record)
        with self._lock:
            self.executables.setdefault(identity, {}).update(
                {k: _jsonable(v) for k, v in info.items()})

    def note_farm(self, info: Dict[str, Any]) -> None:
        """Record the decode farm's configuration + lifetime stats
        (worker count, ring sizing, windows/bytes shipped, respawns) for
        a farm-backed packed run; the section stays ``{}`` on in-process
        runs. Later notes merge over earlier ones (a serve worker's farm
        persists across request waves)."""
        with self._lock:
            self.farm.update({k: _jsonable(v) for k, v in info.items()})

    def note_decode(self, plan: Dict[str, Any],
                    per_lane: Optional[list] = None) -> None:
        """Record how an in-process packed run decoded: the lane plan
        (``streaming.decode_lane_plan``: lanes resolved and why, cores
        seen, videos at hand) of the NEWEST ``run_packed`` call, the
        number of calls, and per lane the videos, windows, chunks, busy
        seconds, seconds blocked on the full hand-over queue and seconds
        from opening a video to its first chunk, summed over calls (a
        benchmark calls ``extract_packed`` once a pass). The section
        stays ``{}`` on farm-backed and per-video runs."""
        with self._lock:
            self.decode.update({k: _jsonable(v) for k, v in plan.items()})
            self.decode['calls'] = self.decode.get('calls', 0) + 1
            totals = self.decode.setdefault('per_lane', [])
            for i, lane in enumerate(per_lane or []):
                if i == len(totals):
                    totals.append({k: 0 for k in lane})
                for k, v in lane.items():
                    totals[i][k] = round(totals[i][k] + v, 6)

    def note_ingress(self, info: Dict[str, Any]) -> None:
        """Record the ingress view of a run (per-tenant request/shed
        counts, live sessions) — written by tooling that drives a run
        THROUGH the front door (the ingress smoke/bench); the section
        stays ``{}`` on loopback/CLI runs. Later notes merge over
        earlier ones."""
        with self._lock:
            self.ingress.update({k: _jsonable(v) for k, v in info.items()})

    def note_programs_lock(self, info: Dict[str, Any]) -> None:
        """Record which PINNED programs this run's families map to:
        ``{family: {mesh<n>: {program: stablehlo_sha256}}}`` from the
        committed ``PROGRAMS.lock.json`` (``analysis/programs.py``) —
        so a production trace names exactly which contract-checked
        program ran, and a trace from BEFORE a re-pin is attributable
        to the old program. ``{}`` when the lock is absent or the
        family unpinned. Later notes merge over earlier ones."""
        with self._lock:
            self.programs_lock.update(
                {k: _jsonable(v) for k, v in info.items()})

    def note_aot(self, info: Dict[str, Any]) -> None:
        """Record the persistent-executable-store view of a run
        (``BaseExtractor.aot_snapshot``): which path each resident
        program took — ``'loaded'`` from the store vs ``'compiled'``
        fresh — with its StableHLO identity, so a run's manifest PROVES
        whether its boot was compile-free instead of implying it. The
        section stays ``{}`` without ``aot_enabled``. Later notes merge
        over earlier ones."""
        with self._lock:
            self.aot.update({k: _jsonable(v) for k, v in info.items()})

    def note_index(self, info: Dict[str, Any]) -> None:
        """Record the feature-index view of a run (``IndexService.stats``
        / ``IndexStore.stats``: rows, shards, ingest lag, query-program
        path) — written by runs that build or query the sharded
        embedding index (the offline ``index`` CLI, the index smoke);
        the section stays ``{}`` otherwise. Later notes merge over
        earlier ones."""
        with self._lock:
            self.index.update({k: _jsonable(v) for k, v in info.items()})

    def note_slo(self, info: Dict[str, Any]) -> None:
        """Record the SLO evaluation view (``SloEvaluator.stats()``:
        objectives, per-window burn rates, alert states) — written by
        servers running with ``slo_latency_p99_s=`` /
        ``slo_availability=``; the section stays ``{}`` otherwise.
        Later notes merge over earlier ones."""
        with self._lock:
            self.slo.update({k: _jsonable(v) for k, v in info.items()})

    def note_kernels(self, info: Dict[str, Any]) -> None:
        """Record which path each call site with a choice compiled to in
        this run (``{'causal_attention': 'kernel' | 'xla'}`` from
        ``ops.attention.resolve_causal``; ``{'retention': 'state',
        'retention_chunk': n}`` from ``models.retention_trunk.kernels``,
        which has one form to report, and its chunk; ``{'raft_lookup':
        'lanes', 'raft_lookup_pixels': 1024, 'raft_lookup_h_chunks': n,
        'raft_lookup_w_chunks': m}`` from ``models.raft.lookup_note``, a
        note a RAFT geometry): a path is all or nothing per program, so this
        line is its engagement counter. ``{}`` for families that have no
        such choice to report."""
        with self._lock:
            self.kernels.update({k: _jsonable(v) for k, v in info.items()})

    def note_mesh(self, info: Dict[str, Any]) -> None:
        """Record the device mesh a mesh-sharded packed run executed on
        (``mesh_devices``, the (data, time) shape, per-device labels,
        per-device capacity vs global batch); the section stays ``{}``
        on single-device runs. Later notes merge over earlier ones."""
        with self._lock:
            self.mesh.update({k: _jsonable(v) for k, v in info.items()})

    # -- publication ---------------------------------------------------------

    def document(self) -> Dict[str, Any]:
        compile_now = _compile_snapshot()
        compile_delta: Dict[str, Dict[str, float]] = {}
        for name, rec in compile_now.items():
            base = self._compile0.get(name, {'count': 0, 'total_s': 0.0})
            d_count = rec['count'] - base['count']
            if d_count > 0:
                compile_delta[name] = {
                    'count': int(d_count),
                    'total_s': round(rec['total_s'] - base['total_s'], 6)}
        with self._lock:
            videos = {p: dict(v) for p, v in self.videos.items()}
            stages = {k: dict(v) for k, v in self.stages.items()}
            executables = {k: dict(v) for k, v in self.executables.items()}
            farm = dict(self.farm)
            decode = dict(self.decode, per_lane=[
                dict(lane) for lane in self.decode.get('per_lane', [])]) \
                if self.decode else {}
            mesh = dict(self.mesh)
            kernels = dict(self.kernels)
            ingress = dict(self.ingress)
            programs_lock = dict(self.programs_lock)
            aot = dict(self.aot)
            index = dict(self.index)
            slo = dict(self.slo)
        outcomes: Dict[str, int] = {}
        for v in videos.values():
            outcomes[v['outcome']] = outcomes.get(v['outcome'], 0) + 1
        from video_features_tpu import __version__
        return {
            'schema': 'video_features_tpu.run_manifest/1',
            'version': __version__,
            'started_at_unix_s': round(self._t0, 3),
            'wall_s': round(time.perf_counter() - self._t0_perf, 3),
            'config': self.config,
            'fingerprints': self.fingerprints,
            'videos': videos,
            'outcomes': outcomes,
            'stages': stages,
            'compile': compile_delta,
            'executables': executables,
            # decode farm (farm/): config + lifetime stats for
            # farm-backed runs, {} on in-process decode
            'farm': farm,
            # in-process decode lanes (extract/streaming.py): the lane
            # plan and per-lane counters of packed runs, {} otherwise
            'decode': decode,
            # mesh-sharded packed execution (mesh_devices > 1): the
            # device mesh the run executed on, {} single-device
            'mesh': mesh,
            # hand-written kernels: which path each call site compiled to
            # ('kernel' or its XLA fallback), {} where there is no choice
            'kernels': kernels,
            # network front door (ingress/): per-tenant request/shed
            # view for runs driven through it, {} otherwise
            'ingress': ingress,
            # program contract lock (analysis/programs.py): the pinned
            # StableHLO hashes this run's families map to, {} when the
            # lock is absent or the family unpinned
            'programs_lock': programs_lock,
            # persistent executable store (aot/): which path each
            # program took (loaded vs compiled) + its StableHLO
            # identity, {} without aot_enabled
            'aot': aot,
            # sharded feature index (index/): rows/shards/ingest-lag +
            # query-program path for runs that build or query it, {}
            # otherwise
            'index': index,
            # SLO burn-rate evaluation (obs/slo): objectives + alert
            # states for runs with slo_* knobs, {} otherwise
            'slo': slo,
        }

    def write(self, path: str) -> str:
        import json
        import os

        from video_features_tpu.utils.output import atomic_write
        doc = self.document()
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        atomic_write(path, lambda f: f.write(
            json.dumps(doc, sort_keys=True, indent=1).encode('utf-8')))
        return path
