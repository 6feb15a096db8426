"""BaseExtractor: per-video orchestration, fault isolation, idempotent output.

Re-design of reference models/_base/base_extractor.py (132 LoC) with the same
externally observable contract:
  * ``_extract`` = skip-if-exists → ``extract()`` → [optional rgb||flow
    concat] → ``action_on_extraction``; any exception is isolated per video
    (KeyboardInterrupt re-raised) so one bad file never kills a worker
    (reference base_extractor.py:29-58);
  * ``action_on_extraction`` prints (with max/mean/min) or saves
    numpy/pickle, warns on empty values, and re-checks existence right before
    writing so concurrent shared-filesystem workers collide benignly
    (reference base_extractor.py:60-98);
  * ``is_already_exist`` requires ALL output files present *and loadable* —
    the load doubles as corruption detection, and is what makes workers
    restartable/elastic (reference base_extractor.py:100-132).

Unlike the fork (which concatenates rgb||flow unconditionally and thereby
breaks every non-I3D extractor, reference base_extractor.py:43-52), the concat
here is opt-in via ``concat_rgb_flow`` and only applies when both streams are
present — upstream behavior for everyone else.
"""
from __future__ import annotations

import logging as _logging
import os
import sys
import time as _time
import warnings
from pathlib import Path
from typing import Any, Dict, List, Optional, Union

import numpy as np

from video_features_tpu.obs.events import event
from video_features_tpu.utils.output import (
    ACTION_TO_EXT, ACTION_TO_LOAD, ACTION_TO_SAVE, make_path,
    read_fingerprint, write_fingerprint,
)
from video_features_tpu.utils.tracing import NULL_TRACER, Tracer


# dispatch-table sentinel: "this geometry permanently falls back to the
# jit" (store-side failure already reported) — distinct from None ("not
# looked up yet") so a failed ensure isn't retried on every batch
_AOT_FALLBACK = object()


def log_extraction_error(video_path, request_id=None, stage=None) -> None:
    """The one per-video failure report (fault-isolation contract): every
    loop — per-video, cross-video windower, packed finalize, serve worker
    — emits the same shape through the structured event log (obs/events:
    warning level, stderr, video path + full traceback), so operators and
    log scrapers see one format and ``on_extraction: print`` stdout stays
    byte-clean."""
    from video_features_tpu.obs.events import log_extraction_error as _log
    _log(video_path, request_id=request_id, stage=stage)


def named_step(fn, name: str):
    """``fn`` under a stable function name, for ``jax.jit``: a
    ``functools.partial`` has no ``__name__``, so every family's module
    showed in the device trace as ``jit__unknown``. The returned callable
    makes it ``jit_<name>`` — the name the ``program`` attr of the
    ``model`` / ``device_wait`` spans repeats. A fresh partial, so a
    function shared with other callers is never renamed."""
    from functools import partial
    step = partial(fn)
    step.__name__ = step.__qualname__ = name
    return step


class BaseExtractor:
    """Common per-video orchestration inherited by every extractor."""

    # subclasses must set: output_feat_keys: List[str]
    output_feat_keys: List[str] = []

    def __init__(
        self,
        feature_type: str,
        on_extraction: str,
        tmp_path: str,
        output_path: str,
        keep_tmp_files: bool,
        device: str,
        concat_rgb_flow: bool = False,
        profile: bool = False,
        precision: str = 'highest',
        inflight: int = 2,
        compute_dtype: str = 'float32',
    ) -> None:
        self.feature_type = feature_type
        self.on_extraction = on_extraction
        self.tmp_path = tmp_path
        self.output_path = output_path
        self.keep_tmp_files = keep_tmp_files
        self.device = device
        self.concat_rgb_flow = concat_rgb_flow
        self.precision = precision
        # compute_dtype fast lanes (ops/precision.py): the STORAGE (+
        # activation) dtype of the device step — 'float32' is
        # byte-for-byte today's graph; 'bfloat16' halves params HBM/H2D
        # and runs bf16 activations with fp32 accumulation islands;
        # 'int8' quarter-sizes params via per-output-channel weight
        # quantization (ops/quant.py) with in-graph dequant and fp32
        # activations — each under the family's pinned parity bound.
        # sanity_check already refused unknown values and non-accepting
        # families at config time; extractors constructed directly get
        # the same guard here.
        from video_features_tpu.ops.precision import COMPUTE_DTYPES
        if compute_dtype not in COMPUTE_DTYPES:
            raise ValueError(f'compute_dtype must be one of '
                             f'{COMPUTE_DTYPES}; got {compute_dtype!r}')
        self.compute_dtype = compute_dtype
        # output-side pipelining depth: the device loop keeps up to this
        # many dispatched batches in flight before materializing the
        # oldest one's results (D2H + scatter + save overlap compute);
        # 1 = fully synchronous, outputs byte-identical at any depth
        self.inflight = max(int(inflight or 1), 1)
        # profile controls the PRINTED stage tables; the tracer may also
        # be enabled (tables off) by configure_obs for trace/manifest runs
        self.profile = profile
        self.tracer = Tracer(enabled=True) if profile else NULL_TRACER
        # videos whose outcome was 'failed' (per-video loop and packed
        # finalize both count here): fault isolation keeps the worklist
        # going, the CLI's exit code must still say that it happened
        self.failed_videos = 0
        self._mesh = None  # set by _ensure_mesh for data_parallel extractors
        # mesh-sharded packed execution (mesh_devices=): resolved device
        # count for the packed loop's data-parallel mesh; 1 = today's
        # single-device loop. configure_mesh resolves 0 (auto) at build
        # time; extractors constructed directly stay single-device.
        self.mesh_devices = 1
        self._packed_mesh_ndev = 1
        # serve placement (serve/pool.DevicePlacer): the specific local
        # chip(s) this extractor is resident on — place_on pins them
        # right after build, before any batch flows; None = default
        # (first local device / every local device for a packed mesh)
        self._placement_devices = None
        # bytes the serve DevicePlacer charged this entry's chips at
        # placement time (params_nbytes at build) — released verbatim at
        # retirement so the per-chip residency ledger nets to zero
        self._placement_nbytes = 0
        # content-addressed feature cache + run identity — attached by
        # configure_cache (registry.create_extractor calls it with the
        # full merged config); None = legacy behavior everywhere
        self.cache = None
        self.run_fingerprint = None
        # persistent executable store (aot/) — attached by configure_aot
        # when aot_enabled; None = every program compiles via the jit,
        # exactly today's behavior. _aot_programs is the per-geometry
        # dispatch table aot_call maintains (resident AotPrograms keyed
        # by batch shape/dtype + static kwargs); aot_stats counts which
        # path each resident program took (the serve pool's
        # builds_loaded / builds_compiled split reads it).
        self._aot_store = None
        self._aot_programs: Dict[tuple, object] = {}
        self._aot_lock = None          # created lazily with the store
        self.aot_stats = {'loaded': 0, 'compiled': 0}
        # flight recorder (obs/) — attached by configure_obs when the
        # trace_out / manifest_out knobs are set; None = no telemetry
        # artifacts, exactly today's behavior
        self.trace_out = None
        self.manifest = None
        self.manifest_out = None
        # vft-flight: the run-level trace context (a CLI run is one
        # "request"; per-video spans derive children) and the crash-dump
        # black box (postmortem_dir knob) — both attached by
        # configure_obs, None = legacy behavior
        self.trace_ctx = None
        self.blackbox = None
        # device steps dispatched over this extractor's life, counted
        # only while the tracer is enabled (step_attrs): the ordinal
        # that joins the span timeline to the device trace
        self._steps_dispatched = 0
        self._last_step = None
        # dispatch key -> what aot_call saw there (jitted function,
        # abstract args, statics, matmul precision), remembered only
        # while a manifest is kept; note_executables lowers each once,
        # off the hot path, for the manifest's cost and scope map
        self._dispatched: Dict[tuple, Optional[Dict[str, Any]]] = {}
        # stall-watchdog feed for farm decode workers: the serve layer
        # installs ``watchdog_pending(worker_idx, n_queued)`` and the
        # DecodeFarm mirrors each worker's backlog into it (None = no
        # watchdog, exactly today's behavior)
        self.watchdog_pending = None
        # decode farm (farm/) — the live DecodeFarm handle while (and
        # after) a farm-backed packed run, for the serve metrics surface;
        # run_packed installs it when decode_workers > 1 takes the
        # multi-process input path
        self._farm = None
        self._kernels_said = None

    def say_kernels(self, subsystem: str, notes: Dict[str, Any]) -> None:
        """Which path a hand-written kernel's call site compiles to: said on
        stderr when the answer is new, and kept for the run manifest's
        ``kernels`` section (``obs/manifest.py::note_kernels``)."""
        if notes != self._kernels_said:
            self._kernels_said = notes
            event(_logging.INFO, f'{subsystem}: the kernels',
                  subsystem=subsystem, **notes)
        if self.manifest is not None:
            self.manifest.note_kernels(notes)

    def precision_scope(self):
        """Matmul-precision context for the device loop. ``highest`` (the
        default) keeps full float32 passes for reference parity; ``default``
        lets the TPU run bf16 MXU passes — the fastest and not correct by
        the benchmark's limits (PERF.md §6, PR 28: 13.13 against 8.177
        clips/s in ``i3d.corpus``); ``mixed`` = parity-grade fast mode
        (ops/precision.py): ambient 3-pass bf16, measured ≤1e-3 feature
        drift on the fused path; ``precision_pins`` carries any
        tuned per-sub-graph overrides to extractors that support them."""
        import jax

        from video_features_tpu.ops.precision import MIXED_AMBIENT
        ambient = MIXED_AMBIENT if self.precision == 'mixed' else self.precision
        return jax.default_matmul_precision(ambient)

    @property
    def param_dtype(self):
        """Numpy STORAGE dtype for transplanted params on this lane
        (``ml_dtypes.bfloat16`` for the bf16 fast lane, ``int8`` for the
        weight-quantized lane, else float32) — what ``load_params``
        hands the transplant layer's ``dtype=`` seam, so a fast-lane
        entry's params are reduced-size in HBM from build (int8 selects
        the quantize-eligible-weights path, not a blanket cast)."""
        from video_features_tpu.ops.precision import param_np_dtype
        return param_np_dtype(self.compute_dtype)

    @property
    def compute_jnp_dtype(self):
        """The jnp activation dtype the device step casts its uint8
        input to — threaded into each family's jitted forward as a
        trace-time constant, so the float32 lane's program is
        byte-identical to the pre-knob graph. The int8 lane ACTIVATES in
        float32 (only weight storage is quantized; the in-graph dequant
        lands in the fp32 compute path)."""
        import jax.numpy as jnp
        return jnp.bfloat16 if self.compute_dtype == 'bfloat16' \
            else jnp.float32

    @property
    def precision_pins(self):
        """Per-sub-graph precision overrides for ``precision='mixed'``
        (None otherwise) — thread into step functions that support pins."""
        if self.precision == 'mixed':
            from video_features_tpu.ops.precision import MIXED_PINS
            return MIXED_PINS
        return None

    @property
    def step_name(self) -> str:
        """Function name of the family's jitted hot-path step
        (``named_step``): the device trace shows the program as
        ``jit_<step_name>``."""
        return f'{self.feature_type}_step'

    def step_attrs(self, valid=None, capacity=None) -> Dict[str, Any]:
        """Attrs of the ``model`` span of the device step about to be
        dispatched (empty, and nothing counted, under a disabled
        tracer): ``step``, this extractor's ordinal of dispatched steps,
        and ``program``, the name under which the device trace shows the
        step — the k-th ``program`` module event on the device IS the
        k-th such span, which is the only clock the host timeline and
        the device trace share. ``valid``/``capacity`` are the batch's
        slot counts."""
        if not self.tracer.enabled:
            return {}
        self._steps_dispatched += 1
        self._last_step = {'step': self._steps_dispatched,
                           'program': f'jit_{self.step_name}'}
        attrs = dict(self._last_step)
        if capacity is not None:
            attrs.update(valid=valid, capacity=capacity)
        return attrs

    def last_step(self) -> Optional[Dict[str, Any]]:
        """``step``/``program`` of the step dispatched last (None until
        one was, with the tracer enabled): what its ``device_wait`` span
        carries (``streaming.overlap_fetch``'s ``step_of``)."""
        return self._last_step

    def fetch_outputs(self, out):
        """Materialize one dispatched device step's outputs on the host —
        the deferred D2H + host copy of the async device loop. ``out`` is
        whatever the step returned (a device array or any pytree of
        them); the result is the same structure as numpy arrays. This is
        the SYNC POINT: an asynchronously raised execution error (OOM, a
        geometry that won't run) surfaces here, not at dispatch, which is
        why the packed scheduler's fault isolation wraps this call too.
        Host arrays pass through unchanged, so legacy ``packed_step``
        overrides that still return numpy keep working."""
        import jax
        return jax.device_get(out)

    def put_input(self, batch):
        """Place one host input batch on the device(s): sharded over the
        mesh when data-parallel, else committed to the extractor's device.
        Safe to call from prefetch producer threads (device_put is async
        and thread-safe), which is how extractors overlap the H2D transfer
        of batch k+1 with the device computing batch k."""
        if self._mesh is not None:
            from video_features_tpu.parallel.mesh import require_shardable
            require_shardable(len(batch), self._mesh)
            return self._put_batch(batch)
        import jax
        return jax.device_put(batch, self._device)

    def _ensure_mesh(self, batch_attr: str) -> None:
        """Lazy in-graph data-parallel setup shared by every DP extractor.

        Builds the local-device mesh, rounds the batch attribute named
        ``batch_attr`` up to the global batch, replicates ``self.params``,
        and installs ``self._put_batch``. Lazy because subclasses set
        ``self.params`` after ``super().__init__``.
        """
        if self._mesh is not None:
            return
        from video_features_tpu.parallel import setup_data_parallel
        mesh, global_batch, params, put = setup_data_parallel(
            self.device, getattr(self, batch_attr), self.params)
        self._mesh, self.params, self._put_batch = mesh, params, put
        setattr(self, batch_attr, global_batch)
        # params just moved (replicated over the mesh): resident AOT
        # executables are bound to the old placement — re-resolve
        self._aot_invalidate()

    # -- mesh-sharded packed execution (mesh_devices=) ----------------------

    def configure_mesh(self, args) -> None:
        """Resolve the ``mesh_devices`` knob against this host's local
        devices: ``0`` auto-detects every local device of the extractor's
        platform, an over-ask raises a clear error at BUILD time (a serve
        submit then fails with 'extractor build failed', not a worker
        crash mid-batch). Called by ``registry.create_extractor``;
        extractors constructed directly stay single-device."""
        n = args.get('mesh_devices', 1)
        n = 1 if n is None else int(n)
        if n != 1:
            from video_features_tpu.utils.device import jax_devices_all
            local = jax_devices_all(self.device)
            if n == 0:
                n = len(local)
            elif n > len(local):
                raise ValueError(
                    f'mesh_devices={n} but this host has only '
                    f'{len(local)} local {local[0].platform} device(s) — '
                    'lower mesh_devices (or 0 to auto-detect)')
        self.mesh_devices = max(n, 1)

    def params_nbytes(self) -> int:
        """Per-chip device residency of this extractor's params (plus
        every declared ``_device_buffer_attrs`` buffer), in REAL bytes —
        what the serve placement layer (``serve/pool.DevicePlacer``)
        ranks chips by, so a bf16 fast-lane entry counts its actual
        ~half-size footprint instead of '1 entry'. Logical (per-copy)
        bytes: a mesh entry replicates params per chip, and the placer
        charges each assigned chip one copy."""
        total = 0
        trees = [getattr(self, 'params', None)]
        trees += [getattr(self, attr, None)
                  for attr in self._device_buffer_attrs]
        import jax
        for tree in trees:
            if tree is None:
                continue
            for leaf in jax.tree_util.tree_leaves(tree):
                total += int(getattr(leaf, 'nbytes', 0) or 0)
        return total

    # names of extra device-committed array attributes (beyond
    # ``params``) that ``place_on`` must migrate with the extractor —
    # subclasses that commit auxiliary buffers at build time (vggish's
    # PCA matrices) list them here, or a placed entry would feed a jit
    # call operands committed to two different chips
    _device_buffer_attrs: tuple = ()

    def place_on(self, devices) -> None:
        """Pin this extractor's residency to specific local chip(s) —
        the serve placement layer calls it right after build, BEFORE any
        batch flows, so different model families can be resident on
        different chips. One device: params (and every declared
        ``_device_buffer_attrs`` buffer) move there and every
        ``put_input`` commits there; several devices: the packed mesh
        (``mesh_devices``) builds over exactly these chips."""
        devices = list(devices)
        if not devices:
            return
        self._placement_devices = devices
        if self._mesh is None and len(devices) == 1 \
                and getattr(self, 'params', None) is not None:
            import jax
            self._device = devices[0]
            self.params = jax.device_put(self.params, devices[0])
            for attr in self._device_buffer_attrs:
                buf = getattr(self, attr, None)
                if buf is not None:
                    setattr(self, attr, jax.device_put(buf, devices[0]))
            # re-placed params invalidate device-bound AOT executables;
            # the next warm/dispatch re-keys under the new chip's ids
            self._aot_invalidate()

    def _ensure_packed_mesh(self) -> int:
        """Build the packed loop's data-parallel mesh when
        ``mesh_devices > 1``: an N-device data-only mesh (over the
        placement devices when the serve placer pinned some, else the
        platform's local devices), params replicated per chip, and the
        data-axis batch placement installed so ``put_input`` shards each
        stacked batch. Returns the data-axis size (1 = single-device
        loop, unchanged). Idempotent — a second ``run_packed`` over the
        same extractor (serve workers, bench warm passes) reuses the
        mesh. A ``data_parallel`` extractor already owns a mesh (with
        its batch attr rounded to the global batch), so this defers to
        it and leaves batch planning alone."""
        n = int(getattr(self, 'mesh_devices', 1) or 1)
        if n <= 1:
            return 1
        if self._mesh is not None:
            return self._packed_mesh_ndev
        from functools import partial

        from video_features_tpu.parallel.mesh import make_mesh
        from video_features_tpu.parallel.pipeline import (
            put_batch, put_replicated,
        )
        from video_features_tpu.utils.device import jax_devices_all
        devices = self._placement_devices or jax_devices_all(self.device)
        mesh = make_mesh(n_devices=n, time_parallel=1, devices=devices)
        self._mesh = mesh
        if getattr(self, 'params', None) is not None:
            self.params = put_replicated(mesh, self.params)
        self._put_batch = partial(put_batch, mesh)
        self._packed_mesh_ndev = n
        # params just replicated over the fresh mesh: drop any
        # single-device AOT residents (re-keyed under the mesh lane)
        self._aot_invalidate()
        return n

    # -- content-addressed feature cache (cache/) ---------------------------

    def configure_cache(self, args) -> None:
        """Attach the run fingerprint (config + weights identity — always,
        it also keys config-aware resume) and, when ``cache_enabled``, the
        shared :class:`cache.FeatureCache` for ``cache_dir``. Called by
        ``registry.create_extractor`` with the full merged config;
        extractors constructed directly (tests, stubs) stay legacy."""
        from video_features_tpu.cache import (
            FeatureCache, log_cache_error, run_fingerprint,
        )
        try:
            self.run_fingerprint = run_fingerprint(args)
        except Exception:
            # e.g. an unreadable checkpoint path: the build itself will
            # report it; a fingerprint failure must not mask that error
            log_cache_error('fingerprint derivation')
            self.run_fingerprint = None
            return
        if args.get('cache_enabled') and self.on_extraction in ACTION_TO_EXT:
            try:
                l2 = args.get('cache_l2_dir')
                if l2:
                    # fleet shared tier: local L1 + shared L2
                    from video_features_tpu.fleet.tier import (
                        TieredFeatureCache,
                    )
                    self.cache = TieredFeatureCache.get_pair(
                        args.get('cache_dir'), l2,
                        args.get('cache_max_bytes'))
                else:
                    self.cache = FeatureCache.get(
                        args.get('cache_dir'), args.get('cache_max_bytes'))
            except Exception:
                log_cache_error(f'open ({args.get("cache_dir")})')
                self.cache = None

    # -- persistent executable store (aot/) ---------------------------------

    def configure_aot(self, args) -> None:
        """Attach the persistent executable store when ``aot_enabled``
        — programs then load from disk instead of compiling whenever a
        previous process published the same program (same StableHLO
        identity, jax version, backend, device kind/ids). Called by
        ``registry.create_extractor``; extractors constructed directly
        (tests, stubs) stay legacy. Store failures degrade to
        compile-everything, never to a failed build."""
        if not args.get('aot_enabled'):
            return
        import threading

        from video_features_tpu.aot import ExecStore, log_aot_error
        try:
            l2 = args.get('aot_l2_dir')
            if l2:
                # fleet shared artifact tier: publish-on-compile,
                # pull-on-miss (fleet/artifacts.py)
                from video_features_tpu.fleet.artifacts import (
                    TieredExecStore,
                )
                self._aot_store = TieredExecStore.get_pair(
                    args.get('aot_dir'), l2, args.get('aot_max_bytes'))
            else:
                self._aot_store = ExecStore.get(args.get('aot_dir'),
                                                args.get('aot_max_bytes'))
            self._aot_lock = threading.Lock()
        except Exception:
            log_aot_error(f'open ({args.get("aot_dir")})')
            self._aot_store = None

    def _aot_lane(self) -> str:
        """The program's ``mesh<n>[@dtype]`` lane key — the same naming
        PROGRAMS.lock.json uses for its per-width/per-dtype variants."""
        from video_features_tpu.analysis.programs import mesh_key
        width = 1
        if self._mesh is not None:
            try:
                width = int(self._mesh.shape['data'])
            except (KeyError, TypeError):
                width = max(int(self._packed_mesh_ndev or 1), 1)
        return mesh_key(width, self.compute_dtype)

    def _aot_invalidate(self) -> None:
        """Drop every resident AotProgram. Called whenever params move
        (placement, mesh build): a resident executable is bound to the
        chips it was compiled for, and dispatching it with re-placed
        args would raise — the next ``aot_call`` re-traces and consults
        the store under the NEW device ids instead."""
        self._aot_programs.clear()

    def _aot_dispatch_key(self, name: str, batch, statics: dict) -> tuple:
        # params are attribute-stable between invalidations, so only the
        # batch geometry + the static kwargs + the ambient matmul
        # precision (a trace-context input: the jit re-traces per
        # context, and so must we) discriminate programs
        import jax
        return (name, tuple(batch.shape), str(batch.dtype),
                str(jax.config.jax_default_matmul_precision),
                tuple(sorted(statics.items())))

    def aot_call(self, name: str, jitted, params, batch, **statics):
        """The hot-path dispatch seam: run ``jitted(params, batch,
        **statics)`` through a resident AOT executable when one exists,
        installing one on first sight of a geometry — loaded from the
        persistent store when a previous process published this exact
        program, compiled (and republished) otherwise. Without a store
        this is EXACTLY the legacy call. Byte-identical either way
        (tests/test_aot.py pins loaded ≡ compiled ≡ jit)."""
        key = None
        if self.manifest is not None:
            key = self._aot_dispatch_key(name, batch, statics)
            if key not in self._dispatched:
                self._remember_dispatch(key, jitted, params, batch, statics)
        if self._aot_store is None or not hasattr(jitted, 'trace'):
            return jitted(params, batch, **statics)
        key = key or self._aot_dispatch_key(name, batch, statics)
        prog = self._aot_programs.get(key)
        if prog is None:
            with self._aot_lock:
                prog = self._aot_programs.get(key)
                if prog is None:
                    prog = self._aot_ensure(name, jitted, (params, batch),
                                            statics)
                    self._aot_programs[key] = prog or _AOT_FALLBACK
        if prog is None or prog is _AOT_FALLBACK:
            return jitted(params, batch, **statics)
        return prog(params, batch)

    def _aot_ensure(self, name: str, jitted, args: tuple, statics: dict):
        """Load-or-compile one program; None = fall back to the jit for
        this geometry forever (store-side failure, already reported)."""
        from video_features_tpu.aot import log_aot_error
        from video_features_tpu.aot.runtime import ensure_program
        try:
            prog, path = ensure_program(
                self._aot_store, name, jitted, args, statics,
                lane=self._aot_lane(), feature_type=self.feature_type)
        except Exception:
            log_aot_error(f'{self.feature_type}/{name}')
            return None
        self.aot_stats[path] += 1
        return prog

    def aot_warm(self) -> Dict[str, int]:
        """Eagerly warm every program this extractor's ``program_specs``
        declare, at its CURRENT device placement — the serve boot path
        (``serve_prewarm`` / cold submits call it right after
        ``place_on``), so the first request finds its executables
        resident instead of compiling under the request. Returns the
        {'loaded': n, 'compiled': n} delta. Never raises: a spec that
        won't warm falls back to the lazy dispatch path. No-op without
        a store."""
        before = dict(self.aot_stats)
        if self._aot_store is None:
            return {'loaded': 0, 'compiled': 0}
        try:
            import jax
            from jax.sharding import SingleDeviceSharding
            self._ensure_packed_mesh()
            specs = self.program_specs(mesh=self._mesh)
        except Exception:
            from video_features_tpu.aot import log_aot_error
            log_aot_error(f'warm specs for {self.feature_type}')
            return {'loaded': 0, 'compiled': 0}
        for spec in specs:
            if not hasattr(spec.jitted, 'trace'):
                # not an AOT-stageable jit (e.g. a data_parallel wrapper
                # closure): the dispatch seam falls back to it directly
                continue
            try:
                args = list(spec.args)
                params = getattr(self, 'params', None)
                if params is not None:
                    # the LIVE params (concrete, placed): the lowering
                    # then carries the real device binding, so the
                    # dispatch-time trace of an actual batch hashes to
                    # the SAME store key (verified equal in test_aot)
                    args[0] = params
                batch = args[spec.batch_argnum]
                if self._mesh is None and hasattr(batch, 'shape'):
                    device = getattr(self, '_device', None)
                    if device is not None:
                        batch = jax.ShapeDtypeStruct(
                            batch.shape, batch.dtype,
                            sharding=SingleDeviceSharding(device))
                        args[spec.batch_argnum] = batch
                with self._aot_lock, self.precision_scope():
                    key = self._aot_dispatch_key(
                        spec.name, batch, dict(spec.kwargs))
                    if key in self._aot_programs:
                        continue
                    prog = self._aot_ensure(spec.name, spec.jitted,
                                            tuple(args),
                                            dict(spec.kwargs))
                    self._aot_programs[key] = prog or _AOT_FALLBACK
            except Exception:
                from video_features_tpu.aot import log_aot_error
                log_aot_error(f'warm {self.feature_type}/{spec.name}')
        return {k: self.aot_stats[k] - before.get(k, 0)
                for k in ('loaded', 'compiled')}

    def aot_snapshot(self) -> Dict[str, Any]:
        """The run-manifest / metrics view of this extractor's AOT
        state: which path each resident program took, plus the pinned
        lock hashes the programs derive from."""
        doc: Dict[str, Any] = {'enabled': self._aot_store is not None,
                               'loaded': self.aot_stats['loaded'],
                               'compiled': self.aot_stats['compiled']}
        if self._aot_store is not None:
            doc['dir'] = self._aot_store.aot_dir
            # keyed by name + program identity, NOT name alone: one
            # name covers several geometry specializations (s3d/i3d),
            # and an audit surface must list every distinct program —
            # a 'compiled' entry must never be masked by a 'loaded'
            # same-name sibling
            doc['programs'] = {
                f'{prog.name}@{prog.program_sha[:12]}':
                    {'path': prog.source,
                     'stablehlo_sha256': prog.program_sha}
                for prog in self._aot_programs.values()
                if prog is not _AOT_FALLBACK and prog is not None}
        return doc

    # -- decode farm (farm/) ------------------------------------------------

    def configure_farm(self, args) -> None:
        """Normalize the decode-farm knobs onto the extractor. Every
        extractor gets ``decode_workers`` (families that already read it
        for their in-process transform thread pool keep the same value —
        one knob, one meaning: how much host-decode parallelism to buy)
        and ``decode_farm_ring_mb`` (per-worker SHM ring size). Called by
        ``registry.create_extractor``; extractors constructed directly
        keep the in-process default (``decode_workers=1``). Unset
        (``None``) stays ``None``: the packed scheduler then derives its
        decode lanes from the cores (``streaming.decode_lane_plan``) and
        the per-video loaders read it as 1."""
        workers = args.get('decode_workers')
        self.decode_workers = (None if workers is None
                               else max(int(workers), 1))
        self.decode_farm_ring_mb = max(
            int(args.get('decode_farm_ring_mb', 64) or 64), 1)

    def farm_recipe(self):
        """Picklable decode recipe (``farm/recipes.py``) replaying this
        extractor's decode + host-preprocess stack in a worker PROCESS
        with byte-exact parity, or None when the preprocessing can't be
        described as a spec (the packed scheduler then falls back to
        in-process decode with a structured warning). Families override
        via :class:`StackPackingMixin`/``BaseFrameWiseExtractor``."""
        return None

    def fused_decode_signature(self):
        """Fused-worklist eligibility (``features=[a,b,...]``): families
        whose signatures are EQUAL can share one raw decode pass per
        video (``parallel.packing.run_packed_fused``) because their
        loaders would decode byte-identical frame streams — the
        signature covers everything upstream of the per-frame host
        transform. None (the default) keeps the family out of any fused
        group; it then runs its own sequential pass, outputs unchanged.
        ``BaseFrameWiseExtractor`` overrides for the frame-wise
        families."""
        return None

    # -- flight recorder (obs/) ---------------------------------------------

    def configure_obs(self, args) -> None:
        """Attach the flight recorder when the ``trace_out`` /
        ``manifest_out`` knobs are set: a span recorder on the tracer
        (enabling timing if profiling is off — the printed tables stay
        gated on ``profile``; also reachable through
        ``obs.spans.attached()``) and a per-run manifest collector. Called by
        ``registry.create_extractor``; extractors constructed directly
        stay legacy."""
        trace_out = args.get('trace_out')
        manifest_out = args.get('manifest_out')
        if args.get('postmortem_dir'):
            # crash-dump black box: CLI/packed runs dump on fatal
            # signals and farm-worker deaths (run_packed hands this to
            # the DecodeFarm supervisor); the serve daemon builds its
            # own server-wide BlackBox instead
            from video_features_tpu.obs.blackbox import BlackBox
            self.blackbox = BlackBox(
                str(args['postmortem_dir']),
                max_bytes=args.get('postmortem_max_bytes'),
                recorders=lambda: [getattr(self.tracer, 'recorder',
                                           None)],
                manifest_fn=lambda: (self.manifest.document()
                                     if self.manifest is not None
                                     else None))
        if not (trace_out or manifest_out):
            return
        # a CLI run is one "request": mint a run-level trace context so
        # per-video spans share one trace_id end to end, like serve
        # requests do
        from video_features_tpu.obs.context import mint
        self.trace_ctx = mint()
        if not self.tracer.enabled:
            self.tracer = Tracer(enabled=True)
        # the timeline is kept under either knob (a stage table says how
        # long, only spans say when); it is EXPORTED only under trace_out
        from video_features_tpu.obs.spans import (
            DEFAULT_CAPACITY, SpanRecorder, attach,
        )
        self.tracer.recorder = attach(SpanRecorder(
            int(args.get('trace_capacity') or DEFAULT_CAPACITY)))
        if trace_out:
            self.trace_out = str(trace_out)
        if manifest_out:
            from video_features_tpu.obs.manifest import RunManifest
            self.manifest_out = str(manifest_out)
            self.manifest = RunManifest(args)
            try:
                # which PINNED programs this family maps to
                # (PROGRAMS.lock.json): a production trace then names
                # exactly which contract-checked program ran
                from video_features_tpu.analysis.programs import (
                    family_lock_hashes,
                )
                hashes = family_lock_hashes(self.feature_type)
                if hashes:
                    self.manifest.note_programs_lock(
                        {self.feature_type: hashes})
            except Exception:
                # vft-lint: ok=swallowed-exception — telemetry never
                # fails a run; an unreadable lock reads as "unpinned"
                pass

    def finish_obs(self, export_trace: bool = True) -> None:
        """Publish the run's telemetry artifacts (CLI end-of-run; serve
        worker drain). ``export_trace=False`` skips the trace export for
        callers that own a merged export of the same path (the serve
        daemon's server-wide ``trace_out``). Never raises — a failed
        telemetry write must not fail a run whose outputs are already
        durably saved."""
        import logging as _logging

        from video_features_tpu.obs.events import event
        if self.manifest is not None and self.manifest_out:
            try:
                # residual stages (the loops fold+reset as they go; this
                # catches anything recorded since the last reset)
                self.manifest.fold_stages(self.tracer.report())
                if self._aot_store is not None:
                    # which path every program took (loaded vs compiled)
                    # — the manifest record the zero-cold-start contract
                    # is audited against
                    self.manifest.note_aot(self.aot_snapshot())
                self.manifest.write(self.manifest_out)
            except Exception:
                event(_logging.WARNING, 'run-manifest write failed',
                      exc_info=True, path=self.manifest_out)
        rec = getattr(self.tracer, 'recorder', None)
        if export_trace and rec is not None and self.trace_out:
            try:
                rec.export(self.trace_out)
            except Exception:
                event(_logging.WARNING, 'trace export failed',
                      exc_info=True, path=self.trace_out)

    # -- abstract program specs (analysis/programs.py: vft-programs) --------
    #
    # The program contract checker lowers each family's ACTUAL jitted
    # step at a canonical abstract geometry and pins the signature in
    # PROGRAMS.lock.json (docs/static_analysis.md "Program contracts").
    # Families override program_specs; the helpers below build the
    # abstract (ShapeDtypeStruct) inputs, sharded over a data mesh when
    # the checker pins a mesh-width variant.

    # canonical raw decode geometry (H, W) the program lock pins — one
    # representative shape; the contract is about dtypes/donation/
    # sharding/closure, which are geometry-independent
    PROGRAM_DECODE_HW = (240, 320)

    def program_specs(self, mesh=None) -> list:
        """Abstract AOT program specs for the vft-programs checker: the
        exact jitted callables the hot paths dispatch, paired with
        abstract inputs at the family's canonical lock geometry — the
        batch sharded over ``mesh``'s data axis when given. Families
        override; an empty list reads as "not covered" and is itself a
        checker finding for the eight known families."""
        return []

    def _abstract_params(self, mesh=None):
        """``self.params`` as ShapeDtypeStructs (replicated over ``mesh``
        when given) — lowering needs shapes/dtypes, never values."""
        import jax
        sharding = None
        if mesh is not None:
            from video_features_tpu.parallel.mesh import replicated
            sharding = replicated(mesh)
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding)
            if hasattr(x, 'shape') else x, self.params)

    def _abstract_batch(self, shape, dtype, mesh=None):
        """One abstract device batch, leading axis sharded over the data
        mesh when given (the packed loop's put_input layout)."""
        import jax
        sharding = None
        if mesh is not None:
            from video_features_tpu.parallel.mesh import batch_sharding
            sharding = batch_sharding(mesh)
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    def _program_batch_slots(self, mesh=None) -> int:
        """Global batch rows at the lock geometry: the family's
        per-device capacity × the mesh's data-axis size — the same
        ``plan_device_batch`` arithmetic the packed loop runs."""
        if self.supports_packing:
            capacity = self.packed_batch_size()
        else:
            capacity = int(getattr(self, 'batch_size', 1) or 1)
        if mesh is None:
            return capacity
        from video_features_tpu.parallel.mesh import plan_device_batch
        return plan_device_batch(capacity, mesh)

    def _remember_dispatch(self, key: tuple, jitted, params, batch,
                           statics: dict) -> None:
        """First sight of dispatch ``key`` with a manifest on: keep what a
        lowering of THIS dispatch needs — the jitted function, the args
        as abstract shapes (with the shardings really run; never the
        arrays: the params must stay free-able), the statics and the
        ambient matmul precision. No lowering here: this is the hot
        path (``aot_call`` pays a key and a dict lookup a step)."""
        if not hasattr(jitted, 'lower'):
            self._dispatched[key] = None    # not a jit: nothing to lower
            return
        import jax
        lane = ('' if self.compute_dtype == 'float32'
                else f':{self.compute_dtype}')
        self._dispatched[key] = {
            # feature family × batch geometry × dtype (× lane when it is
            # not the default: fp32 and bf16 entries lower different
            # programs at the same, usually uint8, input geometry)
            'identity': f'{self.feature_type}:{tuple(batch.shape)}:'
                        f'{batch.dtype}{lane}',
            'jitted': jitted,
            'args': jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, 'sharding', None))
                if hasattr(x, 'shape') else x, (params, batch)),
            'statics': dict(statics),
            'precision': jax.config.jax_default_matmul_precision,
            'batch': int(batch.shape[0]) if batch.shape else None}

    def executable_cost(self, remembered: Dict[str, Any]):
        """Best-effort report on the compiled step of one remembered
        dispatch (``_remember_dispatch``): XLA's ``cost_analysis`` and the
        instruction → scope map, from one compile under the matmul
        precision the step was traced in (a trace-context input: another
        precision is another program). None where the step cannot be
        lowered. An optimization report, never a requirement."""
        from contextlib import nullcontext

        import jax

        from video_features_tpu.obs.manifest import xla_cost_analysis
        precision = remembered['precision']
        with (jax.default_matmul_precision(precision) if precision
              else nullcontext()):
            return xla_cost_analysis(remembered['jitted'],
                                     *remembered['args'],
                                     **remembered['statics'])

    def note_executables(self) -> None:
        """Lower, once each and OFF the hot path, what ``aot_call``
        remembered since the last call, and note every executable's cost
        and scope map in the manifest (``executables``). The packed
        scheduler calls it when a worklist is done, the per-video loop
        after each video: an identity already noted is skipped, so after
        warm-up nothing is lowered or compiled on the manifest's account
        (with the persistent compilation cache on, the one compile is a
        cache read). No-op without a manifest."""
        if self.manifest is None:
            return
        for remembered in self._dispatched.values():
            if remembered is None or 'jitted' not in remembered:
                continue
            # every executable record names its lane, so the section
            # says which precision produced the numbers it reports
            info: Dict[str, Any] = {'batch': remembered['batch'],
                                    'compute_dtype': self.compute_dtype}
            info.update(self.executable_cost(remembered) or {})
            self.manifest.note_executable(remembered['identity'], info)
            # noted: drop what held the jit and its shapes
            del remembered['jitted'], remembered['args']

    def _video_cache_key(self, video_path: str, segment=None) -> str:
        from video_features_tpu.cache import video_cache_key
        return video_cache_key(video_path, self.run_fingerprint,
                               segment=segment)

    def cache_fetch(self, video_path: str, output_path: str = None,
                    segment=None, name_path: str = None) -> bool:
        """Serve this video's outputs from the cache if present: a hit
        atomically materializes byte-identical files under the output
        root (plus the resume sidecar) WITHOUT decoding or running the
        network. Cache failures degrade to a miss, never to a failed
        video. ``segment`` keys a range extraction separately from the
        full video; ``name_path`` (the segment-suffixed pseudo-path)
        names the materialized files — content hashing always uses the
        real ``video_path``."""
        if self.cache is None or self.run_fingerprint is None:
            return False
        out_root = output_path or self.output_path
        from video_features_tpu.cache import log_cache_error
        try:
            hit = self.cache.fetch_to(
                self._video_cache_key(video_path, segment),
                out_root, name_path or video_path,
                fingerprint=self.run_fingerprint)
        except Exception:
            log_cache_error(f'lookup for {video_path}')
            return False
        if hit:
            # reference-parity progress line; stdout-safe by construction:
            # the cache is warn-and-disabled under on_extraction=print
            # (sanity_check), so this never interleaves with features
            # vft-lint: ok=stdout-purity — save-mode-only progress line
            print(f'Features for {video_path} served from cache into '
                  f'{Path(out_root).absolute()}/ - skipping extraction..')
        return hit

    def cache_publish(self, video_path: str, output_path: str = None,
                      segment=None, name_path: str = None) -> None:
        """Publish the just-saved output files into the cache (exact
        bytes, so every future hit is byte-identical to this cold run)."""
        if self.cache is None or self.run_fingerprint is None:
            return
        out_root = output_path or self.output_path
        ext = ACTION_TO_EXT[self.on_extraction]
        name = name_path or video_path
        files = {key: (make_path(out_root, name, key, ext), ext)
                 for key in self._saved_feat_keys()}
        if not all(os.path.exists(src) for src, _ in files.values()):
            return                       # partial save (failed video): skip
        from video_features_tpu.cache import hash_file, log_cache_error
        try:
            # the video CONTENT hash (memoized — the cache key derivation
            # already paid for it) rides in the meta so downstream
            # consumers (the feature index) can group rows by source
            # video without re-reading it
            self.cache.put(self._video_cache_key(video_path, segment),
                           files,
                           meta={'video': Path(name).name,
                                 'feature_type': self.feature_type,
                                 'video_sha256': hash_file(video_path)})
        except Exception:
            log_cache_error(f'publish for {video_path}')

    # -- per-video driver ---------------------------------------------------

    def _extract(self, video_path: str) -> None:
        """Fault-isolating wrapper around :meth:`extract` for the work loop."""
        recorder = getattr(self.tracer, 'recorder', None)
        t0_video = _time.perf_counter() if recorder is not None else 0.0
        # per-video child span under the run-level trace (vft-flight)
        video_ctx = (self.trace_ctx.child()
                     if self.trace_ctx is not None else None)
        outcome = 'failed'
        try:
            if self.is_already_exist(video_path):
                outcome = 'skipped'
                return
            if self.cache is not None:
                with self.tracer.stage('cache_lookup',
                                       video=str(video_path)):
                    hit = self.cache_fetch(video_path)
                if hit:
                    outcome = 'cached'
                    return
            feats_dict = self.extract(video_path)
            feats_dict = self._maybe_concat_streams(feats_dict)
            with self.tracer.stage('save', video=str(video_path)):
                self.action_on_extraction(feats_dict, video_path)
            if self.cache is not None:
                with self.tracer.stage('cache_publish',
                                       video=str(video_path)):
                    self.cache_publish(video_path)
            outcome = ('saved' if self.on_extraction in ACTION_TO_EXT
                       else 'printed')
        except KeyboardInterrupt:
            raise
        except Exception:
            outcome = 'failed'
            self.failed_videos += 1
            log_extraction_error(video_path)
        finally:
            # report+reset even on failure so one bad video's timings never
            # leak into the next video's table; the run manifest keeps the
            # whole-run aggregate by folding each video's report first
            if self.tracer.enabled:
                rep = self.tracer.report()
                if rep:
                    if self.manifest is not None:
                        self.manifest.fold_stages(rep)
                    if self.profile:
                        # stderr: the stage table is a diagnostic, and
                        # with on_extraction=print stdout carries features
                        print(f'--- stage timing: {video_path}',
                              file=sys.stderr)
                        print(self.tracer.summary(), file=sys.stderr)
                    self.tracer.reset()
            if self.manifest is not None:
                self.manifest.video_done(video_path, outcome)
                # the step(s) this video was the first to dispatch: cost
                # and scope map, lowered here, between videos
                self.note_executables()
            if recorder is not None:
                recorder.span('video', t0_video, _time.perf_counter(),
                              video=str(video_path), outcome=outcome,
                              **(video_ctx.attrs()
                                 if video_ctx is not None else {}))

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    # -- packed corpus mode (pack_across_videos=true) -----------------------
    #
    # The batch-major outer loop: instead of draining one video at a time
    # (leaving every video's last batch mostly padded and paying pipeline
    # ramp per video), the scheduler in parallel.packing fills every device
    # batch across video boundaries and scatters features back per video.
    # Subclasses opt in by setting ``supports_packing = True`` and
    # implementing the three hooks below; every per-video contract (output
    # files, resume, fault isolation) is preserved by the scheduler.

    supports_packing = False

    def packed_batch_size(self) -> int:
        """Window slots per packed device batch (the compiled batch)."""
        return int(self.batch_size)

    def _packed_setup(self) -> None:
        """One-time pre-run setup (e.g. lazy data-parallel mesh build) —
        runs before ``packed_batch_size`` is read."""

    def packed_windows(self, task):
        """Yield ``(window, meta)`` for one video, in window order.

        ``window`` is the host array one batch slot carries (a frame stack
        or a single frame); ``meta`` is per-window metadata scattered back
        alongside the features (e.g. a timestamp), or None. Video-level
        metadata goes in ``task.info``. ``task.segment`` (when set) is a
        ``(start_s, end_s)`` time range: implementations must emit only
        the windows overlapping it and stop decoding past its end.
        """
        raise NotImplementedError

    def live_window_spec(self):
        """How to window RAW network frames for a live session, or None
        when the family can't (``registry.LIVE_FEATURES`` mirrors this).
        Returns ``(win, step, transform, timed)``: window length / stride
        in frames, an optional per-frame host transform (HWC uint8 →
        model-ready frame), and whether per-window meta is a timestamp
        (frame-wise families) or None (stack families). The live-session
        layer (``ingress/live.py``) replays the exact windowing the
        packed path applies to decoded files, so a live session's windows
        feed the same compiled step."""
        return None

    def packed_step(self, batch) -> Dict:
        """One compiled device step on a packed ``(B, ...)`` batch →
        ``{key: (B, D) DEVICE array}`` — the step DISPATCHES and returns
        without forcing a device→host readback (no ``np.asarray``); the
        scheduler materializes results later via :meth:`fetch_outputs`,
        k batches behind dispatch, so D2H and host finalization overlap
        device compute. Geometry-dependent state (pads, resize,
        per-shape executables) is derived from ``batch.shape`` and cached
        by the implementation."""
        raise NotImplementedError

    def packed_result(self, task) -> Dict[str, np.ndarray]:
        """Assemble one video's feats_dict from its scattered rows
        (``task.rows`` / ``task.meta_rows`` / ``task.info``) — the same
        mapping :meth:`extract` returns for that video."""
        raise NotImplementedError

    def extract_packed(self, video_paths, decode_ahead: int = 2,
                       batch_size: int = None, on_video_done=None,
                       max_pool_age_s: float = None,
                       inflight: int = None,
                       decode_workers: int = None) -> None:
        """Run the whole worklist batch-major (see parallel.packing).

        ``video_paths`` may be any (lazily consumed, possibly blocking)
        iterable of paths / ``VideoTask``s / ``FLUSH`` sentinels — the
        serving layer feeds a live request queue through here;
        ``on_video_done(task)`` fires as each video finalizes;
        ``max_pool_age_s`` bounds how long a partial geometry pool may
        wait for batch-mates (dynamic sources only — a static worklist
        wants maximally full batches); ``inflight`` overrides the
        extractor's output-side pipelining depth (1 = synchronous);
        ``decode_workers`` overrides the input side's parallelism: >1 =
        the multi-process decode farm, 1 = the serial in-process
        windower, and ``None`` = the extractor's own setting — which,
        unset in the config too, means in-process decode lanes: up to K
        videos at once on threads, K from the usable cores (halved, at
        most 4) and the videos at hand."""
        if not self.supports_packing:
            raise NotImplementedError(
                f'{type(self).__name__} does not support pack_across_videos')
        from video_features_tpu.parallel.packing import run_packed
        run_packed(self, video_paths, batch_size=batch_size,
                   decode_ahead=decode_ahead, on_video_done=on_video_done,
                   max_pool_age_s=max_pool_age_s, inflight=inflight,
                   decode_workers=decode_workers)


    def _maybe_concat_streams(self, feats_dict: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """rgb||flow → single (T, 2C) array under 'rgb' when configured.

        Preserves the fork's flagship output (reference
        base_extractor.py:46-50) without breaking single-stream extractors.
        """
        if self.concat_rgb_flow and 'rgb' in feats_dict and 'flow' in feats_dict:
            feats_dict = dict(feats_dict)
            flow = feats_dict.pop('flow')
            feats_dict['rgb'] = np.concatenate((feats_dict['rgb'], flow), axis=1)
        return feats_dict

    # -- output actions -----------------------------------------------------

    def action_on_extraction(self, feats_dict: Dict[str, np.ndarray], video_path: str,
                             output_path: str = None) -> None:
        """``output_path`` (default: the extractor's configured root)
        routes this one video's files elsewhere — the serving layer passes
        each request's root through a shared warm extractor."""
        out_root = output_path or self.output_path
        if self.on_extraction in ACTION_TO_EXT and \
                self.is_already_exist(video_path, output_path=out_root):
            # A concurrent worker finished this video while we extracted
            # it. obs.events, not warnings.warn: the default warnings
            # filter dedups a constant message per process, and an
            # operator watching a long-lived daemon needs EVERY
            # occurrence of this double-work race, not just the first.
            event(_logging.WARNING,
                  'extraction didnt find feature files on the 1st try '
                  'but did on the 2nd try', video=str(video_path))
            return

        for key, value in feats_dict.items():
            if self.on_extraction == 'print':
                print(key)
                print(value)
                print(f'max: {value.max():.8f}; mean: {value.mean():.8f}; min: {value.min():.8f}')
                print()
            elif self.on_extraction in ACTION_TO_EXT:
                os.makedirs(out_root, exist_ok=True)
                fpath = make_path(out_root, video_path, key,
                                  ACTION_TO_EXT[self.on_extraction])
                if key != 'fps' and len(value) == 0:
                    warnings.warn(
                        f'the value is empty for {key} @ {fpath}')
                ACTION_TO_SAVE[self.on_extraction](fpath, value)
            else:
                raise NotImplementedError(
                    f'on_extraction: {self.on_extraction} is not implemented')
        if self.on_extraction in ACTION_TO_EXT \
                and self.run_fingerprint is not None:
            # resume sidecar: records which config+weights produced these
            # files, so a later run under a DIFFERENT recipe re-extracts
            # instead of silently reusing them (is_already_exist)
            write_fingerprint(out_root, video_path, self.run_fingerprint)

    def is_already_exist(self, video_path: Union[str, Path],
                         output_path: str = None) -> bool:
        """True iff every output file exists and loads cleanly (resume contract)."""
        if self.on_extraction not in ACTION_TO_EXT:
            return False

        out_root = output_path or self.output_path
        keys = self._saved_feat_keys()
        for key in keys:
            fpath = make_path(out_root, video_path, key,
                              ACTION_TO_EXT[self.on_extraction])
            if not Path(fpath).exists():
                return False
            try:
                ACTION_TO_LOAD[self.on_extraction](fpath)
            except Exception:
                # Corrupted (e.g. a worker died mid-write) → re-extract;
                # SAY so — a silently re-extracting resume loop hides
                # recurring corruption (bad disk, torn writers)
                event(_logging.WARNING,
                      'existing output failed to load; re-extracting',
                      exc_info=True, video=str(video_path),
                      path=str(fpath))
                return False
        if self.run_fingerprint is not None:
            recorded = read_fingerprint(out_root, video_path)
            if recorded is not None and recorded != self.run_fingerprint:
                # config-aware resume: these files came from a DIFFERENT
                # config/checkpoint recipe — reusing them would hand the
                # caller features from a run they didn't ask for.
                # warnings.warn (stderr), not print: with
                # on_extraction=print the feature stream owns stdout
                warnings.warn(
                    f'Existing outputs for {video_path} in '
                    f'{Path(out_root).absolute()}/ were produced under a '
                    f'different config/checkpoint (fingerprint '
                    f'{recorded[:12]} != {self.run_fingerprint[:12]}) — '
                    're-extracting instead of reusing them')
                return False
            # no sidecar: pre-fingerprint outputs keep the legacy skip
            # (absence can't prove staleness)
        # reference-parity resume line (pinned by the CLI-equivalence
        # tests); save-mode only — is_already_exist returns False up top
        # for on_extraction=print, so this never touches the stream
        # vft-lint: ok=stdout-purity — save-mode-only progress line
        print(f'Features for {video_path} already exist in '
              f'{Path(out_root).absolute()}/ - skipping..')
        return True

    def _saved_feat_keys(self) -> List[str]:
        """Keys that actually reach disk, accounting for the concat folding 'flow' into 'rgb'."""
        keys = list(self.output_feat_keys)
        if self.concat_rgb_flow and 'rgb' in keys and 'flow' in keys:
            keys.remove('flow')
        return keys


class StackPackingMixin:
    """Shared packed hooks for stack families that window RAW decode
    frames into ``stack_batch``-sized device batches (r21d, s3d — i3d
    differs: host resize transform, stack_size+1 windows, multi-stream
    output). One window = one (stack_size, H, W, 3) frame stack; the
    subclass supplies ``packed_step`` and ``packed_feat_dim``."""

    supports_packing = True
    packed_feat_dim: int = 0          # subclasses set the feature width

    def packed_batch_size(self) -> int:
        return int(self.stack_batch)

    def _packed_setup(self) -> None:
        if self.data_parallel:
            self._ensure_mesh('stack_batch')

    def _make_loader(self, video_path: str):
        from video_features_tpu.io.video import VideoLoader
        return VideoLoader(
            video_path, batch_size=64,
            fps=self.extraction_fps, tmp_path=self.tmp_path,
            keep_tmp=self.keep_tmp_files,
            backend=self.decode_backend)

    def packed_windows(self, task):
        from video_features_tpu.extract.streaming import (
            segment_frame_range, stream_windows,
        )
        loader = self._make_loader(task.path)
        # deterministic close (segment early-stop abandons the stream
        # mid-decode; GC-timed release would strand codec contexts and
        # re-encode temps in a long-lived serve worker)
        try:
            for window in stream_windows(
                    loader, self.stack_size, self.step_size,
                    frame_range=segment_frame_range(task.segment,
                                                    loader.fps)):
                yield window, None
        finally:
            loader.close()

    def live_window_spec(self):
        # raw-frame stacks: live frames window exactly like decoded ones
        return (self.stack_size, self.step_size, None, False)

    def packed_result(self, task) -> Dict[str, np.ndarray]:
        rows = task.rows.get(self.feature_type, [])
        return {self.feature_type: (np.stack(rows) if rows
                                    else np.zeros((0, self.packed_feat_dim),
                                                  np.float32))}

    def farm_recipe(self):
        """The stack families decode RAW frames (no host transform), so
        the farm recipe is fully described by the window geometry plus
        the loader knobs ``_make_loader`` passes."""
        from video_features_tpu.farm.recipes import StackRecipe
        return StackRecipe(
            win=self.stack_size, step=self.step_size, batch_size=64,
            fps=self.extraction_fps, total=None, tmp_path=self.tmp_path,
            keep_tmp=self.keep_tmp_files, backend=self.decode_backend,
            transform=None)
