#!/usr/bin/env python3
"""Sustained multi-video worklist benchmark (VERDICT r4 task 5).

The north-star workload is a corpus (BASELINE.md: 20K Kinetics clips),
not one stack batch: this tool runs N videos through the REAL extraction
loop — the same fault-isolated per-video `_extract` the CLI runs
(cli.py:69-71), with the resume contract, prefetch pipelining, and
decode/compute overlap all live — and reports videos/min, aggregate
clips/s, and the per-stage wall-time split from the production Tracer.

The worklist is N byte-copies of a source clip under distinct stems
(identical decode cost per item, distinct resume keys — what a sharded
corpus looks like to one worker). A second pass over the same worklist
measures the resume path (everything skips) — the already-done check
must stay O(corpus) cheap or restarts of pod-scale jobs burn hours.

Usage:
    python tools/worklist_bench.py                    # real TPU, i3d, N=4
    BENCH_PLATFORM=cpu N_VIDEOS=2 WORKLIST_SECONDS=2 \
        python tools/worklist_bench.py                # smoke

Prints one JSON record per mode on stdout — the per-video loop first,
then the packed corpus pipeline (``pack_across_videos=true``: batch-major
across videos, parallel/packing.py) three times, pinning one knob per
step so every delta is attributable: ``inflight=1 decode_workers=1``
(the synchronous single-process baseline), ``inflight=2`` (the
deferred-D2H async device loop), and ``inflight=2 decode_workers=N``
(the multi-process decode farm, farm/ — N = ``BENCH_DECODE_WORKERS``,
default 4 on accelerators / 2 on CPU), then ``mesh_devices=N`` (the
mesh-sharded device loop: batches plan at capacity × N and shard over
N chips — ``BENCH_MESH_DEVICES``, default every local device), each
with its batch-occupancy
figure; bench.py embeds them as the ``worklist_clips_per_sec``,
``worklist_packed_clips_per_sec``, ``worklist_async_clips_per_sec``,
``worklist_farm_clips_per_sec``, and ``worklist_mesh_clips_per_sec``
rungs. Every record carries the ``inflight`` depth, ``decode_workers``
count, and resolved ``mesh_devices`` width it ran at.

``BENCH_FUSED=1`` adds the fused multi-family record
(``run_worklist_fused``): one ``features=[...]`` pass decoding and
sha256-hashing each video ONCE vs N sequential per-family passes, with
the wall-clock speedup and the decode / hash amortization ratios —
bench.py embeds it as the ``worklist_fused_*`` rungs.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def bench_decode_workers(on_accel: bool) -> int:
    """The ONE place the ``worklist_farm_*`` rung's worker count comes
    from (``BENCH_DECODE_WORKERS`` override, else 4 on accelerators /
    2 on CPU) — bench.py imports this so both tools' farm rungs always
    run the same configuration under the same rung name."""
    return int(os.environ.get('BENCH_DECODE_WORKERS',
                              4 if on_accel else 2))


def bench_mesh_devices() -> int:
    """The ONE place the ``worklist_mesh_*`` rung's device count comes
    from: ``BENCH_MESH_DEVICES`` override, else every local device (the
    near-linear-scaling headline wants the whole slice; CPU CI forces 2
    virtual host devices via ``--xla_force_host_platform_device_count``).
    Returns at least 1 — on a single-device host the rung still runs,
    its metadata naming the degenerate width."""
    n = int(os.environ.get('BENCH_MESH_DEVICES', 0))
    if n == 0:
        import jax
        n = len(jax.local_devices())
    return max(n, 1)


# the fused rung's per-family models: offline-safe picks (random-weight
# capable, no hub download) whose decode signatures all fuse — resnet /
# clip / timm share ('framewise', None, None, 'auto')
_FUSED_MODELS = {'resnet': 'resnet18', 'clip': 'ViT-B/32',
                 'timm': 'vit_tiny_patch16_224'}


def bench_fused_features() -> list:
    """The ONE place the ``worklist_fused_*`` rung's family set comes
    from (``BENCH_FUSED_FEATURES`` override, comma-separated, default
    ``resnet,clip,timm``) — bench.py imports this so both tools' fused
    rungs always run the same family set under the same rung name."""
    raw = os.environ.get('BENCH_FUSED_FEATURES', 'resnet,clip,timm')
    return [f.strip() for f in raw.split(',') if f.strip()]


def make_worklist(tmp_dir: str, n_videos: int, seconds: float) -> list:
    """N distinct-stem byte-copies of the source clip.

    Source selection delegates to bench.py's ``_bench_video`` — the ONE
    place that picks the benchmark clip (reference sample when present,
    synthetic fallback otherwise; ``BENCH_VIDEO=synthetic`` forces the
    fallback) — so the worklist and e2e rungs always measure the same
    content. ``seconds`` applies to the synthetic fallback only; a
    too-short source surfaces loudly via run_worklist's clips>0 guard."""
    from bench import _bench_video
    src = _bench_video(tmp_dir, seconds=str(seconds))
    paths = []
    for i in range(n_videos):
        dst = Path(tmp_dir) / f'worklist_{i:04d}.mp4'
        shutil.copyfile(src, dst)
        paths.append(str(dst))
    return paths


def run_worklist(feature_type: str, paths: list, out_dir: str,
                 tmp_dir: str, platform: str, batch_size: int = 8,
                 stack: int = 16, precision: str = None,
                 packed: bool = False, inflight: int = None,
                 decode_workers: int = None, mesh_devices: int = None,
                 compute_dtype: str = None):
    """One timed pass of the real worklist loop; returns the record.

    ``packed=False`` times the per-video loop cli.py runs by default;
    ``packed=True`` times the batch-major corpus pipeline
    (``pack_across_videos=true`` → ``extract_packed``, parallel/packing.py)
    and additionally reports the compiled step's batch occupancy.
    ``inflight`` pins the output-side pipelining depth (1 = synchronous
    D2H after every dispatch; default = the config's async depth) — the
    resolved value rides in the record so every rung names the loop it
    measured. ``decode_workers`` pins the input side (1 = in-process
    decode; >1 on the packed path = the multi-process decode farm,
    farm/) and likewise rides in the record. ``mesh_devices`` pins the
    packed loop's data-parallel mesh width (1 = single chip; N shards
    capacity × N batches over N chips, parallel/mesh.py) — the RESOLVED
    width rides in the record. The extractor is created
    once (matching cli.py) so compile caches, weights, and the decode
    service amortize across the worklist the way they do in
    production."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor
    from video_features_tpu.utils.tracing import round_report

    if precision is None:
        precision = os.environ.get('BENCH_PRECISION', 'mixed')
    overrides = {
        'video_paths': paths,
        'device': platform,
        'precision': precision,
        'batch_size': batch_size,
        'allow_random_weights': True,
        'profile': True,                       # per-stage Tracer on
        'pack_across_videos': packed,
        'on_extraction': 'save_numpy',         # resume contract is real
        'output_path': os.path.join(out_dir, 'out'),
        'tmp_path': os.path.join(tmp_dir, 'tmp'),
    }
    if feature_type in ('i3d', 'r21d', 's3d'):
        overrides.update({'stack_size': stack, 'step_size': stack})
    if inflight is not None:
        overrides['inflight'] = int(inflight)
    if decode_workers is not None:
        overrides['decode_workers'] = int(decode_workers)
    if mesh_devices is not None:
        overrides['mesh_devices'] = int(mesh_devices)
    if compute_dtype is not None:
        # the bf16 fast lane (ops/precision.py): outputs are NOT
        # byte-identical to float32's — the *_bf16_* rungs record the
        # measured error next to the speedup for exactly that reason
        overrides['compute_dtype'] = str(compute_dtype)
    args = load_config(feature_type, overrides=overrides)
    ex = create_extractor(args)

    def run_pass(worklist):
        if packed:
            ex.extract_packed(worklist)
        else:
            for p in worklist:
                ex._extract(p)

    # warm pass on the FIRST video only: compile time is a per-process
    # constant, not a per-video term — excluding it measures the
    # sustained rate a long worklist converges to
    run_pass(paths[:1])
    warm_outputs = [f for f in Path(ex.output_path).rglob('*') if f.is_file()]
    assert warm_outputs, (
        'warm pass produced no outputs — extraction failed before the '
        'timed loop (see stderr); aborting rather than timing compiles')
    for sub in warm_outputs:
        sub.unlink()
    ex.tracer.reset()
    # _extract resets the tracer after every video (per-video tables);
    # suppress that during the timed loop so stages accumulate worklist-
    # wide, then restore
    real_reset = ex.tracer.reset
    ex.tracer.reset = lambda: None

    t0 = time.perf_counter()
    run_pass(paths)                           # the cli.py loop, timed
    elapsed = time.perf_counter() - t0
    stages = ex.tracer.report()
    ex.tracer.reset = real_reset
    ex.tracer.reset()

    # clips actually produced (from the saved outputs — the real contract)
    from video_features_tpu.utils.output import make_path
    keys = ex._saved_feat_keys()
    clips = 0
    for p in paths:
        fpath = make_path(ex.output_path, p, keys[0], '.npy')
        if Path(fpath).exists():
            arr = np.load(fpath, allow_pickle=True)
            if getattr(arr, 'ndim', 0) >= 1:
                clips += arr.shape[0]

    # success guard: _extract fault-isolates per video, so a worklist of
    # failures would otherwise "complete" fast and record a bogus rate
    assert clips > 0, (
        f'worklist produced 0 clips over {len(paths)} videos — extraction '
        'failed (see stderr) or the source clip is shorter than one stack')

    t1 = time.perf_counter()
    run_pass(paths)                           # resume pass: all skip
    resume_elapsed = time.perf_counter() - t1

    occupancy = stages.get('model', {}).get('occupancy')
    return {
        'feature_type': feature_type,
        'precision': precision,
        'packed': packed,
        # the output-side pipelining depth this rung actually ran at
        # (1 = synchronous loop) — rung metadata, so a BENCH_*.json
        # says which device loop produced its number
        'inflight': int(args.get('inflight', 1)),
        # the input side's decode parallelism (1 = in-process; >1 packed
        # = the decode farm) — rung metadata like inflight
        'decode_workers': int(args.get('decode_workers', 1)),
        # the RESOLVED mesh width the packed loop sharded over (1 =
        # single chip; mesh_devices=0 auto-detect resolves here) —
        # config metadata naming the device set behind the number
        'mesh_devices': int(getattr(ex, '_packed_mesh_ndev', 1) or 1),
        # the precision lane the step computed in ('float32' default;
        # 'bfloat16' = the fast lane) — rung metadata like inflight
        'compute_dtype': str(getattr(ex, 'compute_dtype', 'float32')),
        'n_videos': len(paths),
        'videos_per_min': round(len(paths) / elapsed * 60, 3),
        'clips_total': int(clips),
        'clips_per_sec': round(clips / elapsed, 3),
        'batch_occupancy': (round(occupancy, 4)
                            if occupancy is not None else None),
        'resume_pass_s': round(resume_elapsed, 4),
        # the FULL per-stage Tracer report (not just totals): bench.py
        # embeds it under the record's stage_reports so a BENCH_*.json
        # carries the wall-time split behind every rung
        'stages': round_report(stages),
    }


def run_worklist_fused(families: list, paths: list, out_dir: str,
                       tmp_dir: str, platform: str, batch_size: int = 8,
                       precision: str = None):
    """One fused multi-family pass vs N sequential passes; returns the
    record behind the ``worklist_fused_*`` rungs.

    The fused pass drives every family through ONE decode stream per
    video (``run_packed_fused``, parallel/packing.py) while the
    sequential baseline runs each family's own ``extract_packed`` over
    the same worklist — the exact N-runs-of-the-CLI comparison the
    ``features=[...]`` config replaces. Three ratios ride in the record:

      * ``fused_speedup`` — sequential wall over fused wall (the
        headline: what a corpus owner saves by fusing);
      * ``decode_amortization`` — sequential decode+preprocess seconds
        over fused (→ N for N fully-amortized families);
      * ``hash_amortization`` — sequential sha256 passes over fused
        (the content-cache keying cost; fused hashes each video once).

    Every pass runs over FRESH byte-copies of the worklist: distinct
    paths keep the stat-keyed ``hash_file`` memo provably cold per pass
    (each sequential family pass models its own CLI process) and keep
    resume sidecars from turning later passes into all-skip no-ops.
    A byte-parity sweep over the outputs guards the speedup claim —
    a fused run that diverged from sequential must not record a rate.
    """
    from video_features_tpu.cache.key import (
        hash_file_stats, reset_hash_file_stats,
    )
    from video_features_tpu.cache.store import FeatureCache
    from video_features_tpu.config import load_fused_configs
    from video_features_tpu.parallel.packing import (
        FusedTask, VideoTask, run_packed_fused,
    )
    from video_features_tpu.registry import create_extractor
    from video_features_tpu.utils.output import make_path
    from video_features_tpu.utils.tracing import round_report

    if precision is None:
        precision = os.environ.get('BENCH_PRECISION', 'mixed')
    overrides = {
        'video_paths': paths,
        'device': platform,
        'precision': precision,
        'batch_size': batch_size,
        'allow_random_weights': True,
        'profile': True,                       # per-stage Tracer on
        'pack_across_videos': True,
        'on_extraction': 'save_numpy',
        'output_path': os.path.join(out_dir, 'out'),
        'tmp_path': os.path.join(tmp_dir, 'fused_tmp'),
    }
    for fam in families:
        if fam in _FUSED_MODELS:
            overrides[f'{fam}.model_name'] = _FUSED_MODELS[fam]
    configs = load_fused_configs(families, overrides=overrides)
    exs = {fam: create_extractor(cfg) for fam, cfg in configs.items()}
    sigs = {fam: ex.fused_decode_signature() for fam, ex in exs.items()}
    assert len(set(sigs.values())) == 1 and None not in sigs.values(), (
        f'fused rung families must share one decode signature: {sigs}')

    def copies(tag):
        d = Path(tmp_dir) / f'copies_{tag}'
        d.mkdir(parents=True, exist_ok=True)
        return [str(shutil.copyfile(p, str(d / Path(p).name)) or
                    d / Path(p).name) for p in paths]

    def fused_tasks(worklist, tag):
        tasks = []
        for p in worklist:
            c = FusedTask(p, list(exs))
            for fam, sub in c.subtasks.items():
                sub.out_root = os.path.join(out_dir, tag, fam)
            tasks.append(c)
        return tasks

    def decode_total(rep):
        return sum(rep.get(k, {}).get('total_s', 0.0)
                   for k in ('decode', 'decode+preprocess'))

    # warm pass (fused) compiles every family's programs — the fused
    # packer pools per family at each family's own batch size, so these
    # are the SAME program identities the sequential passes reuse
    run_packed_fused(exs, fused_tasks(copies('warm'), 'warm'))
    warm = [f for f in Path(out_dir, 'warm').rglob('*.npy')]
    assert warm, (
        'fused warm pass produced no outputs — extraction failed before '
        'the timed loop (see stderr); aborting rather than timing compiles')

    # suppress per-video tracer resets so stages accumulate per phase;
    # the saved bound methods reset between phases and restore at the end
    real_resets = {fam: ex.tracer.reset for fam, ex in exs.items()}
    for ex in exs.values():
        ex.tracer.reset = lambda: None
    try:
        for reset in real_resets.values():
            reset()

        # -- sequential baseline: one extract_packed pass per family,
        # each over its own worklist copies + its own content cache
        # (modeling N separate CLI processes: cold sha256 memo each)
        seq_wall = seq_decode = 0.0
        seq_hash_passes = 0
        for fam, ex in exs.items():
            assert ex.run_fingerprint is not None, fam
            wl = copies(f'seq_{fam}')
            tasks = [VideoTask(p, out_root=os.path.join(out_dir, 'seq', fam))
                     for p in wl]
            ex.cache = FeatureCache(os.path.join(tmp_dir, 'cache_seq', fam))
            reset_hash_file_stats()
            t0 = time.perf_counter()
            ex.extract_packed(tasks)
            seq_wall += time.perf_counter() - t0
            seq_hash_passes += hash_file_stats()['passes']
            seq_decode += decode_total(ex.tracer.report())
            ex.cache = None
            real_resets[fam]()

        # -- the fused pass: one decode + one sha256 pass per video
        wl = copies('fused')
        tasks = fused_tasks(wl, 'fused')
        for fam, ex in exs.items():
            ex.cache = FeatureCache(os.path.join(tmp_dir, 'cache_fused',
                                                 fam))
        reset_hash_file_stats()
        t0 = time.perf_counter()
        run_packed_fused(exs, tasks)
        fused_wall = time.perf_counter() - t0
        fused_hash = hash_file_stats()
        lead = exs[next(iter(exs))]
        fused_stages = lead.tracer.report()
        fused_decode = decode_total(fused_stages)
        for ex in exs.values():
            ex.cache = None
    finally:
        for fam, ex in exs.items():
            ex.tracer.reset = real_resets[fam]
            ex.tracer.reset()

    # byte-parity sweep + clip count from the saved outputs (the real
    # contract): a fused run that diverged must not record a speedup
    clips = 0
    for fam, ex in exs.items():
        keys = ex._saved_feat_keys()
        for p in wl:
            fused_f = make_path(os.path.join(out_dir, 'fused', fam),
                                p, keys[0], '.npy')
            seq_f = make_path(os.path.join(out_dir, 'seq', fam),
                              p, keys[0], '.npy')
            a = np.load(fused_f, allow_pickle=True)
            b = np.load(seq_f, allow_pickle=True)
            assert np.array_equal(a, b), (
                f'fused outputs diverged from sequential: {fam} {p}')
            if getattr(a, 'ndim', 0) >= 1:
                clips += a.shape[0]
    assert clips > 0, (
        f'fused worklist produced 0 clips over {len(paths)} videos — '
        'extraction failed (see stderr) or the source clip is too short')

    return {
        'families': list(exs),
        'precision': precision,
        'n_videos': len(paths),
        'n_families': len(exs),
        'clips_total': int(clips),
        'clips_per_sec': round(clips / fused_wall, 3),
        'fused_wall_s': round(fused_wall, 4),
        'sequential_wall_s': round(seq_wall, 4),
        # the headline ratio: N sequential family passes over one fused
        # pass — higher is better, → N as decode dominates
        'fused_speedup': round(seq_wall / fused_wall, 4),
        'decode_s_sequential': round(seq_decode, 4),
        'decode_s_fused': round(fused_decode, 4),
        'decode_amortization': (round(seq_decode / fused_decode, 4)
                                if fused_decode > 0 else None),
        # sha256 content-keying passes: fused streams each video once
        'hash_passes_sequential': int(seq_hash_passes),
        'hash_passes_fused': int(fused_hash['passes']),
        'hash_amortization': (round(seq_hash_passes
                                    / fused_hash['passes'], 4)
                              if fused_hash['passes'] else None),
        # the lead tracer's fused-pass split (shared decode + the lead
        # family's device stages) — embedded under stage_reports
        'stages': round_report(fused_stages),
    }


def main() -> int:
    import contextlib
    import tempfile

    import jax
    if os.environ.get('BENCH_PLATFORM'):
        jax.config.update('jax_platforms', os.environ['BENCH_PLATFORM'])
    from video_features_tpu.utils.device import enable_compilation_cache

    platform = jax.devices()[0].platform
    on_accel = platform != 'cpu'
    enable_compilation_cache('auto', platform)
    n = int(os.environ.get('N_VIDEOS', 4 if on_accel else 2))
    seconds = float(os.environ.get('WORKLIST_SECONDS',
                                   10 if on_accel else 2))
    feature_type = os.environ.get('WORKLIST_FEATURE', 'i3d')
    stdout = sys.stdout
    # the loop's per-video prints (skip messages, warnings) belong on
    # stderr; stdout carries the JSON records only
    with tempfile.TemporaryDirectory() as td, \
            contextlib.redirect_stdout(sys.stderr):
        paths = make_worklist(td, n, seconds)
        batch = 8 if on_accel else 2
        stack = int(os.environ.get('BENCH_STACK', 16))
        rec = run_worklist(feature_type, paths, td, td, platform,
                           batch_size=batch, stack=stack)
        # packed mode writes under its own output root so the per-video
        # pass's resume files can't turn it into an all-skip no-op; only
        # families with packed support run it — an unsupported feature
        # must still emit its per-video record, not crash the tool
        from video_features_tpu.registry import PACKED_FEATURES
        rec_packed = rec_async = rec_farm = rec_mesh = None
        if feature_type in PACKED_FEATURES:
            # the packed ladder pins ONE knob per record so each delta
            # is attributable: sync in-process → async in-process →
            # async + decode farm.
            # inflight=1 decode_workers=1 pins the fully SYNCHRONOUS
            # single-process packed loop (the pre-async baseline)...
            rec_packed = run_worklist(feature_type, paths,
                                      os.path.join(td, 'packed'), td,
                                      platform, batch_size=batch,
                                      stack=stack, packed=True, inflight=1,
                                      decode_workers=1)
            # ...the async record adds only the deferred-D2H loop...
            rec_async = run_worklist(feature_type, paths,
                                     os.path.join(td, 'packed_async'), td,
                                     platform, batch_size=batch,
                                     stack=stack, packed=True, inflight=2,
                                     decode_workers=1)
            # ...and the farm record adds the multi-process decode farm
            # (farm/) on top — the full pipeline
            n_decode = bench_decode_workers(on_accel)
            rec_farm = run_worklist(feature_type, paths,
                                    os.path.join(td, 'packed_farm'), td,
                                    platform, batch_size=batch,
                                    stack=stack, packed=True, inflight=2,
                                    decode_workers=n_decode)
            # ...and the mesh record shards the async loop's batches
            # over N chips (capacity × N planning, parallel/mesh.py) —
            # the pod-scale rung; outputs stay byte-identical
            # (tests/test_mesh_packed.py)
            rec_mesh = run_worklist(feature_type, paths,
                                    os.path.join(td, 'packed_mesh'), td,
                                    platform, batch_size=batch,
                                    stack=stack, packed=True, inflight=2,
                                    decode_workers=1,
                                    mesh_devices=bench_mesh_devices())
        # the fused multi-family record is opt-in for the standalone
        # tool (it transplants one model per family); bench.py gates it
        # the same way under the worklist_fused_* rungs
        rec_fused = None
        if os.environ.get('BENCH_FUSED', '0') == '1':
            rec_fused = run_worklist_fused(bench_fused_features(), paths,
                                           os.path.join(td, 'fused'), td,
                                           platform, batch_size=batch)
    print(json.dumps(rec), file=stdout)
    for extra in (rec_packed, rec_async, rec_farm, rec_mesh, rec_fused):
        if extra is not None:
            print(json.dumps(extra), file=stdout)
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
