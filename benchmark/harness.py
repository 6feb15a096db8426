"""The benchmark's body: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Data drives it. The cell's file (``workloads/<cell>.json``) names a
configuration, a driver and a traffic mix; each of those, each per-layer
metric, each reader and each reference is a file found by name
(``loader.py``). A later PR adds files; nothing here changes for a new cell.

One run: make the weights and the corpus from ``--seed`` → build the program's
extractor exactly as ``cli.main`` does (``create_extractor(load_config(..))``)
→ warm the cell's one shape → **the window**: whole passes over the worklist
through the driver until ``--seconds`` have elapsed → read the memory peak →
free the program → read the saved ``.npy`` files back and compare a sample,
drawn from the seed, with the plain reference run from the video files
(``compare.py``) → one JSON line, last on stdout.

The program's own chatter (``print`` to stdout, also from C) is sent to
stderr for the whole run, so the result line is the only line on stdout.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import loader

EXIT_NO_DEVICE = 3


def log(*parts) -> None:
    print('[bench]', *parts, file=sys.stderr, flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    # not used by the driver's check: the control of "How correct is
    # decided" (the program's own lower-precision lane) for the builder
    ap.add_argument('--control', type=int, choices=(0, 1), default=0)
    # for looking at a trace by hand (benchmark/inspect_trace.py)
    ap.add_argument('--keep-trace', default=None)
    return ap.parse_args(argv)


# -- the cell, from its files ----------------------------------------------

def load_cell(name: str) -> Dict:
    bench = loader.benchmark_json()
    entries = {w['name']: w for w in bench['workloads']}
    if name not in entries:
        raise SystemExit(f'unknown workload {name!r}; BENCHMARK.json has '
                         f'{sorted(entries)}')
    entry = entries[name]
    workload = loader.load_json('workloads', name)
    for key in ('config', 'traffic'):
        if workload[key] != entry[key]:
            raise SystemExit(f'workloads/{name}.json says {key}='
                             f'{workload[key]!r}, BENCHMARK.json says '
                             f'{entry[key]!r}')
    config = loader.load_json('configs', entry['config'])
    traffic = loader.load_json('traffic', entry['traffic'])
    return {'name': name, 'chips': int(entry['chips']), 'bench': bench,
            'workload': workload, 'config': config, 'traffic': traffic}


def metrics_of(cell: Dict, group: str) -> List[Dict]:
    """The cell's metrics of ``end_to_end`` or ``per_layer``: those with no
    ``workloads`` key whose moved metric the cell reports, and those that
    list the cell."""
    bench, name = cell['bench'], cell['name']
    e2e = [m for m in bench['end_to_end']
           if name in m.get('workloads', [name])]
    if group == 'end_to_end':
        return e2e
    names = {m['name'] for m in e2e}
    return [m for m in bench['per_layer']
            if name in m.get('workloads', [name]) and m['moves'] in names]


def peaks_for(kind: str) -> Dict:
    table = json.loads((loader.BENCH / 'peaks.json').read_text())
    if kind not in table['devices']:
        raise SystemExit(f'device kind {kind!r} is not in benchmark/'
                         f'peaks.json ({sorted(table["devices"])}): add it '
                         'with its source, there is no default')
    return table['devices'][kind]


# -- pieces of a run ---------------------------------------------------------

def find_devices(require_tpu: bool, chips: int):
    """(devices, seconds the backend took to come up). Bringing up the TPU
    runtime is libtpu's time, not the program's: 8 to 17 s on one and the same
    machine, by how many processes it has started before (my chip runs,
    PR 24). It is timed apart and left out of ``setup_s``, which keeps every
    second a PR of this repo can move: imports, weights, corpus, extractor
    build, compile or cache load, warm-up."""
    import jax
    t0 = time.perf_counter()
    devices = jax.devices()
    backend_s = time.perf_counter() - t0
    platform = devices[0].platform
    if require_tpu and (platform != 'tpu' or len(devices) < chips):
        log(f'needs {chips} TPU chip(s); jax found {len(devices)} × '
            f'{platform!r}. Nothing was measured.')
        raise SystemExit(EXIT_NO_DEVICE)
    return devices[:chips], backend_s


def make_weights(reference, seed: int, work: Path) -> Dict[str, str]:
    import weights
    paths = {}
    for key, specs in reference.param_specs().items():
        paths[key] = weights.save(weights.make(specs, seed, key),
                                  str(work / f'{key}.npz'))
    return paths


def build_extractor(config: Dict, ckpts: Dict[str, str], work: Path,
                    extra: Dict, first_video: str):
    """``create_extractor(load_config(..))`` — what ``cli.main`` does."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor
    overrides = dict(config['overrides'])
    overrides.update(ckpts)
    overrides.update(output_path=str(work / 'out'), tmp_path=str(work / 'tmp'),
                     video_paths=[first_video])
    overrides.update(extra)
    args = load_config(config['feature_type'], overrides=overrides)
    return create_extractor(args), args


def stage_table(extractor) -> Dict[str, Dict[str, float]]:
    manifest = getattr(extractor, 'manifest', None)
    if manifest is None:
        return {}
    return {k: dict(v) for k, v in manifest.stages.items()}


def stage_delta(after: Dict, before: Dict) -> Dict[str, Dict[str, float]]:
    out = {}
    for name, rec in after.items():
        base = before.get(name, {})
        out[name] = {k: rec.get(k, 0) - base.get(k, 0)
                     for k in ('count', 'total_s', 'occ_valid',
                               'occ_capacity') if k in rec}
    return out


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest chip, as jax reports it. The TPU runtime keeps
    two books: ``peak_bytes_in_use`` counts buffers (weights, batches in
    flight, outputs) and leaves out the temporaries of a loaded program,
    which it *reserves* apart (``peak_bytes_reserved``: 5,347,819,520 bytes
    for the resnet50 step at batch 1,024, to the byte what the compiler says
    the step's temporaries need; my chip run, PR 24). The chip holds both at
    once while a step runs, so the peak is their sum."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get('peak_bytes_in_use', 0))
                   + int(stats.get('peak_bytes_reserved', 0)))
    return peak


class CacheHits:
    """Counts jax's persistent-compilation-cache hits and misses."""

    def __init__(self) -> None:
        self.hits = self.misses = 0
        import jax.monitoring

        def on_event(name: str, **kw) -> None:
            if name.endswith('/cache_hits'):
                self.hits += 1
            elif name.endswith('/cache_misses'):
                self.misses += 1

        jax.monitoring.register_event_listener(on_event)


def saved_path(extractor, config: Dict, video_path: str) -> str:
    return os.path.join(extractor.output_path, config['saved_file'].format(
        stem=Path(video_path).stem))


# -- one run ---------------------------------------------------------------

def run(argv=None, *, t_start: Optional[float] = None,
        require_tpu: bool = True, program_overrides: Optional[Dict] = None,
        traffic_overrides: Optional[Dict] = None,
        workload_overrides: Optional[Dict] = None,
        before_window=None) -> Dict:
    """One run; returns the result object (the caller prints it).

    The keyword arguments are for the tests under tests/bench, which drive a
    run at a tiny size without a chip (``require_tpu=False``, overrides of
    batch, device and corpus) and break the timed path underneath
    (``before_window(extractor)``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    ns = parse_args(argv)
    cell = load_cell(ns.workload)
    workload = dict(cell['workload'], **(workload_overrides or {}))
    config = cell['config']
    traffic = dict(cell['traffic'], **(traffic_overrides or {}))

    devices, backend_s = find_devices(require_tpu, cell['chips'])
    log(f'imports {time.perf_counter() - t_start - backend_s:.1f} s, backend '
        f'bring-up {backend_s:.1f} s (not in setup_s)')
    import video_features_tpu  # noqa: F401  no program here, no run
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices)}
    peaks = peaks_for(device['kind']) if require_tpu else None
    hits = CacheHits()

    import compare
    import traffic_gen
    reference = loader.load_module('references', config['reference'])
    driver = loader.load_module('drivers', workload['driver'])

    work = Path(tempfile.mkdtemp(prefix=f'bench-{cell["name"]}-'))
    try:
        t0 = time.perf_counter()
        ckpts = make_weights(reference, ns.seed, work)
        t1 = time.perf_counter()
        corpus = traffic_gen.generate(traffic, ns.seed, str(work / 'corpus'))
        t2 = time.perf_counter()
        log(f'weights {t1 - t0:.1f} s, corpus {t2 - t1:.1f} s: '
            f'{len(corpus["clips"])} clips, '
            f'{sum(c["frames"] for c in corpus["clips"])} frames')

        extra = dict(program_overrides or {})
        if ns.control:
            extra.update(config['control_overrides'])
            log(f'CONTROL run: {config["control_overrides"]}')
        if ns.trace:
            # the program's own stage table, folded into its run manifest
            extra['manifest_out'] = str(work / 'manifest.json')
        warm_items = [i for i in traffic_gen.pass_paths(corpus, 'warm')
                      if i['clip'] in workload['warm_clips']]
        extractor, args = build_extractor(config, ckpts, work, extra,
                                          warm_items[0]['path'])
        t3 = time.perf_counter()
        driver.warm(extractor, warm_items)
        if extractor.failed_videos:
            raise RuntimeError('the warm-up video failed; see the traceback '
                               'above')
        if before_window is not None:
            before_window(extractor)
        setup_s = time.perf_counter() - t_start - backend_s
        log(f'extractor {t3 - t2:.1f} s, warm-up {time.perf_counter() - t3:.1f}'
            f' s, compile-cache hits {hits.hits} misses {hits.misses}; '
            f'decode backend {decode_backend(args)}')
        hits_at_window = (hits.hits, hits.misses)

        # -- the window ---------------------------------------------------
        stages0 = stage_table(extractor)
        trace_dir = ns.keep_trace or str(work / 'trace')
        if ns.trace:
            start_trace(trace_dir)
        passes: List[List[Dict]] = []
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < ns.seconds:
            items = traffic_gen.pass_paths(corpus, f'p{len(passes)}')
            passes.append(items)
            driver.run_pass(extractor, items)
        window_s = time.perf_counter() - t_open
        if ns.trace:
            stop_trace()
        extractor.finish_obs()
        stages = stage_delta(stage_table(extractor), stages0)
        peak = memory_peak_bytes(devices)
        compiled_in_window = (hits.hits - hits_at_window[0]
                              + hits.misses - hits_at_window[1])

        # -- what the window saved ------------------------------------------
        done = compare.collect(
            passes, lambda p: saved_path(extractor, config, p), reference)
        units = sum(v['rows'] for v in done if v['saved'])
        slots = sum(driver.batch_slots(
            extractor, [reference.rows_of(i['frames']) for i in items])
            for items in passes)
        attempted = sum(len(items) for items in passes)
        failed = sum(1 for v in done if not v['saved'])
        log(f'window {window_s:.3f} s, {len(passes)} pass(es), {attempted} '
            f'videos, {units} {reference.UNIT}s saved, peak bytes in use '
            f'{peak}, programs compiled or loaded inside the window: '
            f'{compiled_in_window}')
        for name, rec in stages.items():
            log(f'span {name}: {rec["total_s"]:.3f} s in {rec["count"]} calls')
        log('device memory', json.dumps(devices[0].memory_stats()))

        # -- free the program, then the reference ---------------------------
        batch_size = int(getattr(extractor, 'batch_size', 0) or 0)
        extractor.params = None
        del extractor
        gc.collect()
        import jax
        jax.clear_caches()
        t_ref = time.perf_counter()
        checks, n_rows = compare.compare(done, reference, ckpts, workload,
                                         ns.seed)
        log(f'reference and comparison {time.perf_counter() - t_ref:.1f} s '
            f'over {n_rows} sampled rows')

        # -- metrics ----------------------------------------------------------
        values = {config['rate_metric']: units / window_s,
                  'setup_s': setup_s}
        extra_device, breakdown = {}, None
        if ns.trace:
            ctx = {'workload': workload, 'config': config, 'peaks': peaks,
                   'window_s': window_s, 'units': units, 'slots': slots,
                   'batch_size': batch_size, 'stages': stages, 'log': log}
            result_metrics, reduced = per_layer_metrics(cell, ctx, trace_dir)
            extra_device = {'busy_s': reduced['busy_s'],
                            'window_s': reduced['window_s']}
            breakdown = {'device_ops': reduced['device_ops'],
                         'idle_gaps': reduced['idle_gaps']}
        else:
            result_metrics = {
                m['name']: {'value': float(values[m['name']]),
                            'unit': m['unit']}
                for m in metrics_of(cell, 'end_to_end')}
        log('metrics', json.dumps({**values, **{
            k: v['value'] for k, v in result_metrics.items()}}))

        correct = all(c['ok'] for c in checks.values())
        result = {'correct': bool(correct), 'attempted': attempted,
                  'failed': failed, 'metrics': result_metrics,
                  'device': {**device, 'memory_peak_bytes': peak,
                             **extra_device}}
        if breakdown is not None:
            result['breakdown'] = breakdown
        result['checks'] = {k: {'value': c['value'], 'limit': c['limit']}
                            for k, c in checks.items()}
        for name, c in checks.items():
            log(f'check {name} = {c["value"]:.6g} (limit {c["limit"]:.6g}) '
                f'{"ok" if c["ok"] else "NOT OK"}')
        return result
    finally:
        shutil.rmtree(work, ignore_errors=True)


def per_layer_metrics(cell: Dict, ctx: Dict, trace_dir: str):
    """Read the trace, then let each of the cell's per-layer metrics' readers
    take its number; a reader with nothing to read leaves its metric out."""
    import trace_reduce
    t0 = time.perf_counter()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    reduced = trace_reduce.reduce(trace, ctx['window_s'])
    log(f'trace read in {time.perf_counter() - t0:.1f} s: '
        f'{reduced["op_events"]} op events, modules '
        f'{ {k: round(v, 3) for k, v in reduced["module_s"].items()} }')
    out = {}
    for m in metrics_of(cell, 'per_layer'):
        spec = loader.load_json('metrics', m['name'])
        reader = loader.load_module('readers', spec['reader'])
        value = reader.read(dict(ctx, metric=spec, trace=trace,
                                 reduced=reduced))
        if value is not None:
            out[m['name']] = {'value': float(value), 'unit': m['unit']}
    # the breakdown's device time by the program's scope paths where it
    # exports its map (names a recompile keeps), by HLO name where not
    by_scope = loader.load_module('readers', 'scope_time').device_ops(trace)
    if by_scope:
        reduced = dict(reduced, device_ops=by_scope)
    return out, reduced


def decode_backend(args) -> str:
    asked = args.get('decode_backend', 'auto')
    try:
        from video_features_tpu.io import native
        have = native.available()
    except Exception as e:  # informational line only
        return f'{asked} (native probe failed: {e})'
    return f'{asked} → {"native libav" if have and asked != "cv2" else "cv2"}'


def start_trace(trace_dir: str) -> None:
    import jax
    options = jax.profiler.ProfileOptions()
    # device lines only: with the host tracer on, a run that ships 150 MB
    # batches records some 40 million host events (every chunk of every
    # layout transpose), writes 1.2 GB and runs three times slower
    # (my chip run, PR 24)
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop_trace() -> None:
    import jax
    jax.profiler.stop_trace()


def main(argv=None, t_start: Optional[float] = None) -> int:
    # the result line must be the last and only line on stdout: keep the
    # real stdout aside and send everything else written to fd 1 to stderr
    sys.stdout.flush()
    real_stdout = os.dup(1)
    os.dup2(2, 1)
    try:
        result = run(argv, t_start=t_start)
    finally:
        sys.stdout.flush()
        os.dup2(real_stdout, 1)
        os.close(real_stdout)
    print(json.dumps(result), flush=True)
    return 0
