"""CLI entry point: ``python -m video_features_tpu feature_type=X key=val ...``

Reference main.py:7-55 behavior: load per-feature YAML, merge dotlist CLI
(CLI wins), sanity-check, build the one extractor, shuffle the video list,
loop ``_extract`` per video with fault isolation. Unlike the reference the
exit code is 1 when any video failed (:func:`_exit_code`).
"""
from __future__ import annotations

import sys
from typing import List, Optional

import yaml

from video_features_tpu.config import (
    form_list_from_user_input, load_config, parse_dotlist,
)
from video_features_tpu.registry import create_extractor


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == 'serve':
        # long-running warm-pool service (serve/): models stay resident,
        # requests arrive over a local socket and pack into shared batches
        from video_features_tpu.serve.server import serve_main
        return serve_main(argv[1:])
    if argv and argv[0] == 'index':
        # offline feature-index surface (index/): fold the cache
        # manifest and run exact top-k queries without a resident server
        from video_features_tpu.index.cli import index_main
        return index_main(argv[1:])
    if argv and argv[0] == 'fleet':
        # multi-host front door (fleet/): consistent-hash routing over
        # N serve daemons — jax-free, so importing it never probes
        # devices in the router process
        from video_features_tpu.fleet.router import fleet_main
        return fleet_main(argv[1:])
    cli_args = parse_dotlist(argv)
    if 'feature_type' not in cli_args and 'features' not in cli_args:
        print('Usage: python -m video_features_tpu feature_type=<name> [key=value ...]\n'
              '       python -m video_features_tpu features=[f1,f2,...] [key=value ...]\n'
              '       python -m video_features_tpu serve [serve_port=N ...]\n'
              '       python -m video_features_tpu index --cache-dir DIR '
              '[--ingest] [--query vec.npy --family f]\n'
              '       python -m video_features_tpu fleet '
              'fleet_hosts=[h1:p1,h2:p2] [fleet_port=N ...]')
        return 2
    # single source of truth: multihost must come from the CLI because the
    # runtime must initialize before anything probes jax devices
    # (sanity_check inside load_config does) — a config-file value would be
    # seen too late and silently skip initialization
    multihost = bool(cli_args.get('multihost'))
    if multihost:
        from video_features_tpu.parallel.distributed import initialize
        # Pod environments autodetect everything (no extra keys needed);
        # manual clusters pass the coordinator triple per host:
        #   multihost=true coordinator_address=host0:1234 \
        #   num_processes=N process_id=<rank>
        initialize(cli_args.get('coordinator_address'),
                   cli_args.get('num_processes'),
                   cli_args.get('process_id'))
    if 'features' in cli_args:
        # fused multi-family worklist: decode each video once, branch the
        # shared frames into every family's transform + model
        return _fused_main(cli_args, multihost)
    args = load_config(cli_args['feature_type'], overrides=cli_args)
    if args.get('multihost') and not multihost:
        raise ValueError(
            'multihost must be passed on the command line (multihost=true), '
            'not via a config file: the distributed runtime must initialize '
            'before device probing')

    print(yaml.safe_dump(dict(args), sort_keys=False, default_flow_style=False))
    if args['on_extraction'] in ('save_numpy', 'save_pickle'):
        print(f'Saving features to {args["output_path"]}')
    print('Device:', args['device'])

    extractor = create_extractor(args)
    if extractor.blackbox is not None:
        # crash-dump black box (obs/blackbox.py): a fatal signal on a
        # CLI run dumps the recent spans/events/manifest before dying;
        # farm-worker deaths dump from the supervisor independently
        from video_features_tpu.obs.blackbox import install_signal_dump
        install_signal_dump(extractor.blackbox)

    # multihost: every host runs this same command; each takes a
    # deterministic interleaved shard of the list (no duplicate work across
    # healthy hosts) instead of the single-host collision-avoidance shuffle.
    video_paths = form_list_from_user_input(
        args.get('video_paths'), args.get('file_with_video_paths'),
        to_shuffle=not multihost)
    if multihost:
        from video_features_tpu.parallel import shard_worklist
        video_paths = shard_worklist(video_paths)
    print(f'The number of specified videos: {len(video_paths)}')

    # profile=true prints per-stage timing tables after each video;
    # profile_dir=<path> additionally captures a jax/XLA device trace;
    # trace_out=<path> records the host-side span timeline (Perfetto) and
    # manifest_out=<path> the per-run JSON manifest — both published by
    # finish_obs below even when a video failed (docs/observability.md).
    from video_features_tpu.utils.tracing import jax_profiler_trace
    try:
        with jax_profiler_trace(args.get('profile_dir')):
            if args.get('pack_across_videos'):
                # corpus mode: batch-major over the whole (per-host)
                # worklist — every device batch fills across video
                # boundaries, outputs and resume behavior are identical to
                # the per-video loop (parallel/packing.py)
                print(f'Packing device batches across {len(video_paths)} '
                      'videos')
                ahead = args.get('pack_decode_ahead')
                extractor.extract_packed(
                    video_paths,
                    decode_ahead=2 if ahead is None else int(ahead))
            else:
                for i, video_path in enumerate(video_paths):
                    print(f'[{i + 1}/{len(video_paths)}] {video_path}')
                    extractor._extract(video_path)
    finally:
        extractor.finish_obs()

    if multihost:
        # process 0 hosts the coordinator service: hold every process at a
        # final barrier so a host that drew short videos can't exit and tear
        # the coordinator down under hosts still extracting
        import jax
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices('extraction_done')
    return _exit_code([extractor])


def _exit_code(extractors) -> int:
    """0 when every video was saved, skipped or served from cache; 1 when
    any video's outcome was ``failed``. The loops keep going past a bad
    video (the reference's fault isolation), but a fault that fails EVERY
    video — a kernel the compiler refuses, a missing accelerator runtime —
    must not report success."""
    failed = sum(ex.failed_videos for ex in extractors)
    if failed:
        print(f'{failed} video(s) failed — see the tracebacks above',
              file=sys.stderr)
    return 1 if failed else 0


def _fused_main(cli_args: dict, multihost: bool) -> int:
    """``features=[i3d,clip,...]`` worklists: one merged per-family config
    set (``config.load_fused_configs``), then families whose
    ``fused_decode_signature()`` values match share ONE decode pass per
    video (``parallel.packing.run_packed_fused``) while unfusable
    families run their own unchanged pass over the same worklist.
    Per-family outputs, cache keys, resume behavior, and fault isolation
    are identical to running each family sequentially — fusion only
    removes the repeated decode + content-hash work."""
    from video_features_tpu.config import load_fused_configs
    configs = load_fused_configs(cli_args['features'], overrides=cli_args)
    for fam_args in configs.values():
        if fam_args.get('multihost') and not multihost:
            raise ValueError(
                'multihost must be passed on the command line '
                '(multihost=true), not via a config file: the distributed '
                'runtime must initialize before device probing')

    print(f'Fused worklist ({len(configs)} families): '
          + ', '.join(configs))
    for fam, fam_args in configs.items():
        line = (f'  {fam}: device={fam_args["device"]} '
                f'on_extraction={fam_args["on_extraction"]}')
        if fam_args['on_extraction'] in ('save_numpy', 'save_pickle'):
            line += f' -> {fam_args["output_path"]}'
        print(line)

    exs = {fam: create_extractor(fam_args)
           for fam, fam_args in configs.items()}
    first = next(iter(exs.values()))
    if first.blackbox is not None:
        from video_features_tpu.obs.blackbox import install_signal_dump
        install_signal_dump(first.blackbox)

    # the worklist knobs are SHARED overrides (split_fused_overrides):
    # every family's config carries the same values, so read the first
    shared = next(iter(configs.values()))
    video_paths = form_list_from_user_input(
        shared.get('video_paths'), shared.get('file_with_video_paths'),
        to_shuffle=not multihost)
    if multihost:
        from video_features_tpu.parallel import shard_worklist
        video_paths = shard_worklist(video_paths)
    print(f'The number of specified videos: {len(video_paths)}')

    # group by decode signature: equal signatures branch off ONE shared
    # raw frame stream; a family with no signature (stack/audio families,
    # or an unspecced transform) can't, and keeps its own decode pass
    groups: dict = {}
    singles: List[str] = []
    for fam, ex in exs.items():
        sig = ex.fused_decode_signature()
        if sig is None:
            singles.append(fam)
        else:
            groups.setdefault(sig, {})[fam] = ex
    fused_groups = [g for g in groups.values() if len(g) > 1]
    singles.extend(fam for g in groups.values() if len(g) == 1
                   for fam in g)

    ahead = shared.get('pack_decode_ahead')
    decode_ahead = 2 if ahead is None else int(ahead)
    from video_features_tpu.utils.tracing import jax_profiler_trace
    try:
        with jax_profiler_trace(shared.get('profile_dir')):
            if fused_groups:
                from video_features_tpu.parallel.packing import (
                    run_packed_fused,
                )
            for group in fused_groups:
                print(f'Fusing decode for [{", ".join(group)}]: one '
                      f'pass over {len(video_paths)} videos')
                run_packed_fused(group, list(video_paths),
                                 decode_ahead=decode_ahead)
            for fam in singles:
                ex = exs[fam]
                print(f'[{fam}] cannot share a decode pass — running '
                      'its own')
                if getattr(ex, 'supports_packing', False):
                    ex.extract_packed(list(video_paths),
                                      decode_ahead=decode_ahead)
                else:
                    for i, video_path in enumerate(video_paths):
                        print(f'[{fam}] [{i + 1}/{len(video_paths)}] '
                              f'{video_path}')
                        ex._extract(video_path)
    finally:
        for ex in exs.values():
            ex.finish_obs()

    if multihost:
        import jax
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices('extraction_done')
    return _exit_code(exs.values())


if __name__ == '__main__':
    raise SystemExit(main())
