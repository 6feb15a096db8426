#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py          # no arguments, no platform switch, no CPU mode

Drives the main path once, at the full published width of i3d and at the
shipped CLI geometry, through the entry points a user calls — video file →
decode → fused two-stream step (RAFT + both I3D towers) → saved ``.npy`` —
with seeded random weights and clips generated from a seed (no network, no
reference checkout):

  * ``cli``    ``video_features_tpu.cli.main`` over three clips;
  * ``kernel`` the RAFT lookup chosen at that geometry is the compiled
    Mosaic kernel (``lanes``) and agrees with the matmul lookup at full depth;
  * ``attention`` the ``lm`` step at the benchmark cell's widths (1 dense + 4
    expert layers, 64 of 256 experts, windows of 8,192 ids, ``mixed``)
    lowers one Mosaic call named ``causal_attention`` a layer, and one
    window through ``mla_block`` agrees between that kernel and the XLA
    tiles it replaces;
  * ``serve``  the warm-pool ``ExtractionServer`` answers two i3d requests
    and one resnet50 request through ``ServeClient``, then drains;
  * ``mesh4``  (hosts with >= 4 chips) resnet50 sharded over four chips
    equals the one-chip run.

One process: a TPU belongs to one process at a time. Every phase raises on
failure (nothing is caught and carried past), so the exit code is 0 — and
anything is written to stdout — only when every phase that ran passed. Stdout
is then two JSON lines: the report (versions, per-phase result and wall
seconds, decode backend, lookup, compile-cache directory and entry count), and
LAST the verdict the driver parses, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as jax reports it. Exits 2 before doing any work when jax finds no
TPU. Wall seconds in the report are information, not metrics.
"""
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

# the body lives under the __main__ check and jax is imported inside it:
# decode-farm workers are SPAWNED and re-import this module (see
# video_features_tpu/__main__.py) — they must neither re-run the smoke nor
# touch jax, or they would wait for the chip their parent holds
ROOT = Path(__file__).resolve().parent

N_CLIPS, N_FRAMES, CLIP_W, CLIP_H = 3, 100, 340, 256
# 100 frames, (16+1)-frame windows every 16 frames → 6 windows; rgb ‖ flow
I3D_SHAPE = (6, 2048)
RESNET50_SHAPE = (N_FRAMES, 2048)
# short side 256 → 256×340, RAFT pads to 256×344 → 1/8-resolution 32×43
RAFT_H, RAFT_W = 256, 344


def check_features(path, shape):
    """The repo's own meaning of a right output file: float32, the expected
    shape, finite, and not constant. Returns the array."""
    import numpy as np
    feats = np.load(path)
    if feats.dtype != np.float32 or feats.shape != shape:
        raise AssertionError(f'{path}: {feats.dtype}{feats.shape}, '
                             f'expected float32{shape}')
    if not np.isfinite(feats).all():
        raise AssertionError(f'{path}: non-finite values')
    if not float(feats.std()) > 0:
        raise AssertionError(f'{path}: constant output')
    return feats


def base_config(work):
    """What every phase asks of the program. The i3d geometry (stack 16,
    step 16, batch 8, both streams, 20 RAFT iterations, concat_rgb_flow) is
    the shipped yml default and deliberately not spelled anywhere here."""
    return {'device': 'tpu', 'precision': 'mixed',
            'allow_random_weights': True, 'on_extraction': 'save_numpy',
            'tmp_path': str(work / 'tmp')}


def dotlist(clips, **config):
    """``key=value`` CLI arguments, as a user would type them."""
    config['video_paths'] = f'[{",".join(clips)}]'
    return [f'{k}={v}' for k, v in config.items()]


def compile_summary(manifest):
    """Time spent in the backend compile step (which includes reading a
    persistent-cache hit) and the number of such hits during a run, from
    the run manifest's jax.monitoring section (obs/manifest.py). A warm
    cache shows as hits > 0 and a compile time of seconds, not a minute."""
    out = {'backend_compile_s': 0.0, 'cache_hits': 0}
    for name, rec in manifest['compile'].items():
        if name.endswith('/backend_compile_duration'):
            out['backend_compile_s'] = round(rec['total_s'], 1)
        elif name.endswith('/compile_time_saved_sec'):
            out['cache_hits'] = rec['count']
    return out


def phase_cli(clips, work, cache_dir):
    from video_features_tpu.cli import main as cli_main
    out, manifest_path = work / 'cli_out', work / 'cli_manifest.json'
    entries_before = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
        else 0
    rc = cli_main(dotlist(clips, feature_type='i3d', output_path=out,
                          manifest_out=manifest_path, **base_config(work)))
    if rc != 0:
        raise AssertionError(f'cli.main returned {rc}')
    manifest = json.loads(manifest_path.read_text())
    if manifest['outcomes'] != {'saved': N_CLIPS}:
        raise AssertionError(f'outcomes {manifest["outcomes"]}')
    shipped = {'stack_size': 16, 'step_size': 16, 'batch_size': 8,
               'streams': None, 'raft_iters': None, 'concat_rgb_flow': True}
    geometry = {k: manifest['config'][k] for k in shipped}
    if geometry != shipped:
        raise AssertionError(f'not the shipped i3d geometry: {geometry}')
    for clip in clips:
        check_features(out / 'i3d' / f'{Path(clip).stem}.npy', I3D_SHAPE)
    entries = len(os.listdir(cache_dir))
    if entries == 0:
        raise AssertionError(f'compile cache {cache_dir} is empty after '
                             'the cli phase')
    return {'videos': N_CLIPS, 'shape': list(I3D_SHAPE),
            'cache_entries_before': entries_before,
            'cache_entries': entries, **compile_summary(manifest)}


def phase_kernel(clips, work, platform):
    import jax
    import numpy as np

    from tools.validate_lanes import measure_drift
    from video_features_tpu.config import load_config
    from video_features_tpu.models import raft
    from video_features_tpu.registry import create_extractor

    impl = raft.resolve_lookup(RAFT_H // 8, RAFT_W // 8, platform)
    if impl != 'lanes':
        raise AssertionError(f'CLI geometry resolves to lookup {impl!r}, '
                             "not the compiled 'lanes' kernel")
    # the i3d step exactly as cli.main built it, lowered at the batch the
    # cli phase ran: the Mosaic kernel must be IN it, not merely available
    ex = create_extractor(load_config('i3d', overrides=dict(
        base_config(work), video_paths=clips,
        output_path=str(work / 'kernel_out'))))
    pads, resize_to = ex._geometry(CLIP_H, CLIP_W)
    batch = jax.ShapeDtypeStruct(
        (ex.batch_size, ex.stack_size + 1, CLIP_H, CLIP_W, 3), np.uint8)
    with ex.precision_scope():
        text = ex._step.lower(ex.params, batch, pads=pads,
                              streams=tuple(ex.streams),
                              resize_to=resize_to).as_text()
    mosaic_calls = text.count('tpu_custom_call')
    if mosaic_calls == 0:
        raise AssertionError('no Mosaic custom call (tpu_custom_call) in '
                             'the lowered i3d step')
    drift = measure_drift(h=RAFT_H, w=RAFT_W, impls=('dense', 'lanes'),
                          iters=20, platform=platform)['lanes']
    if not drift < 1e-3:
        raise AssertionError(f'lanes vs dense rel L2 {drift:.3e} >= 1e-3')
    return {'lookup': impl, 'mosaic_custom_calls': mosaic_calls,
            'lanes_vs_dense_rel_l2': drift}


def phase_attention(clips, work, platform):
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from video_features_tpu.config import load_config
    from video_features_tpu.extract.lm import ExtractLM
    from video_features_tpu.models import latent_moe
    from video_features_tpu.ops.attention import resolve_causal
    from video_features_tpu.ops.precision import MIXED_AMBIENT, rel_l2

    # the cell's trunk (benchmark/configs/joyai-llm-flash-ep4.json
    # `overrides`) over the shipped yml's widths and window, as shapes: the
    # 6.7 GB of parameters are not drawn for a lowering
    args = load_config('lm', overrides=dict(
        base_config(work), video_paths=clips, num_hidden_layers=5,
        n_experts_held=64, output_path=str(work / 'lm_out')))
    cfg = latent_moe.TrunkConfig.from_args(args)
    window = int(args.stack_size) * int(args.patch_grid) ** 2
    impl = resolve_causal(platform, window, cfg.qk_head_dim, cfg.v_head_dim,
                          MIXED_AMBIENT)
    if impl != 'kernel':
        raise AssertionError(f'the cell\'s window resolves to causal path '
                             f'{impl!r}, not the compiled kernel')
    params = {n: jax.ShapeDtypeStruct(shape, jnp.float32)
              for n, shape in latent_moe.param_shapes(cfg).items()}
    ids = jax.ShapeDtypeStruct((int(args.batch_size), window), jnp.int32)
    with jax.default_matmul_precision(MIXED_AMBIENT):
        text = jax.jit(partial(ExtractLM._forward, cfg=cfg,
                               platform=platform)).lower(params,
                                                         ids).as_text()
    named = text.count('kernel_name = "causal_attention"')
    if named != cfg.num_hidden_layers or \
            text.count('tpu_custom_call') != named:
        raise AssertionError(
            f'{named} Mosaic calls named causal_attention among '
            f'{text.count("tpu_custom_call")} in the lowered lm step, '
            f'expected {cfg.num_hidden_layers}')
    # one window through one layer's attention, kernel against XLA tiles:
    # the same three passes in another order
    prefix = 'model.layers.1.self_attn'
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    layer = {}
    for name, shape in latent_moe.param_shapes(cfg).items():
        if name.startswith(prefix):
            w = jax.random.normal(next(keys), shape, jnp.float32)
            layer[name] = (1.0 + 0.1 * w if len(shape) == 1
                           else w * shape[0] ** -0.5)
    x = jax.random.normal(next(keys), (window, cfg.hidden_size), jnp.float32)
    out = {}
    with jax.default_matmul_precision(MIXED_AMBIENT):
        for path, where in (('kernel', platform), ('xla', 'cpu')):
            out[path] = np.asarray(jax.jit(partial(
                latent_moe.mla_block, prefix=prefix, cfg=cfg,
                platform=where))(layer, x=x))
    drift = rel_l2(out['xla'], out['kernel'])
    if not drift < 1e-4 or not np.isfinite(out['kernel']).all():
        raise AssertionError(f'kernel vs XLA tiles rel L2 {drift:.3e} '
                             f'>= 1e-4 on one window')
    return {'causal_attention': impl, 'mosaic_custom_calls': named,
            'kernel_vs_xla_rel_l2': drift}


def phase_serve(clips, work):
    import numpy as np

    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer
    from video_features_tpu.utils.output import make_path

    server = ExtractionServer(base_overrides=base_config(work)).start()
    try:
        client = ServeClient(port=server.port)
        if not client.ping():
            raise AssertionError('serve: no ping')

        def request(feature_type, tag, **overrides):
            out = work / f'serve_{tag}'
            rid = client.submit(feature_type, clips,
                                overrides={'output_path': str(out),
                                           **overrides})
            status = client.wait(rid, timeout_s=900)
            if status['state'] != 'done':
                raise AssertionError(f'serve {tag}: {status}')
            return out

        drift = 0.0
        for tag in ('i3d_1', 'i3d_2'):
            out = request('i3d', tag)
            for clip in clips:
                got = check_features(make_path(out / 'i3d', clip, 'rgb',
                                               '.npy'), I3D_SHAPE)
                # the daemon's packed path and the CLI's per-video loop
                # are two engines over one model: same clip, same features
                ref = np.load(make_path(work / 'cli_out' / 'i3d', clip,
                                        'rgb', '.npy'))
                drift = max(drift, float(np.linalg.norm(got - ref)
                                         / np.linalg.norm(ref)))
            pool = client.metrics()['warm_pool']
            if tag == 'i3d_2' and pool['hits'] < 1:
                raise AssertionError(f'second i3d request missed the warm '
                                     f'pool: {pool}')
        if not drift < 1e-3:
            raise AssertionError(f'serve vs cli i3d rel L2 {drift:.3e}')
        out = request('resnet', 'resnet50', model_name='resnet50',
                      batch_size=32)
        for clip in clips:
            check_features(make_path(out / 'resnet' / 'resnet50', clip,
                                     'resnet', '.npy'), RESNET50_SHAPE)
        metrics = client.metrics()
        client.drain()
    finally:
        server.drain(wait=True, grace_s=120)
    if not server.drained:
        raise AssertionError('serve drain did not complete')
    return {'requests': metrics['requests']['completed'],
            'warm_pool': {k: metrics['warm_pool'][k]
                          for k in ('hits', 'misses')},
            'serve_vs_cli_rel_l2': drift}


def phase_mesh4(clips, work):
    import jax
    import numpy as np

    from video_features_tpu.cli import main as cli_main
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor
    from video_features_tpu.utils.output import make_path

    config = dict(base_config(work), model_name='resnet50', batch_size=8,
                  pack_across_videos=True)

    def run(tag, mesh_devices):
        out, manifest_path = work / f'mesh_{tag}', work / f'mesh_{tag}.json'
        rc = cli_main(dotlist(clips, feature_type='resnet',
                              mesh_devices=mesh_devices, output_path=out,
                              manifest_out=manifest_path, **config))
        if rc != 0:
            raise AssertionError(f'mesh {tag}: cli.main returned {rc}')
        feats = [check_features(make_path(out / 'resnet' / 'resnet50', c,
                                          'resnet', '.npy'), RESNET50_SHAPE)
                 for c in clips]
        return feats, json.loads(manifest_path.read_text())

    one, _ = run('one', 1)
    four, manifest = run('four', 4)
    mesh = manifest['mesh']
    if mesh.get('mesh_devices') != 4 or len(mesh['devices']) != 4:
        raise AssertionError(f'the 4-chip run recorded mesh {mesh}')
    diff = max(float(np.abs(a - b).max()) for a, b in zip(one, four))
    if not diff <= 1e-4:
        raise AssertionError(f'4-chip vs 1-chip resnet50 differ by {diff}')
    # that four chips WORKED: the same configuration's packed step, fed the
    # way the scheduler feeds it, returns an output that lives on four
    # devices (peak-memory deltas cannot say it — on a four-chip host the
    # serve phase already placed its entries on different chips)
    ex = create_extractor(load_config('resnet', overrides=dict(
        config, video_paths=clips, mesh_devices=4,
        output_path=str(work / 'mesh_probe'))))
    ex._packed_setup()
    ndev = ex._ensure_packed_mesh()
    frame = ex.host_transform(np.zeros((CLIP_H, CLIP_W, 3), np.uint8))
    batch = np.zeros((ex.packed_batch_size() * ndev, *frame.shape), np.uint8)
    with ex.precision_scope():
        out = ex.packed_step(ex.put_input(batch))['resnet']
    out.block_until_ready()
    on = sorted(d.id for d in out.sharding.device_set)
    if len(on) != 4 or not np.isfinite(np.asarray(out)).all():
        raise AssertionError(f'mesh_devices=4 step output lives on {on}')
    return {'max_abs_diff_vs_one_chip': diff, 'mesh': mesh['shape'],
            'step_output_devices': on}


def timed(phases, name, fn, *args):
    t0 = time.time()
    detail = fn(*args)
    phases[name] = {'ok': True, 's': round(time.time() - t0, 1), **detail}
    print(f'chip_smoke: {name} ok {phases[name]}', file=sys.stderr)


def main() -> int:
    import jax
    import jaxlib
    devices = jax.devices()
    device = {'platform': devices[0].platform, 'kind': devices[0].device_kind,
              'count': len(devices)}
    if device['platform'] != 'tpu':
        print(f'chip_smoke: jax found no TPU ({device}); this script has '
              'no CPU mode', file=sys.stderr)
        return 2
    import libtpu
    versions = {'jax': jax.__version__, 'jaxlib': jaxlib.__version__,
                'libtpu': libtpu.__version__}
    print(f'chip_smoke: {device} {versions}', file=sys.stderr)

    sys.path.insert(0, str(ROOT))
    from tools.make_sample_video import write_noise_clip
    from video_features_tpu.io.video import VideoLoader
    from video_features_tpu.utils.device import resolve_compilation_cache_dir

    cache_dir = resolve_compilation_cache_dir('auto', 'tpu')
    work = Path(tempfile.mkdtemp(prefix='vft_chip_smoke_'))
    phases = {}
    try:
        clips = [write_noise_clip(work / f'smoke{i}.mp4', N_FRAMES,
                                  w=CLIP_W, h=CLIP_H, seed=i)
                 for i in range(N_CLIPS)]
        decoder = VideoLoader(clips[0])._make_decoder()
        decode_backend = {'NativeFrameDecoder': 'native',
                          'Cv2FrameDecoder': 'cv2'}[type(decoder).__name__]
        decoder.release()

        timed(phases, 'cli', phase_cli, clips, work, cache_dir)
        timed(phases, 'kernel', phase_kernel, clips, work,
              device['platform'])
        timed(phases, 'attention', phase_attention, clips, work,
              device['platform'])
        timed(phases, 'serve', phase_serve, clips, work)
        if jax.local_device_count() >= 4:
            timed(phases, 'mesh4', phase_mesh4, clips, work)
        else:
            phases['mesh4'] = f'not run: {jax.local_device_count()} device(s)'
    finally:
        shutil.rmtree(work, ignore_errors=True)

    import multiprocessing
    children = multiprocessing.active_children()
    if children:
        raise AssertionError(f'child processes outlived the smoke: {children}')
    print(json.dumps({'report': {
        'versions': versions, 'phases': phases,
        'decode_backend': decode_backend,
        'lookup': phases['kernel']['lookup'],
        'causal_attention': phases['attention']['causal_attention'],
        'compile_cache_dir': cache_dir,
        'compile_cache_entries': phases['cli']['cache_entries']}}))
    # the verdict: these keys and no others, and nothing after it
    print(json.dumps({'ok': True, 'device': device}), flush=True)
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
