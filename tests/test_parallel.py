"""Parallel layer: mesh factoring, worklist sharding, sharded-step parity.

The conftest forces 8 virtual CPU devices, so these tests exercise real
(data, time) meshes and XLA's sharding propagation without TPU hardware —
the same path the driver's dryrun_multichip validates.
"""
import numpy as np
import pytest

import jax

from video_features_tpu.parallel import (
    factor_mesh_shape, make_mesh, shard_worklist, shuffled,
)

pytestmark = pytest.mark.slow  # parity/e2e/sharding: full lane only


def test_factor_mesh_shape():
    assert factor_mesh_shape(8) == (4, 2)
    assert factor_mesh_shape(1) == (1, 1)
    assert factor_mesh_shape(8, time_parallel=4) == (2, 4)
    with pytest.raises(ValueError):
        factor_mesh_shape(6, time_parallel=4)


def test_make_mesh_axes():
    mesh = make_mesh(n_devices=8)
    assert mesh.shape == {'data': 4, 'time': 2}
    mesh = make_mesh(n_devices=4, time_parallel=1)
    assert mesh.shape == {'data': 4, 'time': 1}


def test_shard_worklist_partitions_exactly():
    paths = [f'v{i}.mp4' for i in range(11)]
    shards = [shard_worklist(paths, shard_id=i, num_shards=3) for i in range(3)]
    # disjoint and complete
    merged = sorted(p for s in shards for p in s)
    assert merged == sorted(paths)
    assert all(len(s) in (3, 4) for s in shards)
    # deterministic
    assert shards[1] == shard_worklist(paths, shard_id=1, num_shards=3)


def test_shuffled_is_seeded_permutation():
    paths = [f'v{i}.mp4' for i in range(20)]
    a = shuffled(paths, seed=7)
    b = shuffled(paths, seed=7)
    assert a == b and sorted(a) == sorted(paths) and a != paths


def test_sharded_two_stream_step_matches_single_device():
    """The mesh-sharded fused step must be numerically identical to the
    unsharded one — sharding is a layout choice, not a numerics choice."""
    from functools import partial

    from video_features_tpu.extract.i3d import fused_two_stream_step
    from video_features_tpu.models import i3d as i3d_model
    from video_features_tpu.models import raft as raft_model
    from video_features_tpu.parallel import (
        build_sharded_two_stream_step, put_batch, put_replicated,
    )
    from video_features_tpu.transplant.torch2jax import transplant

    params = {
        'rgb': transplant(i3d_model.init_state_dict(modality='rgb')),
        'flow': transplant(i3d_model.init_state_dict(modality='flow')),
        'raft': transplant(raft_model.init_state_dict()),
    }
    rng = np.random.RandomState(0)
    # B=4 over data=4; stack=16 pairs over time=2. 64px is the smallest
    # frame whose /8 feature grid survives RAFT's 4-level corr pyramid.
    stacks = rng.randint(0, 255, size=(4, 17, 64, 64, 3)).astype(np.float32)
    kwargs = dict(pads=(0, 0, 0, 0), streams=('rgb', 'flow'), crop_size=64)

    with jax.default_matmul_precision('highest'):
        ref = jax.jit(partial(fused_two_stream_step, **kwargs))(params, stacks)

        mesh = make_mesh(n_devices=8)
        step = build_sharded_two_stream_step(mesh)
        out = step(put_replicated(mesh, params), put_batch(mesh, stacks),
                   pads=(0, 0, 0, 0), crop_size=64)

    for key in ('rgb', 'flow'):
        np.testing.assert_allclose(np.asarray(out[key]), np.asarray(ref[key]),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('device_resize', [False, True],
                         ids=['host-resize', 'device-resize'])
def test_extractor_data_parallel_e2e(short_video, tmp_path, device_resize):
    """ExtractI3D(data_parallel=true) runs the mesh-sharded step from the
    normal extract() path and matches the single-device extractor — with
    the host PIL resize and (round 5) with the bit-exact in-graph resize,
    which is per-sample work that composes with the data sharding."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    common = {
        'video_paths': short_video, 'device': 'cpu',
        'streams': 'rgb',                       # rgb-only keeps CPU cost low
        'stack_size': 16, 'step_size': 16,
        'concat_rgb_flow': False, 'device_resize': device_resize,
        'output_path': str(tmp_path / 'out'), 'tmp_path': str(tmp_path / 'tmp'),
    }
    dp = create_extractor(load_config('i3d', overrides={
        **common, 'data_parallel': True, 'batch_size': 1}))
    assert dp.mesh.shape['data'] == 4
    assert dp.batch_size == 4        # global batch rounded up to the data axis

    single = create_extractor(load_config('i3d', overrides=common))

    feats_dp = dp.extract(short_video)
    feats_single = single.extract(short_video)
    assert feats_dp['rgb'].shape == feats_single['rgb'].shape
    np.testing.assert_allclose(feats_dp['rgb'], feats_single['rgb'],
                               atol=2e-5, rtol=1e-5)


def test_initialize_passthrough_and_already_init(monkeypatch):
    from video_features_tpu.parallel import distributed

    calls = []
    monkeypatch.setattr(jax.distributed, 'initialize',
                        lambda **kw: calls.append(kw))
    distributed.initialize('host:1234', 4, 2)
    assert calls == [{'coordinator_address': 'host:1234',
                      'num_processes': 4, 'process_id': 2}]

    def boom(**kw):
        raise RuntimeError('backend already initialized')
    monkeypatch.setattr(jax.distributed, 'initialize', boom)
    distributed.initialize()  # swallowed

    def other(**kw):
        raise RuntimeError('connection refused')
    monkeypatch.setattr(jax.distributed, 'initialize', other)
    with pytest.raises(RuntimeError, match='connection refused'):
        distributed.initialize()


def test_cli_multihost_shards_worklist(short_video, tmp_path, monkeypatch, capsys):
    """multihost=true initializes the runtime and takes this host's shard
    (process 0 of 1 == the full list) without shuffling."""
    from video_features_tpu import cli
    from video_features_tpu.parallel import distributed

    inited = []
    monkeypatch.setattr(distributed, 'initialize',
                        lambda *a, **k: inited.append(1))
    rc = cli.main([
        'feature_type=resnet', 'model_name=resnet18', 'device=cpu',
        'batch_size=16', f'video_paths={short_video}', 'multihost=true',
        'on_extraction=save_numpy',
        f'output_path={tmp_path / "out"}', f'tmp_path={tmp_path / "tmp"}',
    ])
    assert rc == 0
    assert inited == [1]
    stem = short_video.rsplit('/', 1)[-1].rsplit('.', 1)[0]
    assert (tmp_path / 'out' / 'resnet' / 'resnet18' / f'{stem}_resnet.npy').exists()


def test_framewise_data_parallel_matches_single_device(short_video, tmp_path):
    """ResNet with data_parallel=true: mesh-sharded batches == single-device."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    common = {
        'model_name': 'resnet18', 'device': 'cpu', 'batch_size': 16,
        'video_paths': short_video,
        'output_path': str(tmp_path / 'out'), 'tmp_path': str(tmp_path / 'tmp'),
    }
    dp = create_extractor(load_config('resnet', overrides={
        **common, 'data_parallel': True}))
    single = create_extractor(load_config('resnet', overrides=common))

    feats_dp = dp.extract(short_video)
    assert dp._mesh is not None and dp.batch_size % dp._mesh.shape['data'] == 0
    feats_single = single.extract(short_video)
    np.testing.assert_allclose(feats_dp['resnet'], feats_single['resnet'],
                               atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(feats_dp['timestamps_ms'],
                                  feats_single['timestamps_ms'])


def test_r21d_data_parallel_matches_single_device(short_video, tmp_path):
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    common = {
        'video_paths': short_video, 'device': 'cpu',
        'output_path': str(tmp_path / 'out'), 'tmp_path': str(tmp_path / 'tmp'),
    }
    dp = create_extractor(load_config('r21d', overrides={
        **common, 'data_parallel': True}))
    single = create_extractor(load_config('r21d', overrides=common))

    feats_dp = dp.extract(short_video)
    assert dp._mesh is not None
    assert dp.stack_batch % dp._mesh.shape['data'] == 0
    feats_single = single.extract(short_video)
    np.testing.assert_allclose(feats_dp['r21d'], feats_single['r21d'],
                               atol=2e-5, rtol=1e-5)


def test_data_parallel_capability_set_is_valid():
    from video_features_tpu.registry import DATA_PARALLEL_FEATURES, EXTRACTORS
    # every claimed-capable type must exist; the set is intentionally a
    # literal so new extractors default to NOT claiming DP support
    assert DATA_PARALLEL_FEATURES <= frozenset(EXTRACTORS)


def test_raft_pair_sharding_matches_single_device():
    """RAFT pairs data-sharded over the mesh (halo paid host-side) at few
    iterations: over the full 20, random (non-contracting) weights amplify
    fp-reorder noise between shardings — same caveat as the pallas
    cross-path tests — so parity is checked where it is meaningful."""
    from video_features_tpu.models import raft as raft_model
    from video_features_tpu.parallel import put_batch, put_replicated
    from video_features_tpu.transplant.torch2jax import transplant

    params = transplant(raft_model.init_state_dict())
    rng = np.random.RandomState(3)
    frames = rng.randint(0, 255, (9, 64, 64, 3)).astype(np.float32)

    with jax.default_matmul_precision('highest'):
        ref = np.asarray(raft_model.forward(
            params, frames[:-1], frames[1:], iters=3))

        mesh = make_mesh(n_devices=8, time_parallel=1)
        sharded = jax.jit(
            lambda p, f1, f2: raft_model.forward(p, f1, f2, iters=3))
        out = np.asarray(sharded(put_replicated(mesh, params),
                                 put_batch(mesh, frames[:-1]),
                                 put_batch(mesh, frames[1:])))
    np.testing.assert_allclose(out, ref, atol=5e-4, rtol=1e-4)


def test_raft_data_parallel_e2e_smoke(short_video, tmp_path):
    """data_parallel=true through the full extractor path: mesh built,
    batch rounded, outputs finite and correctly shaped."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    dp = create_extractor(load_config('raft', overrides={
        'video_paths': short_video, 'device': 'cpu',
        'side_size': 64, 'extraction_total': 9, 'batch_size': 8,
        'data_parallel': True,
        'output_path': str(tmp_path / 'out'), 'tmp_path': str(tmp_path / 'tmp'),
    }))
    feats = dp.extract(short_video)
    assert dp._mesh is not None and dp.batch_size % dp._mesh.shape['data'] == 0
    assert feats['raft'].shape[1] == 2 and feats['raft'].shape[0] >= 8
    assert np.isfinite(feats['raft']).all()


def test_s3d_data_parallel_matches_single_device(short_video, tmp_path):
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    common = {
        'video_paths': short_video, 'device': 'cpu',
        'stack_size': 16, 'step_size': 16, 'extraction_fps': None,
        'output_path': str(tmp_path / 'out'), 'tmp_path': str(tmp_path / 'tmp'),
    }
    dp = create_extractor(load_config('s3d', overrides={
        **common, 'data_parallel': True}))
    single = create_extractor(load_config('s3d', overrides=common))

    feats_dp = dp.extract(short_video)
    assert dp._mesh is not None
    feats_single = single.extract(short_video)
    np.testing.assert_allclose(feats_dp['s3d'], feats_single['s3d'],
                               atol=2e-5, rtol=1e-5)


def test_vggish_data_parallel_matches_single_device(tmp_path):
    import wave

    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    sr = 16000
    t = np.arange(int(sr * 3.5)) / sr
    samples = (np.sin(2 * np.pi * 330 * t) * 0.4 * 32767).astype('<i2')
    wav = str(tmp_path / 'tone.wav')
    with wave.open(wav, 'wb') as f:
        f.setnchannels(1)
        f.setsampwidth(2)
        f.setframerate(sr)
        f.writeframes(samples.tobytes())

    common = {
        'video_paths': wav, 'device': 'cpu',
        'output_path': str(tmp_path / 'out'), 'tmp_path': str(tmp_path / 'tmp'),
    }
    dp = create_extractor(load_config('vggish', overrides={
        **common, 'data_parallel': True, 'batch_size': 8}))
    single = create_extractor(load_config('vggish', overrides=common))

    feats_dp = dp.extract(wav)
    assert dp._mesh is not None and dp.example_batch % dp._mesh.shape['data'] == 0
    feats_single = single.extract(wav)
    np.testing.assert_allclose(feats_dp['vggish'], feats_single['vggish'],
                               atol=2e-5, rtol=1e-5)


def test_data_parallel_warn_path_for_future_unsupported(
        tmp_path, capsys, short_video, monkeypatch):
    """The warn-and-disable gate must keep working when an extractor
    without DP support is added (simulated by shrinking the registry set)."""
    from video_features_tpu import registry
    from video_features_tpu.config import load_config

    monkeypatch.setattr(registry, 'DATA_PARALLEL_FEATURES',
                        frozenset({'i3d'}))
    args = load_config('resnet', overrides={
        'model_name': 'resnet18', 'video_paths': short_video, 'device': 'cpu',
        'data_parallel': True,
        'output_path': str(tmp_path / 'out'), 'tmp_path': str(tmp_path / 'tmp'),
    })
    assert args['data_parallel'] is False
    assert 'not implemented for resnet' in capsys.readouterr().out


def test_raft_halo_shard_dp_matches_single_device():
    """The data-parallel halo layout (each device gets its k+1-frame run,
    boundary frames duplicated host-side) must reproduce the single-device
    forward_consecutive at few iterations (same fp-noise caveat as the
    pair-sharding test)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from video_features_tpu.models import raft as raft_model
    from video_features_tpu.parallel import put_batch, put_replicated
    from video_features_tpu.transplant.torch2jax import transplant

    params = transplant(raft_model.init_state_dict())
    rng = np.random.RandomState(4)
    n, k = 8, 2
    frames = rng.randint(0, 255, (n * k + 1, 64, 64, 3)).astype(np.float32)

    with jax.default_matmul_precision('highest'):
        ref = np.asarray(raft_model.forward_consecutive(
            params, frames, iters=3))

        mesh = make_mesh(n_devices=n, time_parallel=1)
        halo = np.stack([frames[d * k: d * k + k + 1] for d in range(n)])
        halo = halo.reshape(n * (k + 1), 64, 64, 3)
        step = jax.jit(shard_map(
            lambda p, f: raft_model.forward_consecutive(p, f, iters=3),
            mesh=mesh, in_specs=(P(), P('data')), out_specs=P('data')))
        out = np.asarray(step(put_replicated(mesh, params),
                              put_batch(mesh, halo)))
    assert out.shape == ref.shape == (n * k, 64, 64, 2)
    np.testing.assert_allclose(out, ref, atol=5e-4, rtol=1e-4)


def test_vit_sequence_parallel_matches_single_device():
    """Ring-attention sequence parallelism over the token axis: the
    production consumer path for very long token sequences. 197 ragged
    tokens pad to 200 over an 8-device time axis (masked keys rotate with
    their shards) and must match the unsharded forward."""
    from video_features_tpu.models import vit as vit_model
    from video_features_tpu.transplant.torch2jax import transplant

    params = transplant(vit_model.init_state_dict(arch='vit_tiny_patch16_224'))
    x = np.random.RandomState(0).rand(2, 224, 224, 3).astype(np.float32)
    mesh = make_mesh(time_parallel=8)
    assert mesh.shape['time'] == 8

    with jax.default_matmul_precision('highest'):
        ref = np.asarray(vit_model.forward(params, x,
                                           arch='vit_tiny_patch16_224'))
        got = np.asarray(jax.jit(
            lambda p, t: vit_model.forward_sequence_parallel(
                p, t, mesh, arch='vit_tiny_patch16_224'))(params, x))
    rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert rel < 1e-5, f'rel L2 {rel}'


def test_timm_sequence_parallel_extractor_e2e(short_video, tmp_path):
    """sequence_parallel=true through the real extractor: tokens shard over
    all 8 virtual devices, features match the single-device extractor."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    common = {
        'video_paths': short_video, 'device': 'cpu', 'batch_size': 8,
        'model_name': 'vit_tiny_patch16_224', 'allow_random_weights': True,
        'extraction_fps': 2,
        'output_path': str(tmp_path / 'o'), 'tmp_path': str(tmp_path / 't'),
    }
    sp = create_extractor(load_config('timm', overrides={
        **common, 'sequence_parallel': True}))
    assert sp._mesh is not None and sp._mesh.shape['time'] == 8
    single = create_extractor(load_config('timm', overrides=common))

    feats_sp = sp.extract(short_video)
    feats_single = single.extract(short_video)
    np.testing.assert_allclose(feats_sp['timm'], feats_single['timm'],
                               atol=2e-5, rtol=1e-5)


def test_timm_sequence_parallel_rejects_conv_families(tmp_path):
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    args = load_config('timm', overrides={
        'video_paths': 'v.mp4', 'device': 'cpu',
        'model_name': 'resnet18', 'sequence_parallel': True,
        'allow_random_weights': True,
        'output_path': str(tmp_path / 'o'), 'tmp_path': str(tmp_path / 't'),
    })
    with pytest.raises(NotImplementedError, match='token axis'):
        create_extractor(args)
