"""Mesh-sharded packed execution (``mesh_devices=``): the data-parallel
device loop must be externally invisible — byte-identical outputs across
the packed CLI, worklist, and serve warm paths at any device count —
while planning batches at capacity × ndev, masking uneven tails instead
of stalling them, and keeping the per-video fault contract (a poisoned
video fails alone; both shards' siblings survive).

Runs everywhere: tests/conftest.py forces
``--xla_force_host_platform_device_count=8`` virtual host CPU devices,
so the ndev=2 sharded path is exercised without hardware.
"""
import json
import os
from pathlib import Path

import numpy as np
import pytest

from video_features_tpu.config import load_config
from video_features_tpu.registry import create_extractor
from video_features_tpu.utils.output import make_path

from tools.make_sample_video import write_noise_clip as _write_clip  # noqa: E402


@pytest.fixture(scope='module')
def mesh_worklist(tmp_path_factory):
    """Mixed-length clips: 9+4+14 = 27 resnet frames, so capacity 4 over
    2 devices (global batch 8) runs 3 full batches plus an UNEVEN tail of
    3 — the second shard's tail slice is entirely padding."""
    d = tmp_path_factory.mktemp('meshvids')
    return [_write_clip(d / f'mv{i}.mp4', n, seed=i)
            for i, n in enumerate((9, 4, 14))]


def _resnet_args(paths, out, tmp, **kw):
    over = dict(video_paths=paths, device='cpu', model_name='resnet18',
                batch_size=4, allow_random_weights=True,
                on_extraction='save_numpy', output_path=str(out),
                tmp_path=str(tmp))
    over.update(kw)
    return load_config('resnet', overrides=over)


RESNET_KEYS = ('resnet', 'fps', 'timestamps_ms')


def _assert_outputs_identical(root_a, root_b, paths, keys=RESNET_KEYS):
    compared = 0
    for p in paths:
        for k in keys:
            a = Path(make_path(str(root_a), p, k, '.npy'))
            b = Path(make_path(str(root_b), p, k, '.npy'))
            assert a.read_bytes() == b.read_bytes(), (p, k)
            compared += 1
    assert compared == len(paths) * len(keys)


# -- mesh planning units (no extractor) --------------------------------------


def test_make_mesh_autodetect_spans_every_device():
    """``n_devices=0`` is the auto-detect spelling: the mesh spans every
    available device (8 forced host CPUs under the conftest flag)."""
    import jax

    from video_features_tpu.parallel.mesh import DATA_AXIS, make_mesh
    mesh = make_mesh(n_devices=0, time_parallel=1)
    assert mesh.shape[DATA_AXIS] == len(jax.devices())


def test_make_mesh_overask_raises_named_error():
    """Asking for more devices than exist must raise a ValueError naming
    both counts — not an XLA placement error downstream."""
    import jax

    from video_features_tpu.parallel.mesh import make_mesh
    have = len(jax.devices())
    with pytest.raises(ValueError, match=f'requested {have + 1}.*{have}'):
        make_mesh(n_devices=have + 1, time_parallel=1)


def test_batch_planning_errors_are_named():
    """capacity × ndev planning failures surface as clear ValueErrors at
    plan time, never as an XLA shape error mid-batch."""
    from video_features_tpu.parallel.mesh import (
        make_mesh, plan_device_batch, require_shardable,
    )
    mesh = make_mesh(n_devices=2, time_parallel=1)
    assert plan_device_batch(4, mesh) == 8
    with pytest.raises(ValueError, match='capacity'):
        plan_device_batch(0, mesh)
    assert require_shardable(8, mesh) == 4
    with pytest.raises(ValueError, match='cannot shard over 2'):
        require_shardable(7, mesh)


def test_configure_mesh_resolves_and_validates(mesh_worklist, tmp_path):
    """The config knob resolves at BUILD time: 0 auto-detects every local
    device, an over-ask raises with the host's device count named, a
    negative count is rejected by sanity_check, and data_parallel keeps
    ownership of the device set (mesh_devices degrades with a warning)."""
    import jax

    ndev = len(jax.devices())
    ex = create_extractor(_resnet_args(
        mesh_worklist, tmp_path / 'auto', tmp_path / 'ta',
        mesh_devices=0))
    assert ex.mesh_devices == ndev

    with pytest.raises(ValueError, match=f'mesh_devices={ndev + 3}'):
        create_extractor(_resnet_args(
            mesh_worklist, tmp_path / 'over', tmp_path / 'to',
            mesh_devices=ndev + 3))

    with pytest.raises(ValueError, match='mesh_devices'):
        _resnet_args(mesh_worklist, tmp_path / 'neg', tmp_path / 'tn',
                     mesh_devices=-1)

    with pytest.warns(UserWarning, match='data_parallel'):
        args = _resnet_args(mesh_worklist, tmp_path / 'dp',
                            tmp_path / 'tdp',
                            mesh_devices=2, data_parallel=True)
    assert args['mesh_devices'] == 1          # data_parallel wins


# -- packed worklist parity ---------------------------------------------------


def test_mesh_parity_packed_framewise(mesh_worklist, tmp_path):
    """resnet packed worklist: outputs at mesh_devices=2 (batches planned
    at 4 × 2 and sharded over the data axis) are byte-identical to the
    single-device loop, and the sharded run really built a 2-device
    mesh."""
    ex1 = create_extractor(_resnet_args(
        mesh_worklist, tmp_path / 'm1', tmp_path / 't1',
        pack_across_videos=True, mesh_devices=1))
    ex1.extract_packed(mesh_worklist)
    assert ex1._mesh is None                   # 1 ≡ today's loop

    ex2 = create_extractor(_resnet_args(
        mesh_worklist, tmp_path / 'm2', tmp_path / 't2',
        pack_across_videos=True, mesh_devices=2))
    ex2.extract_packed(mesh_worklist)
    assert ex2._packed_mesh_ndev == 2
    assert ex2._mesh is not None

    _assert_outputs_identical(ex1.output_path, ex2.output_path,
                              mesh_worklist)


def test_mesh_parity_packed_stacks(mesh_worklist, tmp_path):
    """r21d (stack family, mixed window counts): byte-identical at
    mesh_devices=1 vs 2."""
    def run(tag, ndev):
        args = load_config('r21d', overrides=dict(
            video_paths=mesh_worklist, device='cpu', stack_size=4,
            step_size=4, batch_size=2, allow_random_weights=True,
            on_extraction='save_numpy',
            output_path=str(tmp_path / tag / 'out'),
            tmp_path=str(tmp_path / tag / 'tmp'),
            pack_across_videos=True, mesh_devices=ndev))
        ex = create_extractor(args)
        ex.extract_packed(mesh_worklist)
        return ex

    ex1 = run('s1', 1)
    ex2 = run('s2', 2)
    assert ex2._packed_mesh_ndev == 2
    _assert_outputs_identical(ex1.output_path, ex2.output_path,
                              mesh_worklist, keys=('r21d',))


def test_cli_mesh_byte_identity_and_manifest(mesh_worklist, tmp_path,
                                             capsys):
    """The full CLI entry at mesh_devices=2 writes byte-identical
    features to mesh_devices=1, the run manifest records the mesh shape
    with per-device occupancy, and the model/d2h spans carry the mesh
    width + per-shard valid counts."""
    from tools.trace_view import validate_events
    from video_features_tpu.cli import main as cli_main

    manifest = str(tmp_path / 'mesh_manifest.json')
    trace = str(tmp_path / 'mesh_trace.json')
    roots = {}
    for ndev in (1, 2):
        out = tmp_path / f'cli{ndev}'
        argv = [
            'feature_type=resnet', 'model_name=resnet18', 'device=cpu',
            'batch_size=4', 'allow_random_weights=true',
            'on_extraction=save_numpy', 'pack_across_videos=true',
            f'mesh_devices={ndev}',
            f'output_path={out}', f'tmp_path={tmp_path / "ctmp"}',
            'video_paths=[' + ','.join(str(p) for p in mesh_worklist) + ']',
        ]
        if ndev == 2:
            argv += [f'manifest_out={manifest}', f'trace_out={trace}']
        assert cli_main(argv) == 0
        roots[ndev] = os.path.join(str(out), 'resnet', 'resnet18')
    capsys.readouterr()
    _assert_outputs_identical(roots[1], roots[2], mesh_worklist)

    man = json.loads(Path(manifest).read_text())
    assert man['mesh']['mesh_devices'] == 2
    assert man['mesh']['shape']['data'] == 2
    assert man['mesh']['capacity_per_device'] == 4
    assert man['mesh']['global_batch'] == 8
    assert len(man['mesh']['devices']) == 2
    occ_dev = man['stages']['model'].get('occ_device') or {}
    assert set(occ_dev) == set(man['mesh']['devices'])
    for rec in occ_dev.values():
        assert 0.0 <= rec['occupancy'] <= 1.0

    events = json.loads(Path(trace).read_text())['traceEvents']
    assert validate_events(events) == []
    mesh_spans = [e for e in events if e['ph'] == 'X'
                  and e['name'] in ('model', 'd2h')
                  and (e.get('args') or {}).get('mesh_devices')]
    assert mesh_spans, 'no mesh-annotated model/d2h spans in the trace'
    for e in mesh_spans:
        assert e['args']['mesh_devices'] == 2
        assert len(e['args']['shard_valid']) == 2


# -- fault isolation + uneven tails -------------------------------------------


def test_mesh_fault_isolation_poisoned_video(mesh_worklist, tmp_path):
    """A decode failure MID-video on the sharded loop: the poisoned video
    fails alone — every sibling (on both shards of its batches) saves
    byte-identically to a clean mesh run."""
    clean = create_extractor(_resnet_args(
        mesh_worklist, tmp_path / 'clean', tmp_path / 'tc',
        pack_across_videos=True, mesh_devices=2))
    clean.extract_packed(mesh_worklist)

    victim = mesh_worklist[1]
    ex = create_extractor(_resnet_args(
        mesh_worklist, tmp_path / 'hurt', tmp_path / 'th',
        pack_across_videos=True, mesh_devices=2))
    orig = ex.packed_windows

    def flaky(task):
        it = orig(task)
        if task.path == victim:
            yield next(it)                    # one window enters a batch
            raise RuntimeError('decoder died mid-video')
        yield from it

    ex.packed_windows = flaky
    ex.extract_packed(mesh_worklist)          # must not raise

    assert not Path(make_path(ex.output_path, victim, 'resnet',
                              '.npy')).exists()
    survivors = [p for p in mesh_worklist if p != victim]
    _assert_outputs_identical(clean.output_path, ex.output_path,
                              survivors)


def test_mesh_uneven_tail_masked_not_stalled(mesh_worklist, tmp_path):
    """27 windows through a global batch of 8 (4 × 2 devices): the final
    batch carries 3 valid rows — the first shard runs partially padded
    and the second ENTIRELY padded, masked at scatter-back. The per-device
    occupancy ledger must show exactly that split (raw valid counts sum
    to the corpus), every ratio staying ≤ 1."""
    ex = create_extractor(_resnet_args(
        mesh_worklist, tmp_path / 'tail', tmp_path / 'tt',
        pack_across_videos=True, mesh_devices=2, profile=True))
    report = {}
    real_reset = ex.tracer.reset
    ex.tracer.reset = lambda: report.update(ex.tracer.report()) \
        or real_reset()
    ex.extract_packed(mesh_worklist)
    ex.tracer.reset = real_reset

    model = report['model']
    assert model['count'] == 4                # 3 full + 1 tail (vs 7 at ndev=1)
    assert model['occ_valid'] == 27
    assert model['occ_capacity'] == 32        # 4 batches × global 8
    occ_dev = model['occ_device']
    assert len(occ_dev) == 2
    valids = sorted(d['occ_valid'] for d in occ_dev.values())
    assert valids == [12, 15]                 # tail: shard0=3, shard1=0
    assert all(d['occ_capacity'] == 16 for d in occ_dev.values())
    assert all(0.0 <= d['occupancy'] <= 1.0 for d in occ_dev.values())
    # every video still completed — the lone tail never stalled
    for p in mesh_worklist:
        assert Path(make_path(ex.output_path, p, 'resnet',
                              '.npy')).exists()


# -- merge_reports device dimension (regression) ------------------------------


def test_merge_reports_device_occupancy_not_double_counted():
    """Regression (the serve metrics bug this PR fixes): merging stage
    tables that carry per-device occupancy must keep the merged aggregate
    at the global-capacity accounting — folding the shard slices into the
    flat counts again would push occupancy past 100%. Device counts merge
    DEVICE-WISE instead."""
    from video_features_tpu.utils.tracing import Tracer, merge_reports

    t1, t2 = Tracer(), Tracer()
    for t, valid in ((t1, 8), (t2, 6)):
        t.add('model', 1.0)
        t.add_occupancy('model', valid, 8)           # aggregate, global cap
        t.add_occupancy('model', min(valid, 4), 4, device='d0')
        t.add_occupancy('model', max(valid - 4, 0), 4, device='d1')

    m = merge_reports([t1.report(), t2.report()])
    model = m['model']
    assert model['occ_valid'] == 14
    assert model['occ_capacity'] == 16
    assert model['occupancy'] == pytest.approx(14 / 16)
    assert model['occupancy'] <= 1.0          # the >100% regression guard
    dev = model['occ_device']
    assert dev['d0']['occ_valid'] == 8 and dev['d0']['occ_capacity'] == 8
    assert dev['d1']['occ_valid'] == 6 and dev['d1']['occ_capacity'] == 8
    assert dev['d0']['occupancy'] == pytest.approx(1.0)
    assert dev['d1']['occupancy'] == pytest.approx(6 / 8)


def test_round_report_rounds_nested_device_records():
    from video_features_tpu.utils.tracing import Tracer, round_report

    t = Tracer()
    t.add('model', 1.0 / 3.0)
    t.add_occupancy('model', 1, 3, device='d0')
    rec = round_report(t.report(), ndigits=3)['model']
    assert rec['occ_device']['d0']['occupancy'] == pytest.approx(0.333)


# -- staged H2D (satellite: overlap device_put with compute) ------------------


def test_transfer_batches_stages_ahead_with_staged_attr():
    """``transfer_batches`` at depth 2 (the default) issues the next
    batch's device_put while the current batch runs; the h2d span's
    ``staged`` attr records the mode so profiles distinguish staged from
    on-demand transfers. depth=1 keeps the old single-buffer overlap."""
    from video_features_tpu.extract.streaming import transfer_batches
    from video_features_tpu.obs.spans import SpanRecorder
    from video_features_tpu.utils.tracing import Tracer

    def run(depth):
        rec = SpanRecorder(capacity=64)
        tracer = Tracer(enabled=True, recorder=rec)
        items = [(np.full((2, 2), i, dtype=np.float32), i)
                 for i in range(3)]
        out = list(transfer_batches(iter(items), put=lambda b: b + 1,
                                    tracer=tracer, depth=depth))
        assert [m for _, _, m in out] == [0, 1, 2]
        assert all((d == np.full((2, 2), m + 1)).all()
                   for d, _, m in out)
        h2d = [e for e in rec.snapshot()
               if e['ph'] == 'X' and e['name'] == 'h2d']
        assert len(h2d) == 3
        return h2d

    assert all(e['args']['staged'] for e in run(2))
    assert not any(e['args']['staged'] for e in run(1))


# -- serve warm path ----------------------------------------------------------


def test_serve_mesh_parity_and_device_metrics(mesh_worklist, tmp_path):
    """A mesh-sharded server (mesh_devices=2 base override) answers warm
    requests byte-identically to the single-device server, the warm pool
    reports which chips each entry is resident on, and the Prometheus
    exposition grows device-labelled series (vft_device_resident_entries,
    vft_stage_occupancy{device=...})."""
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer

    def base(ndev):
        return {
            'device': 'cpu', 'model_name': 'resnet18', 'batch_size': 4,
            'allow_random_weights': True, 'on_extraction': 'save_numpy',
            'tmp_path': str(tmp_path / f'stmp{ndev}'),
            'mesh_devices': ndev,
        }

    roots = {}
    for ndev in (1, 2):
        server = ExtractionServer(base_overrides=base(ndev),
                                  queue_depth=32, pool_size=2).start()
        try:
            client = ServeClient(port=server.port)
            # two passes: the second rides the WARM pool entry
            for tag in ('cold', 'warm'):
                out_root = str(tmp_path / f'serve{ndev}_{tag}')
                rid = client.submit('resnet', mesh_worklist,
                                    overrides={'output_path': out_root})
                st = client.wait(rid, timeout_s=300)
                assert st['state'] == 'done', st
            m = client.metrics()
            assert m['warm_pool']['hit_rate'] > 0       # warm pass hit
            placements = m['warm_pool']['placements']
            assert placements, 'no placement recorded for the warm entry'
            (chips,) = placements.values()
            assert len(chips) == ndev
            residents = m['warm_pool']['device_residents']
            assert sum(residents.values()) == ndev
            prom = client.metrics_prom()
            assert 'vft_device_resident_entries{device=' in prom
            if ndev == 2:
                assert 'vft_stage_occupancy{device=' in prom \
                    or 'device=' in prom.split('vft_stage_occupancy', 1)[-1]
        finally:
            server.drain(wait=True, grace_s=60)
        roots[ndev] = os.path.join(out_root, 'resnet', 'resnet18')
    _assert_outputs_identical(roots[1], roots[2], mesh_worklist)


def test_serve_pool_key_includes_mesh_devices():
    """mesh_devices changes the compiled program's sharding, so it must
    stay IN the serve pool key — a 1-chip and a 2-chip request never
    share a warm entry (unlike the cache fingerprint, which excludes
    it: outputs are byte-identical by contract). The auto-detect
    spelling resolves BEFORE keying: mesh_devices=0 and the equivalent
    explicit width share one entry instead of double-building the same
    sharded program."""
    import jax

    from video_features_tpu.cache.key import config_fingerprint
    from video_features_tpu.serve.server import (
        pool_key, resolve_mesh_devices,
    )

    base = {'feature_type': 'resnet', 'model_name': 'resnet18',
            'device': 'cpu', 'batch_size': 4}
    k1 = pool_key(dict(base, mesh_devices=1))
    k2 = pool_key(dict(base, mesh_devices=2))
    assert k1 != k2

    f1 = config_fingerprint(dict(base, mesh_devices=1))
    f2 = config_fingerprint(dict(base, mesh_devices=2))
    assert f1 == f2

    ndev = len(jax.devices())                 # conftest forces 8
    auto = pool_key(resolve_mesh_devices(dict(base, mesh_devices=0)))
    explicit = pool_key(dict(base, mesh_devices=ndev))
    assert auto == explicit


def test_place_on_moves_declared_device_buffers(mesh_worklist, tmp_path):
    """``place_on`` migrates every buffer a family declares in
    ``_device_buffer_attrs`` along with the params (vggish's PCA
    matrices) — a placed entry must never feed a jit call operands
    committed to two different chips."""
    import jax

    ex = create_extractor(_resnet_args(
        mesh_worklist, tmp_path / 'place', tmp_path / 'tp'))
    d0, d1 = jax.devices()[:2]
    ex._aux = jax.device_put(np.ones(4, np.float32), d0)
    ex._device_buffer_attrs = ('_aux', '_absent')   # absent: skipped
    ex.place_on([d1])
    assert ex._device is d1
    assert next(iter(ex._aux.devices())) is d1
    leaf = jax.tree_util.tree_leaves(ex.params)[0]
    assert next(iter(leaf.devices())) is d1
    # vggish declares its PCA matrices
    from video_features_tpu.extract.vggish import ExtractVGGish
    assert ExtractVGGish._device_buffer_attrs == ('_pca_eig', '_pca_means')


def test_put_input_names_unshardable_batches(mesh_worklist, tmp_path):
    """An indivisible global batch through a sharded ``put_input`` must
    raise the named require_shardable error, not an opaque XLA
    sharding/shape failure."""
    ex = create_extractor(_resnet_args(
        mesh_worklist, tmp_path / 'shard', tmp_path / 'tsh',
        pack_across_videos=True, mesh_devices=2))
    assert ex._ensure_packed_mesh() == 2
    ok = ex.put_input(np.zeros((8, 4, 4, 3), np.float32))
    assert ok.shape[0] == 8
    with pytest.raises(ValueError, match='cannot shard over 2'):
        ex.put_input(np.zeros((7, 4, 4, 3), np.float32))


def test_place_extractor_releases_chips_on_placement_failure():
    """A place_on failure after assign() counted the chips must give
    them back — a leaked count would skew every future least-loaded
    decision for the server's lifetime."""
    from video_features_tpu.serve.pool import DevicePlacer
    from video_features_tpu.serve.server import ExtractionServer

    server = ExtractionServer.__new__(ExtractionServer)   # no socket
    server._placer = DevicePlacer()

    class Boom:
        device = 'cpu'
        mesh_devices = 1

        def place_on(self, devices):
            raise RuntimeError('device_put OOM')

    assert server._place_extractor(Boom()) is None        # best-effort
    # nothing leaked — the count went back to 0 (zero persists so the
    # vft_device_resident_entries gauge can follow it down)
    assert set(server._placer.snapshot().values()) <= {0}


def test_device_placer_spreads_families_and_releases():
    """Least-loaded placement: two single-device entries land on
    DIFFERENT chips, a mesh entry takes N chips, release returns them,
    and ties break deterministically by device id."""
    import jax

    from video_features_tpu.serve.pool import DevicePlacer

    devices = jax.devices()
    assert len(devices) >= 4                  # conftest forces 8
    placer = DevicePlacer()
    a = placer.assign(devices, 1)
    b = placer.assign(devices, 1)
    assert a[0].id != b[0].id                 # different silicon
    mesh_entry = placer.assign(devices, 2)
    assert len(mesh_entry) == 2
    assert {d.id for d in mesh_entry}.isdisjoint({a[0].id, b[0].id})
    snap = placer.snapshot()
    assert sum(snap.values()) == 4
    placer.release(mesh_entry)
    placer.release(a)
    placer.release(b)
    # fully drained: every count back to 0, labels KEPT so the metrics
    # mirror can drive each chip's residency gauge back down
    drained = placer.snapshot()
    assert set(drained) == set(snap)
    assert set(drained.values()) == {0}
    # ask for more than exists: clamped, never raises (build-time
    # validation already rejected genuine over-asks)
    assert len(placer.assign(devices, len(devices) + 5)) == len(devices)


def test_device_placer_ranks_by_real_bytes():
    """Byte-aware placement (the bf16 fast lane's accounting): two
    half-size entries should stack on one chip before a second full-size
    copy does, the bytes gauges read REAL residency, and release nets
    the ledger back to zero."""
    import jax

    from video_features_tpu.serve.pool import DevicePlacer

    devices = jax.devices()[:2]
    placer = DevicePlacer()
    big = placer.assign(devices, 1, nbytes=1000)     # fp32-sized entry
    small1 = placer.assign(devices, 1, nbytes=500)   # bf16-sized
    small2 = placer.assign(devices, 1, nbytes=400)
    assert big[0].id != small1[0].id
    # 500 < 1000: the second small entry stacks on the small chip —
    # byte ranking, not entry-count ranking (which would tie 1 vs 1 and
    # fall back to device id, landing on the BIG chip)
    assert small2[0].id == small1[0].id
    by_bytes = placer.snapshot_bytes()
    assert by_bytes[f'd{big[0].id}'] == 1000
    assert by_bytes[f'd{small1[0].id}'] == 900
    # zero-byte callers (tests, unknown sizes) keep the historical
    # entry-count ordering as the secondary key
    placer.release(small2, nbytes=400)
    placer.release(small1, nbytes=500)
    placer.release(big, nbytes=1000)
    assert set(placer.snapshot_bytes().values()) == {0}
    assert set(placer.snapshot().values()) == {0}
