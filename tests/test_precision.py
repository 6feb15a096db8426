"""ops/precision pins + the precision='mixed' extraction mode + the
compute_dtype fast lanes' pinned parity bounds (PARITY.md-style: the
bounds tables live in ops/precision.BF16_REL_L2_BOUNDS /
INT8_REL_L2_BOUNDS; this module asserts the measured drift of every
accepting family's REAL jitted step stays under them — build-free
numeric gates in tier-1, the full real-build ladders in the slow lane,
with ONE module-scoped fp32 reference build per family shared across
both fast-lane ladders)."""
import numpy as np
import pytest

from video_features_tpu.ops.precision import (
    BF16_REL_L2_BOUNDS, COMPUTE_DTYPES, ComputeDtypeError,
    INT8_REL_L2_BOUNDS, MIXED_PINS, check_compute_dtype, normalize_pins,
    param_np_dtype, pin_scope, rel_l2,
)


def test_normalize_pins():
    assert normalize_pins(None) is None
    assert normalize_pins({'b': 'high', 'a': 'highest'}) == (
        ('a', 'highest'), ('b', 'high'))
    assert normalize_pins((('a', 'x'),)) == (('a', 'x'),)


def test_pin_scope_null_when_unpinned():
    from contextlib import nullcontext
    assert isinstance(pin_scope(None, 'corr'), nullcontext)
    assert isinstance(pin_scope((('iter', 'high'),), 'corr'), nullcontext)
    assert not isinstance(pin_scope((('iter', 'high'),), 'iter'),
                          nullcontext)
    # the tuned 'mixed' policy is ambient-only (no sub-graph survives
    # 1-pass bf16 — see ops/precision.py); pins stay empty
    assert MIXED_PINS == ()


def test_pin_scope_sets_matmul_precision():
    import jax

    from jax._src import config as jax_config
    with pin_scope((('corr', 'high'),), 'corr'):
        assert jax_config.default_matmul_precision.value == 'high'
    # sanity: jax accepts the context in a traced function
    @jax.jit
    def f(x):
        with pin_scope((('corr', 'highest'),), 'corr'):
            return x @ x
    np.testing.assert_allclose(np.asarray(f(np.eye(4, dtype=np.float32))),
                               np.eye(4))


def test_mixed_mode_extractor_runs_and_matches_on_cpu(tmp_path):
    """precision='mixed' compiles and runs; on CPU every precision executes
    fp32, so mixed must be bit-identical to highest — this checks the pin
    plumbing doesn't alter the graph structure. ONE i3d build serves
    both precisions (mixed's pins are empty, so the jitted step is the
    same callable — only the ambient matmul-precision context differs,
    which is exactly the knob under test); the second transplant the old
    two-build version paid bought nothing but tier-1 wall clock."""
    import jax

    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    args = load_config('i3d', overrides={
        'video_paths': 'v.mp4', 'device': 'cpu',
        'precision': 'mixed', 'stack_size': 10, 'step_size': 10,
        'allow_random_weights': True,
        'output_path': str(tmp_path / 'o'),
        'tmp_path': str(tmp_path / 't'),
    })
    ex = create_extractor(args)
    assert ex.precision == 'mixed' and ex.precision_pins == ()

    stacks = np.random.RandomState(0).randint(
        0, 255, (1, 11, 64, 64, 3)).astype(np.float32)
    outs = {}
    scopes = {'mixed': ex.precision_scope(),
              'highest': jax.default_matmul_precision('highest')}
    for precision, scope in scopes.items():
        with scope:
            out = ex._step(ex.params, jax.device_put(stacks),
                           pads=(0, 0, 0, 0), streams=('rgb', 'flow'))
        outs[precision] = {k: np.asarray(v) for k, v in out.items()}
    for k in ('rgb', 'flow'):
        np.testing.assert_array_equal(outs['mixed'][k], outs['highest'][k])


# -- the compute_dtype fast lanes (bfloat16 / int8) ---------------------------
#
# One extractor per (family, lane) serves ALL of a family's assertions
# (parity, census, output dtype — the PR 11 reuse pattern: builds are
# the expensive part); the fp32 reference and the fast-lane candidate
# see IDENTICAL uint8 inputs, so every diff is the lane's. The builds
# live in the SLOW lane (tier-1's 870 s budget has no room for the
# extractor pairs), and the fp32 REFERENCE build+run is module-scoped
# (`_f32_reference`) so the bf16 and int8 ladders share it instead of
# each paying a second fp32 build per family; tier-1 keeps the
# build-free numerics + identity gates below plus the lock-census gate
# in test_programs.

# family → (config overrides, input batch builder). Geometries are the
# smallest each family compiles quickly at on CPU; the bound is rel-L2,
# stable across geometry/weights (max-abs scales with feature magnitude).
_BF16_CASES = {
    'vggish': ({}, lambda: np.random.RandomState(0)
               .rand(4, 96, 64, 1).astype(np.float32)),
    'r21d': ({'stack_size': 10, 'step_size': 10},
             lambda: np.random.RandomState(0)
             .randint(0, 255, (1, 10, 64, 86, 3)).astype(np.uint8)),
    's3d': ({'stack_size': 16, 'step_size': 16},
            lambda: np.random.RandomState(0)
            .randint(0, 255, (1, 16, 64, 86, 3)).astype(np.uint8)),
    'resnet': ({'model_name': 'resnet18', 'batch_size': 2},
               lambda: np.random.RandomState(0)
               .randint(0, 255, (2, 224, 224, 3)).astype(np.uint8)),
    'clip': ({'model_name': 'ViT-B/32', 'batch_size': 2},
             lambda: np.random.RandomState(0)
             .randint(0, 255, (2, 224, 224, 3)).astype(np.uint8)),
    'timm': ({'model_name': 'vit_base_patch16_224', 'batch_size': 2,
              'pretrained': False},
             lambda: np.random.RandomState(0)
             .randint(0, 255, (2, 224, 224, 3)).astype(np.uint8)),
}


def _build_lane(ft, compute_dtype, tmp_root):
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor
    overrides = {
        'video_paths': 'v.mp4', 'device': 'cpu',
        'allow_random_weights': True, 'compute_dtype': compute_dtype,
        'output_path': f'{tmp_root}/out_{ft}_{compute_dtype}',
        'tmp_path': f'{tmp_root}/tmp_{ft}_{compute_dtype}',
    }
    overrides.update(_BF16_CASES[ft][0])
    return create_extractor(load_config(ft, overrides=overrides))


def _run_step(ex, ft, batch):
    """One device step on the REAL jitted callable the hot path
    dispatches (not a re-wrap), family quirks included."""
    import jax
    x = batch
    if ft == 'vggish' and ex.compute_dtype == 'bfloat16':
        x = x.astype(ex.param_dtype)       # the _run_batched edge cast
    if ft == 's3d':
        step, _, _ = ex._geometry_step(*batch.shape[2:4])
        return np.asarray(step(ex.params, jax.device_put(x)))
    return np.asarray(ex._step(ex.params, jax.device_put(x)))


@pytest.fixture(scope='module')
def _f32_reference(tmp_path_factory):
    """Per-family fp32 reference features, built ONCE per module run and
    shared by the bf16 AND int8 slow ladders (the input builders are
    seeded, so every lane sees byte-identical batches). Builds are the
    expensive part — this keeps the two-ladder suite at one fp32 build
    per family instead of two."""
    cache = {}

    def get(ft):
        if ft not in cache:
            root = str(tmp_path_factory.mktemp(f'ref_{ft}'))
            ex = _build_lane(ft, 'float32', root)
            cache[ft] = _run_step(ex, ft, _BF16_CASES[ft][1]())
        return cache[ft]
    return get


def _assert_lane_contract(ft, lane, tmp_root, ref):
    import jax
    bounds = (BF16_REL_L2_BOUNDS if lane == 'bfloat16'
              else INT8_REL_L2_BOUNDS)
    ex = _build_lane(ft, lane, tmp_root)
    fast = _run_step(ex, ft, _BF16_CASES[ft][1]())
    # the lane actually computed differently...
    assert np.abs(ref - fast).max() > 0, f'{ft}: lanes identical?'
    # ...features still leave the device as float32 (on-disk contract)...
    assert fast.dtype == np.float32
    # ...within the family's pinned parity bound...
    err = rel_l2(ref, fast)
    assert err <= bounds[ft], (
        f'{ft}: {lane} lane rel-L2 {err:.3e} over the pinned bound '
        f'{bounds[ft]:.1e}')
    # ...and the storage transform reached the params (the PROGRAMS.lock
    # census holds the same line per lane)
    by_dtype = {}
    for leaf in jax.tree_util.tree_leaves(ex.params):
        if hasattr(leaf, 'dtype'):
            by_dtype[str(leaf.dtype)] = (by_dtype.get(str(leaf.dtype), 0)
                                         + leaf.nbytes)
    if lane == 'bfloat16':
        # the cast reached EVERY param: zero fp32 survivors
        assert set(by_dtype) == {'bfloat16'}, (ft, by_dtype)
    else:
        # int8 weight payloads dominate; fp32 is the declared minority
        # (per-channel scales, biases, norm params, embedding tables)
        assert 'int8' in by_dtype, (ft, by_dtype)
        assert by_dtype.get('float32', 0) < by_dtype['int8'], (ft, by_dtype)


def test_bounds_tables_are_pinned():
    """PARITY.md-style pin: the bounds (and who accepts each lane) are an
    intentional, test-visible contract — moving one is a review event,
    not a drive-by edit."""
    from video_features_tpu.registry import BF16_FEATURES, INT8_FEATURES
    assert BF16_REL_L2_BOUNDS == {
        'r21d': 1.5e-2, 's3d': 2e-2, 'resnet': 2e-2,
        'clip': 3e-2, 'timm': 5e-2, 'vggish': 2.5e-2,
    }
    assert set(BF16_REL_L2_BOUNDS) == BF16_FEATURES
    assert INT8_REL_L2_BOUNDS == {
        'resnet': 5e-2, 'clip': 3.5e-2, 'timm': 7.5e-2,
    }
    assert set(INT8_REL_L2_BOUNDS) == INT8_FEATURES
    # int8 accepts a strict subset of bf16's families: every int8 lane
    # rung sits below an existing bf16 rung on the ladder
    assert INT8_FEATURES < BF16_FEATURES
    assert COMPUTE_DTYPES == ('float32', 'bfloat16', 'int8')


def test_refusal_is_structured_and_echoes_the_requested_dtype():
    """Refusals name the family, the parity bound, the remediation — and
    the REQUESTED dtype (the pre-int8 message hardcoded
    'compute_dtype=bfloat16' whatever was asked)."""
    for lane in ('bfloat16', 'int8'):
        for ft in ('i3d', 'raft'):
            with pytest.raises(ComputeDtypeError) as e:
                check_compute_dtype(ft, lane)
            msg = str(e.value)
            assert f'compute_dtype={lane} is refused' in msg
            assert ft in msg and '1e-3' in msg and 'precision=mixed' in msg
    # families with a bf16 bound but NO int8 bound refuse int8 with the
    # generic opt-in message naming the right registry set
    with pytest.raises(ComputeDtypeError) as e:
        check_compute_dtype('vggish', 'int8')
    assert 'compute_dtype=int8 is refused' in str(e.value)
    assert 'INT8_FEATURES' in str(e.value)
    with pytest.raises(ComputeDtypeError):
        check_compute_dtype('resnet', 'float16')    # unknown value
    # fp8: structured not-yet naming backend support as the gate
    with pytest.raises(ComputeDtypeError) as e:
        check_compute_dtype('resnet', 'float8_e4m3fn')
    assert 'backend' in str(e.value) and 'int8' in str(e.value)
    assert check_compute_dtype('i3d', 'float32') == 'float32'
    assert check_compute_dtype('resnet', 'bfloat16') == 'bfloat16'
    assert check_compute_dtype('resnet', 'int8') == 'int8'
    assert check_compute_dtype('vggish', 'bfloat16') == 'bfloat16'


def test_param_np_dtype():
    import ml_dtypes
    assert param_np_dtype('float32') == np.dtype(np.float32)
    assert param_np_dtype('bfloat16') == np.dtype(ml_dtypes.bfloat16)
    assert param_np_dtype('int8') == np.dtype(np.int8)
    # exhaustive dispatch: an unrecognized lane raises instead of the
    # old silent float32 fall-through
    for bad in ('float16', 'int4', 'fp8', ''):
        with pytest.raises(ComputeDtypeError):
            param_np_dtype(bad)


def test_compute_dtype_is_identity_on_both_axes():
    """The KNOB_CLASSIFICATION 'both' contract, pinned via the two REAL
    consumers: runs of the same video on any two lanes must produce
    distinct cache fingerprints (never share a cache entry) and
    distinct serve pool keys (never share a warm program)."""
    from video_features_tpu.cache.key import config_fingerprint
    from video_features_tpu.config import KNOB_CLASSIFICATION, Config
    from video_features_tpu.serve.server import pool_key
    assert KNOB_CLASSIFICATION['compute_dtype'] == 'both'
    base = dict(feature_type='resnet', model_name='resnet18',
                batch_size=8, device='cpu', output_path='/o',
                tmp_path='/t')
    cfgs = [Config(base, compute_dtype=lane) for lane in COMPUTE_DTYPES]
    fps = [config_fingerprint(c) for c in cfgs]
    keys = [pool_key(c) for c in cfgs]
    assert len(set(fps)) == len(COMPUTE_DTYPES)
    assert len(set(keys)) == len(COMPUTE_DTYPES)


def test_bf16_islands_and_epilogue_cast_tier1():
    """Build-free tier-1 slice of the lane's numerics: the ops/nn fp32
    accumulation islands fire exactly on bf16 input (fp32 input lowers
    the pre-lane graph verbatim — no convert ops appear), and the
    feature epilogue always hands back float32. The full per-family
    error ladder — real extractor builds, measured drift vs the pinned
    bounds — lives in the slow lane below; tier-1's STRUCTURAL bf16
    gate is the lock census in test_programs (resnet, both lanes)."""
    import jax
    import jax.numpy as jnp

    from video_features_tpu.ops.nn import adaptive_avg_pool, softmax
    from video_features_tpu.ops.precision import features_to_f32

    x32 = np.linspace(-3, 3, 4 * 7 * 7 * 5,
                      dtype=np.float32).reshape(4, 7, 7, 5)
    xb = jnp.asarray(x32, jnp.bfloat16)
    # islands keep the lane's dtype on the outside...
    assert softmax(xb).dtype == jnp.bfloat16
    assert adaptive_avg_pool(xb).dtype == jnp.bfloat16
    # ...and compute fp32 inside: the bf16 result equals the fp32
    # computation rounded ONCE at the end (not bf16 all the way through)
    ref = jax.nn.softmax(jnp.asarray(np.asarray(xb, np.float32)), axis=-1)
    np.testing.assert_array_equal(
        np.asarray(softmax(xb), np.float32),
        np.asarray(ref.astype(jnp.bfloat16), np.float32))
    # fp32 path byte-clean: the island branch emits NOTHING for f32
    jx_f32 = jax.make_jaxpr(softmax)(x32)
    assert 'bf16' not in str(jx_f32)
    # the epilogue cast is a no-op (no convert) on the fp32 lane and a
    # single convert on the bf16 lane
    assert features_to_f32(jnp.asarray(x32)) .dtype == jnp.float32
    assert 'convert' not in str(jax.make_jaxpr(features_to_f32)(x32))
    assert features_to_f32(xb).dtype == jnp.float32


def test_int8_quant_dequant_numerics_tier1():
    """Build-free tier-1 slice of the int8 lane's numerics: the
    quantizer's per-channel scales, symmetric clip, zero-guard and the
    in-graph dequant roundtrip — plus the load-bearing structural
    identity (dequantize_tree on a PLAIN tree adds zero graph ops, which
    is what keeps the fp32 lane's StableHLO byte-identical with the call
    compiled into every accepting family's forward). The full
    per-family error ladder — real builds, measured drift vs the pinned
    bounds — lives in the slow lane below; tier-1's STRUCTURAL int8
    gate is the lock census in test_programs."""
    import jax

    from video_features_tpu.ops.quant import (
        QMAX, QuantizedTensor, dequantize_tree, quantize_array,
        quantize_flat, tree_is_quantized,
    )

    rng = np.random.RandomState(0)
    # per-channel: each output channel's amax maps exactly to +/-127
    w = (rng.randn(3, 3, 8, 16) * np.linspace(0.1, 4.0, 16)).astype(
        np.float32)
    qt = quantize_array(w)
    assert qt.q.dtype == np.int8 and qt.q.shape == w.shape
    assert qt.scale.dtype == np.float32
    assert qt.scale.shape == (1, 1, 1, 16)
    assert int(np.abs(qt.q).max()) == QMAX
    np.testing.assert_allclose(
        qt.scale.ravel(), np.abs(w).max(axis=(0, 1, 2)) / QMAX)
    # roundtrip error bounded by scale/2 per element (round-to-nearest)
    deq = np.asarray(qt.dequantize())
    assert np.abs(deq - w).max() <= float(qt.scale.max()) / 2 + 1e-7
    # axis-0 channel layout (CLIP's torch-layout in_proj_weight)
    qt0 = quantize_array(rng.randn(24, 8).astype(np.float32), axis=0)
    assert qt0.scale.shape == (24, 1)
    # all-zero channel: scale guards to 1.0, payload is zeros
    wz = np.zeros((4, 3), np.float32)
    wz[:, 0] = 5.0
    qz = quantize_array(wz)
    assert np.all(np.asarray(qz.scale).ravel()[1:] == 1.0)
    assert np.all(qz.q[:, 1:] == 0)
    assert np.isfinite(np.asarray(qz.dequantize())).all()
    # eligibility (the transplant re-layout rule): weights quantize,
    # biases/norm params stay fp32, embedding tables and the skip set
    # stay fp32, in_proj_weight rides the axis-0 path
    flat = {
        'conv1.weight': rng.randn(3, 3, 3, 8).astype(np.float32),
        'fc.weight': rng.randn(16, 10).astype(np.float32),
        'fc.bias': rng.randn(10).astype(np.float32),
        'bn.weight': rng.randn(8).astype(np.float32),
        'token_embedding.weight': rng.randn(50, 16).astype(np.float32),
        'attn.in_proj_weight': rng.randn(48, 16).astype(np.float32),
        'skipme.weight': rng.randn(4, 4).astype(np.float32),
    }
    q = quantize_flat(flat, skip={'skipme.weight'})
    assert isinstance(q['conv1.weight'], QuantizedTensor)
    assert isinstance(q['fc.weight'], QuantizedTensor)
    assert isinstance(q['attn.in_proj_weight'], QuantizedTensor)
    assert q['attn.in_proj_weight'].scale.shape == (48, 1)
    for kept in ('fc.bias', 'bn.weight', 'token_embedding.weight',
                 'skipme.weight'):
        assert q[kept].dtype == np.float32, kept
    # dequantize_tree: expands quantized leaves, identity on plain trees
    tree = {'a': {'w': quantize_array(w)}, 'b': flat['fc.bias']}
    assert tree_is_quantized(tree) and not tree_is_quantized(flat)
    out = dequantize_tree(tree)
    assert out['a']['w'].dtype == jax.numpy.float32
    assert out['b'] is tree['b']          # untouched leaf, same object
    # the structural-identity proof: on a plain tree the compiled
    # program contains NO convert/multiply from the dequant seam
    plain = {'w': flat['fc.weight'], 'b': flat['fc.bias']}

    def fwd(p, x):
        p = dequantize_tree(p)
        return x @ p['w'] + p['b']

    x = rng.randn(2, 16).astype(np.float32)
    jx = jax.make_jaxpr(fwd)(plain, x)
    assert 'convert' not in str(jx)
    # and on a quantized tree the SAME forward computes the dequantized
    # matmul
    qplain = {'w': quantize_array(flat['fc.weight']), 'b': plain['b']}
    np.testing.assert_allclose(
        np.asarray(jax.jit(fwd)(qplain, x)),
        x @ np.asarray(qplain['w'].dequantize()) + plain['b'], rtol=1e-5)


def test_int8_scale_table_roundtrip(tmp_path):
    """The checkpoint-adjacent calibration store: derived scales pin to
    <ckpt>.int8-scales.npz, load back bit-identical, and
    load_torch_checkpoint consumes a pinned table automatically on the
    int8 lane (same quantized bytes as the derived path — the table is
    the derived scales made explicit)."""
    from video_features_tpu.ops.quant import (
        derive_scales, load_scale_table, save_scale_table,
        scale_table_path,
    )
    from video_features_tpu.transplant.torch2jax import (
        load_torch_checkpoint, save_transplanted,
    )
    rng = np.random.RandomState(1)
    params = {'conv': {'weight': rng.randn(3, 3, 4, 8).astype(np.float32),
                       'bias': rng.randn(8).astype(np.float32)}}
    ckpt = str(tmp_path / 'model.npz')
    save_transplanted(params, ckpt)
    flat = {'conv.weight': params['conv']['weight'],
            'conv.bias': params['conv']['bias']}
    scales = derive_scales(flat)
    assert set(scales) == {'conv.weight'}
    table = scale_table_path(ckpt)
    assert table == f'{ckpt}.int8-scales.npz'
    save_scale_table(table, scales, meta={'measured_rel_l2': '1e-2'})
    loaded = load_scale_table(table)
    np.testing.assert_array_equal(loaded['conv.weight'],
                                  scales['conv.weight'])
    assert load_scale_table(str(tmp_path / 'absent.npz')) == {}
    # the int8 load path consumes the pinned table
    from video_features_tpu.ops.quant import QuantizedTensor
    loaded_params = load_torch_checkpoint(ckpt, dtype=np.int8)
    qt = loaded_params['conv']['weight']
    assert isinstance(qt, QuantizedTensor)
    np.testing.assert_array_equal(np.asarray(qt.scale).ravel(),
                                  scales['conv.weight'].ravel())
    assert loaded_params['conv']['bias'].dtype == np.float32


@pytest.fixture(scope='module')
def _lane_worklist(tmp_path_factory):
    """Two clips, one cache directory, and the float32 lane's packed run
    over them — what each fast lane below is held against."""
    from tools.make_sample_video import write_noise_clip
    root = tmp_path_factory.mktemp('lanes')
    clips = [str(write_noise_clip(root / f'clip{i}.mp4', n, seed=i))
             for i, n in enumerate((9, 5))]

    def build(lane):
        from video_features_tpu.config import load_config
        from video_features_tpu.registry import create_extractor
        return create_extractor(load_config('resnet', overrides={
            'video_paths': clips, 'device': 'cpu', 'model_name': 'resnet18',
            'batch_size': 4, 'allow_random_weights': True,
            'compute_dtype': lane, 'pack_across_videos': True,
            'on_extraction': 'save_numpy',
            'output_path': str(root / f'out_{lane}'),
            'tmp_path': str(root / f'tmp_{lane}'),
            'cache_enabled': True, 'cache_dir': str(root / 'feature_cache'),
            'manifest_out': str(root / f'manifest_{lane}.json')}))

    f32 = build('float32')
    f32.extract_packed(clips)
    assert f32.cache.stats()['puts'] == len(clips)
    return clips, build, _npy_files(f32.output_path)


def _npy_files(root):
    from pathlib import Path
    return {f.name: f.read_bytes() for f in sorted(Path(root).rglob('*.npy'))}


@pytest.mark.parametrize('lane, bounds', [
    ('bfloat16', BF16_REL_L2_BOUNDS), ('int8', INT8_REL_L2_BOUNDS)],
    ids=['bfloat16', 'int8'])
def test_fast_lane_run_never_shares_a_cache_key_and_names_itself(
        lane, bounds, _lane_worklist, tmp_path):
    """A fast lane's packed run over a cache the float32 lane just filled
    is COLD (no cross-lane hit: the lanes' keys never collide), its
    features differ from float32's yet land under the family's pinned
    bound, its manifest names the lane (config, every executable, the
    ``mesh1@<lane>`` pinned hashes), and the lane's own re-run is served
    from the cache byte for byte."""
    import io
    import json
    from pathlib import Path

    from video_features_tpu.parallel.packing import VideoTask
    clips, build, f32_files = _lane_worklist
    ex = build(lane)
    hits_before = ex.cache.stats()['hits']     # the store's, all lanes'
    ex.extract_packed(clips)
    ex.finish_obs()
    assert ex.cache.stats()['hits'] == hits_before
    fast_files = _npy_files(ex.output_path)
    assert set(fast_files) == set(f32_files)
    feats = sorted(n for n in f32_files
                   if not n.endswith(('_fps.npy', '_timestamps_ms.npy')))
    assert feats and any(f32_files[n] != fast_files[n] for n in feats)

    def rows(files):
        return np.concatenate([np.load(io.BytesIO(files[n])).ravel()
                               for n in feats])
    assert rel_l2(rows(f32_files), rows(fast_files)) <= bounds['resnet']

    man = json.loads(Path(ex.manifest_out).read_text())
    assert man['config']['compute_dtype'] == lane
    assert man['executables'], 'packed run recorded no executables'
    assert all(v.get('compute_dtype') == lane
               for v in man['executables'].values()), man['executables']
    assert f'mesh1@{lane}' in man['programs_lock']['resnet']

    again = tmp_path / 'again'
    ex.extract_packed([VideoTask(p, out_root=str(again)) for p in clips])
    assert ex.cache.stats()['hits'] == hits_before + len(clips)
    assert _npy_files(again) == fast_files


@pytest.mark.slow
@pytest.mark.parametrize('ft', sorted(_BF16_CASES))
def test_bf16_lane_parity_all_families(ft, tmp_path, _f32_reference):
    """The full bf16 lane gate, one family per case: real extractor
    builds (fp32 reference shared module-wide), identical inputs,
    measured rel-L2 under the pinned bound, all-bf16 params census,
    float32 feature outputs."""
    _assert_lane_contract(ft, 'bfloat16', str(tmp_path),
                          _f32_reference(ft))


@pytest.mark.slow
@pytest.mark.parametrize('ft', sorted(INT8_REL_L2_BOUNDS))
def test_int8_lane_parity_all_families(ft, tmp_path, _f32_reference):
    """The full int8 lane gate for every accepting family: real builds
    (fp32 reference shared with the bf16 ladder above), identical
    inputs, measured rel-L2 under the pinned INT8_REL_L2_BOUNDS entry,
    int8-majority params census, float32 feature outputs."""
    _assert_lane_contract(ft, 'int8', str(tmp_path), _f32_reference(ft))


def test_iter_early_pin_structurally_sound():
    """iter_early splits the GRU scan; on CPU (fp32 everywhere) the split
    must be bit-identical to the single scan, for any split point."""
    import jax

    from video_features_tpu.models import raft as raft_model
    from video_features_tpu.transplant.torch2jax import transplant

    params = transplant(raft_model.init_state_dict())
    rng = np.random.RandomState(0)
    f1 = (rng.rand(1, 64, 64, 3) * 255).astype(np.float32)
    f2 = (rng.rand(1, 64, 64, 3) * 255).astype(np.float32)
    with jax.default_matmul_precision('highest'):
        base = np.asarray(raft_model.forward(params, f1, f2, iters=6))
        for n in (0, 3, 6, 99):
            split = np.asarray(raft_model.forward(
                params, f1, f2, iters=6,
                pins=(('iter_early', f'default:{n}'),)))
            np.testing.assert_array_equal(split, base)
