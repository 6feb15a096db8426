"""RAFT's correlation lookup: the lanes kernel and the dense fallback
against the XLA gather oracle, and the one function that chooses between them.

Every lookup must reproduce the reference's semantics exactly
(reference models/raft/raft_src/corr.py:29-50 + utils/utils.py:58-72:
zeros padding, align_corners bilinear, dy-major window ordering), which the
gather path in models/raft.py verifies against torch
(tests/test_raft_model.py). CPU runs use interpret mode — the same kernel
body the TPU compiles. The small-shape comparisons run in tier-1; the
full-forward ones are ``slow``.
"""
import numpy as np
import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402

from video_features_tpu.models import raft  # noqa: E402
from video_features_tpu.ops import pallas_corr  # noqa: E402


def _random_pyramid(rng, n, h, w, levels=4):
    pyr = []
    for i in range(levels):
        hi, wi = max(h >> i, 1), max(w >> i, 1)
        pyr.append(jnp.asarray(rng.randn(n, hi, wi, 1).astype(np.float32)))
    return pyr


@pytest.mark.parametrize('h,w', [(12, 9), (13, 9)])
def test_lanes_matches_gather(h, w):
    """Lane-packed mask-reduce kernel (interpret mode) == the gather oracle
    at even and odd sizes, a pair count off the 128-lane tile, and centroids
    in range, fractional and far outside the map (zeros padding)."""
    rng = np.random.RandomState(0)
    b = 2
    pyr = _random_pyramid(rng, b * h * w, h, w)
    coords = rng.uniform(-9, max(h, w) + 9, size=(b, h, w, 2))
    coords = jnp.asarray(coords.astype(np.float32))

    ref = raft.lookup_corr(pyr, coords)
    got = pallas_corr.lookup_corr_lanes(pallas_corr.prep_pyramid_lanes(pyr),
                                        coords, interpret=True)
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_lanes_integer_coords_exact():
    """Integer coords hit map values exactly (weights 0, no blending)."""
    rng = np.random.RandomState(1)
    h = w = 8
    n = h * w
    pyr = _random_pyramid(rng, n, h, w, levels=1)
    yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing='ij')
    coords = jnp.asarray(
        np.stack([xx, yy], -1)[None].astype(np.float32))

    got = np.asarray(pallas_corr.lookup_corr_lanes(
        pallas_corr.prep_pyramid_lanes(pyr), coords, interpret=True))
    corr = np.asarray(pyr[0])[..., 0]
    # window element (i=r, j=r) — zero offset — is flat index r·9 + r
    center = got[0].reshape(h, w, 81)[..., 4 * 9 + 4]
    want = corr[np.arange(n).reshape(h, w), yy, xx]
    np.testing.assert_array_equal(center, want)


def test_dense_matches_gather():
    """The MXU-friendly dense lookup — what runs wherever the kernel does
    not — must equal the gather oracle, including zeros-padding at
    out-of-map coords (reference corr.py:29-50 semantics)."""
    rng = np.random.RandomState(0)
    B, H8, W8, D = 6, 12, 9, 32
    f1 = jnp.asarray(rng.randn(B, H8, W8, D).astype(np.float32))
    f2 = jnp.asarray(rng.randn(B, H8, W8, D).astype(np.float32))
    py = raft.build_corr_pyramid(f1, f2)
    # coords spill past every edge to exercise the zero-weight region
    coords = jnp.asarray(
        (rng.rand(B, H8, W8, 2) * [W8 * 1.6, H8 * 1.6]
         - [W8 * 0.3, H8 * 0.3]).astype(np.float32))
    with jax.default_matmul_precision('highest'):
        a = np.asarray(raft.lookup_corr(py, coords))
        b = np.asarray(raft.lookup_corr_dense(py, coords))
    assert a.shape == b.shape
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_prep_fused_matches_two_step():
    """prep_pyramid_lanes_fused ≡ build_corr_pyramid → prep_pyramid_lanes
    at every level (the transpose-free prep the fused step runs).
    Tolerance is fp reassociation noise only: the einsum contracts in a
    different order."""
    rng = np.random.RandomState(0)
    B, H, W, D = 3, 8, 11, 16     # odd W exercises the valid-pool crop
    f1 = jnp.asarray(0.1 * rng.randn(B, H, W, D).astype(np.float32))
    f2 = jnp.asarray(0.1 * rng.randn(B, H, W, D).astype(np.float32))
    two_step = pallas_corr.prep_pyramid_lanes(raft.build_corr_pyramid(f1, f2))
    fused = pallas_corr.prep_pyramid_lanes_fused(f1, f2)
    assert len(two_step) == len(fused)
    for i, (a, b) in enumerate(zip(two_step, fused)):
        assert a.shape == b.shape, (i, a.shape, b.shape)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-6, err_msg=f'level {i}')


@pytest.mark.parametrize('h8, w8, platform, want', [
    (28, 28, 'tpu', 'lanes'),     # a 224² crop
    (32, 43, 'tpu', 'lanes'),     # the shipped i3d geometry, 256×344
    (28, 28, 'cpu', 'dense'),     # off the TPU the kernel would interpret
    (135, 240, 'tpu', 'dense'),   # 1080p: level 0 is over the VMEM budget
])
def test_resolve_lookup_decides_from_shape_and_platform(
        monkeypatch, h8, w8, platform, want):
    monkeypatch.delenv('VFT_RAFT_LOOKUP', raising=False)
    assert raft.resolve_lookup(h8, w8, platform) == want


def test_lookup_switch_dense_is_honoured_on_tpu(monkeypatch):
    """README "Mesh-sharded packed execution": the operator's way to run
    i3d on more than one chip until the kernel is wrapped in shard_map."""
    monkeypatch.setenv('VFT_RAFT_LOOKUP', 'dense')
    assert raft.resolve_lookup(32, 43, 'tpu') == 'dense'


@pytest.mark.parametrize('value', ['pallas', 'lane'])
def test_lookup_switch_refuses_what_it_does_not_know(monkeypatch, value):
    """A removed or misspelt value raises and names the four that exist;
    it must not fall through to some lookup without a word."""
    monkeypatch.setenv('VFT_RAFT_LOOKUP', value)
    with pytest.raises(ValueError) as err:
        raft.resolve_lookup(32, 43, 'tpu')
    for name in ('auto', 'dense', 'gather', 'lanes'):
        assert repr(name) in str(err.value)
    assert repr(value) in str(err.value)


@pytest.mark.slow
@pytest.mark.parametrize('impl', ['dense', 'lanes'])
def test_forward_matches_gather_oracle(monkeypatch, impl):
    """Full RAFT forward: each lookup that runs == the gather oracle."""
    from video_features_tpu.transplant.torch2jax import transplant
    params = transplant(raft.init_state_dict(seed=0))
    rng = np.random.RandomState(2)
    # ≥64px so the coarsest of the 4 pyramid levels is still non-empty
    img1 = jnp.asarray(rng.randint(0, 255, (1, 64, 80, 3)).astype(np.float32))
    img2 = jnp.asarray(rng.randint(0, 255, (1, 64, 80, 3)).astype(np.float32))
    monkeypatch.setenv('VFT_RAFT_LOOKUP', 'gather')
    ref = np.asarray(raft.forward(params, img1, img2, iters=3))
    monkeypatch.setenv('VFT_RAFT_LOOKUP', impl)
    got = np.asarray(raft.forward(params, img1, img2, iters=3))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def _load_validate_lanes():
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        'validate_lanes',
        Path(__file__).resolve().parents[1] / 'tools' / 'validate_lanes.py')
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow
def test_lanes_full_depth_interpret():
    """The production lanes kernel at FULL 20-iteration depth (reduced
    geometry, interpret mode): a depth-dependent kernel regression —
    accumulated window drift steering later lookups off course — fails
    automation here, not a human remembering tools/validate_lanes.py."""
    vl = _load_validate_lanes()
    # smallest geometry whose 4-level pyramid keeps every level nonzero
    # (H/8 must be ≥ 8 so level 3 is ≥ 1 pixel)
    rels = vl.measure_drift(h=64, w=88, impls=('dense', 'lanes'),
                            iters=20, platform='cpu')
    assert rels['lanes'] < 1e-3, rels


@pytest.mark.slow
@pytest.mark.tpu
def test_lanes_full_depth_tpu():
    """The same full-depth validation on real TPU hardware at CLI geometry
    (the compiled Mosaic kernel, not interpret mode) — the kernel and the
    gather oracle against the matmul lookup:
    `VFT_TEST_PLATFORM=native pytest -m tpu` (conftest skips this test
    in the hermetic lane; here a missing TPU fails)."""
    assert jax.devices()[0].platform == 'tpu', jax.devices()
    vl = _load_validate_lanes()
    rels = vl.measure_drift(impls=('dense', 'lanes', 'gather'))
    for impl, rel in rels.items():
        assert rel < 1e-3, rels
