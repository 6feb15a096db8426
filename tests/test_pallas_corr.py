"""Pallas correlation-lookup kernel vs the XLA gather path.

The kernel must reproduce the reference lookup semantics exactly
(reference models/raft/raft_src/corr.py:29-50 + utils/utils.py:58-72:
zeros padding, align_corners bilinear, dy-major window ordering), which the
XLA path in models/raft.py already verifies against torch. CPU runs use
interpret mode — the same kernel body the TPU compiles.
"""
import numpy as np
import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402

from video_features_tpu.models import raft  # noqa: E402
from video_features_tpu.ops import pallas_corr  # noqa: E402

pytestmark = pytest.mark.slow  # parity/e2e/sharding: full lane only



def _random_pyramid(rng, n, h, w, levels=4):
    pyr = []
    for i in range(levels):
        hi, wi = max(h >> i, 1), max(w >> i, 1)
        pyr.append(jnp.asarray(rng.randn(n, hi, wi, 1).astype(np.float32)))
    return pyr


@pytest.mark.parametrize('h,w', [(8, 12), (13, 9)])
def test_lookup_matches_xla(h, w):
    rng = np.random.RandomState(0)
    b = 2
    n = b * h * w
    pyr = _random_pyramid(rng, n, h, w)
    # centroids spanning in-range, fractional, and far out-of-range coords
    coords = rng.uniform(-9, max(h, w) + 9, size=(b, h, w, 2))
    coords = jnp.asarray(coords.astype(np.float32))

    ref = raft.lookup_corr(pyr, coords)
    got = pallas_corr.lookup_corr(pallas_corr.prep_pyramid(pyr, 4), coords,
                                  interpret=True)
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_lookup_integer_coords_exact():
    """Integer coords hit map values exactly (weights 0, no blending)."""
    rng = np.random.RandomState(1)
    h = w = 8
    n = h * w
    pyr = _random_pyramid(rng, n, h, w, levels=1)
    yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing='ij')
    coords = jnp.asarray(
        np.stack([xx, yy], -1)[None].astype(np.float32))

    got = np.asarray(pallas_corr.lookup_corr(
        pallas_corr.prep_pyramid(pyr, 4), coords, interpret=True))
    corr = np.asarray(pyr[0])[..., 0]
    # window element (i=r, j=r) — zero offset — is flat index r·9 + r
    center = got[0].reshape(h, w, 81)[..., 4 * 9 + 4]
    want = corr[np.arange(n).reshape(h, w), yy, xx]
    np.testing.assert_allclose(center, want, rtol=1e-6, atol=1e-6)


def test_forward_with_all_lookup_impls(monkeypatch):
    """Full RAFT forward: gather oracle == dense == pallas end-to-end."""
    sd = raft.init_state_dict(seed=0)
    from video_features_tpu.transplant.torch2jax import transplant
    params = transplant(sd)
    rng = np.random.RandomState(2)
    # ≥64px so the coarsest of the 4 pyramid levels is still non-empty
    img1 = jnp.asarray(rng.randint(0, 255, (1, 64, 80, 3)).astype(np.float32))
    img2 = jnp.asarray(rng.randint(0, 255, (1, 64, 80, 3)).astype(np.float32))

    monkeypatch.delenv('VFT_RAFT_PALLAS', raising=False)
    monkeypatch.setenv('VFT_RAFT_LOOKUP', 'gather')
    ref = np.asarray(raft.forward(params, img1, img2, iters=3))
    for impl in ('dense', 'pallas'):
        monkeypatch.setenv('VFT_RAFT_LOOKUP', impl)
        got = np.asarray(raft.forward(params, img1, img2, iters=3))
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4,
                                   err_msg=impl)


def test_lanes_lookup_matches_gather_oracle():
    """Lane-packed mask-reduce kernel (interpret mode): identical to the
    gather oracle, incl. zeros padding at out-of-map coords."""
    from video_features_tpu.ops import pallas_corr

    rng = np.random.RandomState(1)
    B, H8, W8, D = 4, 12, 9, 32
    f1 = jnp.asarray(rng.randn(B, H8, W8, D).astype(np.float32))
    f2 = jnp.asarray(rng.randn(B, H8, W8, D).astype(np.float32))
    py = raft.build_corr_pyramid(f1, f2)
    coords = jnp.asarray(
        (rng.rand(B, H8, W8, 2) * [W8 * 1.6, H8 * 1.6]
         - [W8 * 0.3, H8 * 0.3]).astype(np.float32))
    ref = np.asarray(raft.lookup_corr(py, coords))
    prepped = pallas_corr.prep_pyramid_lanes(py)
    got = np.asarray(pallas_corr.lookup_corr_lanes(prepped, coords,
                                                   interpret=True))
    np.testing.assert_allclose(got, ref, atol=1e-4)


def test_forward_with_lanes_lookup(monkeypatch):
    """Full RAFT forward with the lanes lookup == the gather oracle."""
    sd = raft.init_state_dict(seed=0)
    from video_features_tpu.transplant.torch2jax import transplant
    params = transplant(sd)
    rng = np.random.RandomState(2)
    img1 = jnp.asarray(rng.randint(0, 255, (1, 64, 80, 3)).astype(np.float32))
    img2 = jnp.asarray(rng.randint(0, 255, (1, 64, 80, 3)).astype(np.float32))

    monkeypatch.delenv('VFT_RAFT_PALLAS', raising=False)
    monkeypatch.setenv('VFT_RAFT_LOOKUP', 'gather')
    ref = np.asarray(raft.forward(params, img1, img2, iters=3))
    monkeypatch.setenv('VFT_RAFT_LOOKUP', 'lanes')
    got = np.asarray(raft.forward(params, img1, img2, iters=3))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_auto_lookup_dispatch(monkeypatch):
    """Default dispatch: lanes on TPU within the VMEM budget, dense
    otherwise (non-TPU backends and oversized level-0 blocks)."""
    monkeypatch.delenv('VFT_RAFT_PALLAS', raising=False)
    monkeypatch.delenv('VFT_RAFT_LOOKUP', raising=False)
    assert raft._lookup_impl() == 'auto'

    assert raft._resolve_auto_lookup(28, 28, 'tpu') == 'lanes'   # fused i3d
    assert raft._resolve_auto_lookup(28, 28, 'cpu') == 'dense'   # off-TPU
    assert raft._resolve_auto_lookup(135, 240, 'tpu') == 'dense'  # 1080p L0
    monkeypatch.setenv('VFT_RAFT_LANES_VMEM_MB', '64')
    assert raft._resolve_auto_lookup(135, 240, 'tpu') == 'lanes'
    monkeypatch.delenv('VFT_RAFT_LANES_VMEM_MB')


def _load_validate_lanes():
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        'validate_lanes',
        Path(__file__).resolve().parents[1] / 'tools' / 'validate_lanes.py')
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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


@pytest.mark.tpu
def test_lanes_full_depth_tpu():
    """The same full-depth validation on real TPU hardware at CLI geometry
    (the compiled Mosaic kernels, not interpret mode) — both Pallas
    lookups and the gather oracle against the matmul lookup:
    `VFT_TEST_PLATFORM=native pytest -m tpu` (conftest skips this test
    in the hermetic lane; here a missing TPU fails)."""
    assert jax.devices()[0].platform == 'tpu', jax.devices()
    vl = _load_validate_lanes()
    rels = vl.measure_drift(impls=('dense', 'lanes', 'gather', 'pallas'))
    for impl, rel in rels.items():
        assert rel < 1e-3, rels


def test_prep_fused_matches_two_step():
    """prep_pyramid_lanes_fused ≡ build_corr_pyramid → prep_pyramid_lanes
    at every level (the round-5 transpose-free prep — 106 → 75 ms on v5e
    at batch-16 CLI geometry). Tolerance is fp reassociation noise only:
    the einsum contracts in a different order."""
    from video_features_tpu.models.raft import build_corr_pyramid
    from video_features_tpu.ops.pallas_corr import (
        prep_pyramid_lanes, prep_pyramid_lanes_fused,
    )

    rng = np.random.RandomState(0)
    B, H, W, D = 3, 8, 11, 16     # odd W exercises the valid-pool crop
    f1 = jnp.asarray(0.1 * rng.randn(B, H, W, D).astype(np.float32))
    f2 = jnp.asarray(0.1 * rng.randn(B, H, W, D).astype(np.float32))
    two_step = prep_pyramid_lanes(build_corr_pyramid(f1, f2))
    fused = prep_pyramid_lanes_fused(f1, f2)
    assert len(two_step) == len(fused)
    for i, (a, b) in enumerate(zip(two_step, fused)):
        assert a.shape == b.shape, (i, a.shape, b.shape)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-6, err_msg=f'level {i}')
