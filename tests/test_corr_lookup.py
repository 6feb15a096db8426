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


# (pairs, h, w, positions a grid step may hold): even and odd sizes; a
# level-0 w of 43 (the shipped 256×344) whose levels are 43, 21, 10 and 5
# wide — none a multiple of 8; pixel counts off the 1,024-pixel tile
# (2 × 12 × 9 = 216); and planes cut into chunks of rows (36 positions a
# step: 12 × 9 in four chunks of three rows) and of one row (4 a step: a
# row of 9 in three stretches of 3), the sums carried across grid steps
SHAPES = [(2, 12, 9, None), (2, 13, 9, None), (1, 8, 43, None),
          (2, 12, 9, 36), (2, 12, 9, 4)]


def _shape_id(shape):
    b, h, w, cap = shape
    return f'{b}x{h}x{w}' + (f'-chunks{cap}' if cap else '')


def _cap(monkeypatch, positions):
    """Hold a grid step to ``positions`` of a level's plane."""
    if positions:
        monkeypatch.setattr(pallas_corr, 'BLOCK_BYTES',
                            positions * pallas_corr.TILE * 4)


@pytest.mark.parametrize('shape', SHAPES, ids=_shape_id)
def test_lanes_matches_gather(monkeypatch, shape):
    """The 1,024-pixel-tile kernel (interpret mode) == the gather oracle,
    centroids in range, fractional and far outside the map (zeros
    padding)."""
    b, h, w, cap = shape
    _cap(monkeypatch, cap)
    rng = np.random.RandomState(0)
    pyr = _random_pyramid(rng, b * h * w, h, w)
    coords = rng.uniform(-9, max(h, w) + 9, size=(b, h, w, 2))
    coords = jnp.asarray(coords.astype(np.float32))

    ref = raft.lookup_corr(pyr, coords)
    got = pallas_corr.lookup_corr_lanes(
        pallas_corr.prep_pyramid_lanes(pyr, b), coords, interpret=True)
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('side', ['left', 'right', 'top', 'bottom'])
def test_lanes_matches_gather_off_every_side(side):
    """Centroids past one edge of the plane, at and beyond the window's
    reach: the taps that fall off read zeros, the others the plane."""
    rng = np.random.RandomState(3)
    b, h, w = 2, 12, 9
    pyr = _random_pyramid(rng, b * h * w, h, w, levels=1)
    coords = rng.uniform(0, 1, size=(b, h, w, 2)) * [w - 1, h - 1]
    axis, sign = {'left': (0, -1), 'right': (0, 1), 'top': (1, -1),
                  'bottom': (1, 1)}[side]
    extent = (w, h)[axis]
    coords[..., axis] = (extent - 1) * (sign > 0) + sign * rng.uniform(
        0, 7, size=(b, h, w))
    coords = jnp.asarray(coords.astype(np.float32))
    ref = raft.lookup_corr(pyr, coords)
    got = pallas_corr.lookup_corr_lanes(
        pallas_corr.prep_pyramid_lanes(pyr, b), coords, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('shape', [(1, 8, 8, None), *SHAPES[2:]],
                         ids=_shape_id)
def test_lanes_integer_coords_exact(monkeypatch, shape):
    """Integer coords hit map values exactly (weights 0, no blending), and
    a window centre off the plane reads 0."""
    b, h, w, cap = shape
    _cap(monkeypatch, cap)
    rng = np.random.RandomState(1)
    n = b * h * w
    pyr = _random_pyramid(rng, n, h, w, levels=1)
    shift = rng.randint(-3, 4, size=(b, h, w, 2))
    yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing='ij')
    cx, cy = xx + shift[..., 0], yy + shift[..., 1]
    coords = jnp.asarray(np.stack([cx, cy], -1).astype(np.float32))

    got = np.asarray(pallas_corr.lookup_corr_lanes(
        pallas_corr.prep_pyramid_lanes(pyr, b), coords, interpret=True))
    corr = np.asarray(pyr[0])[..., 0]
    # window element (i=r, j=r) — zero offset — is flat index r·9 + r
    center = got.reshape(b, h, w, 81)[..., 4 * 9 + 4]
    inside = (cx >= 0) & (cx < w) & (cy >= 0) & (cy < h)
    want = np.where(inside, corr[np.arange(n).reshape(b, h, w),
                                 np.clip(cy, 0, h - 1), np.clip(cx, 0, w - 1)],
                    0)
    assert inside.any() and not inside.all()
    np.testing.assert_array_equal(center, want)


def _parents_level(corr, x, y, radius=4):
    """The parent's per-level formula (PR 38's ``_lanes_kernel``), written
    out: masked sums over w for each x tap, the x blend, masked sums over h
    for each y tap, the y blend. corr (N, h, w); x, y (N,) → (N, 81)."""
    p1 = 2 * radius + 1
    x0, y0 = np.floor(x), np.floor(y)
    xi, yi = x0.astype(np.int64), y0.astype(np.int64)
    fx, fy = (x - x0).astype(np.float32), (y - y0).astype(np.float32)
    n, h, w = corr.shape
    s = [np.sum(corr * (np.arange(w)[None, None, :]
                        == (xi + k - radius)[:, None, None]), axis=2)
         for k in range(p1 + 1)]                                 # (N, h)
    rows = [(1 - fx)[:, None] * s[i] + fx[:, None] * s[i + 1]
            for i in range(p1)]
    out = []
    for i in range(p1):
        v = [np.sum(rows[i] * (np.arange(h)[None, :]
                               == (yi + k - radius)[:, None]), axis=1)
             for k in range(p1 + 1)]
        out += [(1 - fy) * v[j] + fy * v[j + 1] for j in range(p1)]
    return np.stack(out, -1).astype(np.float32)


@pytest.mark.parametrize('shape', [SHAPES[2], SHAPES[4]], ids=_shape_id)
def test_lanes_is_bit_identical_to_the_parents_formula(monkeypatch, shape):
    """The kernel picks each window value as ONE element of the level where
    the parent summed it under 0/1 masks, and blends as the parent did: the
    same bits. Values and fractions on a dyadic grid (eighths) make every
    product and sum exact, so the comparison is bit for bit whatever order
    or contraction a compiler gives the blends (on the chip: equal to the
    parent's compiled kernel on random floats, PERF.md §6, PR 39)."""
    b, h, w, cap = shape
    _cap(monkeypatch, cap)
    rng = np.random.RandomState(5)
    pyr = [jnp.asarray(rng.randint(-64, 65, size=(b * h * w, h >> i, w >> i,
                                                  1)).astype(np.float32) / 8)
           for i in range(4)]
    coords = (rng.randint(-12 * 8, (max(h, w) + 12) * 8, size=(b, h, w, 2))
              / 8).astype(np.float32)
    got = np.asarray(pallas_corr.lookup_corr_lanes(
        pallas_corr.prep_pyramid_lanes(pyr, b), jnp.asarray(coords),
        interpret=True)).reshape(-1, 4, 81)
    for level, corr in enumerate(pyr):
        want = _parents_level(np.asarray(corr)[..., 0],
                              coords[..., 0].reshape(-1) / 2 ** level,
                              coords[..., 1].reshape(-1) / 2 ** level)
        np.testing.assert_array_equal(got[:, level], want,
                                      err_msg=f'level {level}')


def test_lanes_buffer_feeds_convc1_as_channels_last_would():
    """``conv_from_lanes`` over the lookup's (324, rows, 128) buffer ==
    the 1×1 convolution over its channels-last form: the same products."""
    from video_features_tpu.ops.nn import conv
    rng = np.random.RandomState(2)
    b, h, w, c, o = 3, 5, 7, 324, 16
    buf = jnp.asarray(rng.randn(c, pallas_corr.pixel_rows(b * h * w),
                                128).astype(np.float32))
    kernel = jnp.asarray(rng.randn(1, 1, c, o).astype(np.float32))
    bias = jnp.asarray(rng.randn(o).astype(np.float32))
    with jax.default_matmul_precision('highest'):
        got = pallas_corr.conv_from_lanes(buf, kernel, bias, (b, h, w))
        want = conv(pallas_corr.unpack(buf, (b, h, w)), kernel, bias=bias)
    assert got.shape == want.shape == (b, h, w, o)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


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
    two_step = pallas_corr.prep_pyramid_lanes(raft.build_corr_pyramid(f1, f2),
                                              B)
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
    (135, 240, 'tpu', 'dense'),   # 1080p: level 0 is over the plane budget
])
def test_resolve_lookup_decides_from_shape_and_platform(
        monkeypatch, h8, w8, platform, want):
    monkeypatch.delenv('VFT_RAFT_LOOKUP', raising=False)
    assert raft.resolve_lookup(h8, w8, platform) == want


@pytest.mark.parametrize('h8', [1, 2, 3, 5, 7, 8, 9, 16, 31, 32, 43, 64, 90,
                                127, 128, 135, 255, 1000, 4096, 16384])
def test_resolve_lookup_keeps_every_plane_the_128_pixel_kernel_took(
        monkeypatch, h8):
    """PR 38's rule took 'lanes' on a TPU wherever the (h8, w8, 128) f32
    block fit 8 MiB (h8·w8 ≤ 16,384) and 'dense' past it; the plane test
    answers the same at both edges, and every plane it takes is streamed
    in chunks that divide it and fit ``BLOCK_BYTES`` a grid step."""
    monkeypatch.delenv('VFT_RAFT_LOOKUP', raising=False)
    widest = 16384 // h8
    assert raft.resolve_lookup(h8, widest, 'tpu') == 'lanes'
    assert raft.resolve_lookup(h8, widest + 1, 'tpu') == 'dense'
    for w8 in {1, 43, widest}:
        hc, wc = pallas_corr.chunks(h8, w8)
        assert h8 % hc == 0 and w8 % wc == 0, (h8, w8, hc, wc)
        assert hc * wc * pallas_corr.TILE * 4 <= pallas_corr.BLOCK_BYTES


@pytest.mark.parametrize('frame, note', [
    # i3d's shipped geometry, 256×344: the whole plane a grid step
    ((256, 344), {'raft_lookup': 'lanes', 'raft_lookup_pixels': 1024,
                  'raft_lookup_h_chunks': 1, 'raft_lookup_w_chunks': 1}),
    # feature_type=raft at 720p: 90 × 160 positions, nine chunks of ten rows
    ((720, 1280), {'raft_lookup': 'lanes', 'raft_lookup_pixels': 1024,
                   'raft_lookup_h_chunks': 9, 'raft_lookup_w_chunks': 1}),
    ((1080, 1920), {'raft_lookup': 'dense'}),
])
def test_the_manifest_note_names_the_lookup_and_its_tile(monkeypatch, frame,
                                                         note):
    """The run manifest's ``kernels`` note at a frame size (padded to /8 as
    the extractors pad it): the lookup, the pixels a grid step and the
    chunks its level-0 plane is streamed in."""
    monkeypatch.delenv('VFT_RAFT_LOOKUP', raising=False)
    _, pads = raft.pad_to_multiple(np.zeros((1, *frame, 1), np.float32))
    t, b, l, r = pads
    h8, w8 = (frame[0] + t + b) // 8, (frame[1] + l + r) // 8
    assert raft.lookup_note(h8, w8, 'tpu') == note
    assert raft.lookup_note(h8, w8, 'cpu') == {'raft_lookup': 'dense'}


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
