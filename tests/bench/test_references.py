"""Each plain reference against the program, at a tiny spatial size and the
full published width, on seeded weights in the checkpoint layout the program
loads. One module-scoped build per family.

The last tests are the *control* of "How correct is decided", kept at a size a
test run can hold: the reference put in the program's place and computed in
the nearest precision below the configuration's (bfloat16 operands, float32
accumulation — what one MXU pass does) has to read far above what the program
reads, by the comparison's own measure."""
import jax
import numpy as np
import pytest

import compare
import loader
import weights
from _layers import Ops

SEED = 2 ** 31 + 24        # the driver's seeds are larger than 32 signed bits


def _program_params(flat):
    """The program's view of a benchmark-made checkpoint: written to the
    .npz layout and read back by the program's own loader."""
    import tempfile
    from video_features_tpu.transplant.torch2jax import load_torch_checkpoint
    with tempfile.TemporaryDirectory() as d:
        return load_torch_checkpoint(weights.save(flat, f'{d}/w.npz'))


# -- resnet50 ----------------------------------------------------------------

@pytest.fixture(scope='module')
def resnet():
    ref = loader.load_module('references', 'resnet50-framewise')
    flat = weights.make(ref.param_specs()['checkpoint_path'], SEED,
                        'checkpoint_path')
    frames = np.random.RandomState(0).randint(
        0, 256, (2, 64, 64, 3)).astype(np.uint8)
    ops = Ops()
    want = np.asarray(jax.jit(lambda p, u: ref.forward(
        ops, {'checkpoint_path': p}, u))(flat, frames))
    return ref, flat, frames, want


def test_resnet50_reference_matches_the_program(resnet):
    from video_features_tpu.extract.resnet import ExtractResNet
    ref, flat, frames, want = resnet
    with jax.default_matmul_precision('highest'):
        got = np.asarray(jax.jit(
            lambda p, b: ExtractResNet._forward(p, b, 'resnet50'))(
                _program_params(flat), frames))
    assert got.shape == want.shape == (2, 2048)
    assert np.isfinite(want).all() and want.std() > 0
    assert compare.rel_l2(got, want) < 1e-5


def test_resnet50_checkpoint_layout_is_complete(resnet):
    """Every parameter the program's own random initialiser has, the
    benchmark's maker has, in the program's layout."""
    from video_features_tpu.models import resnet as model
    from video_features_tpu.transplant.torch2jax import _flatten, transplant
    theirs = _flatten(transplant(model.init_state_dict(0, 'resnet50')))
    ours = resnet[1]
    assert set(ours) == set(theirs)
    assert all(ours[k].shape == theirs[k].shape for k in ours)


def test_resnet50_control_reads_far_above_the_program(resnet):
    ref, flat, frames, want = resnet
    ops = Ops('bfloat16')
    control = np.asarray(jax.jit(lambda p, u: ref.forward(
        ops, {'checkpoint_path': p}, u))(flat, frames))
    assert compare.rel_l2(control, want) > 1e-3


# -- i3d two-stream with raft --------------------------------------------------

@pytest.fixture(scope='module')
def i3d():
    ref = loader.load_module('references', 'i3d-two-stream-raft')
    flat = {k: weights.make(s, SEED, k) for k, s in ref.param_specs().items()}
    prog = {k: _program_params(v) for k, v in flat.items()}
    return ref, flat, prog


def _smooth_frames(n, h, w, seed=0):
    """A smooth picture drifting by (2, 1) px a frame: what RAFT can track."""
    import cv2
    rs = np.random.RandomState(seed)
    base = cv2.GaussianBlur((rs.rand(h + 2 * n, w + 4 * n, 3) * 255)
                            .astype(np.float32), (0, 0), 3)
    base = (base - base.min()) / (base.max() - base.min()) * 255
    return np.stack([base[t:t + h, 2 * t:2 * t + w] for t in range(n)]
                    ).astype(np.uint8)


def test_i3d_tower_reference_matches_the_program(i3d):
    from video_features_tpu.models import i3d as model
    ref, flat, prog = i3d
    x = np.random.RandomState(1).rand(1, 16, 64, 64, 3).astype(np.float32) \
        * 2 - 1
    with jax.default_matmul_precision('highest'):
        got = np.asarray(jax.jit(model.forward)(
            prog['i3d_rgb_checkpoint_path'], x))
    ops = Ops()
    want = np.asarray(jax.jit(lambda p, u: ref.i3d_tower(ops, p, u))(
        flat['i3d_rgb_checkpoint_path'], x))
    assert got.shape == want.shape == (1, 1024)
    assert compare.rel_l2(got, want) < 1e-5


def test_raft_reference_matches_the_program(i3d):
    from video_features_tpu.models import raft as model
    ref, flat, prog = i3d
    frames = _smooth_frames(2, 64, 80)
    with jax.default_matmul_precision('highest'):
        got = np.asarray(jax.jit(lambda p, s: model.forward_stack_pairs(
            p, s, platform='cpu'))(prog['raft_checkpoint_path'],
                                   frames[None]))[0]
    ops = Ops()
    want = np.asarray(jax.jit(lambda p, a, b: ref.raft_flow(ops, p, a, b))(
        flat['raft_checkpoint_path'], frames[:1], frames[1:]))
    assert got.shape == want.shape == (1, 64, 80, 2)
    # twenty recurrent updates amplify float32 rounding; 3e-5 measured
    assert compare.rel_l2(got.reshape(1, -1), want.reshape(1, -1)) < 1e-3
    # the field stays off the +-20 px clamp of the flow quantisation
    assert np.abs(want).max() < 20.0


def test_raft_checkpoint_layout_is_complete(i3d):
    from video_features_tpu.models import i3d as i3d_model
    from video_features_tpu.models import raft as raft_model
    from video_features_tpu.transplant.torch2jax import _flatten, transplant
    ref, flat, prog = i3d
    for key, theirs in (
            ('raft_checkpoint_path', raft_model.init_state_dict(0)),
            ('i3d_rgb_checkpoint_path', i3d_model.init_state_dict(0, 'rgb')),
            ('i3d_flow_checkpoint_path',
             i3d_model.init_state_dict(0, 'flow'))):
        theirs = _flatten(transplant(theirs))
        assert set(flat[key]) == set(theirs), key
        assert all(flat[key][k].shape == theirs[k].shape for k in theirs)


@pytest.fixture(scope='module')
def two_stream(i3d):
    """The whole recipe — pad to 8, RAFT on the pairs of a stack, crop of the
    padded flow, clamp, quantise, both towers, concat — at 72x90 frames with
    a 64 crop (odd pads on both axes), program and reference."""
    from video_features_tpu.extract.i3d import fused_two_stream_step
    from video_features_tpu.models import raft as raft_model
    ref, flat, prog = i3d
    stacks = _smooth_frames(17, 72, 90, seed=2)[None]       # (1, 16+1, ..)
    pads = tuple(raft_model.pad_to_multiple(
        np.zeros((1, 72, 90, 1), np.float32))[1])
    assert pads == ref.pad_to_8(72, 90)
    params = {'rgb': prog['i3d_rgb_checkpoint_path'],
              'flow': prog['i3d_flow_checkpoint_path'],
              'raft': prog['raft_checkpoint_path']}
    with jax.default_matmul_precision('highest'):
        out = jax.jit(lambda p, s: fused_two_stream_step(
            p, s, pads=pads, streams=('rgb', 'flow'), crop_size=64,
            platform='cpu'))(params, stacks)
    got = np.concatenate([np.asarray(out['rgb']), np.asarray(out['flow'])],
                         axis=1)
    crop, ref.CROP = ref.CROP, 64
    try:
        rows = {}
        for mode in ('highest', 'bfloat16'):
            ops = Ops(mode)
            rows[mode] = np.asarray(jax.jit(
                lambda p, u: ref.forward(ops, p, u))(flat, stacks))
    finally:
        ref.CROP = crop
    return got, rows['highest'], rows['bfloat16']


def test_two_stream_reference_matches_the_program(two_stream):
    got, want, _ = two_stream
    assert got.shape == want.shape == (1, 2048)
    assert np.isfinite(want).all()
    # rgb half: no flow in it
    assert compare.rel_l2(got[:, :1024], want[:, :1024]) < 1e-5
    # flow half: a few uint8 levels of the quantised flow may flip
    assert compare.rel_l2(got[:, 1024:], want[:, 1024:]) < 2e-3


def test_two_stream_control_reads_far_above_the_program(two_stream):
    got, want, control = two_stream
    assert compare.rel_l2(control, want) > 3 * compare.rel_l2(got, want)
    assert compare.rel_l2(control, want) > 2e-3


@pytest.mark.parametrize('frames,rows', [(16, 0), (17, 1), (32, 1), (33, 2),
                                         (96, 5), (640, 39)])
def test_i3d_rows_of(frames, rows):
    ref = loader.load_module('references', 'i3d-two-stream-raft')
    assert ref.rows_of(frames) == rows
