"""``ops/nn.py::conv_space_to_depth`` against ``conv``: the same products and
sums in another order, for any stride, kernel and padding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from video_features_tpu.models.i3d import tf_same_pads
from video_features_tpu.ops.nn import conv, conv_space_to_depth

I3D_PADS = tf_same_pads((7, 7, 7), (2, 2, 2))

CASES = {
    # the I3D stems at a cut geometry
    'i3d_rgb': ((2, 8, 32, 32, 3), (7, 7, 7, 3, 64), 2, I3D_PADS, False),
    'i3d_flow': ((2, 8, 32, 32, 2), (7, 7, 7, 2, 64), 2, I3D_PADS, False),
    'i3d_rgb_bias': ((2, 8, 32, 32, 3), (7, 7, 7, 3, 64), 2, I3D_PADS, True),
    # odd sizes: the padded length is odd, so the fold needs one more zero
    'odd_sizes': ((2, 9, 31, 33, 3), (7, 7, 7, 3, 16), 2, I3D_PADS, False),
    'odd_sizes_bias': ((1, 9, 31, 33, 2), (7, 7, 7, 2, 16), 2, I3D_PADS, True),
    # a 2-D 7×7 stride-2 stem with torch's symmetric padding
    'conv2d_7x7': ((2, 31, 33, 3), (7, 7, 3, 16), 2, 3, False),
    'conv2d_7x7_bias': ((2, 32, 32, 3), (7, 7, 3, 16), 2, 3, True),
    # one dimension at stride 1 among strided ones
    'mixed_strides': ((2, 6, 11, 12, 3), (3, 3, 3, 3, 8), (1, 2, 2), 1, False),
    # a kernel smaller than its stride: the high edge is cut, not padded
    'kernel_under_stride': ((2, 10, 12, 3), (2, 2, 3, 8), 3, 0, True),
    'kernel_one': ((2, 10, 12, 3), (1, 1, 3, 8), 2, 0, False),
    'conv1d': ((2, 37, 4), (5, 4, 8), 3, [(1, 2)], False),
}


def _inputs(x_shape, k_shape, bias):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*x_shape), jnp.float32)
    kernel = jnp.asarray(rng.randn(*k_shape) * 0.05, jnp.float32)
    b = jnp.asarray(rng.randn(k_shape[-1]), jnp.float32) if bias else None
    return x, kernel, b


@pytest.mark.parametrize('case', sorted(CASES))
def test_matches_conv(case):
    x_shape, k_shape, stride, padding, bias = CASES[case]
    x, kernel, b = _inputs(x_shape, k_shape, bias)
    with jax.default_matmul_precision('highest'):
        want = conv(x, kernel, stride, padding, bias=b)
        got = conv_space_to_depth(x, kernel, stride, padding, bias=b)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize('bias', [False, True])
def test_stride_one_falls_through_unchanged(bias):
    """Nothing to fold: the very program ``conv`` lowers to."""
    x, kernel, b = _inputs((2, 10, 12, 3), (3, 3, 3, 8), bias)
    args = (x, kernel) if b is None else (x, kernel, b)

    def lowered(fn):
        return jax.jit(lambda x, k, b=None: fn(x, k, 1, 1, bias=b)).lower(
            *args).as_text()

    assert lowered(conv_space_to_depth) == lowered(conv)
    np.testing.assert_array_equal(
        np.asarray(conv_space_to_depth(x, kernel, 1, 1, bias=b)),
        np.asarray(conv(x, kernel, 1, 1, bias=b)))


@pytest.mark.parametrize('modality,channels', [('rgb', 3), ('flow', 2)])
def test_i3d_stem_is_folded_and_named(modality, channels):
    """``models/i3d.py::forward`` runs its stem at stride 1 over more than
    ``channels`` channels, under the ``i3d_stem`` scope the trace is read
    by; every other convolution of the tower is ``conv`` as before."""
    from video_features_tpu.models import i3d as i3d_model
    from video_features_tpu.transplant.torch2jax import transplant
    params = jax.eval_shape(
        lambda: transplant(i3d_model.init_state_dict(modality=modality)))
    x = jax.ShapeDtypeStruct((1, 16, 64, 64, channels), jnp.float32)
    text = jax.jit(i3d_model.forward).lower(params, x).as_text(
        debug_info=True)
    convs = [line for line in text.splitlines()
             if 'stablehlo.convolution' in line]
    assert len(convs) == 57            # one a unit: none added, none lost
    assert 'stride = [1, 1, 1]' in convs[0]
    assert not any('tensor<7x7x7x' in line for line in convs)
    assert f'x{channels}xf32>, tensor<' not in convs[0]
    assert text.count('/i3d_stem/conv_general_dilated"') == 1
