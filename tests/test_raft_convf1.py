"""RAFT's update scan without a 2-channel tensor: the flow's two components
travel as planes, ``convf1`` reads them with W's taps folded into channels,
the flow head writes them as planes — the same products and sums as the
channel-minor form, in another order."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from video_features_tpu.models import raft
from video_features_tpu.ops import pallas_corr
from video_features_tpu.ops.nn import (conv, conv_from_planes, conv_to_planes,
                                       relu)
from video_features_tpu.transplant.torch2jax import transplant

SIZES = [(2, 32, 43), (1, 5, 7), (3, 8, 8), (1, 1, 1)]


def _ids(size):
    return 'x'.join(map(str, size))


@pytest.mark.parametrize('bias', [False, True], ids=['no_bias', 'bias'])
@pytest.mark.parametrize('size', SIZES, ids=_ids)
def test_convf1_from_planes_matches_conv(size, bias):
    """``convf1`` — 7×7 over the 2 flow components → 128, then relu — from
    planes against ``conv`` over the channel-minor tensor, at the cell's
    1/8 map, odd sizes, and a map smaller than the kernel."""
    rng = np.random.RandomState(0)
    planes = jnp.asarray(rng.randn(2, *size), jnp.float32)
    kernel = jnp.asarray(rng.randn(7, 7, 2, 128) * 0.05, jnp.float32)
    b = jnp.asarray(rng.randn(128), jnp.float32) if bias else None
    with jax.default_matmul_precision('highest'):
        want = relu(conv(jnp.moveaxis(planes, 0, -1), kernel, padding=3,
                         bias=b))
        got = relu(conv_from_planes(planes, kernel, bias=b))
    assert got.shape == want.shape == (*size, 128) and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize('bias', [False, True], ids=['no_bias', 'bias'])
@pytest.mark.parametrize('size', SIZES[1:], ids=_ids)
def test_flow_head_to_planes_matches_conv(size, bias):
    """The flow head's last convolution — 3×3 onto the 2 flow components —
    as one product onto 18 planes and 9 shifted adds."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(*size, 32), jnp.float32)
    kernel = jnp.asarray(rng.randn(3, 3, 32, 2) * 0.05, jnp.float32)
    b = jnp.asarray(rng.randn(2), jnp.float32) if bias else None
    with jax.default_matmul_precision('highest'):
        want = jnp.moveaxis(conv(x, kernel, padding=1, bias=b), -1, 0)
        got = conv_to_planes(x, kernel, bias=b)
    assert got.shape == want.shape == (2, *size) and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_the_planes_fold_other_kernels_than_rafts():
    """Any odd kernel over any few channels, either way."""
    rng = np.random.RandomState(2)
    planes = jnp.asarray(rng.randn(3, 2, 6, 9), jnp.float32)
    k_in = jnp.asarray(rng.randn(5, 3, 3, 8) * 0.1, jnp.float32)
    x = jnp.asarray(rng.randn(2, 6, 9, 8), jnp.float32)
    k_out = jnp.asarray(rng.randn(3, 5, 8, 3) * 0.1, jnp.float32)
    with jax.default_matmul_precision('highest'):
        np.testing.assert_allclose(
            np.asarray(conv_from_planes(planes, k_in)),
            np.asarray(conv(jnp.moveaxis(planes, 0, -1), k_in,
                            padding=(2, 1))), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(conv_to_planes(x, k_out)),
            np.asarray(jnp.moveaxis(conv(x, k_out, padding=(1, 2)), -1, 0)),
            rtol=1e-5, atol=1e-5)


def _refine_channel_minor(params, fmap1, fmap2, cnet, iters, lookup):
    """The update scan as it was before the planes: coordinates carried
    (B, H, W, 2), ``convf1`` and the flow head as plain ``conv`` over and
    onto 2 channels, the flow concatenated behind the motion features."""
    up = params['update_block']
    enc, fh = up['encoder'], up['flow_head']
    net, inp = jnp.split(cnet, [raft.HIDDEN_DIM], axis=-1)
    net, inp = jnp.tanh(net), relu(inp)
    coords0 = raft.coords_grid(*fmap1.shape[:3])
    gru = raft.fuse_gru_params(up['gru'])
    terms = raft.gru_inp_terms(gru, inp)
    coords1 = coords0
    for _ in range(iters):
        corr = lookup(coords1)
        flow = coords1 - coords0
        cor = relu(raft._conv_b(enc['convc1'], corr))
        cor = relu(raft._conv_b(enc['convc2'], cor, padding=1))
        flo = relu(raft._conv_b(enc['convf1'], flow, padding=3))
        flo = relu(raft._conv_b(enc['convf2'], flo, padding=1))
        out = relu(raft._conv_b(enc['conv'], jnp.concatenate([cor, flo], -1),
                                padding=1))
        net = raft.sep_conv_gru(gru, terms, net,
                                jnp.concatenate([out, flow], -1))
        t = relu(raft._conv_b(fh['conv1'], net, padding=1))
        coords1 = coords1 + raft._conv_b(fh['conv2'], t, padding=1)
    mask = 0.25 * raft._conv_b(
        up['mask']['2'], relu(raft._conv_b(up['mask']['0'], net, padding=1)))
    return raft.upsample_flow(coords1 - coords0, mask)


@pytest.fixture(scope='module')
def refine_inputs():
    params = transplant(raft.init_state_dict(0))
    rng = np.random.RandomState(0)
    return (params, *(jnp.asarray(rng.randn(2, 8, 11, 256), jnp.float32)
                      for _ in range(3)))


@pytest.mark.parametrize('impl', ['dense', 'gather', 'lanes'])
def test_refine_with_planes_matches_the_channel_minor_scan(
        impl, refine_inputs, monkeypatch):
    """Three updates on a fixed seed through every lookup (``lanes``
    interpreted): the flow the channel-minor scan gives, and not by being
    blind to the parts that changed."""
    params, fmap1, fmap2, cnet = refine_inputs
    monkeypatch.setenv('VFT_RAFT_LOOKUP', impl)
    if impl == 'lanes':
        lookup = jax.tree_util.Partial(
            pallas_corr.lookup_corr_lanes,
            pallas_corr.prep_pyramid_lanes_fused(fmap1, fmap2),
            interpret=True)
    else:
        by_grid = raft.lookup_corr if impl == 'gather' \
            else raft.lookup_corr_dense
        lookup = jax.tree_util.Partial(
            by_grid, raft.build_corr_pyramid(fmap1, fmap2))
    with jax.default_matmul_precision('highest'):
        want = np.asarray(_refine_channel_minor(params, fmap1, fmap2, cnet,
                                                3, lookup))
        got = np.asarray(raft._refine(params, fmap1, fmap2, cnet, 3, 'cpu'))
    assert got.shape == want.shape == (2, 64, 88, 2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    if impl == 'dense':                       # convf1 does reach the flow
        blind = jax.tree.map(lambda a: a, params)
        blind['update_block']['encoder']['convf1'] = jax.tree.map(
            jnp.zeros_like, params['update_block']['encoder']['convf1'])
        with jax.default_matmul_precision('highest'):
            off = np.asarray(raft._refine(blind, fmap1, fmap2, cnet, 3,
                                          'cpu'))
        assert np.abs(off - want).max() > 1e-2


def test_no_convolution_over_or_onto_two_channels_in_the_lowered_scan(
        refine_inputs):
    """The lowered StableHLO of ``_refine``: no convolution reads 2 input
    features or writes 2 output features, ``convf1`` is the 7×1 over 14
    under the ``raft_convf1`` scope the trace is read by, the conversions
    of the carry sit under ``raft_coords``, and the scan carries planes."""
    params, fmap1, fmap2, cnet = refine_inputs
    text = jax.jit(
        lambda p, a, b, c: raft._refine(p, a, b, c, 3, 'cpu')).lower(
        params, fmap1, fmap2, cnet).as_text(debug_info=True)
    convs = [line for line in text.splitlines()
             if 'stablehlo.convolution' in line]
    types = [re.search(r': \(tensor<([\dx]+)xf32>, tensor<([\dx]+)xf32>\) '
                       r'-> tensor<([\dx]+)xf32>', line).groups()
             for line in convs]
    assert len(types) == len(convs) > 10
    for lhs, kernel, out in types:
        assert not lhs.endswith('x2') and not out.endswith('x2'), (lhs, out)
        assert kernel.split('x')[-2] != '2', kernel
    assert [t for t in types if t[1].startswith('7x')] == [
        ('2x8x11x14', '7x1x14x128', '2x8x11x128')]
    assert text.count('raft_convf1/conv_general_dilated"') == 1
    assert 'raft_coords/' in text
    assert 'tensor<2x2x8x11xf32>' in text          # the carry: (2, B, H8, W8)
    assert 'tensor<2x8x11x2xf32>' in text          # after the scan, once
