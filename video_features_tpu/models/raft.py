"""RAFT optical flow (princeton-vl architecture, 'basic' variant).

Functional re-implementation of the architecture behind the reference raft
extractor (reference models/raft/raft_src/ — raft.py, extractor.py, update.py,
corr.py). TPU-native design choices:

  * the 20 recurrent GRU iterations are a single ``lax.scan`` body compiled
    once (reference loops in python, raft.py:153-171), with two exact-math
    FLOP cuts: the context encoder's loop-invariant contribution to every
    GRU conv is hoisted out of the scan (see :func:`fuse_gru_params`), and
    the convex-upsample mask head runs once after the scan instead of per
    iteration (only the final mask is ever consumed);
  * the all-pairs correlation volume is one batched matmul
    (B, H·W, H·W)/√dim (corr.py:53-60) and its 4-level pyramid lives as four
    arrays closed over by the scan;
  * the (2r+1)² window lookup (corr.py:29-50) is a vectorized gather-based
    bilinear sample with ``align_corners=True`` / zeros-padding semantics
    (utils/utils.py:58-72 wraps grid_sample the same way);
  * convex 8× upsampling (raft.py:103-115) is a softmax-weighted sum over
    3×3 flow patches, channels-last;
  * inside the scan the 2 flow components are never a channel axis:
    coordinates and flow travel as two (B, H/8, W/8) planes (see
    :func:`_refine`), because a 2-wide minor axis fills 2 of the TPU's 128
    lanes and a convolution over or onto 2 channels leaves the MXU empty.

Params mirror the torch state_dict (fnet./cnet./update_block. prefixes).
Instance norms are affine-less (torch default) and carry no params.
Input: two (B, H, W, 3) uint8/float RGB frames, H and W divisible by 8
(use :func:`pad_to_multiple`); output (B, H, W, 2) flow in pixels (x, y).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from video_features_tpu.ops.nn import (avg_pool, batch_norm, conv,
                                       conv_from_planes, conv_to_planes,
                                       instance_norm, relu)

Params = Dict[str, Any]

CORR_LEVELS = 4
CORR_RADIUS = 4
HIDDEN_DIM = 128
CONTEXT_DIM = 128
ITERS = 20


def resolve_iters(value) -> int:
    """Validate a config ``raft_iters`` (None → the fork's 20-iteration
    pin). Shared by the i3d and raft extractors so 0/negative values fail
    loudly instead of silently running full-depth or returning the
    unrefined init flow."""
    if value is None:
        return ITERS
    iters = int(value)
    if iters < 1:
        raise ValueError(f'raft_iters must be >= 1 (got {iters})')
    return iters


# -- encoders ----------------------------------------------------------------

def _residual_block(p: Params, x: jax.Array, norm_fn: str, stride: int) -> jax.Array:
    def norm(name, t):
        if norm_fn == 'batch':
            return batch_norm(t, p[name])
        if norm_fn == 'instance':
            return instance_norm(t, p.get(name, {}))
        return t

    y = relu(norm('norm1', conv(x, p['conv1']['weight'], stride=stride,
                                padding=1, bias=p['conv1']['bias'])))
    y = relu(norm('norm2', conv(y, p['conv2']['weight'], padding=1,
                                bias=p['conv2']['bias'])))
    if 'downsample' in p:
        x = conv(x, p['downsample']['0']['weight'], stride=stride,
                 bias=p['downsample']['0']['bias'])
        x = norm('norm3', x)
    return relu(x + y)


def basic_encoder(p: Params, x: jax.Array, norm_fn: str) -> jax.Array:
    """(B, H, W, 3) in [-1,1] → (B, H/8, W/8, out_dim)."""
    x = conv(x, p['conv1']['weight'], stride=2, padding=3, bias=p['conv1']['bias'])
    if norm_fn == 'batch':
        x = batch_norm(x, p['norm1'])
    elif norm_fn == 'instance':
        x = instance_norm(x, p.get('norm1', {}))
    x = relu(x)
    for layer in ('layer1', 'layer2', 'layer3'):
        stride = 1 if layer == 'layer1' else 2
        x = _residual_block(p[layer]['0'], x, norm_fn, stride)
        x = _residual_block(p[layer]['1'], x, norm_fn, 1)
    return conv(x, p['conv2']['weight'], bias=p['conv2']['bias'])


# -- correlation pyramid -----------------------------------------------------

def build_corr_pyramid(fmap1: jax.Array, fmap2: jax.Array) -> List[jax.Array]:
    """All-pairs correlation pyramid.

    fmap: (B, H, W, D). Level i: (B·H·W, H/2^i, W/2^i, 1).
    """
    B, H, W, D = fmap1.shape
    f1 = fmap1.reshape(B, H * W, D)
    f2 = fmap2.reshape(B, H * W, D)
    corr = jnp.einsum('bnd,bmd->bnm', f1, f2) / jnp.sqrt(jnp.asarray(D, f1.dtype))
    corr = corr.reshape(B * H * W, H, W, 1)
    pyramid = [corr]
    for _ in range(CORR_LEVELS - 1):
        corr = avg_pool(corr, 2, stride=2)
        pyramid.append(corr)
    return pyramid


def bilinear_sample(img: jax.Array, coords: jax.Array) -> jax.Array:
    """grid_sample(align_corners=True, padding_mode='zeros') in pixel coords.

    img: (N, h, w, C); coords: (N, P, 2) as (x, y) pixel positions.
    Returns (N, P, C).
    """
    N, h, w, C = img.shape
    x, y = coords[..., 0], coords[..., 1]
    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    wx = x - x0
    wy = y - y0

    flat = img.reshape(N, h * w, C)
    batch_idx = jnp.arange(N)[:, None]

    def corner(xi, yi, weight):
        valid = (xi >= 0) & (xi <= w - 1) & (yi >= 0) & (yi <= h - 1)
        xi_c = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
        yi_c = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
        vals = flat[batch_idx, yi_c * w + xi_c]              # (N, P, C)
        return vals * (weight * valid)[..., None].astype(img.dtype)

    return (corner(x0, y0, (1 - wx) * (1 - wy))
            + corner(x0 + 1, y0, wx * (1 - wy))
            + corner(x0, y0 + 1, (1 - wx) * wy)
            + corner(x0 + 1, y0 + 1, wx * wy))


def lookup_corr(pyramid: List[jax.Array], coords: jax.Array,
                radius: int = CORR_RADIUS) -> jax.Array:
    """Sample a (2r+1)² window at every level around ``coords``.

    coords: (B, H, W, 2) in level-0 pixel units → (B, H, W, levels·(2r+1)²).
    """
    B, H, W, _ = coords.shape
    r = radius
    d = jnp.arange(-r, r + 1, dtype=coords.dtype)
    # torch meshgrid(dy, dx) stacked as (dy, dx) then added to (x, y) coords
    # via broadcasting of (..., 2) — delta ordering is (y, x) in the
    # reference (corr.py:38-40), but it is added to centroids whose last dim
    # is (x, y); grid points form the same set either way because the window
    # is square and symmetric, yet the *ordering* of the 81 outputs matters
    # for weight parity: reference orders dy-major with (dy,dx) added as-is.
    dy, dx = jnp.meshgrid(d, d, indexing='ij')
    delta = jnp.stack([dy, dx], axis=-1).reshape(-1, 2)      # (81, 2) (dy,dx)

    out = []
    for i, corr in enumerate(pyramid):
        centroid = coords.reshape(B * H * W, 1, 2) / (2 ** i)  # (N,1,2) (x,y)
        # reference adds delta (dy,dx) directly onto (x,y) centroids
        pts = centroid + delta[None, :, :]
        sampled = bilinear_sample(corr, pts)                  # (N, 81, 1)
        out.append(sampled.reshape(B, H, W, -1))
    return jnp.concatenate(out, axis=-1)


def lookup_corr_dense(pyramid: List[jax.Array], coords: jax.Array,
                      radius: int = CORR_RADIUS) -> jax.Array:
    """Gather-free corr-window lookup: two batched matmul contractions.

    Identical output to :func:`lookup_corr` (reference corr.py:29-50
    semantics, dy-major ordering, zeros padding) but built for the MXU: the
    window offsets are integers, so every sample in a window shares one
    bilinear fraction per axis, and the whole (2r+1)² window is

        out[n, i, j] = Σ_h Σ_w corr[n, h, w] · WY[n, j, h] · WX[n, i, w]

    where WX/WY each have two nonzeros per row ((1-f) at the floor index, f
    at floor+1; out-of-range columns are simply never matched — exactly the
    reference's zeros padding_mode). Gathers are the one access pattern TPUs
    do poorly — XLA lowers them to serialized HBM touches — while these two
    einsums run on the MXU.
    """
    B, H, W, _ = coords.shape
    r = radius
    p1 = 2 * r + 1
    d = jnp.arange(-r, r + 1, dtype=jnp.int32)

    flat = coords.reshape(-1, 2)
    N = flat.shape[0]

    out = []
    for i, corr in enumerate(pyramid):
        _, h, w, _ = corr.shape
        c = flat / (2.0 ** i)                                  # (N, 2) (x, y)
        x0 = jnp.floor(c[:, 0])
        y0 = jnp.floor(c[:, 1])
        fx = (c[:, 0] - x0).astype(corr.dtype)
        fy = (c[:, 1] - y0).astype(corr.dtype)
        # window base indices per output row/col: floor + integer offset
        xi = x0.astype(jnp.int32)[:, None] + d[None, :]        # (N, p1)
        yi = y0.astype(jnp.int32)[:, None] + d[None, :]

        def weights(base, frac, extent):
            ids = jnp.arange(extent, dtype=jnp.int32)[None, None, :]
            lo = (ids == base[:, :, None]).astype(corr.dtype)
            hi = (ids == (base + 1)[:, :, None]).astype(corr.dtype)
            return lo * (1 - frac)[:, None, None] + hi * frac[:, None, None]

        wx = weights(xi, fx, w)                                # (N, p1, w)
        wy = weights(yi, fy, h)                                # (N, p1, h)
        cc = jnp.squeeze(corr, -1)                             # (N, h, w)
        t = jnp.einsum('nhw,niw->nih', cc, wx)                 # x-axis blend
        o = jnp.einsum('nih,njh->nij', t, wy)                  # y-axis blend
        # output k = i·p1 + j is the sample at (x + d[i], y + d[j]) —
        # the reference's dy-major ordering (corr.py:38-44)
        out.append(o.reshape(B, H, W, p1 * p1))
    return jnp.concatenate(out, axis=-1)


# -- update block ------------------------------------------------------------

def _conv_b(p: Params, x: jax.Array, padding=0) -> jax.Array:
    return conv(x, p['weight'], padding=padding, bias=p['bias'])


def motion_encoder(p: Params, flow: jax.Array, corr: jax.Array) -> jax.Array:
    """BasicMotionEncoder (reference update.py:79-97). ``flow`` comes as
    its two planes, (2, B, H, W) — x then y; ``corr`` is channels-last
    (B, H, W, 324) from the 'dense' and 'gather' lookups, or the 'lanes'
    lookup's (324, rows, 128) buffer, which ``convc1`` contracts over its
    leading axis as it stands (``ops/pallas_corr.conv_from_lanes``); the
    result is channels-last: (B, H, W, 126 + 2), the flow in the last two
    channels.

    Neither end treats the flow as a 2-channel tensor: ``convf1`` (7×7,
    2 → 128) folds W's taps into 14 channels (:func:`conv_from_planes`),
    and the closing concatenation is an ADD — the last convolution is
    padded to 128 outputs with two zero kernels (``relu(0) = 0``) and the
    flow is added into them, exactly: with no 2-channel operand beside it
    the compiler keeps the GRU batch-minor and needs no transposed copy of
    the 126 channels.
    """
    if corr.ndim == 3:          # the lanes lookup's buffer, channels leading
        from video_features_tpu.ops.pallas_corr import conv_from_lanes
        cor = relu(conv_from_lanes(corr, p['convc1']['weight'],
                                   p['convc1']['bias'], flow.shape[1:]))
    else:
        cor = relu(_conv_b(p['convc1'], corr))
    cor = relu(_conv_b(p['convc2'], cor, padding=1))
    with jax.named_scope('raft_convf1'):
        flo = relu(conv_from_planes(flow, p['convf1']['weight'],
                                    bias=p['convf1']['bias']))
    flo = relu(_conv_b(p['convf2'], flo, padding=1))
    c = flow.shape[0]
    w = jnp.pad(p['conv']['weight'], [(0, 0), (0, 0), (0, 0), (0, c)])
    b = jnp.pad(p['conv']['bias'], [(0, c)])
    out = relu(conv(jnp.concatenate([cor, flo], -1), w, padding=1, bias=b))
    with jax.named_scope('raft_coords'):
        return out + jnp.pad(jnp.moveaxis(flow, 0, -1),
                             [(0, 0), (0, 0), (0, 0), (out.shape[-1] - c, 0)])


GRU_PADS = (('1', ((0, 0), (2, 2))), ('2', ((2, 2), (0, 0))))


def fuse_gru_params(p: Params, hidden: int = HIDDEN_DIM,
                    context: int = CONTEXT_DIM) -> Params:
    """Restructure the six GRU conv weights for the scan body, once.

    Two exact-math transforms (reference math: update.py:39-77):

      * the z and r gates read the same input, so each direction's z/r
        weights stack on the OUTPUT axis — one conv computes both gates
        (independent per-output-channel reductions), halving that input's
        HBM reads;
      * every GRU conv's INPUT channels split as (h | inp | motion), and
        the ``inp`` block — the context encoder's half, reference
        raft.py:139-143 — is LOOP-INVARIANT across the 20 refinement
        iterations. Conv is linear in input channels, so the inp
        contribution is a per-pixel constant computed once before the scan
        (:func:`gru_inp_terms`); the per-iteration convs then contract 256
        channels instead of 384 — a third of the GRU FLOPs deleted from
        the scan with identical math (the q conv's input is
        ``concat(r·h, x)``: the r gate never multiplies the inp block, so
        its term is invariant too).
    """
    out = {}
    sl_h = slice(0, hidden)
    sl_i = slice(hidden, hidden + context)
    sl_m = slice(hidden + context, None)
    for suffix, _ in GRU_PADS:
        zw, rw = p[f'convz{suffix}'], p[f'convr{suffix}']
        w = jnp.concatenate([zw['weight'], rw['weight']], axis=-1)
        b = jnp.concatenate([zw['bias'], rw['bias']])
        qw = p[f'convq{suffix}']['weight']
        out[f'zr{suffix}'] = {
            'hm': jnp.concatenate([w[:, :, sl_h], w[:, :, sl_m]], axis=2),
            'inp': w[:, :, sl_i], 'bias': b}
        out[f'q{suffix}'] = {
            'hm': jnp.concatenate([qw[:, :, sl_h], qw[:, :, sl_m]], axis=2),
            'inp': qw[:, :, sl_i], 'bias': p[f'convq{suffix}']['bias']}
    return out


def gru_inp_terms(fused: Params, inp: jax.Array) -> Params:
    """The loop-invariant context contribution to all four GRU convs
    (+ their biases), computed once before the refinement scan."""
    terms = {}
    for suffix, pad in GRU_PADS:
        for gate in ('zr', 'q'):
            pp = fused[f'{gate}{suffix}']
            terms[f'{gate}{suffix}'] = conv(inp, pp['inp'], padding=list(pad),
                                            bias=pp['bias'])
    return terms


def sep_conv_gru(fused: Params, terms: Params, h: jax.Array,
                 motion: jax.Array) -> jax.Array:
    """SepConvGRU (reference update.py:39-77): 1×5 then 5×1 passes over
    :func:`fuse_gru_params`-prepared weights + precomputed context terms."""
    for suffix, pad in GRU_PADS:
        hm = jnp.concatenate([h, motion], -1)
        zr = jax.nn.sigmoid(conv(hm, fused[f'zr{suffix}']['hm'],
                                 padding=list(pad)) + terms[f'zr{suffix}'])
        z, r = jnp.split(zr, 2, axis=-1)
        q = jnp.tanh(conv(jnp.concatenate([r * h, motion], -1),
                          fused[f'q{suffix}']['hm'], padding=list(pad))
                     + terms[f'q{suffix}'])
        h = (1 - z) * h + z * q
    return h


def upsample_flow(flow: jax.Array, mask: jax.Array) -> jax.Array:
    """Convex-combination 8× upsample (reference raft.py:103-115).

    flow: (B, H, W, 2); mask: (B, H, W, 576=9·8·8) → (B, 8H, 8W, 2).
    """
    B, H, W, _ = flow.shape
    mask = mask.reshape(B, H, W, 9, 8, 8)
    mask = jax.nn.softmax(mask, axis=3)

    fp = jnp.pad(8.0 * flow, [(0, 0), (1, 1), (1, 1), (0, 0)])
    # 3×3 patches, row-major to match F.unfold ordering
    patches = jnp.stack([fp[:, i:i + H, j:j + W, :]
                         for i in range(3) for j in range(3)], axis=3)  # (B,H,W,9,2)
    up = jnp.einsum('bhwkij,bhwkc->bhwijc', mask, patches)  # (B,H,W,8,8,2)
    return up.transpose(0, 1, 3, 2, 4, 5).reshape(B, 8 * H, 8 * W, 2)


# -- full model --------------------------------------------------------------

def coords_grid(B: int, H: int, W: int, dtype=jnp.float32) -> jax.Array:
    """(B, H, W, 2) grid of (x, y) pixel coordinates."""
    y, x = jnp.meshgrid(jnp.arange(H, dtype=dtype), jnp.arange(W, dtype=dtype),
                        indexing='ij')
    return jnp.broadcast_to(jnp.stack([x, y], -1), (B, H, W, 2))


def coords_planes(B: int, H: int, W: int, dtype=jnp.float32) -> jax.Array:
    """:func:`coords_grid` as (2, B, H, W): the x plane, then the y plane."""
    return jnp.moveaxis(coords_grid(B, H, W, dtype), -1, 0)


# The lanes kernel streams a level's plane for each tile of 1,024 pixels
# through VMEM, in chunks of at most ops/pallas_corr.BLOCK_BYTES
# (``pallas_corr.chunks``); past this plane (level 0, MiB a tile: 16,384
# positions, the frames the 128-pixel kernel took up to an 8 MiB block)
# auto-dispatch takes dense — the kernel there is not measured.
LANES_PLANE_MB = 64.0

LOOKUPS = ('auto', 'dense', 'gather', 'lanes')


def resolve_lookup(h8: int, w8: int, platform: str) -> str:
    """Which corr lookup the forward pass compiles at a 1/8-resolution map
    of ``h8 × w8`` on ``platform``: 'lanes', 'dense' or 'gather'.

    The code decides ('auto'): 'lanes' — the Pallas kernel over 1,024
    pixels a grid step, ops/pallas_corr.py — on a TPU while the level-0
    plane of one tile, ``h8·w8`` positions of 4 KiB, fits
    ``LANES_PLANE_MB``; 'dense' (:func:`lookup_corr_dense`, gather-free
    batched matmuls) anywhere else, the CPU included, where the kernel
    would run interpreted. Shapes are static at trace time, so the choice
    compiles away. ``VFT_RAFT_LOOKUP`` still overrides it for two callers:
    'dense' is the operator's workaround for i3d on more than one chip (jax
    cannot partition the Mosaic call) and 'gather' (:func:`lookup_corr`,
    the XLA gather lowering) is the oracle the tests compare against; the
    switch goes when the kernel is wrapped in ``shard_map`` (ROADMAP R1,
    D13).
    """
    import os
    impl = os.environ.get('VFT_RAFT_LOOKUP', 'auto')
    if impl not in LOOKUPS:
        raise ValueError(
            f'VFT_RAFT_LOOKUP={impl!r}: the lookups are '
            + ', '.join(repr(name) for name in LOOKUPS))
    if impl != 'auto':
        return impl
    from video_features_tpu.ops.pallas_corr import TILE
    plane_mb = h8 * w8 * TILE * 4 / 2 ** 20
    if platform == 'tpu' and plane_mb <= LANES_PLANE_MB:
        return 'lanes'
    return 'dense'


def lookup_note(h8: int, w8: int, platform: str) -> Dict[str, Any]:
    """The run manifest's ``kernels`` note for RAFT at an ``h8 × w8`` map:
    the lookup :func:`resolve_lookup` picks and, for 'lanes', the pixels a
    grid step and the chunks its level-0 plane is streamed in."""
    impl = resolve_lookup(h8, w8, platform)
    if impl != 'lanes':
        return {'raft_lookup': impl}
    from video_features_tpu.ops import pallas_corr
    hc, wc = pallas_corr.chunks(h8, w8)
    return {'raft_lookup': impl, 'raft_lookup_pixels': pallas_corr.TILE,
            'raft_lookup_h_chunks': h8 // hc,
            'raft_lookup_w_chunks': w8 // wc}


def _pallas_interpret(platform: str) -> bool:
    """Whether the Pallas lookups run in the interpreter on ``platform``:
    compiled by Mosaic on 'tpu', interpreted on 'cpu' (the test path), and
    an error anywhere else — a misspelt or unknown platform must not turn
    the production kernel into an interpreted one without a word."""
    if platform == 'tpu':
        return False
    if platform == 'cpu':
        return True
    raise ValueError(
        f'Pallas corr lookup: unknown platform {platform!r} (the kernels '
        "compile for 'tpu' and interpret on 'cpu'); use "
        'VFT_RAFT_LOOKUP=dense elsewhere')


def _normalize_frames(img: jax.Array) -> jax.Array:
    """0..255 RGB → ±1 (done inside forward in the reference, raft.py:121-122)."""
    return 2.0 * (jnp.asarray(img, jnp.float32) / 255.0) - 1.0


def forward(params: Params, image1: jax.Array, image2: jax.Array,
            iters: int = ITERS, platform: Optional[str] = None,
            pins=None) -> jax.Array:
    """Two (B, H, W, 3) frames (values 0..255) → (B, H, W, 2) flow.

    H, W must be divisible by 8 (reference pads with InputPadder, raft.py:30-48
    — see :func:`pad_to_multiple` / :func:`unpad`). ``platform`` selects the
    corr-lookup implementation for the platform the graph will run on (see
    :func:`_refine`); ``pins`` per-sub-graph precision (ops/precision.py).
    """
    from video_features_tpu.ops.precision import pin_scope
    image1 = _normalize_frames(image1)
    image2 = _normalize_frames(image2)
    with pin_scope(pins, 'encoder'), jax.named_scope('raft_encoders'):
        fmap1 = basic_encoder(params['fnet'], image1, 'instance')
        fmap2 = basic_encoder(params['fnet'], image2, 'instance')
        cnet = basic_encoder(params['cnet'], image1, 'batch')
    return _refine(params, fmap1, fmap2, cnet, iters, platform, pins)


def forward_consecutive(params: Params, frames: jax.Array,
                        iters: int = ITERS,
                        platform: Optional[str] = None,
                        pins=None) -> jax.Array:
    """(N, H, W, 3) consecutive frames → (N-1, H, W, 2) pairwise flows.

    Same math as :func:`forward` on ``(frames[:-1], frames[1:])`` — the
    extractors' consecutive-pair batching (reference
    base_flow_extractor.py:76-84) makes every interior frame both the
    ``image2`` of one pair and the ``image1`` of the next, so its fnet
    encoding is computed ONCE here and shared, where the reference's
    stacked-pair form encodes it twice (raft.py:84-85).
    """
    return forward_stack_pairs(params, frames[None], iters,
                               platform=platform, pins=pins)[0]


def forward_stack_pairs(params: Params, stacks: jax.Array, iters: int = ITERS,
                        constrain=None,
                        platform: Optional[str] = None,
                        pins=None) -> jax.Array:
    """(B, S+1, H, W, 3) frame stacks → (B, S, H, W, 2) within-stack flows.

    The fused I3D path's form of :func:`forward_consecutive`: fnet runs on
    the B·(S+1) unique frames instead of the 2·B·S stacked pair halves.
    ``constrain`` (optional) applies a sharding constraint to every
    leading-flattened tensor entering the heavy sub-graphs (frames, fmap
    pairs, cnet) so the sub-graphs spread over a (data, time) mesh. The
    B·(S+1) frames tensor generally does not divide the mesh evenly (the
    +1 halo); GSPMD pads the last shards, a ≤1-frame-per-shard imbalance
    on fnet that still beats sharding fnet over the data axis alone.
    """
    from video_features_tpu.ops.precision import pin_scope
    B, S1, H, W, C = stacks.shape
    S = S1 - 1
    flat = _normalize_frames(stacks.reshape(B * S1, H, W, C))
    if constrain is not None:
        flat = constrain(flat)
    with pin_scope(pins, 'encoder'), jax.named_scope('raft_encoders'):
        fmaps = basic_encoder(params['fnet'], flat, 'instance')
    h8, w8, c = fmaps.shape[1:]
    fmaps = fmaps.reshape(B, S1, h8, w8, c)
    fmap1 = fmaps[:, :-1].reshape(B * S, h8, w8, c)
    fmap2 = fmaps[:, 1:].reshape(B * S, h8, w8, c)
    first = flat.reshape(B, S1, H, W, C)[:, :-1].reshape(B * S, H, W, C)
    if constrain is not None:
        fmap1, fmap2, first = constrain(fmap1), constrain(fmap2), constrain(first)
    with pin_scope(pins, 'encoder'), jax.named_scope('raft_encoders'):
        cnet = basic_encoder(params['cnet'], first, 'batch')
    flow = _refine(params, fmap1, fmap2, cnet, iters, platform, pins)
    return flow.reshape(B, S, flow.shape[1], flow.shape[2], 2)


def _refine(params: Params, fmap1: jax.Array, fmap2: jax.Array,
            cnet: jax.Array, iters: int,
            platform: Optional[str] = None, pins=None) -> jax.Array:
    """Correlation pyramid + 20-iteration GRU refinement + 8× upsample —
    the shared core behind every forward variant (reference raft.py:118-175
    from the post-encoder point on).

    ``platform`` is the platform the compiled graph will RUN on ('tpu' /
    'cpu' / ...); it picks the corr-lookup implementation and Pallas
    interpret mode. Defaults to ``jax.default_backend()``, which is only
    correct when the operands live on the default backend — extractors
    thread their resolved device's platform instead (a CPU-committed call
    in a TPU-default process must not get the Mosaic lanes kernel).
    ``pins`` optionally overrides matmul precision per sub-graph
    (ops/precision.py): 'corr', 'iter', 'upsample'.

    Through the scan the coordinates travel as PLANES, ``(2, B, H8, W8)``
    — x then y — and ``(B, H8, W8, 2)`` appears again only after it, for
    :func:`upsample_flow`. With the 2 components minor, the carry filled 2
    of 128 lanes (1.4 MB held in 90 MB), was copied between three layouts
    an update, and made ``convf1`` 49 MXU products 2 lanes wide. As planes
    the compiler lays the carry out batch-minor, which is how the flow
    head's last convolution writes it (:func:`conv_to_planes`); the lanes
    lookup takes the planes in that (h, w, b) order as (8, 128) tiles of
    pixels, and its four calls write the one buffer ``convc1`` contracts;
    :func:`motion_encoder` reads and re-emits the flow without a 2-channel
    tensor. One form at every size and on every platform; the
    'dense' and 'gather' lookups keep their (B, H, W, 2) signature and are
    converted to at that boundary (scope ``raft_coords``)."""
    from video_features_tpu.ops.precision import pin_scope
    platform = platform or jax.default_backend()
    if platform not in ('tpu', 'cpu', 'gpu'):
        raise ValueError(
            f'raft: unknown platform {platform!r} — the lookup dispatch '
            "knows 'tpu', 'cpu' and 'gpu' (a jax.Device.platform value)")
    net, inp = jnp.split(cnet, [HIDDEN_DIM], axis=-1)
    net = jnp.tanh(net)
    inp = relu(inp)

    B, H8, W8, _ = fmap1.shape
    # + zeros_like keeps shard_map's varying-axes type: constant carry
    # inits must match the varying outputs of the scan body when _refine
    # runs inside a shard_map shard (the add folds away otherwise)
    coords0 = coords_planes(B, H8, W8) + jnp.zeros_like(fmap1[..., 0])
    up = params['update_block']

    impl = resolve_lookup(H8, W8, platform)
    if impl == 'lanes':
        # the kernel's pyramid built straight from the fmaps: the
        # (N, h, w) detour + physical transpose was the fixed phase's
        # single worst HBM pattern (see prep_pyramid_lanes_fused)
        from video_features_tpu.ops import pallas_corr
        with pin_scope(pins, 'corr'), jax.named_scope('raft_corr'):
            prepped = pallas_corr.prep_pyramid_lanes_fused(
                fmap1, fmap2, levels=CORR_LEVELS)
        lookup = partial(pallas_corr.lookup_corr_planes, prepped,
                         radius=CORR_RADIUS,
                         interpret=_pallas_interpret(platform))
    else:
        with pin_scope(pins, 'corr'), jax.named_scope('raft_corr'):
            pyramid = build_corr_pyramid(fmap1, fmap2)
        by_grid = partial(lookup_corr if impl == 'gather'
                          else lookup_corr_dense, pyramid)

        def lookup(planes):
            with jax.named_scope('raft_coords'):
                coords = jnp.moveaxis(planes, 0, -1)
            return by_grid(coords)

    fh, mk = up['flow_head'], up['mask']
    gru = fuse_gru_params(up['gru'])
    with pin_scope(pins, 'iter'):
        gru_terms = gru_inp_terms(gru, inp)

    def make_step(early_prec=None):
        """Scan body; ``early_prec`` overrides the WHOLE body's matmul
        precision (the 'iter_early' pin — see below)."""
        def step(carry, _):
            from contextlib import nullcontext
            outer = (jax.default_matmul_precision(early_prec)
                     if early_prec else nullcontext())
            with outer:
                net, coords1 = carry
                # the named scopes are the device-time vocabulary
                # (obs/scopes.py: metadata only); the pins beside them
                # stay what they are, precision contexts
                with pin_scope(pins, 'corr'), \
                        jax.named_scope('raft_lookup'):
                    corr = lookup(coords1)
                flow = coords1 - coords0
                # finer pins nest inside 'iter': an unpinned sub-component
                # inherits the 'iter' (or ambient) precision
                with pin_scope(pins, 'iter'):
                    with pin_scope(pins, 'iter_motion'), \
                            jax.named_scope('raft_motion'):
                        motion = motion_encoder(up['encoder'], flow, corr)
                    with pin_scope(pins, 'iter_gru'), \
                            jax.named_scope('raft_gru'):
                        net_new = sep_conv_gru(gru, gru_terms, net, motion)
                    with pin_scope(pins, 'iter_head'), \
                            jax.named_scope('raft_flow_head'):
                        t = relu(_conv_b(fh['conv1'], net_new, padding=1))
                        delta = conv_to_planes(t, fh['conv2']['weight'],
                                               bias=fh['conv2']['bias'])
                    coords1_new = coords1 + delta
            return (net_new, coords1_new), None
        return step

    # 'iter_early' pin ('<precision>:<n>') runs the FIRST n refinement
    # iterations at a faster precision: RAFT is iterative refinement, so
    # early-iteration error is substantially corrected by the remaining
    # full-precision iterations (measured by tools/precision_study.py).
    early_prec, early_n = None, 0
    for name, val in (pins or ()):
        if name == 'iter_early':
            early_prec, _, n = str(val).partition(':')
            early_n = min(int(n or 0), iters)

    carry = (net, coords0)
    with jax.named_scope('raft_update'):
        if early_n:
            carry, _ = lax.scan(make_step(early_prec), carry, None,
                                length=early_n)
        (net, coords1), _ = lax.scan(make_step(), carry, None,
                                     length=iters - early_n)
    # Convex-upsample mask head, ONCE after the scan: the reference
    # computes `.25·mask(net)` every iteration (update.py:139-144) but the
    # extractor consumes only the final flow (raft.py:153-175 predictions
    # [-1]) — every non-final mask is dead code, so 19/20 of the mask
    # head's FLOPs (a 3×3 128→256 + 1×1 256→576 stack) leave the scan
    # with bit-identical output.
    with pin_scope(pins, 'iter'), jax.named_scope('raft_upsample'):
        t_mask = relu(_conv_b(mk['0'], net, padding=1))
        mask = 0.25 * _conv_b(mk['2'], t_mask)
    with jax.named_scope('raft_coords'):
        flow = jnp.moveaxis(coords1 - coords0, 0, -1)
    with pin_scope(pins, 'upsample'), jax.named_scope('raft_upsample'):
        return upsample_flow(flow, mask)


def pad_to_multiple(x: jax.Array, mode: str = 'sintel',
                    multiple: int = 8) -> Tuple[jax.Array, Tuple[int, int, int, int]]:
    """Replicate-pad (B, H, W, C) so H, W divide ``multiple``.

    Reference InputPadder (raft.py:30-48): sintel centers the pad; kitti pads
    bottom-only in height. Returns (padded, (top, bottom, left, right)).
    numpy input pads with numpy (a ``jnp.pad`` here would silently bounce a
    host batch through the default device and back — one extra H2D+D2H round
    trip per extraction step).
    """
    H, W = x.shape[1], x.shape[2]
    pad_h = (((H // multiple) + 1) * multiple - H) % multiple
    pad_w = (((W // multiple) + 1) * multiple - W) % multiple
    if mode == 'sintel':
        pads = (pad_h // 2, pad_h - pad_h // 2, pad_w // 2, pad_w - pad_w // 2)
    else:
        pads = (0, pad_h, pad_w // 2, pad_w - pad_w // 2)
    t, b, l, r = pads
    pad_fn = np.pad if isinstance(x, np.ndarray) else jnp.pad
    x = pad_fn(x, [(0, 0), (t, b), (l, r), (0, 0)], mode='edge')
    return x, pads


def unpad(x: jax.Array, pads: Tuple[int, int, int, int]) -> jax.Array:
    t, b, l, r = pads
    H, W = x.shape[1], x.shape[2]
    return x[:, t:H - b, l:W - r, :]


# -- random init for tests ---------------------------------------------------

def init_state_dict(seed: int = 0) -> Dict[str, np.ndarray]:
    """Random torch-layout state_dict with princeton-vl RAFT naming/shapes."""
    rng = np.random.RandomState(seed)
    sd: Dict[str, np.ndarray] = {}

    def conv_w(name, o, i, kh, kw, scale=0.05):
        sd[f'{name}.weight'] = rng.randn(o, i, kh, kw).astype(np.float32) * scale
        sd[f'{name}.bias'] = rng.randn(o).astype(np.float32) * 0.05

    def bn(name, c):
        sd[f'{name}.weight'] = rng.rand(c).astype(np.float32) + 0.5
        sd[f'{name}.bias'] = rng.randn(c).astype(np.float32) * 0.1
        sd[f'{name}.running_mean'] = rng.randn(c).astype(np.float32) * 0.1
        sd[f'{name}.running_var'] = rng.rand(c).astype(np.float32) + 0.5

    def encoder(prefix, out_dim, norm_fn):
        conv_w(f'{prefix}.conv1', 64, 3, 7, 7)
        if norm_fn == 'batch':
            bn(f'{prefix}.norm1', 64)
        dims = [(64, 64, 1), (64, 96, 2), (96, 128, 2)]
        for li, (i_p, o_p, stride) in enumerate(dims, start=1):
            for bi in range(2):
                base = f'{prefix}.layer{li}.{bi}'
                cin = i_p if bi == 0 else o_p
                s = stride if bi == 0 else 1
                conv_w(f'{base}.conv1', o_p, cin, 3, 3)
                conv_w(f'{base}.conv2', o_p, o_p, 3, 3)
                if norm_fn == 'batch':
                    bn(f'{base}.norm1', o_p)
                    bn(f'{base}.norm2', o_p)
                if s != 1 or cin != o_p:
                    conv_w(f'{base}.downsample.0', o_p, cin, 1, 1)
                    if norm_fn == 'batch':
                        bn(f'{base}.norm3', o_p)
        conv_w(f'{prefix}.conv2', out_dim, 128, 1, 1)

    encoder('fnet', 256, 'instance')
    encoder('cnet', HIDDEN_DIM + CONTEXT_DIM, 'batch')

    cor_planes = CORR_LEVELS * (2 * CORR_RADIUS + 1) ** 2
    conv_w('update_block.encoder.convc1', 256, cor_planes, 1, 1)
    conv_w('update_block.encoder.convc2', 192, 256, 3, 3)
    conv_w('update_block.encoder.convf1', 128, 2, 7, 7)
    conv_w('update_block.encoder.convf2', 64, 128, 3, 3)
    conv_w('update_block.encoder.conv', 126, 256, 3, 3)
    for g in ('z', 'r', 'q'):
        conv_w(f'update_block.gru.conv{g}1', 128, 256 + 128, 1, 5)
        conv_w(f'update_block.gru.conv{g}2', 128, 256 + 128, 5, 1)
    conv_w('update_block.flow_head.conv1', 256, 128, 3, 3)
    conv_w('update_block.flow_head.conv2', 2, 256, 3, 3)
    conv_w('update_block.mask.0', 256, 128, 3, 3)
    conv_w('update_block.mask.2', 64 * 9, 256, 1, 1)
    return sd
