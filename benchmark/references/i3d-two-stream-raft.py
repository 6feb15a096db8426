"""Plain reference: two-stream Inception-3D features with RAFT optical flow.

Written from the published descriptions:

* **I3D** — Carreira & Zisserman 2017, "Quo Vadis, Action Recognition?":
  Inception-v1 inflated to 3-D (Figure 3), TensorFlow 'SAME' padding in
  every convolution and max-pool, batch norm + ReLU after every convolution,
  a 2×7×7 average pool and the mean over the remaining time steps → 1024
  numbers a tower.
* **RAFT** — Teed & Deng 2020 ("basic" model, sintel checkpoint geometry):
  feature encoder with instance norm and context encoder with batch norm at
  1/8 resolution, all-pairs correlation / sqrt(256) pooled into 4 levels,
  20 updates of a separable ConvGRU that looks up a 9×9 window per level
  (bilinear, zero outside) and adds a flow step, convex 8× upsampling.
* **The extractor** (kinetics-i3d recipe as the published fork runs it): stacks
  of 16+1 frames every 16 frames, short side 256; rgb = first 16 frames, centre
  crop 224, 2x/255−1; flow = RAFT on the 16 consecutive pairs of the stack
  replicate-padded to a multiple of 8 (centred, "sintel"), centre crop 224 of
  the *padded* field, clamp ±20, round(128 + 255/40·x), 2x/255−1; output
  rgb ‖ flow → 2048 numbers a stack. A last partial stack is dropped.

float32, ``highest``, no kernels. Departures, each without effect on the
result: the mask head of RAFT is evaluated once after the last update (only
the last mask is used); the window lookup is written as a sum over the level
with hat weights ``max(0, 1 − |x − w|)``, which *is* bilinear sampling with
zeros outside; max-pool padding is −inf (the published port pads zeros on
post-ReLU input). Decoding is OpenCV's.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from _layers import (
    avg_pool, batch_norm, center_crop_offsets, instance_norm, max_pool,
    tf_same,
)
from _video import read_frames, resize_short_side

STACK, STEP = 16, 16
MIN_SIDE, CROP = 256, 224
RAFT_ITERS, CORR_LEVELS, CORR_RADIUS = 20, 4, 4
HIDDEN, CONTEXT, FMAP = 128, 128, 256
FLOW_BOUND = 20.0
FEATURE_DIM = 2048
UNIT = 'clip'

# Inception blocks: in, (1x1, 3x3 reduce, 3x3, "5x5" reduce, "5x5", pool proj)
MIXED = {
    'mixed_3b': (192, (64, 96, 128, 16, 32, 32)),
    'mixed_3c': (256, (128, 128, 192, 32, 96, 64)),
    'mixed_4b': (480, (192, 96, 208, 16, 48, 64)),
    'mixed_4c': (512, (160, 112, 224, 24, 64, 64)),
    'mixed_4d': (512, (128, 128, 256, 24, 64, 64)),
    'mixed_4e': (512, (112, 144, 288, 32, 64, 64)),
    'mixed_4f': (528, (256, 160, 320, 32, 128, 128)),
    'mixed_5b': (832, (256, 160, 320, 32, 128, 128)),
    'mixed_5c': (832, (384, 192, 384, 48, 128, 128)),
}


# -- parameters ------------------------------------------------------------

def _i3d_specs(in_channels: int):
    from weights import bn_specs
    specs = []

    def unit(name, cin, cout, k):
        specs.append((f'{name}.conv3d.weight', 'conv', (k, k, k, cin, cout),
                      1.0))
        specs.extend(bn_specs(f'{name}.batch3d', cout))

    unit('conv3d_1a_7x7', in_channels, 64, 7)
    unit('conv3d_2b_1x1', 64, 64, 1)
    unit('conv3d_2c_3x3', 64, 192, 3)
    for name, (cin, (b0, b1r, b1, b2r, b2, b3)) in MIXED.items():
        unit(f'{name}.branch_0', cin, b0, 1)
        unit(f'{name}.branch_1.0', cin, b1r, 1)
        unit(f'{name}.branch_1.1', b1r, b1, 3)
        unit(f'{name}.branch_2.0', cin, b2r, 1)
        unit(f'{name}.branch_2.1', b2r, b2, 3)
        unit(f'{name}.branch_3.1', cin, b3, 1)
    # the classifier head is part of the checkpoint, unused for features
    specs.append(('conv3d_0c_1x1.conv3d.weight', 'conv',
                  (1, 1, 1, 1024, 400), 1.0))
    specs.append(('conv3d_0c_1x1.conv3d.bias', 'bias', (400,), 1.0))
    return specs


def _raft_specs():
    from weights import bn_specs
    specs = []

    def conv(name, kh, kw, cin, cout, scale=1.0):
        specs.append((f'{name}.weight', 'conv', (kh, kw, cin, cout), scale))
        specs.append((f'{name}.bias', 'bias', (cout,), 0.5 * scale))

    def encoder(prefix, out_dim, batch):
        conv(f'{prefix}.conv1', 7, 7, 3, 64)
        if batch:
            specs.extend(bn_specs(f'{prefix}.norm1', 64))
        for li, (cin, cout, stride) in enumerate(
                ((64, 64, 1), (64, 96, 2), (96, 128, 2)), start=1):
            for bi in range(2):
                base = f'{prefix}.layer{li}.{bi}'
                c = cin if bi == 0 else cout
                s = stride if bi == 0 else 1
                conv(f'{base}.conv1', 3, 3, c, cout)
                conv(f'{base}.conv2', 3, 3, cout, cout)
                if batch:
                    specs.extend(bn_specs(f'{base}.norm1', cout))
                    specs.extend(bn_specs(f'{base}.norm2', cout))
                if s != 1:
                    conv(f'{base}.downsample.0', 1, 1, c, cout)
                    if batch:
                        specs.extend(bn_specs(f'{base}.norm3', cout))
        conv(f'{prefix}.conv2', 1, 1, 128, out_dim)

    encoder('fnet', FMAP, batch=False)
    encoder('cnet', HIDDEN + CONTEXT, batch=True)
    planes = CORR_LEVELS * (2 * CORR_RADIUS + 1) ** 2
    ub = 'update_block'
    conv(f'{ub}.encoder.convc1', 1, 1, planes, 256)
    conv(f'{ub}.encoder.convc2', 3, 3, 256, 192)
    conv(f'{ub}.encoder.convf1', 7, 7, 2, 128)
    conv(f'{ub}.encoder.convf2', 3, 3, 128, 64)
    conv(f'{ub}.encoder.conv', 3, 3, 256, 126)
    for g in 'zrq':
        conv(f'{ub}.gru.conv{g}1', 1, 5, HIDDEN + CONTEXT + 128, HIDDEN)
        conv(f'{ub}.gru.conv{g}2', 5, 1, HIDDEN + CONTEXT + 128, HIDDEN)
    conv(f'{ub}.flow_head.conv1', 3, 3, HIDDEN, 256)
    # hot random weights drive |flow| to the ±20 px clamp, whose value sits
    # exactly on a rounding edge of the uint8 quantisation, so a 1e-6
    # difference flips a whole level there; the repo's own parity tests
    # scale the flow head down for the same reason
    # (tests/reference_pipeline.py). He-normal weights are hotter than the
    # repo's, so the factor is smaller: fields of a few pixels, as a trained
    # RAFT gives on such clips
    conv(f'{ub}.flow_head.conv2', 3, 3, 256, 2, scale=FLOW_HEAD_SCALE)
    conv(f'{ub}.mask.0', 3, 3, HIDDEN, 256)
    conv(f'{ub}.mask.2', 1, 1, 256, 64 * 9)
    return specs


FLOW_HEAD_SCALE = 0.03


def param_specs():
    return {'i3d_rgb_checkpoint_path': _i3d_specs(3),
            'i3d_flow_checkpoint_path': _i3d_specs(2),
            'raft_checkpoint_path': _raft_specs()}


# -- from a video file to the model's input ---------------------------------

def rows_of(n_frames: int, cfg=None) -> int:
    """Stacks of STACK+1 frames every STEP frames; a partial one is dropped."""
    n = n_frames - (STACK + 1)
    return 0 if n < 0 else n // STEP + 1


def load_units(video_path: str, rows, cfg=None) -> np.ndarray:
    """(n, 17, H, W, 3) uint8 stacks of the given rows, short side 256."""
    rows = list(rows)
    frames = read_frames(video_path, upto=max(rows) * STEP + STACK + 1)
    out = []
    for r in rows:
        stack = frames[r * STEP:r * STEP + STACK + 1]
        out.append(np.stack([resize_short_side(f, MIN_SIDE) for f in stack]))
    return np.stack(out)


def unit_shape(cfg=None):
    h, w = (cfg or {}).get('frame_hw', (256, 340))
    return (STACK + 1, h, w, 3), np.uint8


# -- I3D -------------------------------------------------------------------

def _same(shape, kernel, stride):
    return [tf_same(n, k, s) for n, k, s in zip(shape, kernel, stride)]


def _unit3d(ops, p, name, x, k, stride=(1, 1, 1)):
    kernel = (k, k, k)
    x = ops.conv(x, p[f'{name}.conv3d.weight'], stride=stride,
                 padding=_same(x.shape[1:4], kernel, stride))
    return jnp.maximum(batch_norm(x, p, f'{name}.batch3d', eps=1e-5), 0)


def _max_pool_same(x, kernel, stride):
    return max_pool(x, kernel, stride, _same(x.shape[1:4], kernel, stride))


def _mixed(ops, p, name, x):
    b0 = _unit3d(ops, p, f'{name}.branch_0', x, 1)
    b1 = _unit3d(ops, p, f'{name}.branch_1.1',
                 _unit3d(ops, p, f'{name}.branch_1.0', x, 1), 3)
    b2 = _unit3d(ops, p, f'{name}.branch_2.1',
                 _unit3d(ops, p, f'{name}.branch_2.0', x, 1), 3)
    b3 = _unit3d(ops, p, f'{name}.branch_3.1',
                 _max_pool_same(x, (3, 3, 3), (1, 1, 1)), 1)
    return jnp.concatenate([b0, b1, b2, b3], axis=-1)


def i3d_tower(ops, p, x):
    """(B, T, H, W, C) in [-1, 1] → (B, 1024)."""
    x = _unit3d(ops, p, 'conv3d_1a_7x7', x, 7, (2, 2, 2))
    x = _max_pool_same(x, (1, 3, 3), (1, 2, 2))
    x = _unit3d(ops, p, 'conv3d_2b_1x1', x, 1)
    x = _unit3d(ops, p, 'conv3d_2c_3x3', x, 3)
    x = _max_pool_same(x, (1, 3, 3), (1, 2, 2))
    x = _mixed(ops, p, 'mixed_3b', x)
    x = _mixed(ops, p, 'mixed_3c', x)
    x = _max_pool_same(x, (3, 3, 3), (2, 2, 2))
    for name in ('mixed_4b', 'mixed_4c', 'mixed_4d', 'mixed_4e', 'mixed_4f'):
        x = _mixed(ops, p, name, x)
    x = _max_pool_same(x, (2, 2, 2), (2, 2, 2))
    x = _mixed(ops, p, 'mixed_5b', x)
    x = _mixed(ops, p, 'mixed_5c', x)
    x = avg_pool(x, (2, x.shape[2], x.shape[3]), (1, 1, 1))
    return x.reshape(x.shape[0], x.shape[1], -1).mean(axis=1)


# -- RAFT ------------------------------------------------------------------

def _cb(ops, p, name, x, stride=1, padding=0):
    return ops.conv(x, p[f'{name}.weight'], stride=stride, padding=padding,
                    bias=p[f'{name}.bias'])


def _encoder(ops, p, prefix, x, batch):
    def norm(t, name):
        return batch_norm(t, p, name) if batch else instance_norm(t)

    x = jnp.maximum(norm(_cb(ops, p, f'{prefix}.conv1', x, 2, 3),
                         f'{prefix}.norm1'), 0)
    for li, stride in ((1, 1), (2, 2), (3, 2)):
        for bi in range(2):
            base = f'{prefix}.layer{li}.{bi}'
            s = stride if bi == 0 else 1
            y = jnp.maximum(norm(_cb(ops, p, f'{base}.conv1', x, s, 1),
                                 f'{base}.norm1'), 0)
            y = jnp.maximum(norm(_cb(ops, p, f'{base}.conv2', y, 1, 1),
                                 f'{base}.norm2'), 0)
            if s != 1:
                x = norm(_cb(ops, p, f'{base}.downsample.0', x, s),
                         f'{base}.norm3')
            x = jnp.maximum(x + y, 0)
    return _cb(ops, p, f'{prefix}.conv2', x)


def _corr_pyramid(ops, fmap1, fmap2):
    n, h, w, d = fmap1.shape
    corr = ops.einsum('nid,njd->nij', fmap1.reshape(n, h * w, d),
                      fmap2.reshape(n, h * w, d)) / np.sqrt(np.float32(d))
    corr = corr.reshape(n * h * w, h, w, 1)
    pyramid = [corr]
    for _ in range(CORR_LEVELS - 1):
        corr = avg_pool(corr, (2, 2), (2, 2))
        pyramid.append(corr)
    return [c[..., 0] for c in pyramid]        # (n·h·w, h_l, w_l)


def _lookup(ops, pyramid, coords):
    """coords (n, h, w, 2) as (x, y) at level 0 → (n, h, w, 4·81). Entry
    a·9+b of a level is the bilinear sample at (x/2^l + d[a], y/2^l + d[b]),
    d = −4…4: the published code adds its (dy, dx) grid onto (x, y) as is."""
    n, h, w, _ = coords.shape
    flat = coords.reshape(n * h * w, 2)
    d = jnp.arange(-CORR_RADIUS, CORR_RADIUS + 1, dtype=jnp.float32)
    out = []
    for lvl, corr in enumerate(pyramid):
        _, hl, wl = corr.shape
        cx = flat[:, 0:1] / (2 ** lvl) + d[None, :]            # (N, 9)
        cy = flat[:, 1:2] / (2 ** lvl) + d[None, :]
        wx = jnp.maximum(0.0, 1.0 - jnp.abs(
            cx[:, :, None] - jnp.arange(wl, dtype=jnp.float32)))
        wy = jnp.maximum(0.0, 1.0 - jnp.abs(
            cy[:, :, None] - jnp.arange(hl, dtype=jnp.float32)))
        t = ops.einsum('nhw,naw->nah', corr, wx)
        o = ops.einsum('nah,nbh->nab', t, wy)
        out.append(o.reshape(n, h, w, -1))
    return jnp.concatenate(out, axis=-1)


def _gru(ops, p, h, x):
    ub = 'update_block.gru'
    for suffix, pad in (('1', [(0, 0), (2, 2)]), ('2', [(2, 2), (0, 0)])):
        hx = jnp.concatenate([h, x], axis=-1)
        z = jax.nn.sigmoid(_cb(ops, p, f'{ub}.convz{suffix}', hx, 1, pad))
        r = jax.nn.sigmoid(_cb(ops, p, f'{ub}.convr{suffix}', hx, 1, pad))
        q = jnp.tanh(_cb(ops, p, f'{ub}.convq{suffix}',
                         jnp.concatenate([r * h, x], axis=-1), 1, pad))
        h = (1 - z) * h + z * q
    return h


def _upsample(ops, flow, mask):
    n, h, w, _ = flow.shape
    mask = jax.nn.softmax(mask.reshape(n, h, w, 9, 8, 8), axis=3)
    fp = jnp.pad(8.0 * flow, [(0, 0), (1, 1), (1, 1), (0, 0)])
    patches = jnp.stack([fp[:, i:i + h, j:j + w] for i in range(3)
                         for j in range(3)], axis=3)          # (n,h,w,9,2)
    up = (mask[..., None] * patches[:, :, :, :, None, None, :]).sum(axis=3)
    return up.transpose(0, 1, 3, 2, 4, 5).reshape(n, 8 * h, 8 * w, 2)


def raft_flow(ops, p, image1, image2, iters=RAFT_ITERS):
    """Two (n, H, W, 3) frames with values 0…255, H and W multiples of 8 →
    (n, H, W, 2) flow in pixels, (x, y)."""
    image1 = 2.0 * (image1.astype(jnp.float32) / 255.0) - 1.0
    image2 = 2.0 * (image2.astype(jnp.float32) / 255.0) - 1.0
    fmap1 = _encoder(ops, p, 'fnet', image1, batch=False)
    fmap2 = _encoder(ops, p, 'fnet', image2, batch=False)
    cnet = _encoder(ops, p, 'cnet', image1, batch=True)
    net = jnp.tanh(cnet[..., :HIDDEN])
    inp = jnp.maximum(cnet[..., HIDDEN:], 0)
    pyramid = _corr_pyramid(ops, fmap1, fmap2)
    n, h, w, _ = fmap1.shape
    ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing='ij')
    coords0 = jnp.broadcast_to(jnp.stack([xs, ys], axis=-1), (n, h, w, 2))
    ub = 'update_block'

    def update(carry, _):
        net, coords1 = carry
        corr = _lookup(ops, pyramid, coords1)
        flow = coords1 - coords0
        cor = jnp.maximum(_cb(ops, p, f'{ub}.encoder.convc1', corr), 0)
        cor = jnp.maximum(_cb(ops, p, f'{ub}.encoder.convc2', cor, 1, 1), 0)
        flo = jnp.maximum(_cb(ops, p, f'{ub}.encoder.convf1', flow, 1, 3), 0)
        flo = jnp.maximum(_cb(ops, p, f'{ub}.encoder.convf2', flo, 1, 1), 0)
        out = jnp.maximum(_cb(ops, p, f'{ub}.encoder.conv',
                              jnp.concatenate([cor, flo], -1), 1, 1), 0)
        motion = jnp.concatenate([out, flow], axis=-1)
        net = _gru(ops, p, net, jnp.concatenate([inp, motion], axis=-1))
        delta = _cb(ops, p, f'{ub}.flow_head.conv2', jnp.maximum(
            _cb(ops, p, f'{ub}.flow_head.conv1', net, 1, 1), 0), 1, 1)
        return (net, coords1 + delta), None

    with ops.repeat(iters):
        (net, coords1), _ = lax.scan(update, (net, coords0), None,
                                     length=iters)
    mask = 0.25 * _cb(ops, p, f'{ub}.mask.2', jnp.maximum(
        _cb(ops, p, f'{ub}.mask.0', net, 1, 1), 0))
    return _upsample(ops, coords1 - coords0, mask)


# -- the extractor's recipe --------------------------------------------------

def pad_to_8(h: int, w: int):
    """RAFT's "sintel" padding to a multiple of 8, centred, the odd pixel
    below / to the right: (top, bottom, left, right)."""
    ph, pw = (-h) % 8, (-w) % 8
    return ph // 2, ph - ph // 2, pw // 2, pw - pw // 2


def flow_stream_input(ops, raft, stacks):
    """(B, 17, H, W, 3) → the flow tower's input (B, 16, 224, 224, 2)."""
    b, s1, h, w, _ = stacks.shape
    t, bo, le, ri = pad_to_8(h, w)
    padded = jnp.pad(stacks, [(0, 0), (0, 0), (t, bo), (le, ri), (0, 0)],
                     mode='edge')
    hp, wp = padded.shape[2:4]
    first = padded[:, :-1].reshape(b * (s1 - 1), hp, wp, 3)
    second = padded[:, 1:].reshape(b * (s1 - 1), hp, wp, 3)
    flow = raft_flow(ops, raft, first, second).reshape(b, s1 - 1, hp, wp, 2)
    i, j = center_crop_offsets(hp, wp, CROP)
    flow = jnp.clip(flow[:, :, i:i + CROP, j:j + CROP], -FLOW_BOUND,
                    FLOW_BOUND)
    levels = jnp.round(128.0 + flow * (255.0 / (2.0 * FLOW_BOUND)))
    return levels * (2.0 / 255.0) - 1.0


def forward(ops, params, units):
    """(B, 17, H, W, 3) uint8 → (B, 2048): rgb tower ‖ flow tower."""
    _, _, h, w, _ = units.shape
    i, j = center_crop_offsets(h, w, CROP)
    rgb = units[:, :-1, i:i + CROP, j:j + CROP].astype(jnp.float32) \
        * (2.0 / 255.0) - 1.0
    out_rgb = i3d_tower(ops, params['i3d_rgb_checkpoint_path'], rgb)
    flow = flow_stream_input(ops, params['raft_checkpoint_path'], units)
    out_flow = i3d_tower(ops, params['i3d_flow_checkpoint_path'], flow)
    return jnp.concatenate([out_rgb, out_flow], axis=-1)
