#!/usr/bin/env python3
"""Measured answer to "would a fused GRU Pallas kernel beat XLA?": NO.

Implements ONE SepConvGRU direction (reference update.py:39-77, the 1x5
pass — zr gate conv + q conv + sigmoid/tanh gating, 45% of the refinement
iteration's FLOPs and its cleanest structure) as a Mosaic kernel:

  * a (P pairs x HB rows) activation block resident in VMEM (the 1x5 conv
    has no H halo, so H blocks freely);
  * inputs hi/lo-split to bf16 ONCE per buffer; each of the 5 conv taps is
    3 bf16 MXU dots (manual bf16_3x == XLA 'high' — Mosaic does not expose
    multi-pass precision natively);
  * the tap window slides over the LEADING (untiled) buffer dim so dynamic
    slices need no sublane alignment;
  * gating fused in-kernel, one f32 write per output.

Result on v5e (2026-07-31, B=256 pairs, 28x28 maps, 30-iteration scan):

    xla conv direction (precision 'high'):  2.72 ms
    this kernel        (manual bf16_3x):    2.71 ms

i.e. XLA's implicit-GEMM conv + fused epilogues already sits at the
hand-kernel frontier for these shapes. Together with the precision sweep
(tools/precision_study.py: no component tolerates 1-pass) this closes the
"build a per-iteration GRU fusion" question — the mixed/default gap is
3-pass bf16 arithmetic, not a schedulable kernel win. Full analysis:
docs/benchmarks.md "Why a fused GRU kernel does not close the gap".
"""
import time
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from video_features_tpu.utils.device import enable_compilation_cache, jax_device

platform = jax.devices()[0].platform
enable_compilation_cache('auto', platform)
dev = jax_device(platform)
interpret = platform != 'tpu'

B, H, W, C = 256, 28, 28, 128   # pairs, map, hidden dim
CM = 2 * C                       # hm channels
P, HB = 4, 7                     # block: P pairs x HB rows
K = 5                            # tap count
PREC = jax.lax.Precision.HIGH

rng = np.random.RandomState(0)
h = jax.device_put(np.tanh(rng.randn(B, H, W, C)).astype(np.float32), dev)
motion = jax.device_put(rng.randn(B, H, W, C).astype(np.float32), dev)
Wzr = jax.device_put((rng.randn(K, CM, CM) * 0.05).astype(np.float32), dev)
Wq = jax.device_put((rng.randn(K, CM, C) * 0.05).astype(np.float32), dev)
zr_term = jax.device_put((rng.randn(B, H, W, CM) * 0.1).astype(np.float32), dev)
q_term = jax.device_put((rng.randn(B, H, W, C) * 0.1).astype(np.float32), dev)


def xla_direction(h, motion, Wzr, Wq, zr_term, q_term):
    with jax.default_matmul_precision('high'):
        hm = jnp.concatenate([h, motion], -1)
        hp = jnp.pad(hm, [(0, 0), (0, 0), (2, 2), (0, 0)])
        zr = zr_term
        for s in range(K):
            zr = zr + jnp.einsum('bhwc,cn->bhwn', hp[:, :, s:s + W], Wzr[s],
                                 precision=PREC)
        zr = jax.nn.sigmoid(zr)
        z, r = jnp.split(zr, 2, -1)
        rhm = jnp.concatenate([r * h, motion], -1)
        rp = jnp.pad(rhm, [(0, 0), (0, 0), (2, 2), (0, 0)])
        q = q_term
        for s in range(K):
            q = q + jnp.einsum('bhwc,cn->bhwn', rp[:, :, s:s + W], Wq[s],
                               precision=PREC)
        q = jnp.tanh(q)
        return (1 - z) * h + z * q


def xla_conv_direction(h, motion, Wzr, Wq, zr_term, q_term):
    from video_features_tpu.ops.nn import conv
    with jax.default_matmul_precision('high'):
        hm = jnp.concatenate([h, motion], -1)
        zr = conv(hm, Wzr.transpose(1, 0, 2).reshape(1, K, CM, CM),
                  padding=[(0, 0), (2, 2)]) + zr_term
        zr = jax.nn.sigmoid(zr)
        z, r = jnp.split(zr, 2, -1)
        q = conv(jnp.concatenate([r * h, motion], -1),
                 Wq.transpose(1, 0, 2).reshape(1, K, CM, C),
                 padding=[(0, 0), (2, 2)]) + q_term
        q = jnp.tanh(q)
        return (1 - z) * h + z * q


# ------------------------------------------------------------- the kernel --
def _split(x):
    xh = x.astype(jnp.bfloat16)
    xl = (x - xh.astype(jnp.float32)).astype(jnp.bfloat16)
    return xh, xl


def _band_matmul(bh_ref, bl_ref, w_h_ref, w_l_ref, acc):
    """acc += 1x5 conv of the (W+4, M, CM) padded hi/lo scratch refs with
    the (K, CM, n_out) hi/lo weights — per tap, 3 bf16 dots (bf16_3x).
    The sliding dim is LEADING (untiled), so dynamic taps need no sublane
    alignment."""
    M = bh_ref.shape[1]

    def tap(s, acc):
        sh = bh_ref[pl.ds(s, W)].reshape(W * M, CM)
        sl = bl_ref[pl.ds(s, W)].reshape(W * M, CM)
        wh = w_h_ref[s]
        wl = w_l_ref[s]
        acc += jnp.dot(sh, wh, preferred_element_type=jnp.float32)
        acc += jnp.dot(sh, wl, preferred_element_type=jnp.float32)
        acc += jnp.dot(sl, wh, preferred_element_type=jnp.float32)
        return acc

    return lax.fori_loop(0, K, tap, acc)


def _kernel(h_ref, m_ref, zrt_ref, qt_ref, wzrh_ref, wzrl_ref,
            wqh_ref, wql_ref, out_ref, bh_ref, bl_ref):
    # everything in (W, M, C) layout: W leads so the conv taps slide over
    # an untiled dim; one transpose in, one out
    M = P * HB
    h = h_ref[:].reshape(M, W, C).swapaxes(0, 1)           # (W, M, C)
    m = m_ref[:].reshape(M, W, C).swapaxes(0, 1)
    zrt = zrt_ref[:].reshape(M, W, CM).swapaxes(0, 1).reshape(W * M, CM)
    qt = qt_ref[:].reshape(M, W, C).swapaxes(0, 1).reshape(W * M, C)
    zpad = jnp.zeros((2, M, CM), jnp.bfloat16)

    hm_h, hm_l = _split(jnp.concatenate([h, m], -1))
    bh_ref[0:2] = zpad
    bl_ref[0:2] = zpad
    bh_ref[W + 2:] = zpad
    bl_ref[W + 2:] = zpad
    bh_ref[2:W + 2] = hm_h
    bl_ref[2:W + 2] = hm_l
    zr = _band_matmul(bh_ref, bl_ref, wzrh_ref, wzrl_ref, zrt)
    zr = jax.nn.sigmoid(zr).reshape(W, M, CM)
    z = zr[:, :, :C]
    r = zr[:, :, C:]

    rhm_h, rhm_l = _split(jnp.concatenate([r * h, m], -1))
    bh_ref[2:W + 2] = rhm_h
    bl_ref[2:W + 2] = rhm_l
    q = _band_matmul(bh_ref, bl_ref, wqh_ref, wql_ref, qt)
    q = jnp.tanh(q).reshape(W, M, C)

    out = (1 - z) * h + z * q                              # (W, M, C)
    out_ref[:] = out.swapaxes(0, 1).reshape(P, HB, W, C)


def pallas_direction(h, motion, Wzr, Wq, zr_term, q_term):
    grid = (B // P, H // HB)
    blk = lambda c: pl.BlockSpec((P, HB, W, c), lambda i, j: (i, j, 0, 0),
                                 memory_space=pltpu.VMEM)
    wspec = lambda shape: pl.BlockSpec(shape, lambda i, j: (0,) * len(shape),
                                       memory_space=pltpu.VMEM)
    wzrh, wzrl = _split(Wzr)
    wqh, wql = _split(Wq)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[blk(C), blk(C), blk(CM), blk(C),
                  wspec((K, CM, CM)), wspec((K, CM, CM)),
                  wspec((K, CM, C)), wspec((K, CM, C))],
        out_specs=blk(C),
        out_shape=jax.ShapeDtypeStruct((B, H, W, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((W + 4, P * HB, CM), jnp.bfloat16),
                        pltpu.VMEM((W + 4, P * HB, CM), jnp.bfloat16)],
        interpret=interpret,
    )(h, motion, zr_term, q_term, wzrh, wzrl, wqh, wql)


def bench(fn, iters=30):
    j = jax.jit(lambda *a: lax.scan(
        lambda acc, _: (acc + fn(*a).sum(), None),
        jnp.float32(0), None, length=iters)[0])
    float(j(h, motion, Wzr, Wq, zr_term, q_term))
    t0 = time.perf_counter()
    float(j(h, motion, Wzr, Wq, zr_term, q_term))
    return (time.perf_counter() - t0) / iters * 1000


ref = np.asarray(jax.jit(xla_direction)(h, motion, Wzr, Wq, zr_term, q_term))
got = np.asarray(jax.jit(pallas_direction)(h, motion, Wzr, Wq, zr_term, q_term))
rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
print(f'kernel vs xla rel L2: {rel:.2e}')
print(f'xla einsum direction: {bench(xla_direction):.2f} ms')
print(f'xla conv   direction: {bench(xla_conv_direction):.2f} ms')
print(f'pallas     direction: {bench(pallas_direction):.2f} ms')
