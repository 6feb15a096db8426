"""Causal attention under a window — query i sees keys i − W + 1 … i — on
both causal paths: the XLA tiles (``ops.attention._causal_blockwise``) and
the fused kernel's windowed lane (``ops/pallas_attention.py``, named
``window_attention``, here under ``interpret=True``), against a dense masked
softmax in float64. Neither path may compute or fetch a key tile that holds
no visible key."""
import numpy as np
import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402

from video_features_tpu.ops import pallas_attention as kernel  # noqa: E402
from video_features_tpu.ops.attention import (  # noqa: E402
    blockwise_attention, resolve_causal,
)
from video_features_tpu.ops.precision import rel_l2  # noqa: E402

S = 64


def visible(s, window):
    """(s, s) bool: key j for query i ⇔ 0 ≤ i − j < window."""
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    return (j <= i) & ((i - j < window) if window else True)


def dense(q, k, v, window, scale=None):
    """Dense attention under the window's mask in float64: what every path
    is held to. Query head j reads key-value head j div group."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    group = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, group, axis=2), np.repeat(v, group, axis=2)
    s = np.einsum('bqhd,bkhd->bhqk', q, k) * (scale or q.shape[-1] ** -0.5)
    s = np.where(visible(q.shape[1], window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum('bhqk,bkhd->bqhd', p / p.sum(-1, keepdims=True), v)


def qkv(seed, heads, kv_heads, d, s=S, b=2):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(1.5 * rng.randn(b, s, heads, d).astype(np.float32)),
            jnp.asarray(rng.randn(b, s, kv_heads, d).astype(np.float32)),
            jnp.asarray(rng.randn(b, s, kv_heads, d).astype(np.float32)))


# a window smaller than a tile, a tile, between two, two tiles, past them
# and one key short of the sequence, against tiles of 16
WINDOWS = [1, 5, 16, 24, 32, 33, S - 1]


@pytest.mark.parametrize('window', WINDOWS)
@pytest.mark.parametrize('heads,kv_heads', [(4, 4), (8, 2)])
def test_the_xla_tiles_under_a_window_match_the_dense_masked_softmax(
        window, heads, kv_heads):
    q, k, v = qkv(3, heads, kv_heads, 16)
    with jax.default_matmul_precision('highest'):
        got = blockwise_attention(q, k, v, block_size=16, causal=True,
                                  window=window)
    assert got.shape == q.shape and got.dtype == jnp.float32
    assert rel_l2(dense(q, k, v, window), got) < 1e-6


@pytest.mark.parametrize('window', [S, S + 5, None])
def test_a_window_no_shorter_than_the_sequence_is_causal_bit_for_bit(window):
    """``window=None`` and ``window ≥ S`` are the plain triangle: the same
    numbers to the bit on the XLA tiles and in the kernel, and on the tiles
    the same program (the jaxpr's text)."""
    q, k, v = qkv(5, 8, 2, 64)

    def tiles(q, k, v, **kw):
        return blockwise_attention(q, k, v, block_size=16, causal=True, **kw)

    with jax.default_matmul_precision('highest'):
        want = tiles(q, k, v)
        got = tiles(q, k, v, window=window)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert str(jax.make_jaxpr(lambda *a: tiles(*a, window=window))(q, k, v)) \
        == str(jax.make_jaxpr(tiles)(q, k, v))
    for passes in (1, 3):
        plain = kernel.causal_attention(q, k, v, 0.125, passes, 16, 32,
                                        interpret=True)
        under = kernel.causal_attention(q, k, v, 0.125, passes, 16, 32,
                                        interpret=True, window=window)
        assert np.array_equal(np.asarray(under), np.asarray(plain))


@pytest.mark.parametrize('window', WINDOWS)
@pytest.mark.parametrize('heads,kv_heads,d,block_q,block_k', [
    (16, 2, 128, 8, 16),   # the cell's group: eight 128-wide heads a step
    (8, 2, 64, 8, 16),     # lfm2's: four 64-wide heads
    (8, 2, 64, 32, 16),    # a query tile that reaches two new key tiles
    (4, 4, 64, 16, 16),    # equal head counts
])
def test_the_kernels_windowed_lane_matches_the_dense_masked_softmax(
        window, heads, kv_heads, d, block_q, block_k):
    q, k, v = qkv(7, heads, kv_heads, d)
    want = dense(q, k, v, window)
    got = kernel.causal_attention(q, k, v, d ** -0.5, 3, block_q, block_k,
                                  interpret=True, window=window)
    assert got.shape == q.shape and got.dtype == jnp.float32
    assert rel_l2(want, got) < 1e-5
    with jax.default_matmul_precision('highest'):
        tiles = blockwise_attention(q, k, v, block_size=16, causal=True,
                                    window=window)
    assert rel_l2(tiles, got) < 1e-5


@pytest.mark.parametrize('passes,low,high', [
    (3, 0.0, 1e-5),      # hi·hi + hi·lo + lo·hi: float32-grade
    (1, 5e-4, 1e-2),     # the head alone: the control lane stays a control
])
def test_the_windowed_lane_makes_the_passes_it_is_asked_for(passes, low,
                                                            high):
    q, k, v = qkv(9, 16, 2, 128)
    got = kernel.causal_attention(q, k, v, 128 ** -0.5, passes, 8, 16,
                                  interpret=True, window=24)
    assert low <= rel_l2(dense(q, k, v, 24), got) < high


def test_the_windowed_lane_survives_rows_that_see_no_key_of_an_edge_tile():
    """Window 17 over query tiles of 8 and key tiles of 16: the last rows
    of every second query tile see nothing of the first tile their band
    visits (their oldest key lies in the next), at scores large enough that
    a wrong running max would overflow."""
    q, k, v = qkv(13, 8, 2, 64)
    got = kernel.causal_attention(30.0 * q, k, v, 0.125, 3, 8, 16,
                                  interpret=True, window=17)
    assert np.isfinite(np.asarray(got)).all()
    # scores thirty times larger carry thirty times the passes' rounding
    assert rel_l2(dense(30.0 * q, k, v, 17), got) < 1e-4


# -- no key tile without a visible key ------------------------------------------

def band_of(qi, block_q, block_k, window, s=S):
    """Key tiles that hold a key some row of query tile ``qi`` sees, by
    brute force over the mask."""
    seen = visible(s, window)[qi * block_q:(qi + 1) * block_q]
    return [t for t in range(s // block_k)
            if seen[:, t * block_k:(t + 1) * block_k].any()]


@pytest.mark.parametrize('block_q,block_k,window', [
    (8, 16, 24), (8, 16, 17), (16, 16, 16), (32, 16, 5), (8, 8, 33),
    (16, 16, 1)])
def test_the_windowed_grid_is_the_band_and_no_more(block_q, block_k, window):
    """The grid's key axis has as many steps as the widest band has tiles
    (the ring's slots too), and a query tile's steps start at its band's
    first tile and end at its last: counted against the mask itself."""
    bands = [band_of(qi, block_q, block_k, window)
             for qi in range(S // block_q)]
    ring = kernel.resident_tiles(S, block_q, block_k, window)
    assert ring == max(len(b) for b in bands) < S // block_k
    for qi, band in enumerate(bands):
        first = int(kernel._first_key_tile(qi, block_q, block_k, window))
        last = int(kernel._last_key_tile(qi, block_q, block_k))
        assert list(range(first, last + 1)) == band, qi
    q, k, v = (jax.ShapeDtypeStruct((1, S, h, 64), jnp.float32)
               for h in (8, 2, 2))
    jaxpr = jax.make_jaxpr(lambda *a: kernel.causal_attention(
        *a, 0.125, 3, block_q, block_k, interpret=True, window=window))(
        q, k, v)
    call = [e for e in jaxpr.eqns if e.primitive.name == 'pallas_call'][0]
    assert call.params['grid_mapping'].grid == (1, 2, S // block_q, ring)
    assert call.params['name'] == 'window_attention'
    # and without a window the key axis is the whole sequence's tiles
    assert kernel.resident_tiles(S, block_q, block_k) == S // block_k
    assert kernel.resident_tiles(S, block_q, block_k, S) == S // block_k


@pytest.mark.parametrize('path', ['kernel', 'xla'])
@pytest.mark.parametrize('block_q,block_k,window', [
    (8, 16, 24), (8, 16, 17), (32, 16, 5)])
def test_a_key_tile_outside_a_query_tiles_band_is_neither_read_nor_computed(
        path, block_q, block_k, window):
    """Keys and values outside one query tile's band are NaN. A path that
    computed such a tile under a mask would multiply its zero weights with
    NaN values, one that read the ring's wrong slot would see NaN keys: the
    tile's rows come out finite and right only if neither happens."""
    q, k, v = qkv(17, 8, 2, 64, b=1)
    want = dense(q, k, v, window)
    if path == 'xla':
        block_q = block_k           # the XLA tiles are square
    for qi in range(S // block_q):
        band = band_of(qi, block_q, block_k, window)
        poison = np.ones((S,), bool)
        for t in band:
            poison[t * block_k:(t + 1) * block_k] = False
        kp, vp = (jnp.where(poison[None, :, None, None], jnp.nan, t)
                  for t in (k, v))
        if path == 'kernel':
            got = kernel.causal_attention(q, kp, vp, 0.125, 3, block_q,
                                          block_k, interpret=True,
                                          window=window)
        else:
            with jax.default_matmul_precision('highest'):
                got = blockwise_attention(q, kp, vp, block_size=block_k,
                                          causal=True, window=window)
        rows = slice(qi * block_q, (qi + 1) * block_q)
        assert np.isfinite(np.asarray(got[:, rows])).all(), qi
        assert rel_l2(want[:, rows], got[:, rows]) < 1e-5, qi


# -- where the lane applies -------------------------------------------------------

@pytest.mark.parametrize('platform,s,precision,window,want', [
    ('tpu', 32768, 'high', 2048, 'kernel'),     # the cell's sliding layers
    ('tpu', 32768, 'default', 2048, 'kernel'),  # their control lane
    ('tpu', 32768, 'highest', 2048, 'xla'),
    ('cpu', 32768, 'high', 2048, 'xla'),
    ('tpu', 131072, 'high', 2048, 'kernel'),    # the band, not the sequence
    ('tpu', 131072, 'high', None, 'xla'),       # 168 MB of packed K and V
    ('tpu', 131072, 'high', 131072, 'xla'),     # a window that is none
])
def test_resolve_causal_takes_the_window_into_its_vmem_test(
        platform, s, precision, window, want):
    """32 query heads of 128 over 4 key-value heads: under a window the
    kernel keeps the band's 5 tiles of 512 keys, whatever the sequence; a
    full layer's 41.9 MB at 32,768 positions pass the budget too."""
    assert resolve_causal('tpu', 32768, 128, 128, 'high', 32, 4) == 'kernel'
    assert resolve_causal(platform, s, 128, 128, precision, 32, 4,
                          window) == want
    if want == 'kernel':
        block_q, block_k = kernel.tiles(s, 8, window)
        assert (block_q, block_k) == (128, 512)
        assert kernel.resident_tiles(s, block_q, block_k, window) == 5
        assert kernel.tiles(s, 8) == (128, 1024)


def test_the_windowed_call_lowered_for_a_tpu_carries_its_own_name():
    """One custom call named window_attention (never causal_attention…: the
    listed causal_attention_roofline matches by that prefix); the plain
    triangle keeps causal_attention. Lowered from the CPU: nothing runs."""
    q, k, v = (jax.ShapeDtypeStruct((2, 512, h, 128), jnp.float32)
               for h in (16, 2, 2))
    for window, name in ((256, 'window_attention'),
                         (None, 'causal_attention'),
                         (512, 'causal_attention')):
        text = jax.jit(lambda *a: kernel.causal_attention(
            *a, 128 ** -0.5, 3, 128, 128, window=window)).trace(
                q, k, v).lower(lowering_platforms=('tpu',)).as_text()
        assert text.count('tpu_custom_call') == 1
        assert text.count(f'kernel_name = "{name}"') == 1


@pytest.mark.parametrize('window', [0, -3])
def test_a_window_that_sees_nothing_is_refused(window):
    q, k, v = qkv(1, 4, 4, 64)
    with pytest.raises(ValueError, match='sees nothing'):
        kernel.causal_attention(q, k, v, 0.125, 3, 16, 16, interpret=True,
                                window=window)
    with pytest.raises(ValueError, match='sees nothing'):
        blockwise_attention(q, k, v, block_size=16, causal=True,
                            window=window)
    with pytest.raises(ValueError, match='causal'):
        blockwise_attention(q, k, v, block_size=16, window=8)
