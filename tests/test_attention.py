"""dense / blockwise / ring attention equivalence.

Ring attention runs on the 8-virtual-device CPU mesh from conftest — the
same shard_map program a TPU slice would compile, with ppermute collectives
over the time axis.
"""
import numpy as np
import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402

from video_features_tpu.ops.attention import (  # noqa: E402
    blockwise_attention, dense_attention,
)
from video_features_tpu.parallel.mesh import make_mesh  # noqa: E402
from video_features_tpu.parallel.ring import (  # noqa: E402
    sequence_sharded_attention, sequence_sharding,
)


def _qkv(rng, b=2, s=64, h=4, d=16):
    def t():
        return jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    return t(), t(), t()


def test_blockwise_matches_dense():
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng)
    ref = dense_attention(q, k, v)
    for block in (8, 16, 64):
        got = blockwise_attention(q, k, v, block_size=block)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_blockwise_large_scale_stability():
    """Large score magnitudes: online softmax must not overflow."""
    rng = np.random.RandomState(1)
    q, k, v = _qkv(rng, s=32)
    q = q * 40.0  # scores ~ O(1000) pre-softmax
    ref = dense_attention(q, k, v)
    got = blockwise_attention(q, k, v, block_size=8)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize('time_parallel', [2, 4, 8])
def test_ring_matches_dense(time_parallel):
    if len(jax.devices()) < time_parallel:
        pytest.skip('needs virtual device mesh')
    mesh = make_mesh(time_parallel, time_parallel=time_parallel)
    rng = np.random.RandomState(2)
    q, k, v = _qkv(rng, s=8 * time_parallel)
    ref = dense_attention(q, k, v)

    sharding = sequence_sharding(mesh)
    qs, ks, vs = (jax.device_put(t, sharding) for t in (q, k, v))
    got = sequence_sharded_attention(mesh, qs, ks, vs)
    assert got.sharding.is_equivalent_to(sharding, got.ndim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_custom_scale():
    mesh = make_mesh(2, time_parallel=2)
    rng = np.random.RandomState(3)
    q, k, v = _qkv(rng, s=16)
    ref = dense_attention(q, k, v, scale=0.5)
    got = sequence_sharded_attention(mesh, q, k, v, scale=0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_blockwise_ragged_matches_dense():
    """ViT token counts (grid²+1) are never block-aligned; the pad+mask
    path must agree with dense attention."""
    import numpy as np

    from video_features_tpu.ops.attention import (
        blockwise_attention, dense_attention,
    )
    rng = np.random.RandomState(0)
    q, k, v = (rng.randn(2, 197, 3, 16).astype(np.float32) for _ in range(3))
    ref = np.asarray(dense_attention(q, k, v))
    got = np.asarray(blockwise_attention(q, k, v, block_size=64))
    np.testing.assert_allclose(got, ref, atol=2e-5)


# -- the fused causal kernel (ops/pallas_attention.py), interpreted -----------

def _causal_reference(q, k, v, scale=None):
    """Dense causal attention in float64: what every path is held to."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    group = q.shape[2] // k.shape[2]    # query head j reads head j // group
    k, v = np.repeat(k, group, axis=2), np.repeat(v, group, axis=2)
    s = np.einsum('bqhd,bkhd->bhqk', q, k) * (scale or q.shape[-1] ** -0.5)
    n = q.shape[1]
    s = np.where(np.tril(np.ones((n, n), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum('bhqk,bkhd->bqhd', p / p.sum(-1, keepdims=True), v)


def _rel_l2(got, want):
    from video_features_tpu.ops.precision import rel_l2
    return rel_l2(want, got)


def _latent_qkv(seed, b=2, s=64, h=3, qk=192, v=128, kv_heads=None):
    """q/k and v at latent attention's unequal head widths, 192 and 128;
    ``kv_heads`` gives k and v fewer heads than q."""
    rng = np.random.RandomState(seed)
    g = kv_heads or h
    return (jnp.asarray(rng.randn(b, s, h, qk).astype(np.float32)),
            jnp.asarray(rng.randn(b, s, g, qk).astype(np.float32)),
            jnp.asarray(rng.randn(b, s, g, v).astype(np.float32)))


@pytest.mark.parametrize('block_q,block_k', [
    (64, 64),           # one tile a side: the diagonal tile alone
    (32, 32),           # two
    (16, 16),           # four
    (32, 16),           # a query tile the diagonal crosses two key tiles of
    (16, 32),           # a key tile wider than the query tile
])
def test_causal_kernel_matches_dense_with_a_narrower_value_head(block_q,
                                                                block_k):
    from video_features_tpu.ops.pallas_attention import causal_attention
    q, k, v = _latent_qkv(4)
    got = causal_attention(q, k, v, 192 ** -0.5, 3, block_q, block_k,
                           interpret=True)
    assert got.shape == (2, 64, 3, 128) and got.dtype == jnp.float32
    want = _causal_reference(q, k, v)
    assert _rel_l2(got, want) < 1e-5
    # the first row sees one key (its output is that key's value), the last
    # row all of them
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(v[:, 0]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got[:, -1]), want[:, -1],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('passes', [1, 3])
def test_causal_kernel_takes_heads_as_column_groups(passes):
    """Latent attention's call: q and k as (nope, rope) groups, the rotary
    key ONE head shared by all — the same numbers as the concatenated,
    broadcast head."""
    from video_features_tpu.ops.pallas_attention import causal_attention
    q, k, v = _latent_qkv(10)
    k = k.at[..., 128:].set(k[:, :, :1, 128:])     # one rotary key for all
    whole = causal_attention(q, k, v, 192 ** -0.5, passes, 32, 16,
                             interpret=True)
    parts = causal_attention((q[..., :128], q[..., 128:]),
                             (k[..., :128], k[:, :, :1, 128:]), v,
                             192 ** -0.5, passes, 32, 16, interpret=True)
    np.testing.assert_array_equal(np.asarray(parts), np.asarray(whole))
    assert _rel_l2(parts, _causal_reference(q, k, v)) < (1e-5 if passes == 3
                                                         else 1e-2)


@pytest.mark.parametrize('passes,low,high', [
    (3, 0.0, 1e-5),      # hi·hi + hi·lo + lo·hi: float32-grade
    (1, 5e-4, 1e-2),     # the head alone: the control lane stays a control
])
def test_causal_kernel_makes_the_passes_it_is_asked_for(passes, low, high):
    """Held to the XLA path at 'highest': three passes sit within 1e-5 of
    it, one pass measurably does not — what the benchmark's control lane
    (precision=default) relies on to stay not correct."""
    from video_features_tpu.ops.pallas_attention import causal_attention
    q, k, v = _latent_qkv(5)
    with jax.default_matmul_precision('highest'):
        want = blockwise_attention(q, k, v, block_size=16, causal=True)
    got = causal_attention(q, k, v, 192 ** -0.5, passes, 32, 16,
                           interpret=True)
    assert low <= _rel_l2(got, want) < high


def test_causal_kernel_survives_large_scores():
    """Scores of O(1000): the running max must carry the online softmax."""
    from video_features_tpu.ops.pallas_attention import causal_attention
    q, k, v = _latent_qkv(6, b=1, s=32)
    got = causal_attention(q * 20.0, k, v, 192 ** -0.5, 3, 16, 16,
                           interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    assert _rel_l2(got, _causal_reference(q * 20.0, k, v)) < 1e-4


@pytest.mark.parametrize('passes,block_q,qk,match', [
    (6, 16, 192, '1 or 3'),             # 'highest' has no lane here
    (3, 32, 192, 'no multiple'),        # 48 positions, tiles of 32
    (3, 16, 96, 'multiples of 64'),     # a head width the packing cannot lay
])
def test_causal_kernel_refuses_what_it_has_no_lane_for(passes, block_q, qk,
                                                       match):
    from video_features_tpu.ops.pallas_attention import causal_attention
    q, k, v = _latent_qkv(7, b=1, s=48, qk=qk)
    with pytest.raises(ValueError, match=match):
        causal_attention(q, k, v, 1.0, passes, block_q, 16, interpret=True)


def _grouped_qkv(seed, b=2, s=64, heads=8, kv_heads=2, d=64):
    """Grouped-query heads at lfm2's width: 64-wide q, k and v, ``heads``
    query heads reading ``kv_heads`` key-value heads."""
    return _latent_qkv(seed, b, s, heads, d, d, kv_heads)


@pytest.mark.parametrize('heads,kv_heads,block_q,block_k', [
    (8, 2, 64, 64),     # groups of 4, one tile a side
    (8, 2, 16, 16),     # four tiles a side
    (8, 2, 16, 32),     # a key tile two query tiles cross: packed by the first
    (8, 2, 32, 16),     # a query tile the diagonal crosses two key tiles of
    (8, 2, 16, 64),     # every query tile crosses the one key tile
    (4, 2, 16, 32),     # groups of 2: 128 columns a key-value head
    (6, 1, 32, 32),     # one key-value head for all six query heads
])
def test_causal_kernel_grouped_query_lane_matches_dense_and_the_xla_tiles(
        heads, kv_heads, block_q, block_k):
    """A grid step is one key-value head and its query heads (batch 2): the
    numbers of dense causal attention with the keys and values repeated,
    and of the XLA tiles the CPU keeps, which fold the group onto the query
    axis."""
    from video_features_tpu.ops.pallas_attention import causal_attention
    q, k, v = _grouped_qkv(11, heads=heads, kv_heads=kv_heads)
    got = causal_attention(q, k, v, 0.125, 3, block_q, block_k,
                           interpret=True)
    assert got.shape == (2, 64, heads, 64) and got.dtype == jnp.float32
    want = _causal_reference(q, k, v)
    assert _rel_l2(got, want) < 1e-5
    group = heads // kv_heads
    with jax.default_matmul_precision('highest'):
        tiles = blockwise_attention(q, k, v, block_size=16, causal=True)
        # the last row sees every key: dense attention's row, heads repeated
        last = dense_attention(q[:, -1:], jnp.repeat(k, group, 2),
                               jnp.repeat(v, group, 2))
    assert _rel_l2(got, tiles) < 1e-5
    np.testing.assert_allclose(np.asarray(got[:, -1:]), np.asarray(last),
                               rtol=2e-5, atol=2e-5)
    # the first row sees one key: its output is its key-value head's value
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.repeat(np.asarray(v[:, 0]), group, 1),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('passes,low,high', [
    (3, 0.0, 1e-5),      # hi·hi + hi·lo + lo·hi: float32-grade
    (1, 5e-4, 1e-2),     # the head alone: the control lane stays a control
])
def test_causal_kernel_grouped_lane_makes_the_passes_it_is_asked_for(
        passes, low, high):
    from video_features_tpu.ops.pallas_attention import causal_attention
    q, k, v = _grouped_qkv(12)
    with jax.default_matmul_precision('highest'):
        want = blockwise_attention(q, k, v, block_size=16, causal=True)
    got = causal_attention(q, k, v, 0.125, passes, 16, 32, interpret=True)
    assert low <= _rel_l2(got, want) < high


def test_causal_kernel_grouped_lane_is_the_equal_heads_lane_on_repeats():
    """Group 1 is unchanged, and the grouped lane is it to the bit: the
    same heads with keys and values repeated (128-wide values: the equal-
    heads lane's output block) give the same numbers, head by head."""
    from video_features_tpu.ops.pallas_attention import causal_attention
    rng = np.random.RandomState(13)
    q = jnp.asarray(rng.randn(2, 64, 4, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 64, 2, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 64, 2, 128).astype(np.float32))
    grouped = causal_attention(q, k, v, 0.125, 3, 16, 32, interpret=True)
    equal = causal_attention(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2),
                             0.125, 3, 16, 32, interpret=True)
    np.testing.assert_array_equal(np.asarray(grouped), np.asarray(equal))


def test_causal_kernel_grouped_lane_survives_large_scores():
    """Scores of O(1000): the running max is kept per query head of the
    group."""
    from video_features_tpu.ops.pallas_attention import causal_attention
    q, k, v = _grouped_qkv(14, b=1, s=32)
    q = q * jnp.asarray([1., 40., 5., 20., 40., 1., 20., 5.])[:, None]
    got = causal_attention(q, k, v, 0.125, 3, 16, 16, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    assert _rel_l2(got, _causal_reference(q, k, v)) < 1e-4


@pytest.mark.parametrize('heads,kv_heads,v_dim,match', [
    (6, 4, 64, 'no whole number of groups'),
    (6, 2, 64, 'fill no whole 128-lane blocks'),    # 3 × 64 columns
    (4, 2, 96, 'fill no whole 128-lane blocks'),    # 2 × 96 value columns
])
def test_causal_kernel_refuses_groups_it_has_no_lane_for(heads, kv_heads,
                                                         v_dim, match):
    from video_features_tpu.ops.pallas_attention import causal_attention
    q, k, _ = _grouped_qkv(15, b=1, s=32, heads=heads, kv_heads=kv_heads)
    v = jnp.zeros((1, 32, kv_heads, v_dim), jnp.float32)
    with pytest.raises(ValueError, match=match):
        causal_attention(q, k, v, 1.0, 3, 16, 16, interpret=True)


@pytest.mark.parametrize('groups,passes,want', [
    ((192,), 3, (640, 256)),    # [hi hi lo] of 128, then of 64 padded to 256
    ((128, 64), 3, (640, 256)),  # latent attention's nope and rope groups
    ((192,), 1, (192, 128)),
    ((128,), 3, (384, 256)),
    ((64,), 3, (256, 256)),
])
def test_packed_widths_are_whole_lane_chunks(groups, passes, want):
    from video_features_tpu.ops.pallas_attention import packed_widths
    assert packed_widths(groups, 128, passes) == want


# (platform, positions, q/k width, v width, ambient precision, answer), equal
# head counts
EQUAL_HEADS = [
    ('tpu', 8192, 192, 128, 'high', 'kernel'),      # the cell
    ('tpu', 8192, 192, 128, 'default', 'kernel'),   # its control lane
    ('tpu', 8192, 192, 128, None, 'kernel'),        # unset = one pass
    ('tpu', 8192, 192, 128, 'highest', 'xla'),      # never under six passes
    ('tpu', 8192, 192, 128, 'float32', 'xla'),
    ('tpu', 8192, 192, 128, 'tensorfloat32', 'xla'),
    ('cpu', 8192, 192, 128, 'high', 'xla'),         # tier-1's path
    ('gpu', 8192, 192, 128, 'high', 'xla'),
    ('tpu', 8192 + 512, 192, 128, 'high', 'xla'),   # no tile multiple
    ('tpu', 8192, 192, 96, 'high', 'xla'),          # v not 128-wide
    ('tpu', 8192, 200, 128, 'high', 'xla'),         # odd q/k width
    ('tpu', 64, 192, 128, 'high', 'xla'),           # a tile under 128 lanes
    ('tpu', 128, 128, 128, 'high', 'kernel'),       # one aligned tile
    ('tpu', 65536, 192, 128, 'high', 'xla'),        # K/V past the VMEM budget
]
# the same with (query, key-value) head counts last
GROUPED_HEADS = [
    # lfm2-moe's cell
    ('tpu', 8192, 64, 64, 'high', 'kernel', (32, 8)),
    ('tpu', 8192, 64, 64, 'default', 'kernel', (32, 8)),    # its control
    ('cpu', 8192, 64, 64, 'high', 'xla', (32, 8)),
    ('tpu', 8192, 64, 64, 'highest', 'xla', (32, 8)),
    ('tpu', 8192, 64, 64, 'high', 'xla', (32, 32)),     # one 64-wide v a step
    ('tpu', 8192, 64, 64, 'high', 'kernel', (32, 16)),  # two fill a block
    ('tpu', 8192, 64, 64, 'high', 'xla', (24, 8)),      # three do not
    ('tpu', 8192, 64, 64, 'high', 'xla', (32, 12)),     # no whole groups
    ('tpu', 8192 + 256, 64, 64, 'high', 'xla', (32, 8)),    # no tile multiple
    ('tpu', 128, 64, 64, 'high', 'kernel', (32, 8)),    # one aligned tile
    ('tpu', 8192, 128, 128, 'high', 'kernel', (40, 8)),  # 128-wide: any group
]


@pytest.mark.parametrize(
    'platform,s,qk,v,precision,want,heads',
    [(*row, (1, 1)) for row in EQUAL_HEADS] + GROUPED_HEADS)
def test_resolve_causal_decides_from_platform_shapes_and_precision(
        platform, s, qk, v, precision, want, heads):
    from video_features_tpu.ops.attention import resolve_causal
    assert resolve_causal(platform, s, qk, v, precision, *heads) == want
    if heads == (1, 1):      # the head counts left out: equal
        assert resolve_causal(platform, s, qk, v, precision) == want


def test_causal_kernel_lowered_for_a_tpu_is_one_named_mosaic_call():
    """One custom call named causal_attention whatever the batch, under
    three passes and under one. Lowered from the CPU: nothing runs."""
    from video_features_tpu.ops.pallas_attention import causal_attention
    q, k, v = (jax.ShapeDtypeStruct((2, 256, 2, d), jnp.float32)
               for d in (192, 192, 128))
    for passes in (3, 1):
        text = jax.jit(lambda *a: causal_attention(
            *a, 192 ** -0.5, passes, 128, 128)).trace(q, k, v).lower(
                lowering_platforms=('tpu',)).as_text()
        assert text.count('tpu_custom_call') == 1
        assert text.count('kernel_name = "causal_attention"') == 1
