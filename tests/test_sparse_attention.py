"""Learned sparse attention's ops on the CPU: the selection of keys as bits
(``ops.attention.pack_keep``), the lightning indexer that makes it
(``ops/sparse_index.py``), and both causal paths under it — the XLA tiles
(``ops.attention._causal_blockwise``) and the kernel's keep lane
(``ops/pallas_attention.py``, interpreted) — against a dense masked
softmax."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from video_features_tpu.ops.attention import (
    KEEP_BITS, blockwise_attention, keep_lanes, pack_keep, resolve_causal,
    unpack_keep,
)
from video_features_tpu.ops.pallas_attention import (
    SPARSE_NAME, causal_attention,
)
from video_features_tpu.ops.sparse_index import select_keys, top_keys

S = 64


def selection(seed, s=S, share=0.3, b=1):
    """A random selection a causal layer could get: every row keeps some
    keys at or before it, its own among them, and two rows keep one early
    key alone (so they see no key of most tiles)."""
    rng = np.random.default_rng(seed)
    keep = (rng.random((b, s, s)) < share) & np.tril(np.ones((s, s), bool))
    keep[:, np.arange(s), np.arange(s)] = True
    keep[:, 40] = False
    keep[:, 40, 3] = True
    keep[:, 21] = False
    keep[:, 21, 20] = True
    return keep


def dense(q, k, v, keep, scale):
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * scale
    s = jnp.where(jnp.asarray(keep)[:, None], s, -jnp.inf)
    return jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, axis=-1), v)


def qkv(seed, heads=2, d=64, dv=64, s=S, b=1):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((b, s, heads, w)), jnp.float32)
            for w in (d, d, dv)]


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# -- the bits ---------------------------------------------------------------------

@pytest.mark.parametrize('s,lanes', [(32, 1), (64, 2), (2048, 64),
                                     (8192, 128), (12288, 128)])
def test_a_selection_packs_32_keys_a_word_in_planes_of_lanes(s, lanes):
    """Key u is bit b of word l of group g, u = g·32·lanes + b·lanes + l: a
    bit plane of a group is ``lanes`` consecutive keys."""
    assert keep_lanes(s) == lanes
    rng = np.random.default_rng(s)
    for u in rng.integers(0, s, 5):
        keep = np.zeros((1, s), bool)
        keep[0, u] = True
        words = np.asarray(pack_keep(jnp.asarray(keep)))
        assert words.shape == (1, s // KEEP_BITS)
        g, rest = divmod(int(u), KEEP_BITS * lanes)
        b, l = divmod(rest, lanes)
        want = np.zeros((1, s // KEEP_BITS), np.int64)
        want[0, g * lanes + l] = 1 << b
        assert (words.astype(np.int64) & 0xFFFFFFFF == want).all()
    keep = rng.random((3, s)) < 0.5
    assert (np.asarray(unpack_keep(pack_keep(jnp.asarray(keep)), s))
            == keep).all()


def test_a_sequence_that_is_no_multiple_of_32_keys_is_refused():
    with pytest.raises(ValueError, match='multiple of 32'):
        keep_lanes(48)


# -- both causal paths under a selection --------------------------------------------

@pytest.mark.parametrize('path,block_q,block_k', [
    ('xla', 16, 16), ('xla', 64, 64),
    ('kernel', 16, 16), ('kernel', 32, 16), ('kernel', 16, 32),
    ('kernel', 64, 64)])
def test_both_paths_under_a_selection_are_the_dense_masked_softmax(
        path, block_q, block_k):
    """Rows 21 and 40 keep one key alone: they see no key of most tiles and
    pass them unchanged."""
    q, k, v = qkv(1, dv=128)
    keep = selection(2)
    words = pack_keep(jnp.asarray(keep))
    want = dense(q, k, v, keep, 64 ** -0.5)
    if path == 'xla':
        with jax.default_matmul_precision('highest'):
            got = blockwise_attention(q, k, v, block_size=block_q,
                                      causal=True, keep=words)
        assert rel_l2(got, want) < 1e-6
    else:
        got = causal_attention(q, k, v, 64 ** -0.5, 3, block_q=block_q,
                               block_k=block_k, interpret=True, keep=words)
        # three bf16 passes in the kernel against float32
        assert rel_l2(got, want) < 3e-5
    # a row that keeps one key reads that key's value alone
    np.testing.assert_allclose(np.asarray(got)[0, 40], np.asarray(v)[0, 3],
                               rtol=3e-5, atol=3e-5)


def test_the_kernel_takes_latent_column_groups_under_a_selection():
    """q and k as (nope, rope) groups with ONE rotary key for all heads, as
    latent attention hands them over."""
    rng = np.random.default_rng(3)
    parts = [jnp.asarray(rng.standard_normal((1, S, h, w)), jnp.float32)
             for h, w in ((2, 64), (2, 64), (2, 64), (1, 64), (2, 128))]
    qn, qr, kn, kr, v = parts
    keep = selection(4)
    got = causal_attention((qn, qr), (kn, kr), v, 128 ** -0.5, 3,
                           block_q=16, block_k=16, interpret=True,
                           keep=pack_keep(jnp.asarray(keep)))
    q = jnp.concatenate([qn, qr], -1)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, kn.shape[:3] + (64,))], -1)
    assert rel_l2(got, dense(q, k, v, keep, 128 ** -0.5)) < 3e-5


def test_the_whole_triangle_selected_is_plain_causal_attention():
    q, k, v = qkv(5)
    keep = np.tril(np.ones((1, S, S), bool))
    words = pack_keep(jnp.asarray(keep))
    with jax.default_matmul_precision('highest'):
        plain = blockwise_attention(q, k, v, block_size=16, causal=True)
        xla = blockwise_attention(q, k, v, block_size=16, causal=True,
                                  keep=words)
    ker = causal_attention(q, k, v, 64 ** -0.5, 3, block_q=16, block_k=16,
                           interpret=True, keep=words)
    assert rel_l2(xla, plain) < 1e-6 and rel_l2(ker, plain) < 3e-5


@pytest.mark.parametrize('kwargs,match', [
    (dict(window=8), 'no window'),
    # 256 keys pack as one group of 8 lanes, 8 keys a bit plane: a key tile
    # of 4 would be half a plane
    (dict(block_q=4, block_k=4), 'bit planes'),
])
def test_what_the_keep_lane_cannot_take_is_refused(kwargs, match):
    q, k, v = qkv(6, s=256)
    keep = pack_keep(jnp.asarray(np.tril(np.ones((1, 256, 256), bool))))
    with pytest.raises(ValueError, match=match):
        causal_attention(q, k, v, 0.125, 3, interpret=True, keep=keep,
                         **kwargs)


def test_grouped_heads_take_no_selection():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, S, 4, 64)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, S, 2, 64)), jnp.float32)
            for _ in range(2))
    keep = pack_keep(jnp.asarray(selection(8)))
    with pytest.raises(ValueError, match='equal head counts'):
        causal_attention(q, k, v, 0.125, 3, interpret=True, keep=keep)
    with pytest.raises(ValueError, match='equal head counts'):
        blockwise_attention(q, k, v, block_size=16, causal=True, keep=keep)


@pytest.mark.parametrize('platform,s,precision,heads,kv_heads,window,want', [
    ('tpu', 8192, 'high', 1, 1, None, 'kernel'),     # dots3-note's full layers
    ('tpu', 8192, 'default', 1, 1, None, 'kernel'),  # the control lane
    ('tpu', 8192, 'highest', 1, 1, None, 'xla'),
    ('cpu', 8192, 'high', 1, 1, None, 'xla'),
    ('tpu', 8192, 'high', 4, 1, None, 'xla'),        # grouped heads
    ('tpu', 8192, 'high', 1, 1, 513, 'xla'),         # a window
    ('tpu', 2048, 'high', 1, 1, None, 'kernel'),     # one group of 64 lanes
])
def test_resolve_causal_decides_the_keep_lane(platform, s, precision, heads,
                                              kv_heads, window, want):
    assert resolve_causal(platform, s, 192, 128, precision, heads, kv_heads,
                          window, True) == want


def test_the_sparse_call_lowered_for_a_tpu_carries_its_own_name():
    q, k, v = (jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.float32)
               for _ in range(3))
    keep = jax.ShapeDtypeStruct((1, 1024, 32), jnp.int32)
    text = jax.jit(partial(causal_attention, scale=0.1, passes=3)).trace(
        q, k, v, keep=keep).lower(lowering_platforms=('tpu',)).as_text()
    assert text.count(f'kernel_name = "{SPARSE_NAME}"') == 1
    assert 'kernel_name = "causal_attention"' not in text


# -- the indexer ------------------------------------------------------------------

def indexer_inputs(seed, s=32, d=48, r=40, heads=4, dim=16):
    rng = np.random.default_rng(seed)

    def m(*shape):
        return jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[0]),
                           jnp.float32)
    x = jnp.asarray(rng.standard_normal((s, d)), jnp.float32)
    c_q = jnp.asarray(rng.standard_normal((s, r)), jnp.float32)
    return dict(x=x, c_q=c_q, wq=m(r, heads * dim), wk=m(d, dim),
                k_gain=jnp.asarray(0.9 + 0.2 * rng.random(dim), jnp.float32),
                k_bias=jnp.asarray(0.05 * rng.standard_normal(dim),
                                   jnp.float32),
                w_weights=m(d, heads))


def brute_selection(inp, heads, dim, rope, topk, theta):
    """The indexer written out in numpy at float64: every score of the
    square, a stable sort of each row's visible keys, its ``topk`` best
    (ties to the lower index, as lax.top_k takes them)."""
    from video_features_tpu.ops.attention import rotary_half
    f = {k: np.asarray(v, np.float64) for k, v in inp.items()}
    s = f['x'].shape[0]
    pos = jnp.arange(s)
    q = (f['c_q'] @ f['wq']).reshape(s, heads, dim)
    q[..., :rope] = np.asarray(rotary_half(jnp.asarray(q[..., :rope],
                                                       jnp.float32),
                                           pos, theta))
    k = f['x'] @ f['wk']
    k = (k - k.mean(-1, keepdims=True)) / np.sqrt(
        k.var(-1, keepdims=True) + 1e-6) * f['k_gain'] + f['k_bias']
    k[:, :rope] = np.asarray(rotary_half(jnp.asarray(k[:, None, :rope],
                                                     jnp.float32),
                                         pos, theta))[:, 0]
    w = f['x'] @ f['w_weights'] / np.sqrt(heads)
    dots = np.einsum('tjd,ud->tju', q, k) / np.sqrt(dim)
    scores = np.einsum('tju,tj->tu', np.maximum(dots, 0), w)
    keep = np.zeros((s, s), bool)
    for t in range(s):
        order = np.argsort(-scores[t, :t + 1], kind='stable')
        keep[t, order[:topk]] = True
    return keep


@pytest.mark.parametrize('topk', [1, 5, 8, 20, 32, 40])
def test_each_row_keeps_exactly_min_t_plus_1_topk_keys(topk):
    inp = indexer_inputs(9)
    with jax.default_matmul_precision('highest'):
        words, _ = select_keys(**inp, heads=4, dim=16, rope=8, topk=topk,
                               theta=8e7, block=8)
    keep = np.asarray(unpack_keep(words, 32))
    assert not np.triu(keep, 1).any()                  # nothing ahead
    assert (keep.sum(1) == np.minimum(np.arange(32) + 1, topk)).all()
    want = brute_selection(inp, 4, 16, 8, topk, 8e7)
    # float32 against float64: a row whose topk-th and next scores lie
    # within rounding of each other may swap them, no more
    assert (keep != want).sum(1).max() <= 2
    assert (keep != want).sum() <= 4


def test_ties_go_to_the_lower_index_as_top_k_takes_them():
    """With the indexer's weights zero every score is 0: a row keeps its
    first ``topk`` keys."""
    inp = indexer_inputs(10)
    inp['w_weights'] = jnp.zeros_like(inp['w_weights'])
    keep = np.asarray(unpack_keep(select_keys(
        **inp, heads=4, dim=16, rope=8, topk=8, theta=8e7, block=16)[0], 32))
    want = np.tril(np.ones((32, 32), bool)) & (np.arange(32) < 8)[None]
    assert (keep == want).all()
    scores = jnp.asarray([[0.0, 2.0, 0.0, 0.0, 1.0, 0.0]])
    assert np.asarray(top_keys(scores, 3)).tolist() == [
        [True, True, False, False, True, False]]


def test_blocks_before_topk_keep_their_triangle_and_score_nothing():
    inp = indexer_inputs(11)
    keep = np.asarray(unpack_keep(select_keys(
        **inp, heads=4, dim=16, rope=8, topk=32, theta=8e7, block=8)[0], 32))
    assert (keep == np.tril(np.ones((32, 32), bool))).all()
    for topk, scored in ((32, False), (8, True)):
        text = jax.jit(partial(select_keys, heads=4, dim=16, rope=8,
                               topk=topk, theta=8e7, block=8)).lower(
            **inp).as_text()
        assert ('top_k' in text) is scored
