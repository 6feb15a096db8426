"""Gated power retention (``ops/retention.py``) and the half-split rotary
code: the feature map, and the three forms of the layer — the recurrence
and the attention form (both written out here in float64) and the chunked
scan (what runs) — held to each other at a tiny size on the CPU (d = 8,
4 query / 2 key-value heads, windows of 64)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from video_features_tpu.ops.attention import rotary_half, rotary_interleaved
from video_features_tpu.ops.retention import (
    EPS, feature_dim, init_state, power_features, retention_chunked,
)

S, G, R, D = 64, 2, 2, 8


def phi_by_definition(x):
    """x_a² for a = b, √2·x_a x_b for a < b, the upper triangle row-major."""
    d = x.shape[-1]
    return np.stack([x[..., a] * x[..., b] * (1.0 if a == b else np.sqrt(2.0))
                     for a in range(d) for b in range(a, d)], axis=-1)


def recurrent(q, k, v, log_gate, eps=EPS):
    """Equations (3)-(4) of the module, float64, one position at a time."""
    s, g, r, d = q.shape
    q, k, v, log_gate = (np.asarray(a, np.float64)
                         for a in (q, k, v, log_gate))
    big_s = np.zeros((g, feature_dim(d), v.shape[-1]))
    z = np.zeros((g, feature_dim(d)))
    out = np.zeros((s, g, r, v.shape[-1]))
    for t in range(s):
        decay = np.exp(log_gate[t])
        pk = phi_by_definition(k[t])                          # (g, D)
        big_s = decay[:, None, None] * big_s + pk[:, :, None] * v[t][:, None]
        z = decay[:, None] * z + pk
        pq = phi_by_definition(q[t])                          # (g, r, D)
        num = np.einsum('grD,gDv->grv', pq, big_s)
        den = np.einsum('grD,gD->gr', pq, z)
        out[t] = num / (den[..., None] + eps)
    return out, (big_s, z)


def attention_form(q, k, v, log_gate, eps=EPS):
    """Equations (1)-(2) of the module, float64, the S × S weights whole."""
    q, k, v, log_gate = (np.asarray(a, np.float64)
                         for a in (q, k, v, log_gate))
    s = q.shape[0]
    total = np.cumsum(log_gate, axis=0).T                     # (g, S)
    seen = np.tril(np.ones((s, s), bool))
    decay = np.exp(np.where(seen, total[:, :, None] - total[:, None, :],
                            -np.inf))                         # (g, t, s)
    a = np.einsum('tgrd,sgd->grts', q, k) ** 2 * decay[:, None]
    return np.einsum('grts,sgv->tgrv', a, v) / (
        a.sum(-1).transpose(2, 0, 1)[..., None] + eps)


@pytest.fixture(scope='module')
def window():
    rng = np.random.default_rng(31)
    q = rng.standard_normal((S, G, R, D)).astype(np.float32)
    k = rng.standard_normal((S, G, D)).astype(np.float32)
    v = rng.standard_normal((S, G, D)).astype(np.float32)
    # log σ of logits around 2: a position fades over some ten further ones
    logits = 2.0 + rng.standard_normal((S, G))
    log_gate = (-np.log1p(np.exp(-logits))).astype(np.float32)
    return q, k, v, log_gate


def chunked(q, k, v, log_gate, chunk, state=None):
    with jax.default_matmul_precision('highest'):
        y, state = retention_chunked(q, k, v, log_gate, chunk, state)
    return np.asarray(y), state


# -- the feature map ---------------------------------------------------------------

@pytest.mark.parametrize('d', [8, 7, 128])
def test_the_feature_map_squares_the_dot_product(d):
    rng = np.random.default_rng(d)
    q = rng.standard_normal((5, d)).astype(np.float32)
    k = rng.standard_normal((5, d)).astype(np.float32)
    pq, pk = np.asarray(power_features(q)), np.asarray(power_features(k))
    assert pq.shape == (5, feature_dim(d)) and feature_dim(d) == d * (d + 1) // 2
    np.testing.assert_allclose((pq.astype(np.float64) * pk).sum(-1),
                               ((q.astype(np.float64) * k).sum(-1)) ** 2,
                               rtol=1e-4, atol=1e-6)
    # the same entries as the definition's upper triangle, in another order
    np.testing.assert_allclose(np.sort(pq, axis=-1),
                               np.sort(phi_by_definition(q), axis=-1),
                               rtol=1e-6, atol=1e-7)


def test_the_published_head_has_a_state_of_8256_by_129():
    """8,256 × 128 numbers of S and the normaliser's 8,256, the latter held
    as the symmetric 128 × 128 matrix whose upper triangle they are."""
    assert feature_dim(128) == 8256 == 128 * 129 // 2
    big_s, big_z = init_state(8, 128, 128)
    assert big_s.shape == (8, 8256, 128) and big_z.shape == (8, 128, 128)
    assert big_s.dtype == big_z.dtype == jnp.float32


# -- the three forms ---------------------------------------------------------------

@pytest.mark.parametrize('chunk', [8, 16, 64])
def test_recurrent_chunked_and_attention_forms_agree(window, chunk):
    q, k, v, log_gate = window
    want, (want_s, want_z) = recurrent(q, k, v, log_gate)
    got, (big_s, z) = chunked(q, k, v, log_gate, chunk)
    assert got.shape == (S, G, R, D)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # the final state too: its rows stand in the feature map's own order,
    # so it is read with a probe through either map
    probe = np.random.default_rng(9).standard_normal((G, D))
    ours = np.asarray(power_features(probe.astype(np.float32)), np.float64)
    np.testing.assert_allclose(
        np.einsum('gD,gDv->gv', ours, big_s),
        np.einsum('gD,gDv->gv', phi_by_definition(probe), want_s),
        rtol=2e-4, atol=2e-4)
    # the normaliser is the matrix whose upper triangle z is: z·φ(p) = pᵀZp
    np.testing.assert_allclose(np.einsum('gd,gde,ge->g', probe, z, probe),
                               (phi_by_definition(probe) * want_z).sum(-1),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(z, np.swapaxes(z, 1, 2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(attention_form(q, k, v, log_gate), want,
                               rtol=1e-9, atol=1e-12)


def test_no_gate_is_plain_power_attention(window):
    q, k, v, _ = window
    got, _ = chunked(q, k, v, np.zeros((S, G), np.float32), 16)
    scores = np.einsum('tgrd,sgd->grts', q.astype(np.float64), k) ** 2
    scores *= np.tril(np.ones((S, S)))
    want = np.einsum('grts,sgv->tgrv', scores, v) / (
        scores.sum(-1).transpose(2, 0, 1)[..., None] + EPS)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_a_gate_that_forgets_everything_returns_the_positions_own_value(
        window):
    q, k, v, _ = window
    got, (big_s, z) = chunked(q, k, v, np.full((S, G), -60.0, np.float32), 16)
    own = np.einsum('tgrd,tgd->tgr', q, k) ** 2
    want = (own / (own + EPS))[..., None] * v[:, :, None, :]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # and the state holds the last position alone
    np.testing.assert_allclose(z, np.einsum('gd,ge->gde', k[-1], k[-1]),
                               rtol=1e-5, atol=1e-7)


def test_a_later_position_changes_no_earlier_one(window):
    q, k, v, log_gate = window
    base, _ = chunked(q, k, v, log_gate, 16)
    k2, v2, g2 = k.copy(), v.copy(), log_gate.copy()
    k2[40] += 1.0
    v2[40] -= 2.0
    g2[40] *= 3.0
    other, _ = chunked(q, k2, v2, g2, 16)
    np.testing.assert_array_equal(base[:40], other[:40])
    assert np.abs(base[40:] - other[40:]).max() > 1e-3


def test_a_window_in_two_halves_with_the_state_handed_over_is_the_window(
        window):
    q, k, v, log_gate = window
    whole, (s_whole, z_whole) = chunked(q, k, v, log_gate, 8)
    h = S // 2
    first, state = chunked(q[:h], k[:h], v[:h], log_gate[:h], 8)
    second, (s_end, z_end) = chunked(q[h:], k[h:], v[h:], log_gate[h:], 8,
                                     state)
    np.testing.assert_allclose(np.concatenate([first, second]), whole,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s_end, s_whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z_end, z_whole, rtol=1e-5, atol=1e-5)


def test_state_and_normaliser_stay_float32_under_the_one_pass_lane(window):
    """precision=default makes one bf16 pass of every product; the state
    still accumulates in float32."""
    q, k, v, log_gate = window
    with jax.default_matmul_precision('default'):
        y, (big_s, z) = jax.jit(
            lambda *a: retention_chunked(*a, chunk=16))(q, k, v, log_gate)
    assert big_s.dtype == z.dtype == jnp.float32 and y.dtype == jnp.float32
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize('s,chunk', [(50, 16), (64, 48), (37, 512)])
def test_a_ragged_window_is_padded_to_whole_chunks_and_cut_back(window, s,
                                                                chunk):
    """Zero keys and values weigh nothing and come after every real
    position: the rows and the state are those of the window as it is (a
    window shorter than the chunk is one chunk, unpadded)."""
    q, k, v, log_gate = (a[:s] for a in window)
    want, (want_s, want_z) = recurrent(q, k, v, log_gate)
    got, (big_s, z) = chunked(q, k, v, log_gate, chunk)
    assert got.shape == (s, G, R, D)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got, attention_form(q, k, v, log_gate),
                               rtol=2e-4, atol=2e-5)
    # the state after the padding is the state after the last real position
    _, (s_same, z_same) = chunked(q, k, v, log_gate, s)
    np.testing.assert_allclose(big_s, s_same, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z, z_same, rtol=1e-5, atol=1e-5)
    probe = np.random.default_rng(9).standard_normal((G, D))
    np.testing.assert_allclose(np.einsum('gd,gde,ge->g', probe, z, probe),
                               (phi_by_definition(probe) * want_z).sum(-1),
                               rtol=2e-4, atol=2e-4)


# -- the half-split rotary code ------------------------------------------------------

def test_half_split_rotary_is_a_rotation_of_pairs_half_a_head_apart():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((7, 3, 8)).astype(np.float32)    # (S, H, d)
    theta = 1e6
    got = np.asarray(rotary_half(jnp.asarray(x), jnp.arange(7), theta))
    z = x[..., :4].astype(np.complex128) + 1j * x[..., 4:]
    freq = theta ** (-np.arange(0, 8, 2) / 8)
    turned = z * np.exp(1j * np.arange(7)[:, None, None] * freq)
    want = np.concatenate([turned.real, turned.imag], axis=-1)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # position 0 is the identity, a rotation keeps every pair's length, and
    # q·k depends on the distance between two positions alone
    np.testing.assert_allclose(got[0], x[0], atol=1e-7)
    np.testing.assert_allclose(np.hypot(got[..., :4], got[..., 4:]),
                               np.abs(z), rtol=1e-5)
    same = np.broadcast_to(x[:1], x.shape)
    turned = np.asarray(rotary_half(jnp.asarray(same), jnp.arange(7), theta))
    dots = np.einsum('shd,thd->hst', turned, turned)
    np.testing.assert_allclose(dots[:, 1, 3], dots[:, 4, 6], rtol=1e-5)
    # the interleaved form is the same rotation on the other pairing
    inter = np.asarray(rotary_interleaved(
        jnp.asarray(np.stack([x[..., :4], x[..., 4:]], -1).reshape(x.shape)),
        jnp.arange(7), theta))
    np.testing.assert_allclose(inter[..., 0::2], got[..., :4], atol=2e-6)
    np.testing.assert_allclose(inter[..., 1::2], got[..., 4:], atol=2e-6)
