"""The state-product kernels of power retention (``ops/pallas_retention.py``)
run interpreted on the CPU (``pltpu.force_tpu_interpret_mode``, for the
whole file) at d = d_v = 128 and small chunks: each against
``power_features`` + the einsum it replaces, the chunked scan on the kernel
path against the attention form, and ``resolve_retention``'s choices."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from tests.test_retention import attention_form
from video_features_tpu.ops import pallas_retention as kernel
from video_features_tpu.ops.retention import (
    feature_dim, power_features, resolve_retention, retention_chunked,
)

G, R, D = 2, 2, 128
# product rounding against float32-exact references: three bf16 passes keep
# 16 bits of each operand, one keeps 8
TOLERANCE = {3: 2e-5, 1: 6e-3}


@pytest.fixture(autouse=True)
def interpreted():
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        yield


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.fixture(scope='module')
def operands():
    rng = np.random.default_rng(32)
    q = rng.standard_normal((G, R * 24, D)).astype(np.float32)
    state = rng.standard_normal((G, feature_dim(D), D)).astype(np.float32)
    k = rng.standard_normal((G, 24, D)).astype(np.float32)
    v = rng.standard_normal((G, 24, D)).astype(np.float32)
    return q, state, k, v


@pytest.fixture(scope='module')
def window():
    """64 positions; q and k at a head's RMS-normed scale over √d so the
    squared scores stay near 1."""
    rng = np.random.default_rng(7)
    s = 64
    q = rng.standard_normal((s, G, R, D)).astype(np.float32) / 8
    k = rng.standard_normal((s, G, D)).astype(np.float32) / 8
    v = rng.standard_normal((s, G, D)).astype(np.float32)
    logits = 2.0 + rng.standard_normal((s, G))
    log_gate = (-np.log1p(np.exp(-logits))).astype(np.float32)
    return q, k, v, log_gate


def einsum_highest(spec, *ops):
    with jax.default_matmul_precision('highest'):
        return jnp.einsum(spec, *ops, preferred_element_type=jnp.float32)


# -- the two kernels against the einsums they replace ---------------------------------

@pytest.mark.parametrize('passes', [3, 1])
@pytest.mark.parametrize('rows,unroll', [(48, 1), (16, 4), (8, 3)])
def test_state_read_is_phi_q_times_the_state(operands, passes, rows, unroll):
    q, state, _, _ = operands
    want = einsum_highest('gtD,gDv->gtv', power_features(q), state)
    got = kernel.state_read(q, state, passes, block_rows=rows,
                            unroll=unroll)
    assert got.shape == (G, R * 24, D) and got.dtype == jnp.float32
    assert rel(got, want) < TOLERANCE[passes]
    if passes == 1:     # and the one-pass lane IS one pass, not three
        assert rel(got, want) > 10 * TOLERANCE[3]


@pytest.mark.parametrize('passes', [3, 1])
@pytest.mark.parametrize('blocks', [1, 7, 9])
def test_state_update_is_phi_k_transposed_times_v(operands, passes, blocks):
    _, _, k, v = operands
    want = einsum_highest('gsD,gsv->gDv', power_features(k), v)
    empty = jnp.zeros((G, feature_dim(D), D), jnp.float32)
    got = kernel.state_update(empty, jnp.ones((G,), jnp.float32),
                              k.swapaxes(1, 2), v, passes, blocks=blocks)
    assert got.shape == (G, feature_dim(D), D) and got.dtype == jnp.float32
    assert rel(got, want) < TOLERANCE[passes]
    if passes == 1:
        assert rel(got, want) > 10 * TOLERANCE[3]


def test_state_update_decays_the_old_state_a_head_in_float32(operands):
    """``decay · S + φ(k)ᵀv``: the old state's part is float32 arithmetic
    whatever the passes (no bf16 touches the carried state), so with zero
    keys the kernel returns ``decay · S`` to the bit."""
    _, state, k, v = operands
    decay = jnp.asarray([0.75, 0.3], jnp.float32)
    still = kernel.state_update(state, decay, jnp.zeros_like(k).swapaxes(1, 2),
                                v, 1)
    np.testing.assert_array_equal(still, decay[:, None, None] * state)
    want = decay[:, None, None] * state + einsum_highest(
        'gsD,gsv->gDv', power_features(k), v)
    got = kernel.state_update(state, decay, k.swapaxes(1, 2), v, 3)
    assert rel(got, want) < TOLERANCE[3]


@pytest.mark.parametrize('which', ['read', 'update'])
def test_the_half_block_is_the_last_64_features_and_nothing_else(operands,
                                                                which):
    """Rotation d/2 holds each pair twice: the kernels keep the first 64
    lanes, as ``power_features`` does, and nothing of the duplicate half."""
    q, state, k, v = operands
    tail = feature_dim(D) - D // 2
    if which == 'read':
        only_half = np.zeros_like(state)
        only_half[:, tail:] = state[:, tail:]
        want = einsum_highest('gtd,gdv->gtv', power_features(q)[..., tail:],
                              state[:, tail:])
        got = kernel.state_read(q, only_half, 3)
        assert rel(got, want) < TOLERANCE[3]
    else:
        want = einsum_highest('gsd,gsv->gdv', power_features(k)[..., tail:],
                              v)
        got = kernel.state_update(
            jnp.zeros((G, feature_dim(D), D), jnp.float32),
            jnp.ones((G,), jnp.float32), k.swapaxes(1, 2), v, 3)
        assert got.shape[1] == tail + D // 2
        assert rel(got[:, tail:], want) < TOLERANCE[3]


@pytest.mark.parametrize('t,block_rows,want', [
    (2560, 2560, 2560),         # brumby.corpus: a head's 5 × 512 rows whole
    (48, 2560, 48), (48, 32, 24), (5120, 2560, 2560), (3584, 2560, 1792),
])
def test_the_row_tile_is_the_largest_whole_divisor(t, block_rows, want):
    assert kernel.row_tile(t, block_rows) == want


def test_rows_without_a_whole_tile_are_refused(operands):
    _, state, _, _ = operands
    q = np.zeros((G, 50, D), np.float32)
    with pytest.raises(ValueError, match='no divisor'):
        kernel.state_read(q, state, 3, block_rows=32)


# -- the scan on the kernel path ------------------------------------------------------

def scan(window, chunk, passes, state=None):
    q, k, v, log_gate = window
    with jax.default_matmul_precision('highest'):
        y, state = retention_chunked(q, k, v, log_gate, chunk, state,
                                     kernel_passes=passes)
    return np.asarray(y), state


@pytest.mark.parametrize('passes', [3, 1])
@pytest.mark.parametrize('chunk', [16, 64])
def test_the_kernel_path_is_the_attention_form(window, chunk, passes):
    want = attention_form(*window)
    got, (big_s, big_z) = scan(window, chunk, passes)
    assert got.shape == want.shape
    assert big_s.shape == (G, feature_dim(D), D) and big_s.dtype == jnp.float32
    assert big_z.shape == (G, D, D)
    assert rel(got, want) < TOLERANCE[passes]
    # XLA's form at full precision, state and all, to the passes' rounding
    ours, (xla_s, xla_z) = scan(window, chunk, None)
    assert rel(got, ours) < TOLERANCE[passes]
    assert rel(big_s, xla_s) < TOLERANCE[passes]
    np.testing.assert_allclose(big_z, xla_z, rtol=1e-6, atol=1e-7)


def test_a_state_handed_over_between_two_halves_is_the_window(window):
    whole, (s_whole, z_whole) = scan(window, 16, 3)
    first, state = scan(tuple(a[:32] for a in window), 16, 3)
    second, (s_end, z_end) = scan(tuple(a[32:] for a in window), 16, 3,
                                  state)
    # the kernels split what they are handed: the same numbers either way
    np.testing.assert_allclose(np.concatenate([first, second]), whole,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s_end, s_whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(z_end, z_whole, rtol=1e-5, atol=1e-6)
    # and the second half does read the state: without it, another answer
    alone, _ = scan(tuple(a[32:] for a in window), 16, 3)
    assert rel(alone, second) > 1e-2


def test_a_ragged_window_on_the_kernel_path_is_padded_and_cut_back(window):
    short = tuple(a[:40] for a in window)
    got, (big_s, _) = scan(short, 16, 3)
    assert got.shape == (40, G, R, D)
    assert rel(got, attention_form(*short)) < TOLERANCE[3]
    _, (same_s, _) = scan(short, 40, 3)
    assert rel(big_s, same_s) < TOLERANCE[3]


# -- where the kernels apply ------------------------------------------------------------

@pytest.mark.parametrize('platform,d,d_v,chunk,precision,want', [
    ('tpu', 128, 128, 512, 'high', 'kernel'),         # precision=mixed
    ('tpu', 128, 128, 512, 'default', 'kernel'),      # the control lane
    ('tpu', 128, 128, 512, None, 'kernel'),
    ('tpu', 128, 128, 1024, 'high', 'kernel'),
    ('cpu', 128, 128, 512, 'high', 'state'),
    ('gpu', 128, 128, 512, 'high', 'state'),
    ('tpu', 64, 64, 512, 'high', 'state'),            # half a lane block
    ('tpu', 128, 64, 512, 'high', 'state'),
    ('tpu', 256, 256, 512, 'high', 'state'),          # a state past VMEM
    ('tpu', 128, 128, 500, 'high', 'state'),          # a ragged tail
    ('tpu', 128, 128, 64, 'high', 'state'),           # a short window
    ('tpu', 128, 128, 512, 'highest', 'state'),       # six passes: no lane
    ('tpu', 128, 128, 512, 'float32', 'state'),
])
def test_resolve_retention(platform, d, d_v, chunk, precision, want):
    assert resolve_retention(platform, d, d_v, chunk, precision) == want
