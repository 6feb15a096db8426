"""The Mamba-2 SSD scan (``ops/ssd.py``) at small sizes on the CPU: the
chunked XLA form against the recurrence written out one position at a time
(whole and ragged last chunks, step sizes and rates at both ends of Mamba-2's
initialisation), and the ``ssd_scan`` kernel (``ops/pallas_ssd.py``) run
interpreted against the XLA form under three passes and one; and
``resolve_ssd``'s choices."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from video_features_tpu.ops import pallas_ssd
from video_features_tpu.ops.ssd import chunk_decays, resolve_ssd, ssd_chunked

H, P, N = 4, 64, 128
# the recurrence in float32 against the chunked form in float32: sums in
# another order, some 1e-7; a lost term or a wrong decay reads 1e-2 and more
TOLERANCE = 2e-6
# product rounding against float32: three bf16 passes keep 16 bits of each
# operand, one keeps 8
PASSES_TOLERANCE = {3: 5e-5, 1: 2e-2}


def recurrence(x, dt, a, b, c, d):
    """state_t = exp(Δ_t A) state_{t−1} + Δ_t B_t ⊗ x_t; y_t = C_t · state_t
    + D x_t: (S, H·P) in, (S, H·P) out, one position a step."""
    s, width = x.shape
    heads = dt.shape[1]
    xs = x.reshape(s, heads, width // heads)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + dt_t[:, None, None] * b_t[None, :, None] * x_t[:, None, :])
        return state, jnp.einsum('n,hnp->hp', c_t, state,
                                 precision=lax.Precision.HIGHEST)

    state = jnp.zeros((heads, b.shape[1], width // heads), jnp.float32)
    _, y = lax.scan(step, state, (xs, dt, b, c))
    return (y + d[None, :, None] * xs).reshape(s, width)


def operands(s, seed, dt_range=(1e-3, 1e-1), a_range=(1.0, 16.0),
             skip=0.01):
    """Seeded inputs: Δ log-uniform over ``dt_range``, −A uniform over
    ``a_range`` (Mamba-2's init: 1e-3…0.1 and 1…16), B and C at a state's
    scale; a small skip, so that the scan is most of ``y``."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((s, H * P)).astype(np.float32)
    dt = np.exp(rng.uniform(*np.log(dt_range), (s, H))).astype(np.float32)
    a = -rng.uniform(*a_range, H).astype(np.float32)
    b = (rng.standard_normal((s, N)) / np.sqrt(N)).astype(np.float32)
    c = rng.standard_normal((s, N)).astype(np.float32)
    d = np.full(H, skip, np.float32)
    return x, dt, a, b, c, d


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize('s,chunk', [
    (256, 64),      # four whole chunks
    (300, 64),      # a ragged last chunk, padded and cut back
    (40, 64),       # a window shorter than a chunk: one chunk
])
@pytest.mark.parametrize('dt_range,a_range', [
    ((1e-3, 1e-1), (1.0, 16.0)),       # Mamba-2's init
    ((1e-3, 2e-3), (1.0, 1.5)),        # its slow end: a long memory
    ((0.08, 0.1), (12.0, 16.0)),       # its fast end: decays of e^-1.6 a step
])
def test_the_chunked_form_is_the_recurrence(s, chunk, dt_range, a_range):
    ops = operands(s, 11, dt_range, a_range)
    with jax.default_matmul_precision('highest'):
        want = recurrence(*ops)
        got = ssd_chunked(*ops, chunk)
    assert got.shape == (s, H * P)
    assert rel(got, want) < TOLERANCE


def test_a_window_starts_from_zero_and_sees_no_later_position():
    x, dt, a, b, c, d = operands(128, 3)
    with jax.default_matmul_precision('highest'):
        whole = ssd_chunked(x, dt, a, b, c, d, 32)
        changed = x.copy()
        changed[70] += 1.0
        later = ssd_chunked(changed, dt, a, b, c, d, 32)
        # the second half alone: the same as a window of its own
        tail = ssd_chunked(x[64:], dt[64:], a, b[64:], c[64:], d, 32)
        want_tail = recurrence(x[64:], dt[64:], a, b[64:], c[64:], d)
    np.testing.assert_array_equal(np.asarray(whole[:70]),
                                  np.asarray(later[:70]))
    assert np.abs(np.asarray(whole[70:]) - np.asarray(later[70:])).max() > 1e-3
    assert rel(tail, want_tail) < TOLERANCE


def test_the_decays_are_each_chunks_own_cumulative_sums():
    dt = np.full((8, 2), 0.5, np.float32)
    a = np.array([-1.0, -2.0], np.float32)
    cs = np.asarray(chunk_decays(dt, a, 4))
    want = -0.5 * np.array([1, 2, 3, 4, 1, 2, 3, 4], np.float32)
    np.testing.assert_allclose(cs[:, 0], want)
    np.testing.assert_allclose(cs[:, 1], 2 * want)


@pytest.fixture()
def interpreted():
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.mark.parametrize('heads', [2, 4])       # one or two grid steps
@pytest.mark.parametrize('passes,precision', [(3, 'high'), (1, 'default')])
def test_the_kernel_is_the_xla_form_to_rounding(interpreted, monkeypatch,
                                                heads, passes, precision):
    """Three chunks of 128 (the state carried across two boundaries), four
    64-wide heads two to a 128-lane group, ``heads`` of them a grid step."""
    monkeypatch.setattr(pallas_ssd, 'HEADS_PER_STEP', heads)
    x, dt, a, b, c, d = operands(384, 5)
    with jax.default_matmul_precision('highest'):
        want = ssd_chunked(x, dt, a, b, c, d, 128)
    cs = chunk_decays(jnp.asarray(dt), jnp.asarray(a), 128)
    got = pallas_ssd.ssd_scan(jnp.asarray(x), dt, cs, b, c, d, 128, passes)
    assert got.shape == (384, H * P)
    error = rel(got, want)
    assert 0 < error < PASSES_TOLERANCE[passes]
    with jax.default_matmul_precision(precision):
        through = ssd_chunked(x, dt, a, b, c, d, 128, kernel_passes=passes)
    np.testing.assert_array_equal(np.asarray(through), np.asarray(
        pallas_ssd.ssd_scan(jnp.asarray(x), dt, cs, b, c, d, 128, passes)))


def test_three_passes_are_nearer_than_one(interpreted):
    x, dt, a, b, c, d = operands(256, 9)
    with jax.default_matmul_precision('highest'):
        want = ssd_chunked(x, dt, a, b, c, d, 128)
    cs = chunk_decays(jnp.asarray(dt), jnp.asarray(a), 128)
    three, one = (rel(pallas_ssd.ssd_scan(jnp.asarray(x), dt, cs, b, c, d,
                                          128, passes), want)
                  for passes in (3, 1))
    assert 50 * three < one


def test_resolve_ssd_takes_the_kernel_only_where_it_applies():
    cell = dict(s=32768, heads=64, head_dim=64, state_dim=128, chunk=256)
    assert resolve_ssd('tpu', precision='high', **cell) == 'kernel'
    assert resolve_ssd('tpu', precision='default', **cell) == 'kernel'
    assert resolve_ssd('tpu', precision='highest', **cell) == 'xla'
    assert resolve_ssd('cpu', precision='high', **cell) == 'xla'
    for bad in (dict(s=32768 + 8),          # a ragged window
                dict(state_dim=96),         # a state of no whole lane block
                dict(chunk=64),             # a chunk of no whole lane block
                dict(head_dim=96),          # heads that split a lane group
                dict(heads=1)):             # less than one lane group
        assert resolve_ssd('tpu', precision='high',
                           **dict(cell, **bad)) == 'xla', bad
    assert pallas_ssd.heads_per_step(64, 64) == pallas_ssd.HEADS_PER_STEP
    assert pallas_ssd.heads_per_step(4, 64) == 4
