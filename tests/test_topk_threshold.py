"""The lightning indexer's thresholds through ``ops/pallas_index.py``'s
``topk_threshold``, interpreted on the CPU, against ``lax.top_k``'s form
(``ops.sparse_index.thresholds``), which stays the CPU path and the oracle:
the keep bits each scored block's rows get from the two, exactly, on random
scores and on rows whose ties straddle the threshold; the rows the tie rule
settles (``topk_ties``); and ``resolve_threshold``'s choice."""
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from video_features_tpu.ops import pallas_index
from video_features_tpu.ops.sparse_index import (
    kept, resolve_threshold, scored_blocks, thresholds, top_keys,
)

S, BLOCK = 1024, 128


def random_rows(rng, rows, s):
    return rng.standard_normal((rows, s))


def whole_row_equal(rng, rows, s):
    return np.full((rows, s), 1.5)


def ties_below_only(rng, rows, s):
    """Distinct scores at the top, one value shared below them."""
    x = rng.standard_normal((rows, s))
    return np.where(x < np.quantile(x, 0.5, axis=1, keepdims=True), -3.0, x)


def ties_across_the_threshold(rng, rows, s):
    """Small integers: every row's ``topk``-th score has many equals on
    both sides of its ``topk``-th place."""
    return rng.integers(0, 12, (rows, s)).astype(np.float64)


def signed_zeros(rng, rows, s):
    """−0.0 and +0.0 among ±1: one tie to the comparisons."""
    return rng.choice(np.array([-1.0, -0.0, 0.0, 0.0, 1.0]), (rows, s))


def table_of(make, seed, topk, s=S, block=BLOCK):
    """What ``index_scores`` hands the kernel: the scored blocks' rows,
    −inf above the diagonal, and garbage (NaN, +inf, huge) in the columns
    past each block's last row, which it never writes."""
    rng = np.random.default_rng(seed)
    blocks = scored_blocks(s, topk, block)
    table = make(rng, len(blocks) * block, s).astype(np.float32)
    garbage = rng.choice(np.array([np.nan, np.inf, 3e38], np.float32),
                         table.shape)
    for i, b in enumerate(blocks):
        rows = slice(i * block, (i + 1) * block)
        row = b * block + np.arange(block)[:, None]
        col = np.arange(s)[None]
        table[rows] = np.where(col > row, -np.inf, table[rows])
        table[rows, (b + 1) * block:] = garbage[rows, (b + 1) * block:]
    return table, blocks


def through_both(table, blocks, topk, block=BLOCK):
    """Per scored block: (the kernel's keep bits and tied rows, the XLA
    form's), the diagonal applied to both as ``select_keys`` does."""
    with pltpu.force_tpu_interpret_mode():
        tau, last, tied = pallas_index.topk_threshold(
            jnp.asarray(table), blocks, block, topk)
    out = []
    for i, b in enumerate(blocks):
        keys = (b + 1) * block
        rows = slice(i * block, (i + 1) * block)
        scores = jnp.asarray(table[rows, :keys])
        causal = (np.arange(keys)[None]
                  <= b * block + np.arange(block)[:, None])
        got = np.asarray(kept(scores, tau[rows], last[rows])) & causal
        want = np.asarray(top_keys(scores, topk)) & causal
        out.append((got, np.asarray(tied[rows]), want,
                    np.asarray(thresholds(scores, topk)[2])))
    return out


@pytest.mark.parametrize('make,topk', [
    (random_rows, 256),
    (random_rows, 300),     # rows 256…299 see no more than topk keys
    (whole_row_equal, 256),
    (ties_below_only, 256),
    (ties_across_the_threshold, 256),
    (signed_zeros, 256),
], ids=['random', 'rows_within_topk', 'whole_row_equal', 'ties_below_only',
        'ties_across_the_threshold', 'signed_zeros'])
def test_the_kernels_keep_bits_are_top_ks(make, topk):
    """1,024 positions, blocks of 128: each scored row keeps exactly the
    keys ``lax.top_k`` takes — min(t + 1, topk) of them, those on the
    threshold from the lowest index up — and the two forms name the same
    rows as settled by the tie rule."""
    table, blocks = table_of(make, 7, topk)
    for (got, tied, want, want_tied), b in zip(through_both(table, blocks,
                                                            topk), blocks):
        np.testing.assert_array_equal(got, want, err_msg=f'block {b}')
        row = b * BLOCK + np.arange(BLOCK)
        np.testing.assert_array_equal(got.sum(1), np.minimum(row + 1, topk))
        np.testing.assert_array_equal(tied, want_tied, err_msg=f'block {b}')


def test_topk_ties_counts_a_planted_tie_and_nothing_else():
    """Distinct scores but in one row, whose ``topk``-th and next largest
    are made equal: that row alone is settled by the tie rule, on both
    forms, and keeps the lower index of the two."""
    topk = 256
    table, blocks = table_of(random_rows, 8, topk)
    i, b, r = 2, blocks[2], 77
    row = table[i * BLOCK + r, :(b + 1) * BLOCK]
    order = np.argsort(-row, kind='stable')
    row[order[topk]] = row[order[topk - 1]]
    results = through_both(table, blocks, topk)
    for n, (got, tied, want, want_tied) in enumerate(results):
        planted = np.arange(BLOCK) == r if n == i else np.zeros(BLOCK, bool)
        np.testing.assert_array_equal(tied, planted)
        np.testing.assert_array_equal(want_tied, planted)
        np.testing.assert_array_equal(got, want)
    pair = sorted(order[topk - 1:topk + 1])
    assert results[i][0][r, pair].tolist() == [True, False]


@pytest.mark.parametrize('platform,s,block,path', [
    ('tpu', 8192, 256, 'kernel'),       # dots3-note.corpus
    ('tpu', 256, 256, 'kernel'),        # one block
    ('tpu', 1024, 128, 'kernel'),
    ('cpu', 8192, 256, 'xla'),          # interpreted there
    ('tpu', 8000, 256, 'xla'),          # ragged blocks
    ('tpu', 8192, 64, 'xla'),           # a block off the lanes
    ('tpu', 32768, 256, 'xla'),         # a block's scores past VMEM
])
def test_resolve_threshold_decides_the_form(platform, s, block, path):
    assert resolve_threshold(platform, s, block) == path


def test_a_block_of_scores_over_the_vmem_budget_is_refused():
    assert pallas_index.threshold_vmem_bytes(8192, 256) == 25_165_824
    assert pallas_index.threshold_vmem_bytes(32768, 256) > \
        pallas_index.THRESHOLD_VMEM_BYTES
