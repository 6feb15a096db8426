"""The lightning indexer's scores through ``ops/pallas_index.py``'s Mosaic
kernel, interpreted on the CPU, against the XLA form that stays the CPU
path and the oracle: the scores themselves (−inf above the diagonal, the
block that straddles ``topk`` among them), the keep bits they select,
``resolve_index``'s choice, the step that carries the call on a TPU, and
the ``index_kernel`` counter."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from video_features_tpu.models import latent_moe as lm
from video_features_tpu.ops import pallas_index
from video_features_tpu.ops.sparse_index import (
    resolve_index, scored_blocks, top_keys,
)
from video_features_tpu.utils.tracing import Tracer

S, HEADS, DIM, BLOCK = 1024, 8, 128, 256


def draw(seed, s=S, heads=HEADS, dim=DIM):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((s, heads, dim)), jnp.float32),
            jnp.asarray(rng.standard_normal((s, dim)), jnp.float32),
            jnp.asarray(rng.standard_normal((s, heads)), jnp.float32))


def xla_scores(q, k, w):
    """Every row against every key, −inf above the diagonal, at float32."""
    with jax.default_matmul_precision('highest'):
        scores = jnp.einsum('tju,tj->tu', jax.nn.relu(
            jnp.einsum('tjd,ud->tju', q, k)), w)
    s = q.shape[0]
    causal = jnp.arange(s)[None] <= jnp.arange(s)[:, None]
    return np.asarray(jnp.where(causal, scores, -jnp.inf))


@pytest.mark.parametrize('passes,limit', [(3, 2e-5), (1, 1e-2)])
@pytest.mark.parametrize('topk', [256, 384])
def test_the_kernels_scores_are_the_xla_forms(topk, passes, limit):
    """Blocks of 256 over 1,024 positions, 8 heads of 128: the blocks whose
    last row sees more than ``topk`` keys are scored (at 384 the first of
    them straddles it), each against the keys up to its last row, −inf
    exactly where the key lies after the row; three passes are float32's
    rounding, one pass a bf16 product's."""
    q, k, w = draw(1)
    blocks = scored_blocks(S, topk, BLOCK)
    assert blocks == [1, 2, 3]
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(pallas_index.index_scores(q, k, w, blocks, BLOCK,
                                                   passes))
    want = xla_scores(q, k, w)
    assert got.shape == (len(blocks) * BLOCK, S)
    for i, b in enumerate(blocks):
        keys = (b + 1) * BLOCK
        g = got[i * BLOCK:(i + 1) * BLOCK, :keys]
        e = want[b * BLOCK:(b + 1) * BLOCK, :keys]
        seen = np.isfinite(e)
        np.testing.assert_array_equal(np.isfinite(g), seen)
        assert np.isneginf(g[~seen]).all()
        err = np.linalg.norm(g[seen] - e[seen]) / np.linalg.norm(e[seen])
        assert err < limit, (b, err)


@pytest.mark.parametrize('topk', [256, 384])
def test_well_separated_scores_keep_the_same_bits_on_both_forms(topk):
    """Each scored block's selection (``top_keys``) from the kernel's scores
    (three passes) and from XLA's: a row whose ``topk``-th and next score
    lie further apart than the passes' rounding (1e-4 of the row's largest)
    keeps the same keys, and nearly every row is such a row; a row whose two
    lie closer may flip, as the summation order over heads changed."""
    q, k, w = draw(2)
    blocks = scored_blocks(S, topk, BLOCK)
    with pltpu.force_tpu_interpret_mode():
        got = pallas_index.index_scores(q, k, w, blocks, BLOCK, 3)
    want = xla_scores(q, k, w)
    separated = same = 0
    for i, b in enumerate(blocks):
        keys = (b + 1) * BLOCK
        g = got[i * BLOCK:(i + 1) * BLOCK, :keys]
        e = jnp.asarray(want[b * BLOCK:(b + 1) * BLOCK, :keys])
        apart = np.asarray(top_keys(g, topk) == top_keys(e, topk)).all(1)
        ranked = -np.sort(-np.asarray(e), axis=1)
        row = np.arange(b * BLOCK, keys)
        gap = np.where(row + 1 > topk, ranked[:, topk - 1]
                       - ranked[:, min(topk, keys - 1)], np.inf)
        clear = gap > 1e-4 * np.abs(ranked[:, 0])
        assert apart[clear].all(), (b, np.flatnonzero(clear & ~apart))
        separated += int(clear.sum())
        same += int(apart.sum())
    assert separated > 0.8 * len(blocks) * BLOCK
    assert same >= separated


@pytest.mark.parametrize('platform,s,heads,dim,block,precision,path', [
    ('tpu', 8192, 64, 128, 256, 'high', 'kernel'),     # the cell, mixed
    ('tpu', 8192, 64, 128, 256, 'default', 'kernel'),  # its control lane
    ('tpu', 8192, 64, 128, 256, None, 'kernel'),
    ('tpu', 8192, 64, 128, 256, 'highest', 'xla'),     # no lane for it
    ('cpu', 8192, 64, 128, 256, 'high', 'xla'),        # interpreted there
    ('tpu', 8000, 64, 128, 256, 'high', 'xla'),        # ragged blocks
    ('tpu', 8192, 64, 16, 256, 'high', 'xla'),         # a head off the lanes
    ('tpu', 8192, 64, 128, 64, 'high', 'xla'),         # a block off the lanes
    ('tpu', 8320, 64, 128, 128, 'high', 'xla'),        # ragged key tiles
    ('tpu', 8192, 256, 128, 256, 'high', 'xla'),       # queries past VMEM
    ('tpu', 256, 4, 128, 256, 'high', 'kernel'),       # one block, one tile
])
def test_resolve_index_decides_the_form(platform, s, heads, dim, block,
                                        precision, path):
    assert resolve_index(platform, s, heads, dim, block, precision) == path


def test_a_block_of_packed_queries_over_the_vmem_budget_is_refused():
    assert pallas_index.query_vmem_bytes(64, 128, 256, 3) == 29_360_128
    assert pallas_index.query_vmem_bytes(256, 128, 256, 3) > \
        pallas_index.QUERY_VMEM_BYTES


# -- the step ----------------------------------------------------------------------

INDEXED = dict(index_head_dim=128, qk_nope_head_dim=64, qk_rope_head_dim=64,
               v_head_dim=128, swa_qk_nope_head_dim=128,
               swa_qk_rope_head_dim=64, swa_v_head_dim=128)


@pytest.mark.parametrize('platform,precision,calls', [
    ('tpu', 'high', 2),         # precision=mixed: one call a full layer
    ('tpu', 'default', 2),      # the control lane too
    ('tpu', 'highest', 0),      # highest keeps XLA's form
    ('cpu', 'high', 0),
])
def test_the_step_lowered_for_a_tpu_scores_through_the_kernel(platform,
                                                              precision,
                                                              calls):
    """A dots3_note trunk at 128-wide index heads, 256 ids a window (one
    scored block of 256 over one key tile): one index_scores call in each
    full layer's window loop where resolve_index says 'kernel', one
    topk_threshold call beside it on its table, and the step's notes say
    so."""
    from tests.test_dots3_trunk import program_cfg

    from video_features_tpu.extract.lm import ExtractLM
    cfg = program_cfg(**INDEXED)
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in lm.param_shapes(cfg).items()}
    ids = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    with jax.default_matmul_precision(precision):
        text = jax.jit(partial(ExtractLM._forward, cfg=cfg,
                               platform=platform)).trace(
            params, ids).lower(lowering_platforms=('tpu',)).as_text()
    assert text.count('kernel_name = "index_scores"') == calls
    # the thresholds beside it, from its table
    assert text.count('kernel_name = "topk_threshold"') == calls
    notes = lm.kernels(cfg, platform, 256, precision)
    assert notes['index_scores'] == ('kernel' if calls else 'xla')
    assert notes['topk_threshold'] == notes['index_scores']


def test_a_full_layer_through_the_kernel_is_the_xla_layer_and_counts_it(
        monkeypatch):
    """sparse_mla_block at 32 ids (one block, scored) on the XLA form and
    through the kernel interpreted: the same layer to the passes' rounding,
    and the count [blocks scored, of them through the kernel, rows the tie
    rule settled, rows scored]; the kernel's table thresholded by
    ``topk_threshold`` too (interpreted) gives the same layer exactly, and
    the same count."""
    from tests.test_dots3_trunk import program_cfg

    from video_features_tpu.ops.precision import rel_l2
    cfg = program_cfg(index_head_dim=128)
    params = {n: jnp.asarray(v) for n, v in lm.init_params(cfg, 3).items()}
    x = jnp.asarray(np.random.default_rng(5).standard_normal((32, 64)),
                    jnp.float32)
    a = 'model.layers.0.self_attn'
    with jax.default_matmul_precision('high'):
        want, n_xla = lm.sparse_mla_block(params, a, x, cfg, 8, 'cpu')
        monkeypatch.setattr(lm, 'resolve_index', lambda *args: 'kernel')
        with pltpu.force_tpu_interpret_mode():
            got, n_kernel = lm.sparse_mla_block(params, a, x, cfg, 8, 'cpu')
            monkeypatch.setattr(lm, 'resolve_threshold',
                                lambda *args: 'kernel')
            both, n_both = lm.sparse_mla_block(params, a, x, cfg, 8, 'cpu')
    assert rel_l2(got, want) < 1e-5
    np.testing.assert_array_equal(np.asarray(both), np.asarray(got))
    ties = int(n_xla[2])
    np.testing.assert_array_equal(np.asarray(n_xla), [1, 0, ties, 32])
    np.testing.assert_array_equal(np.asarray(n_kernel), [1, 1, ties, 32])
    np.testing.assert_array_equal(np.asarray(n_both), n_kernel)


@pytest.mark.parametrize('index,valid,capacity', [
    # dots3-note.corpus: 24 blocks of 256 a window, 2 windows; 3 rows tied
    ([[48, 48, 1, 12288], [48, 48, 2, 12288]], 96, 96),
    ([[48, 0, 0, 12288], [48, 0, 0, 12288]], 0, 96),    # XLA's form
    ([[0, 0, 0, 0], [0, 0, 0, 0]], 0, 0),   # topk past the window: none
])
def test_the_counter_adds_blocks_scored_through_the_kernel(index, valid,
                                                           capacity):
    """One fetched step's (expert counts, indexer counts) → the stage
    table: ``index_kernel`` is blocks through the kernel ÷ blocks scored
    over the full layers, ``topk_ties`` rows the tie rule settled ÷ rows
    scored, beside the expert rows."""
    from tests.test_dots3_trunk import program_cfg
    cfg = program_cfg()
    tracer = Tracer()
    experts = np.full((4, 8), 8, np.int32)
    lm.count_selection(tracer, (experts, np.asarray(index, np.int32)), cfg,
                       64)
    lm.count_selection(tracer, (experts, np.asarray(index, np.int32)), cfg,
                       64)
    table = tracer.report()
    # a table row shows slot counts only where some were recorded
    row = table['index_kernel']
    assert (row.get('occ_valid', 0), row.get('occ_capacity', 0)) == (
        2 * valid, 2 * capacity)
    ties = table['topk_ties']
    index = np.asarray(index)
    assert (ties.get('occ_valid', 0), ties.get('occ_capacity', 0)) == (
        2 * int(index[:, 2].sum()), 2 * int(index[:, 3].sum()))
    assert table['moe_held']['occ_valid'] == 2 * 4 * 8 * 8
    from video_features_tpu.extract.lm import step_counter
    assert step_counter(cfg) == (lm.SELECTION_COUNTER, lm.count_selection)
