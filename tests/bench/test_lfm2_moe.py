"""The cell ``lfm2-moe.corpus`` and its configuration ``lfm2-8b-a1b-l8``: the
configuration file against the published config, the FLOP count recounted,
a whole run of the cell through ``harness.run`` at a tiny size on the CPU —
sound, then broken underneath — and the reader of the block walk's counter.
(The trunk, its ops and the extractor against the plain reference, and the
reference's operators against ``transformers``':
``tests/test_hybrid_trunk.py``.)"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import loader
from _layers import Ops

from .conftest import BENCH

CELL = 'lfm2-moe.corpus'
CONFIG = 'lfm2-8b-a1b-l8'
SEED = 2 ** 31 + 2033
REF = loader.load_module('references', CONFIG)

# https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json, as the
# catalog beside the model-configs guide holds it
PUBLISHED = {
    'conv_L_cache': 3, 'conv_bias': False, 'hidden_size': 2048,
    'intermediate_size': 7168,
    'layer_types': [
        'conv', 'conv', 'full_attention', 'conv', 'conv', 'conv',
        'full_attention', 'conv', 'conv', 'conv', 'full_attention', 'conv',
        'conv', 'conv', 'full_attention', 'conv', 'conv', 'conv',
        'full_attention', 'conv', 'conv', 'full_attention', 'conv', 'conv'],
    'max_position_embeddings': 128000, 'model_type': 'lfm2_moe',
    'moe_intermediate_size': 1792, 'norm_eps': 1e-05, 'norm_topk_prob': True,
    'num_attention_heads': 32, 'num_dense_layers': 2, 'num_experts': 32,
    'num_experts_per_tok': 4, 'num_hidden_layers': 24,
    'num_key_value_heads': 8, 'rope_theta': 1000000,
    'routed_scaling_factor': 1, 'use_expert_bias': True, 'vocab_size': 65536}
# the keys the program's trunk is built from (models/hybrid_trunk.py) that
# the cut leaves as published
MODEL_KEYS = ('vocab_size', 'hidden_size', 'conv_L_cache', 'num_dense_layers',
              'intermediate_size', 'moe_intermediate_size', 'num_experts',
              'num_experts_per_tok', 'routed_scaling_factor',
              'norm_topk_prob', 'use_expert_bias', 'num_attention_heads',
              'num_key_value_heads', 'rope_theta', 'norm_eps')

# the trunk at a size a test run can hold: the program's overrides, and the
# same sizes under the reference's names
KINDS = ['conv', 'full_attention', 'conv', 'conv']
TINY_PROGRAM = dict(
    device='cpu', batch_size=2, vocab_size=512, hidden_size=64,
    num_hidden_layers=4, layer_types=KINDS, num_dense_layers=1,
    intermediate_size=160, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, num_attention_heads=4, num_key_value_heads=2,
    stack_size=4, step_size=4, patch_grid=4)
TINY_REFERENCE = dict(
    REF.CFG, vocab_size=512, hidden_size=64, layers=4,
    layer_types=tuple(KINDS), num_dense_layers=1, intermediate_size=160,
    moe_intermediate_size=32, router_experts=8, n_routed_experts=8,
    num_experts_per_tok=2, num_attention_heads=4, num_key_value_heads=2,
    frames=4, patch_grid=4, query_block=16)
TINY = dict(
    require_tpu=False, program_overrides=TINY_PROGRAM,
    traffic_overrides={'clips': 3, 'frames': [9, 22, 13], 'width': 96,
                       'height': 64},
    workload_overrides={'sample': {'videos': 3, 'rows': 4, 'block': 2}})
ARGV = ['--workload', CELL, '--seed', str(SEED), '--seconds', '0.3',
        '--trace', '0']


@pytest.fixture()
def tiny_reference(monkeypatch):
    monkeypatch.setattr(REF, 'CFG', TINY_REFERENCE)


# -- the configuration and the cell, as files -------------------------------------

def test_the_cell_reports_its_end_to_end_metrics(bench_json):
    got = harness.metrics_of({'name': CELL, 'bench': bench_json},
                             'end_to_end')
    assert {m['name'] for m in got} == {'clips_per_s', 'setup_s'}
    per_layer = {m['name'] for m in harness.metrics_of(
        {'name': CELL, 'bench': bench_json}, 'per_layer')}
    # the four list-less .clips metrics, the walk's own, and since PR 37
    # the host's waits, the device time of each of the trunk's scopes and
    # the causal kernel's roofline; a later PR may list more for the cell
    assert per_layer >= {
        'batch_occupancy.clips', 'decode_busy.clips', 'device_idle.clips',
        'step_mfu.clips', 'moe_walk_fill.clips', 'moe_balance.clips',
        'tokenise_busy.clips', 'device_wait.clips', 'input_wait.clips',
        'moe_ms.clips', 'dense_mlp_ms.clips', 'attention_ms.clips',
        'short_conv_ms.clips', 'unscoped_ms.clips',
        'causal_attention_roofline'}
    entry = [w for w in bench_json['workloads'] if w['name'] == CELL][0]
    assert (entry['config'], entry['traffic'], entry['chips']) == (
        CONFIG, 'corpus-8', 1)
    assert loader.load_json('workloads', CELL)['driver'] == 'packed'


def test_the_configuration_keeps_every_published_key_but_the_cut(bench_json):
    body = loader.load_json('configs', CONFIG)
    entry = [c for c in bench_json['configs'] if c['name'] == CONFIG][0]
    assert body['reduced'] == entry['reduced'] == ['layers']
    assert body['source'] == ('https://huggingface.co/LiquidAI/LFM2-8B-A1B/'
                              'blob/main/config.json')
    for key, value in PUBLISHED.items():
        assert body[key] == value, key
    assert body['layers'] == 8
    assert body['published'] == {'layers': 24, 'num_hidden_layers': 24}
    assert body['deployment'] and body['departures']
    assert set(body['assumed']) >= {'tokeniser', 'parameters', 'normaliser',
                                    'weights', 'positions'}
    assert body['feature_type'] == 'lm'
    assert body['control_overrides'] == {'precision': 'default'}
    # what the program is handed spells every model key (the shipped yml is
    # another model's), at the published value but for the depth
    over = body['overrides']
    assert over['model_type'] == 'lfm2_moe' and over['num_hidden_layers'] == 8
    assert over['layer_types'] == PUBLISHED['layer_types'][:8] \
        == list(REF.CFG['layer_types'])
    # two whole periods: 6 conv : 2 attention as the published 18 : 6
    assert over['layer_types'].count('conv') == 6
    assert PUBLISHED['layer_types'].count('conv') == 18
    for key in MODEL_KEYS:
        assert over[key] == PUBLISHED[key], key
    for key in ('vocab_size', 'hidden_size', 'conv_L_cache',
                'num_dense_layers', 'intermediate_size',
                'moe_intermediate_size', 'num_experts_per_tok',
                'num_attention_heads', 'num_key_value_heads', 'norm_eps'):
        assert REF.CFG[key] == PUBLISHED[key], key
    assert REF.CFG['router_experts'] == REF.CFG['n_routed_experts'] == 32
    assert REF.CFG['layers'] == 8
    assert (over['device'], over['precision'], over['on_extraction'],
            over['pack_across_videos'], over['batch_size']) == (
        'tpu', 'mixed', 'save_numpy', True, 4)
    assert REF.window_ids() == 32 * 16 ** 2 == 8192
    from video_features_tpu.config import load_config
    from video_features_tpu.models import hybrid_trunk
    args = load_config('lm', overrides=dict(over, video_paths=['x.mp4'],
                                            device='cpu'))
    # the yml's geometry and every expert held
    assert (args['stack_size'], args['step_size'], args['patch_grid']) == (
        32, 32, 16)
    cfg = hybrid_trunk.TrunkConfig.from_args(args)
    assert cfg.n_experts_held == 32 and cfg.first_expert == 0
    assert hybrid_trunk.param_count(cfg) == 2_458_327_488


def test_the_cells_pass_is_71_windows_in_18_steps():
    traffic = loader.load_json('traffic', 'corpus-8')
    rows = [REF.rows_of(n) for n in traffic['frames']]
    assert rows == [3, 3, 5, 6, 8, 11, 15, 20] and sum(rows) == 71
    driver = loader.load_module('drivers', 'packed')

    class Four:
        def packed_batch_size(self):
            return 4
    assert driver.batch_slots(Four(), rows) == 72          # 18 steps of 4
    # 4 windows a step: 4,096 assignments an expert and layer at even routing
    assert 4 * REF.window_ids() * 4 // 32 == 4096
    workload = loader.load_json('workloads', CELL)
    assert workload['warm_clips'] == [0]
    assert workload['sample'] == {'videos': 4, 'rows': 2, 'block': 1}


def test_flops_per_unit_is_the_models_work_recounted():
    """Trace the reference at the published widths (shapes only: nothing is
    computed), take its waste away and put the model's work in."""
    specs = REF.param_specs()['checkpoint_path']
    params = {'checkpoint_path': {
        name: jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
        for name, _, shape, _ in specs}}
    assert sum(int(np.prod(s)) for _, _, s, _ in specs) == 2_458_327_488
    ops = Ops()
    out = jax.eval_shape(lambda p, u: REF.forward(ops, p, u), params,
                         jax.ShapeDtypeStruct((1, 8192), jnp.int32))
    assert out.shape == (1, 2048)
    s = 8192
    # a token's multiply-adds outside the experts and the score pairs
    conv = 6 * (2048 * 6144 + 2048 * 2048)
    projections = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    dense = 2 * 3 * 2048 * 7168
    routers = 6 * 2048 * 32
    assert (conv, projections, dense, routers) == (
        100_663_296, 20_971_520, 88_080_384, 393_216)
    outside = s * (conv + projections + dense + routers)
    waste_attn, waste_routed = REF.reference_waste_macs()
    assert ops.macs == outside + waste_attn + waste_routed
    assert waste_routed == s * 32 * 3 * 2048 * 1792 * 6
    pairs = s * (s + 1) // 2 * 32 * 128 * 2
    routed = s * 4 * 3 * 2048 * 1792 * 6
    assert REF.model_macs(ops.macs) == outside + pairs + routed
    body = loader.load_json('configs', CONFIG)
    assert body['flops_per_unit'] == 2 * REF.model_macs(ops.macs) \
        == 8_321_566_244_864
    # the new mechanisms are the step: experts 52 %, conv operators 20 %
    total = REF.model_macs(ops.macs)
    assert round(100 * routed / total) == 52
    assert round(100 * s * conv / total) == 20
    assert round(100 * s * dense / total) == 17
    assert round(100 * (s * projections + pairs) / total) == 11


# -- a whole run, sound and broken --------------------------------------------------

def test_a_sound_tiny_run_is_correct(tiny_reference):
    result = harness.run(ARGV, **TINY)
    assert result['correct'] is True
    assert result['failed'] == 0 and result['attempted'] % 3 == 0
    assert set(result['metrics']) == {'clips_per_s', 'setup_s'}
    # on the CPU the program computes in float32: it sits on the reference,
    # decode, tokeniser, packing, scatter and save included
    assert result['checks']['rel_l2']['value'] < 1e-5
    assert result['checks']['rows_off']['value'] == 0
    json.dumps(result)


def _alter_a_row(extractor):
    step = extractor.packed_step

    def bad(batch):
        out = dict(step(batch))
        out['lm'] = out['lm'].at[0].multiply(1.05)
        return out
    extractor.packed_step = bad


def _shift_the_rows(extractor):
    step = extractor.packed_step

    def bad(batch):
        out = dict(step(batch))
        out['lm'] = jnp.roll(out['lm'], 1, axis=0)
        return out
    extractor.packed_step = bad


def _lose_the_tail(extractor):
    result = extractor.packed_result

    def bad(task):
        return {k: v[:-1] for k, v in result(task).items()}
    extractor.packed_result = bad


@pytest.mark.parametrize('fault,number', [
    (_alter_a_row, 'row_rel_l2_max'),
    (_shift_the_rows, 'row_rel_l2_max'),
    (_lose_the_tail, 'rows_off'),
])
def test_a_broken_timed_path_is_not_correct(tiny_reference, fault, number):
    result = harness.run(ARGV, before_window=fault, **TINY)
    assert result['correct'] is False
    check = result['checks'][number]
    assert check['value'] > check['limit']


def test_the_precision_control_is_not_correct(tiny_reference, tmp_path):
    """The reference in one bfloat16 pass, saved as the program would have
    saved it, fails ``rel_l2`` under the cell's own limits."""
    import compare
    import traffic_gen
    cell = harness.load_cell(CELL)
    ckpts = harness.make_weights(REF, SEED, tmp_path)
    corpus = traffic_gen.generate(
        dict(cell['traffic'], **TINY['traffic_overrides']), SEED,
        str(tmp_path / 'corpus'))
    items = traffic_gen.pass_paths(corpus, 'p0')
    for item in items:
        units = REF.load_units(item['path'],
                               range(REF.rows_of(item['frames'])))
        np.save(item['path'] + '.npy', compare.reference_rows(
            REF, ckpts, units, 2, mode='bfloat16'))
    done = compare.collect([items], lambda p: p + '.npy', REF)
    workload = dict(cell['workload'], **TINY['workload_overrides'])
    checks, n = compare.compare(done, REF, ckpts, workload, SEED)
    assert n == 9                       # 2 + 4 of 5 + 3 windows
    assert checks['rows_off']['ok'] and checks['nonfinite']['ok']
    assert not checks['rel_l2']['ok']


# -- the walk counter's reader ----------------------------------------------------

def test_moe_walk_fill_reads_the_counter_and_nothing_where_there_is_none(
        bench_json):
    reader = loader.load_module('readers', 'stage_occupancy')
    spec = json.loads((BENCH / 'metrics' / 'moe_walk_fill.clips.json')
                      .read_text())
    assert (spec['reader'], spec['stage'], spec['also_log']) == (
        'stage_occupancy', 'moe_walk', ['moe_route', 'moe_held'])
    entry = [m for m in bench_json['per_layer']
             if m['name'] == 'moe_walk_fill.clips'][0]
    assert entry == {'name': 'moe_walk_fill.clips', 'unit': '%',
                     'better': 'higher', 'source': 'program_counter',
                     'layer': 'device step', 'moves': 'clips_per_s',
                     'workloads': ['lfm2-moe.corpus']}
    logged = []
    # one step at even routing: 32 experts × 4,096 assignments, 6 layers,
    # every block full
    even = 6 * 32 * 4096
    stages = {'moe_walk': {'count': 0, 'total_s': 0.0, 'occ_valid': even,
                           'occ_capacity': even},
              'moe_route': {'occ_valid': even, 'occ_capacity': 2 * even},
              'moe_held': {'occ_valid': even, 'occ_capacity': even}}
    got = reader.read({'metric': spec, 'stages': stages,
                       'log': lambda *a: logged.append(' '.join(map(str, a)))})
    assert got == 100.0
    assert logged == [f'counter moe_route: {even} / {2 * even} = 50.000 %',
                      f'counter moe_held: {even} / {even} = 100.000 %']
    # uneven counts leave the last block of each expert part empty
    part = {'moe_walk': {'occ_valid': 900, 'occ_capacity': 1024}}
    assert reader.read({'metric': spec, 'stages': part,
                        'log': print}) == pytest.approx(87.890625)
    # the parent commit, or the retention trunk: no such counter, no number
    assert reader.read({'metric': spec, 'stages': {'model': {'count': 3},
                                                   'moe_route': {
                                                       'occ_valid': 1,
                                                       'occ_capacity': 2}},
                        'log': lambda *a: None}) is None
