"""The cell ``joyai-flash.corpus`` and its configuration
``joyai-llm-flash-ep4``: the configuration file against the published
config, the FLOP count recounted, a whole run of the cell through
``harness.run`` at a tiny size on the CPU — sound, then broken underneath —
and the reader of the routing counters. (The trunk, its ops and the extractor
against the plain reference: ``tests/test_latent_moe.py``.)"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import loader
from _layers import Ops

from .conftest import BENCH

CELL = 'joyai-flash.corpus'
SEED = 2 ** 31 + 2027
REF = loader.load_module('references', 'joyai-llm-flash-ep4')

# https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json,
# as the catalog beside the model-configs guide holds it
PUBLISHED = {
    'attention_bias': False, 'ep_size': 1, 'first_k_dense_replace': 1,
    'head_dim': 64, 'hidden_act': 'silu', 'hidden_size': 2048,
    'intermediate_size': 7168, 'kv_lora_rank': 512,
    'max_position_embeddings': 131072, 'model_type': 'joyai_llm_flash',
    'moe_intermediate_size': 768, 'moe_layer_freq': 1, 'n_group': 1,
    'n_routed_experts': 256, 'n_shared_experts': 1, 'norm_topk_prob': True,
    'num_attention_heads': 32, 'num_experts_per_tok': 8,
    'num_hidden_layers': 40, 'num_key_value_heads': 32,
    'num_nextn_predict_layers': 1, 'q_lora_rank': 1536, 'qk_head_dim': 192,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'rms_norm_eps': 1e-06,
    'rope_interleave': True, 'rope_scaling': None, 'rope_theta': 32000000,
    'routed_scaling_factor': 2.5, 'scoring_func': 'sigmoid',
    'tie_word_embeddings': False, 'topk_group': 1, 'topk_method': 'noaux_tc',
    'v_head_dim': 128, 'vocab_size': 129280}

# the trunk at a size a test run can hold: the program's overrides, and the
# same sizes under the reference's names
TINY_PROGRAM = dict(
    device='cpu', batch_size=2, vocab_size=512, hidden_size=64,
    num_hidden_layers=3, intermediate_size=160, moe_intermediate_size=32,
    n_routed_experts=16, n_experts_held=4, num_experts_per_tok=4,
    num_attention_heads=2, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, stack_size=4,
    step_size=4, patch_grid=4)
TINY_REFERENCE = dict(
    REF.CFG, vocab_size=512, hidden_size=64, layers=3, intermediate_size=160,
    moe_intermediate_size=32, router_experts=16, n_routed_experts=4,
    num_experts_per_tok=4, num_attention_heads=2, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
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
    # ... and since PR 37 the host's waits, the device time of each of the
    # trunk's scopes and the causal kernel's roofline; a later PR may list
    # more for the cell
    assert per_layer >= {
        'batch_occupancy.clips', 'decode_busy.clips', 'device_idle.clips',
        'step_mfu.clips', 'tokenise_busy.clips', 'moe_balance.clips',
        'device_wait.clips', 'input_wait.clips', 'idle_decode.clips',
        'idle_unexplained.clips', 'mla_ms.clips', 'moe_ms.clips',
        'dense_mlp_ms.clips', 'unscoped_ms.clips',
        'causal_attention_roofline'}


def test_the_configuration_keeps_every_published_key_but_the_cut(bench_json):
    body = loader.load_json('configs', 'joyai-llm-flash-ep4')
    entry = [c for c in bench_json['configs']
             if c['name'] == 'joyai-llm-flash-ep4'][0]
    assert body['reduced'] == entry['reduced'] == [
        'layers', 'n_routed_experts', 'num_nextn_predict_layers']
    assert body['source'] == ('https://huggingface.co/jdopensource/'
                              'JoyAI-LLM-Flash/blob/main/config.json')
    for key, value in PUBLISHED.items():
        if key not in body['reduced']:
            assert body[key] == value, key
    assert (body['layers'], body['n_routed_experts'],
            body['num_nextn_predict_layers']) == (5, 64, 0)
    assert body['published'] == {
        'layers': 40, 'num_hidden_layers': 40, 'n_routed_experts': 256,
        'num_nextn_predict_layers': 1}
    assert body['deployment'] and body['departures'] and body['assumed']
    # what the program is handed is the same cut, and the shipped yml and the
    # reference hold the published widths
    over = body['overrides']
    assert (over['num_hidden_layers'], over['n_experts_held'],
            over['batch_size']) == (5, 64, 4)
    from video_features_tpu.config import load_config
    yml = load_config('lm', overrides={'video_paths': ['x.mp4'],
                                       'device': 'cpu'})
    for key in ('vocab_size', 'hidden_size', 'intermediate_size',
                'moe_intermediate_size', 'n_routed_experts',
                'n_shared_experts', 'num_experts_per_tok',
                'routed_scaling_factor', 'num_attention_heads',
                'q_lora_rank', 'kv_lora_rank', 'qk_nope_head_dim',
                'qk_rope_head_dim', 'v_head_dim', 'rope_theta',
                'rms_norm_eps', 'first_k_dense_replace',
                'num_hidden_layers', 'norm_topk_prob'):
        assert yml[key] == PUBLISHED[key], key
    for key in ('vocab_size', 'hidden_size', 'intermediate_size',
                'moe_intermediate_size', 'num_experts_per_tok',
                'num_attention_heads', 'q_lora_rank', 'kv_lora_rank',
                'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim',
                'rope_theta', 'rms_norm_eps', 'routed_scaling_factor'):
        assert REF.CFG[key] == PUBLISHED[key], key
    assert REF.CFG['router_experts'] == PUBLISHED['n_routed_experts']
    assert (REF.CFG['layers'], REF.CFG['n_routed_experts']) == (5, 64)
    assert REF.window_ids() == yml['stack_size'] * yml['patch_grid'] ** 2 \
        == 8192


def test_the_cells_pass_is_71_windows_in_18_steps():
    traffic = loader.load_json('traffic', 'corpus-8')
    rows = [REF.rows_of(n) for n in traffic['frames']]
    assert rows == [3, 3, 5, 6, 8, 11, 15, 20] and sum(rows) == 71
    driver = loader.load_module('drivers', 'packed')

    class Four:
        def packed_batch_size(self):
            return 4
    assert driver.batch_slots(Four(), rows) == 72


def test_flops_per_unit_is_the_models_work_recounted():
    """Trace the reference at the published widths (shapes only: nothing is
    computed), take away what it wastes and put the model's terms in."""
    specs = REF.param_specs()['checkpoint_path']
    params = {'checkpoint_path': {
        name: jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
        for name, _, shape, _ in specs}}
    assert sum(int(np.prod(s)) for _, _, s, _ in specs) == 1_669_497_856
    ops = Ops()
    out = jax.eval_shape(lambda p, u: REF.forward(ops, p, u), params,
                         jax.ShapeDtypeStruct((1, 8192), jnp.int32))
    assert out.shape == (1, 2048)
    s = 8192
    attention = s * (s + 1) // 2 * 32 * (192 + 128) * 5
    routed = s * 8 * 64 // 256 * 3 * 2048 * 768 * 4
    outside = s * (5 * 26_345_472 + 3 * 2048 * 7168
                   + 4 * (3 * 2048 * 768 + 2048 * 256))
    assert REF.model_macs(ops.macs) == outside + attention + routed
    body = loader.load_json('configs', 'joyai-llm-flash-ep4')
    assert body['flops_per_unit'] == 2 * REF.model_macs(ops.macs) \
        == 7_278_241_513_472


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


def _swap_two_windows(extractor):
    step = extractor.packed_step

    def bad(batch):
        out = dict(step(batch))
        out['lm'] = out['lm'][::-1]
        return out
    extractor.packed_step = bad


def _route_everything_to_one_expert(extractor):
    """A fault only this family can have: the router's bias grown until
    every token takes the same experts."""
    params = dict(extractor.params)
    for name in list(params):
        if name.endswith('e_score_correction_bias'):
            params[name] = params[name].at[:4].add(10.0)
    extractor.params = params


def _lose_the_tail(extractor):
    result = extractor.packed_result

    def bad(task):
        return {k: v[:-1] for k, v in result(task).items()}
    extractor.packed_result = bad


@pytest.mark.parametrize('fault,number', [
    (_swap_two_windows, 'row_rel_l2_max'),
    (_route_everything_to_one_expert, 'rel_l2'),
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


# -- the routing counters' reader -------------------------------------------------

def test_stage_occupancy_reads_a_counter_and_nothing_where_there_is_none():
    reader = loader.load_module('readers', 'stage_occupancy')
    spec = json.loads((BENCH / 'metrics' / 'moe_balance.clips.json')
                      .read_text())
    assert spec['reader'] == 'stage_occupancy'
    logged = []
    stages = {'moe_route': {'count': 0, 'total_s': 0.0,
                            'occ_valid': 900, 'occ_capacity': 1200},
              'moe_held': {'occ_valid': 900, 'occ_capacity': 3600}}
    got = reader.read({'metric': spec, 'stages': stages,
                       'log': lambda *a: logged.append(' '.join(map(str, a)))})
    assert got == 75.0
    assert logged == ['counter moe_held: 900 / 3600 = 25.000 %']
    # the parent commit, or any other family: no such counter, no number
    assert reader.read({'metric': spec, 'stages': {'model': {'count': 3}},
                        'log': logged.append}) is None
