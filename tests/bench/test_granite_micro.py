"""The cell ``granite-micro.corpus`` and its configuration
``granite-4.0-h-micro-l20``: the configuration file against the published
config key by key, the parameter and FLOP counts recounted, a whole run of
the cell through ``harness.run`` at a tiny size on the CPU — sound, then
broken underneath —, the kernel's yardstick (operations and bytes against a
brute count, the event pattern, the roofline reader) and the scope metrics'
names. (The trunk and its mechanisms against the plain reference:
``tests/test_granite_trunk.py``; the scan's two forms: ``tests/test_ssd.py``.)"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import loader
from _layers import Ops

CELL = 'granite-micro.corpus'
CONFIG = 'granite-4.0-h-micro-l20'
SEED = 2 ** 31 + 4409
REF = loader.load_module('references', CONFIG)
M, A = 'mamba', 'attention'
METRICS = ('mamba_ms.clips', 'ssd_ms.clips', 'ssd_scan_roofline')

# https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json,
# as the catalog beside the model-configs guide holds it
PUBLISHED = {
    'attention_bias': False, 'attention_multiplier': 0.015625,
    'embedding_multiplier': 12, 'hidden_act': 'silu', 'hidden_size': 2048,
    'intermediate_size': 8192,
    'layer_types': ([M] * 5 + [A] + [M] * 4) * 4, 'logits_scaling': 8,
    'mamba_chunk_size': 256, 'mamba_conv_bias': True, 'mamba_d_conv': 4,
    'mamba_d_head': 64, 'mamba_d_state': 128, 'mamba_expand': 2,
    'mamba_n_groups': 1, 'mamba_n_heads': 64, 'mamba_proj_bias': False,
    'max_position_embeddings': 131072, 'model_type': 'granitemoehybrid',
    'normalization_function': 'rmsnorm', 'num_attention_heads': 32,
    'num_experts_per_tok': 0, 'num_hidden_layers': 40,
    'num_key_value_heads': 8, 'num_local_experts': 0,
    'position_embedding_type': 'nope', 'residual_multiplier': 0.22,
    'rms_norm_eps': 1e-05, 'rope_scaling': None, 'rope_theta': 10000,
    'shared_intermediate_size': 8192, 'tie_word_embeddings': True,
    'vocab_size': 100352}
REDUCED = {'layers': (40, 20)}

# the trunk at a size a test run can hold: the program's overrides, and the
# same sizes under the reference's names
KINDS = [M, M, A, M]
WIDTHS = dict(vocab_size=512, hidden_size=64, shared_intermediate_size=96,
              num_attention_heads=4, num_key_value_heads=2,
              attention_multiplier=0.0625, mamba_n_heads=4, mamba_d_head=32,
              mamba_d_state=16)
TINY_PROGRAM = dict(
    device='cpu', batch_size=2, num_hidden_layers=4, layer_types=KINDS,
    mamba_chunk_size=8, stack_size=2, step_size=2, patch_grid=4, **WIDTHS)
TINY_REFERENCE = dict(REF.CFG, layers=4, layer_types=tuple(KINDS), frames=2,
                      patch_grid=4, query_block=8, **WIDTHS)
TINY = dict(
    require_tpu=False, program_overrides=TINY_PROGRAM,
    traffic_overrides={'clips': 3, 'frames': [5, 11, 7], 'width': 96,
                       'height': 64},
    workload_overrides={'sample': {'videos': 3, 'rows': 4, 'block': 2}})
ARGV = ['--workload', CELL, '--seed', str(SEED), '--seconds', '0.3',
        '--trace', '0']


@pytest.fixture()
def tiny_reference(monkeypatch):
    monkeypatch.setattr(REF, 'CFG', TINY_REFERENCE)


# -- the configuration and the cell, as files -------------------------------------

def test_the_cell_reports_its_end_to_end_and_its_own_metrics(bench_json):
    cell = {'name': CELL, 'bench': bench_json}
    assert {m['name'] for m in harness.metrics_of(cell, 'end_to_end')} == {
        'clips_per_s', 'setup_s'}
    per_layer = {m['name'] for m in harness.metrics_of(cell, 'per_layer')}
    # the four list-less .clips metrics and the cell's own three; a later PR
    # may list more for the cell
    assert per_layer >= {'batch_occupancy.clips', 'decode_busy.clips',
                         'device_idle.clips', 'step_mfu.clips', *METRICS}
    entry = [w for w in bench_json['workloads'] if w['name'] == CELL][0]
    assert (entry['config'], entry['traffic'], entry['chips']) == (
        CONFIG, 'corpus-6', 1)
    workload = loader.load_json('workloads', CELL)
    assert workload['driver'] == 'packed' and workload['warm_clips'] == [0]
    assert workload['sample'] == {'videos': 3, 'rows': 2, 'block': 1}


def test_the_scope_metrics_name_the_programs_scopes_and_the_kernel_its_name(
        bench_json):
    from video_features_tpu.obs.scopes import SCOPES
    from video_features_tpu.ops import pallas_ssd
    listed = {m['name']: m for m in bench_json['per_layer']}
    for name in METRICS:
        spec = loader.load_json('metrics', name)
        assert spec['workloads'] == listed[name]['workloads'] == [CELL]
        assert spec['moves'] == listed[name]['moves'] == 'clips_per_s'
        assert spec['layer'] == listed[name]['layer']
        if spec['reader'] == 'scope_time':
            assert spec['scope'] in SCOPES and spec['unit'] == 'ms/clip'
            assert name == f'{spec["scope"]}_ms.clips'
        else:
            assert spec['kernel'] == pallas_ssd.NAME
    # ssd opens inside mamba: the path of a scan's op holds both
    assert SCOPES.index('mamba') < SCOPES.index('ssd')


def test_the_configuration_keeps_every_published_key_but_the_cut(bench_json):
    body = loader.load_json('configs', CONFIG)
    entry = [c for c in bench_json['configs'] if c['name'] == CONFIG][0]
    assert body['reduced'] == entry['reduced'] == sorted(REDUCED)
    assert body['source'] == entry['source'] == (
        'https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/'
        'config.json')
    for key, value in PUBLISHED.items():
        assert body[key] == value, key
    for key, (published, held) in REDUCED.items():
        assert body[key] == held and body['published'][key] == published
    o = body['overrides']
    assert o['num_hidden_layers'] == 20
    assert o['layer_types'] == PUBLISHED['layer_types'][:20]
    assert o['layer_types'].count(M) == 18
    for key in ('vocab_size', 'hidden_size', 'shared_intermediate_size',
                'num_attention_heads', 'num_key_value_heads',
                'attention_multiplier', 'embedding_multiplier',
                'residual_multiplier', 'logits_scaling',
                'position_embedding_type', 'rms_norm_eps',
                'num_local_experts', 'mamba_n_heads', 'mamba_d_head',
                'mamba_d_state', 'mamba_n_groups', 'mamba_d_conv',
                'mamba_expand', 'mamba_chunk_size', 'mamba_conv_bias',
                'mamba_proj_bias'):
        assert o[key] == PUBLISHED[key], key
    # 32,768 ids a window, one window a step
    assert (o['stack_size'], o['patch_grid'], o['batch_size']) == (32, 32, 1)
    assert o['precision'] == 'mixed' and body['control_overrides'] == {
        'precision': 'default'}


def test_the_parameters_are_recounted_from_the_programs_shapes():
    body = loader.load_json('configs', CONFIG)
    from video_features_tpu.config import load_config
    from video_features_tpu.models import hybrid_trunk
    args = load_config('lm', overrides=dict(
        body['overrides'], video_paths=['x.mp4'], device='cpu'))
    cfg = hybrid_trunk.TrunkConfig.from_args(args)
    assert cfg.model_type == 'granitemoehybrid'
    shapes = hybrid_trunk.param_shapes(cfg)

    def count(*parts):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if all(p in n for p in parts))
    assert count('layers.0.mamba') == 25_847_232
    assert count('layers.0.mamba.in_proj') == 17_432_576
    assert count('layers.0.mamba.out_proj') == 8_388_608
    assert count('layers.0.shared_mlp') == 50_331_648
    assert count('layers.0.') == 76_182_976
    assert count('layers.5.self_attn') == 10_485_760
    assert count('layers.5.') == 60_821_504
    assert count('embed_tokens') == 205_520_896
    assert hybrid_trunk.param_count(cfg) == 1_698_459_520
    specs = REF.param_specs()['checkpoint_path']
    assert {n: tuple(s) for n, _, s, _ in specs} == shapes
    assert '1,698,459,520 parameters = 6.79 GB' in body['departures']
    # the whole published model: 36 Mamba and 4 attention layers
    assert 36 * 76_182_976 + 4 * 60_821_504 + 205_520_896 + 2_048 \
        == 3_191_396_096


def test_the_cells_pass_is_10_windows_in_10_steps_of_1():
    traffic = loader.load_json('traffic', 'corpus-6')
    rows = [REF.rows_of(n) for n in traffic['frames']]
    assert sum(rows) == 10
    driver = loader.load_module('drivers', 'packed')

    class One:
        def packed_batch_size(self):
            return 1
    assert driver.batch_slots(One(), rows) == 10            # no padded slot
    # 128 chunks of 256 a layer-window
    assert REF.window_ids() // 256 == 128


def test_flops_per_unit_is_the_models_work_recounted():
    """Trace the reference at the published widths (shapes only: nothing is
    computed), take its waste away: the model's work is what is left."""
    specs = REF.param_specs()['checkpoint_path']
    params = {'checkpoint_path': {
        name: jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
        for name, _, shape, _ in specs}}
    ops = Ops()
    out = jax.eval_shape(lambda p, u: REF.forward(ops, p, u), params,
                         jax.ShapeDtypeStruct((1, 32768), jnp.int32))
    assert out.shape == (1, 2048)
    s = 32768
    # a token's multiply-adds outside the attention pairs
    mamba = 2048 * 8512 + 4096 * 2048 + 2 * 64 * 128 * 64
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert (mamba, attention) == (26_869_760, 10_485_760)
    mlp = 2048 * 16384 + 8192 * 2048
    outside = s * (18 * mamba + 2 * attention + 20 * mlp)
    per_pair = 32 * (64 + 64)
    assert ops.macs == outside + 2 * s * s * per_pair
    assert REF.reference_waste_macs() == 2 * (s * s - s * (s + 1) // 2) \
        * per_pair
    total = REF.model_macs(ops.macs)
    assert total == outside + 2 * s * (s + 1) // 2 * per_pair \
        == 53_919_153_651_712
    body = loader.load_json('configs', CONFIG)
    assert body['flops_per_unit'] == 2 * total == 107_838_307_303_424
    assert total / s == 1_645_481_984
    # the Mamba mixers, the SwiGLUs and the attention layers
    assert round(100 * s * 18 * mamba / total, 1) == 29.4
    assert round(100 * s * 20 * mlp / total, 1) == 61.2
    assert round(100 * (s * 2 * attention + 2 * s * (s + 1) // 2 * per_pair)
                 / total, 1) == 9.4


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


def _lose_the_tail(extractor):
    result = extractor.packed_result

    def bad(task):
        return {k: v[:-1] for k, v in result(task).items()}
    extractor.packed_result = bad


@pytest.mark.parametrize('fault,number', [
    (_alter_a_row, 'row_rel_l2_max'),
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
    assert checks['rows_off']['ok'] and checks['nonfinite']['ok']
    assert not checks['rel_l2']['ok']


# -- the kernel's yardstick ---------------------------------------------------------

CALL = {'positions': 32768, 'chunk': 256, 'heads': 64, 'head_dim': 64,
        'state': 128}


@pytest.fixture(scope='module')
def kernel():
    return loader.load_module('kernels', 'ssd_scan')


@pytest.fixture(scope='module')
def v5e():
    peaks = json.loads((loader.BENCH / 'peaks.json').read_text())
    return peaks['devices']['TPU v5 lite']


@pytest.mark.parametrize('positions,chunk', [
    (4, 4), (8, 4), (10, 4), (7, 16), (96, 32)])
def test_operations_and_bytes_by_brute_count(kernel, positions, chunk):
    heads, head_dim, state = 3, 2, 5
    macs = 0
    for q0 in range(0, positions, chunk):
        q = min(chunk, positions - q0)
        pairs = sum(t + 1 for t in range(q))        # (t, s) with s <= t
        macs += pairs * state + heads * pairs * head_dim
        macs += 2 * heads * q * state * head_dim
    shape = {'positions': positions, 'chunk': chunk, 'heads': heads,
             'head_dim': head_dim, 'state': state}
    assert kernel.flops(**shape) == 2 * macs
    assert kernel.bytes_moved(**shape) == 4 * positions * (
        2 * heads * head_dim + heads + 2 * state)


def test_the_cells_window_layer_from_its_keys(kernel, v5e):
    cfg = loader.load_json('configs', CONFIG)
    # one event is one window of one Mamba layer: the batch does not enter
    assert kernel.shapes(cfg, 1) == kernel.shapes(cfg, 2) == CALL
    assert kernel.flops(**CALL) == 2 * 128 * (
        32896 * 128 + 64 * 32896 * 64 + 2 * 64 * 256 * 128 * 64) \
        == 104_291_368_960
    assert kernel.bytes_moved(**CALL) == 32768 * 8512 * 4 == 1_115_684_864
    least, bound = kernel.min_seconds(v5e, **CALL)
    assert bound == 'bytes'
    assert least * 1e3 == pytest.approx(1.362, abs=0.001)


def test_the_event_pattern_matches_the_ssd_call_and_no_other(kernel):
    rx = re.compile(kernel.EVENT_MATCH)
    spec = loader.load_json('metrics', 'ssd_scan_roofline')
    assert (spec['reader'], spec['kernel'], spec['match'],
            spec['events_per_call'], spec['unit']) == (
        'kernel_roofline', 'ssd_scan', kernel.EVENT_MATCH,
        kernel.EVENTS_PER_CALL, '%')
    mine = ('%ssd_scan.7 = f32[32768,4096]{1,0:T(8,128)} '
            'custom-call(f32[32768,4096]{1,0:T(8,128)} %fusion.2), '
            'custom_call_target="tpu_custom_call"')
    assert rx.search(mine)
    for other in ('causal_attention', 'retention_read'):
        assert not rx.search(mine.replace('%ssd_scan', f'%{other}'))
        assert not re.compile(loader.load_module(
            'kernels', other).EVENT_MATCH).search(mine)
    assert not rx.search(mine.replace('tpu_custom_call', 'other'))


def test_the_roofline_reader_counts_one_call_an_event(kernel, v5e):
    import trace_reduce
    roof = loader.load_module('readers', 'kernel_roofline')
    cfg = loader.load_json('configs', CONFIG)
    least, _ = kernel.min_seconds(v5e, **CALL)
    name = ('%{}.{} = f32[32768,4096]{{1,0:T(8,128)}} '
            'custom-call(f32[32768,4096]{{1,0}} %x), '
            'custom_call_target="tpu_custom_call"')
    # 36 events (2 steps x 18 Mamba layers), each four times the least:
    # 25 %; the attention layers' calls beside them are not counted
    events = [(name.format('ssd_scan', i), 1e8 * i, 4 * least * 1e9)
              for i in range(36)]
    events += [(name.format('causal_attention', i), 5e7 + 1e8 * i, 2e8)
               for i in range(4)]
    trace = {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': trace_reduce.OPS_LINE, 'events': events}]}]}
    ctx = {'metric': loader.load_json('metrics', 'ssd_scan_roofline'),
           'trace': trace, 'config': cfg, 'batch_size': 1, 'peaks': v5e,
           'log': lambda *a: None}
    assert roof.read(ctx) == pytest.approx(25.0)
    # a parent without the kernel: no such event, nothing to read, no number
    trace['planes'][0]['lines'][0]['events'] = events[36:]
    assert roof.read(ctx) is None
