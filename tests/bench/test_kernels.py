"""The functions that compute operations and bytes, against hand-worked
shapes; and the configurations' FLOPs per unit against a recount on the
plain references."""
import json

import jax
import numpy as np
import pytest

import loader
from _layers import Ops


def test_raft_lookup_flops_and_bytes_by_hand():
    k = loader.load_module('kernels', 'raft_lookup')
    # one pair, 2x3 positions, 1 level, radius 1: 9 taps of 4 MACs
    assert k.flops(1, 2, 3, levels=1, radius=1) == 6 * 9 * 4 * 2
    # per position: 4x4 values touched + 2 coordinates + 9 results, float32
    assert k.bytes_moved(1, 2, 3, levels=1, radius=1) == 6 * (16 + 2 + 9) * 4
    # the cell's call: 8 stacks x 16 pairs, 32x43, 4 levels, radius 4
    assert k.flops(128, 32, 43) == 128 * 1376 * 4 * 81 * 8
    assert k.bytes_moved(128, 32, 43) == 128 * 1376 * (400 + 2 + 324) * 4


def test_raft_lookup_is_bytes_bound_on_the_v5e():
    k = loader.load_module('kernels', 'raft_lookup')
    peaks = json.loads((loader.BENCH / 'peaks.json').read_text())
    v5e = peaks['devices']['TPU v5 lite']
    cfg = loader.load_json('configs', 'i3d-two-stream-raft')
    shape = k.shapes(cfg, batch=8)
    assert shape == {'pairs': 128, 'h8': 32, 'w8': 43, 'levels': 4,
                     'radius': 4}
    least, bound = k.min_seconds(v5e, **shape)
    assert bound == 'bytes'
    assert least == pytest.approx(128 * 1376 * 726 * 4 / 819e9)


def test_peaks_table_has_a_source_and_no_default():
    import harness
    peaks = json.loads((loader.BENCH / 'peaks.json').read_text())
    assert peaks['source']
    assert harness.peaks_for('TPU v5 lite')['bf16_flops_per_s'] == 197e12
    with pytest.raises(SystemExit):
        harness.peaks_for('TPU v9 imaginary')


@pytest.mark.parametrize('spec,x,w,kw,macs', [
    # 2-D conv: 1x8x8x3 -> 3x3 kernel, 16 out, stride 1 pad 1: 64 outputs
    ('conv', (1, 8, 8, 3), (3, 3, 3, 16), dict(padding=1), 64 * 16 * 27),
    # stride 2, no padding: 3x3 outputs
    ('conv', (1, 8, 8, 3), (3, 3, 3, 16), dict(stride=2), 9 * 16 * 27),
    # 3-D conv: 1x4x6x6x2, 1x1x1 kernel, 5 out
    ('conv', (1, 4, 6, 6, 2), (1, 1, 1, 2, 5), {}, 144 * 5 * 2),
    # grouped: 4 in, 2 groups -> kernel in-dim 2
    ('conv', (1, 5, 5, 4), (1, 1, 2, 6), dict(groups=2), 25 * 6 * 2),
    ('einsum', (7, 5, 3), (7, 4, 3), 'nid,njd->nij', 7 * 5 * 4 * 3),
])
def test_ops_counts_multiply_adds(spec, x, w, kw, macs):
    ops = Ops()
    a = jax.ShapeDtypeStruct(x, np.float32)
    b = jax.ShapeDtypeStruct(w, np.float32)
    if spec == 'conv':
        jax.eval_shape(lambda p, q: ops.conv(p, q, **kw), a, b)
    else:
        jax.eval_shape(lambda p, q: ops.einsum(kw, p, q), a, b)
    assert ops.macs == macs


def test_ops_repeat_multiplies_a_scan_body():
    ops = Ops()
    a = jax.ShapeDtypeStruct((1, 4, 4, 2), np.float32)
    b = jax.ShapeDtypeStruct((1, 1, 2, 2), np.float32)
    with ops.repeat(20):
        jax.eval_shape(lambda p, q: ops.conv(p, q), a, b)
    assert ops.macs == 20 * 16 * 2 * 2


@pytest.mark.parametrize('config,unit_batch', [
    ('resnet50-framewise', (1, 224, 224, 3)),
    ('i3d-two-stream-raft', (1, 17, 256, 340, 3)),
])
def test_flops_per_unit_is_the_reference_s_count(config, unit_batch):
    cfg = loader.load_json('configs', config)
    ref = loader.load_module('references', cfg['reference'])
    shape, dtype = ref.unit_shape(cfg.get('assumed'))
    assert (1,) + tuple(shape) == unit_batch
    params = {key: {n: jax.ShapeDtypeStruct(tuple(s), np.float32)
                    for n, _, s, _ in specs}
              for key, specs in ref.param_specs().items()}
    ops = Ops()
    out = jax.eval_shape(lambda p, u: ref.forward(ops, p, u), params,
                         jax.ShapeDtypeStruct(unit_batch, dtype))
    assert out.shape == (1, ref.FEATURE_DIM)
    assert cfg['flops_per_unit'] == 2 * ops.macs


def test_resnet50_count_is_the_published_one():
    """He et al. give 3.8e9 multiply-adds for ResNet-50 v1; torchvision's
    v1.5 (stride on the 3x3) is 4.09e9."""
    cfg = loader.load_json('configs', 'resnet50-framewise')
    assert cfg['flops_per_unit'] / 2 == pytest.approx(4.09e9, rel=0.01)


def test_memory_peak_adds_the_reserved_temporaries():
    """The TPU runtime books a loaded program's temporaries apart from the
    buffers; the chip holds both, and the fullest chip counts."""
    import harness

    class Chip:
        def __init__(self, **stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    chips = [Chip(peak_bytes_in_use=400, peak_bytes_reserved=5000),
             Chip(peak_bytes_in_use=900, peak_bytes_reserved=100),
             Chip()]
    assert harness.memory_peak_bytes(chips) == 5400
    assert harness.memory_peak_bytes([Chip()]) == 0


def test_kernel_roofline_counts_a_call_per_four_level_events():
    import trace_reduce
    roof = loader.load_module('readers', 'kernel_roofline')
    spec = loader.load_json('metrics', 'raft_lookup_roofline')
    cfg = loader.load_json('configs', 'i3d-two-stream-raft')
    peaks = json.loads((loader.BENCH / 'peaks.json').read_text())
    k = loader.load_module('kernels', 'raft_lookup')
    least, _ = k.min_seconds(peaks['devices']['TPU v5 lite'],
                             **k.shapes(cfg, 8))
    name = ('%raft_corr_lookup_lanes.{} = f32[81,176128]{{1,0:T(8,128)}} '
            'custom-call(s32[1,176128]{{1,0}} %a), custom_call_target='
            '"tpu_custom_call"')
    other = ('%custom-call.7 = f32[136,32,43,128]{3,0,2,1} custom-call('
             'f32[136,8,43,128]{3,0,2,1} %b), custom_call_target='
             '"ConcatBitcast"')
    # a second Mosaic kernel in the step is not the lookup's time
    mosaic = ('%causal_attention.1 = f32[1,8192,4096]{2,1,0} custom-call('
              '%q), custom_call_target="tpu_custom_call"')
    # two lookups of four levels, each level taking the least time of a
    # whole call: the share is a quarter
    events = [(name.format(i % 4), 10.0 * i, least * 1e9) for i in range(8)]
    events += [(other, 1000.0, 5e9), (mosaic, 7e9, 5e9)]
    trace = {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': trace_reduce.OPS_LINE, 'events': events}]}]}
    ctx = {'metric': spec, 'trace': trace, 'config': cfg, 'batch_size': 8,
           'peaks': peaks['devices']['TPU v5 lite'], 'log': lambda *a: None}
    assert roof.read(ctx) == pytest.approx(25.0)
    # the pattern is the kernel's own name, as the program gives it
    from video_features_tpu.ops import pallas_corr
    import inspect
    assert "name='raft_corr_lookup_lanes'" in inspect.getsource(pallas_corr)
    assert spec['match'].startswith('^%raft_corr_lookup_lanes')
    assert 'match_note' not in spec
