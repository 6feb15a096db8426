"""Unit tests for the per-stage tracing subsystem (utils/tracing.py)."""
import threading
import time

import pytest

from video_features_tpu.utils.tracing import (
    NULL_TRACER, Tracer, jax_profiler_trace, merge_reports,
)


def test_stage_accumulates():
    t = Tracer()
    for _ in range(3):
        with t.stage('work'):
            time.sleep(0.001)
    rep = t.report()
    assert rep['work']['count'] == 3
    assert rep['work']['total_s'] >= 0.003
    assert rep['work']['max_s'] <= rep['work']['total_s']


def test_stage_records_on_exception():
    t = Tracer()
    try:
        with t.stage('boom'):
            raise ValueError
    except ValueError:
        pass
    assert t.report()['boom']['count'] == 1


def test_wrap_iter_times_each_next():
    t = Tracer()

    def gen():
        for i in range(4):
            time.sleep(0.001)
            yield i

    assert list(t.wrap_iter('decode', gen())) == [0, 1, 2, 3]
    rep = t.report()
    # 4 yields + the final StopIteration probe
    assert rep['decode']['count'] == 5
    assert rep['decode']['total_s'] >= 0.004


def test_null_tracer_is_noop():
    with NULL_TRACER.stage('x'):
        pass
    assert list(NULL_TRACER.wrap_iter('y', iter([1, 2]))) == [1, 2]
    assert NULL_TRACER.report() == {}


def test_thread_safety():
    t = Tracer()

    def worker():
        for _ in range(200):
            with t.stage('shared'):
                pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert t.report()['shared']['count'] == 800


def test_summary_and_reset():
    t = Tracer()
    with t.stage('a'):
        pass
    with t.stage('b'):
        pass
    s = t.summary()
    assert 'a' in s and 'b' in s and 'share' in s
    t.reset()
    assert t.report() == {}
    assert t.summary() == '(no stages recorded)'


def test_merge_reports_occupancy_recombines_from_raw_counts():
    """Aggregate occupancy must recompute from the raw slot counts —
    averaging the per-tracer ratios would weight batches wrongly (a
    1-batch 50% tracer would pull down a 100-batch 95% tracer)."""
    a = Tracer()
    a.add('model', 0.1)
    a.add_occupancy('model', 1, 2)            # 50% over 2 slots
    b = Tracer()
    b.add('model', 0.2)
    b.add_occupancy('model', 95, 100)         # 95% over 100 slots
    merged = merge_reports([a.report(), b.report()])
    m = merged['model']
    assert m['occ_valid'] == 96 and m['occ_capacity'] == 102
    assert m['occupancy'] == pytest.approx(96 / 102)
    # NOT the mean of ratios (0.725)
    assert abs(m['occupancy'] - 0.725) > 0.1
    assert m['count'] == 2
    assert m['total_s'] == pytest.approx(0.3)
    assert m['mean_s'] == pytest.approx(0.15)


def test_merge_reports_first_s_keeps_worst_cold_start():
    """The fleet view's first_s is the WORST cold start across tracers
    (the number an operator sizes warm-up budgets by), and max_s maxes;
    per-tracer ramp is dropped rather than faked."""
    a = Tracer()
    a.add('model', 3.0)                       # cold compile wall
    a.add('model', 0.1)
    b = Tracer()
    b.add('model', 0.5)
    b.add('model', 0.1)
    rep_a, rep_b = a.report(), b.report()
    assert 'ramp' in rep_a['model']
    merged = merge_reports([rep_a, rep_b])
    m = merged['model']
    assert m['first_s'] == pytest.approx(3.0)
    assert m['max_s'] == pytest.approx(3.0)
    assert m['count'] == 4
    assert 'ramp' not in m
    # stages without occupancy never grow occupancy keys
    assert 'occupancy' not in m and 'occ_valid' not in m


def test_merge_reports_disjoint_stages_union():
    a = Tracer()
    a.add('decode', 1.0)
    b = Tracer()
    b.add('save', 2.0)
    merged = merge_reports([a.report(), b.report()])
    assert set(merged) == {'decode', 'save'}
    assert merged['save']['mean_s'] == pytest.approx(2.0)


def test_jax_profiler_trace_none_is_noop():
    with jax_profiler_trace(None):
        pass


def test_jax_profiler_trace_writes(tmp_path):
    import jax
    import jax.numpy as jnp
    with jax_profiler_trace(str(tmp_path)):
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    assert any(tmp_path.rglob('*')), 'profiler wrote nothing'


# -- the recorder behind manifest_out, attached(), and the programs' names ----

def _stub(tmp_path):
    from video_features_tpu.extract.base import BaseExtractor

    class Stub(BaseExtractor):
        pass

    return Stub('stub', 'save_numpy', str(tmp_path), str(tmp_path), False,
                'cpu')


def test_no_obs_knob_no_recorder(tmp_path, monkeypatch):
    """With ``manifest_out`` and ``trace_out`` both unset nothing is created:
    the tracer stays the disabled singleton and ``attached()`` is empty."""
    from video_features_tpu.obs import spans
    monkeypatch.setattr(spans, '_ATTACHED', type(spans._ATTACHED)(maxlen=4))
    ex = _stub(tmp_path)
    ex.configure_obs({})
    assert ex.tracer is NULL_TRACER
    assert getattr(ex.tracer, 'recorder', None) is None
    assert spans.attached() == []


@pytest.mark.parametrize('knob', ['manifest_out', 'trace_out'])
def test_attached_outlives_the_extractor_and_is_bounded(tmp_path,
                                                        monkeypatch, knob):
    """Either knob attaches a recorder (exported only under ``trace_out``)
    that a reader in the same process still finds after the extractor is
    freed; only the last four are kept."""
    import gc
    import weakref

    from video_features_tpu.obs import spans
    monkeypatch.setattr(spans, '_ATTACHED', type(spans._ATTACHED)(maxlen=4))
    ex = _stub(tmp_path)
    ex.configure_obs({knob: str(tmp_path / 'out.json')})
    assert ex.tracer.enabled and ex.tracer.recorder is not None
    assert (ex.trace_out is not None) == (knob == 'trace_out')
    with ex.tracer.stage('save', video='a.mp4'):
        pass
    gone = weakref.ref(ex)
    del ex
    gc.collect()
    assert gone() is None
    (rec,) = spans.attached()
    assert [e['name'] for e in rec.snapshot() if e['ph'] == 'X'] == ['save']
    for i in range(5):
        _stub(tmp_path).configure_obs({knob: str(tmp_path / f'{i}.json')})
    assert len(spans.attached()) == 4 and rec not in spans.attached()


@pytest.mark.parametrize('family, name', [
    ('i3d', 'i3d_two_stream_step'), ('resnet', 'resnet_step'),
    ('r21d', 'r21d_step'), ('s3d', 's3d_step'), ('raft', 'raft_step'),
    ('clip', 'clip_step'), ('timm', 'timm_step'), ('vggish', 'vggish_step'),
])
def test_every_family_names_its_step_program(family, name):
    """The jitted hot-path step lowers to the module ``jit_<name>`` (what the
    device trace shows, where it showed ``jit__unknown``), and the ``program``
    attr of the step's spans says the same."""
    from video_features_tpu.analysis.programs import (
        abstract_lowering, build_family,
    )
    ex = build_family(family)
    (spec,) = ex.program_specs()
    text = abstract_lowering(spec.jitted, *spec.args, **spec.kwargs).as_text()
    assert text.startswith(f'module @jit_{name} ')
    ex.tracer = Tracer(enabled=True)
    assert ex.step_attrs()['program'] == f'jit_{name}'


def test_lookup_kernel_is_named_in_the_lowered_module():
    """Each Mosaic call of RAFT's correlation lookup carries the kernel's name
    (one call per pyramid level), so a trace reduction can find the lookup by
    name and not as "the step's only custom call". Lowered for the TPU from
    the CPU: no chip, nothing runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from video_features_tpu.models import raft
    from video_features_tpu.ops import pallas_corr
    rng = np.random.RandomState(1)
    f1, f2 = (jnp.asarray(rng.randn(2, 12, 9, 32).astype(np.float32))
              for _ in range(2))
    prepped = pallas_corr.prep_pyramid_lanes(raft.build_corr_pyramid(f1, f2),
                                             2)
    coords = jnp.zeros((2, 12, 9, 2), jnp.float32)
    text = jax.jit(pallas_corr.lookup_corr_lanes).trace(
        prepped, coords).lower(lowering_platforms=('tpu',)).as_text()
    assert text.count('kernel_name = "raft_corr_lookup_lanes"') == len(prepped)
    assert text.count('tpu_custom_call') == len(prepped)
