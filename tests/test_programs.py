"""vft-programs (video_features_tpu/analysis/programs.py): the program
contract checker itself.

Three layers, mirroring tests/test_analysis.py:

  * toy jitted functions with PLANTED violations, one per rule — the
    signature extraction + rule pass must catch each (and must NOT fire
    on the clean variant);
  * lock semantics on a real family (r21d — the cheapest build):
    ``--write-lock`` idempotence, injected dtype drift → exit 2, stale /
    unknown lock entries reported;
  * the live-tree gate: the cheap families checked against the SHIPPED
    ``PROGRAMS.lock.json`` in tier-1, all eight in the slow lane — the
    same gate CI's ``programs-check`` job enforces.

Plus the float32-boundary parity assertions the no-f64 rule leans on
(vggish's explicit host-side narrowing must equal jax's old implicit
device_put downcast; host transforms must preserve uint8).
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from video_features_tpu.analysis.core import EXIT_CLEAN, EXIT_FINDINGS
from video_features_tpu.analysis.programs import (
    ALL_PINNED, FAMILIES, ProgramSpec, build_family, check_program, collect,
    default_lock_path, diff_lock, family_lock_hashes, lane_families,
    load_lock, main, mesh_key, parse_mesh_key, program_signature,
    write_lock,
)
from video_features_tpu.parallel.mesh import make_mesh
from video_features_tpu.registry import BF16_FEATURES, INT8_FEATURES


def sig_and_findings(spec, family='toy', width=1, mesh=None):
    sig = program_signature(spec)
    return sig, check_program(spec, sig, family, width, mesh)


def rules_of(findings):
    return {f.rule for f in findings}


P = jax.ShapeDtypeStruct((), np.float32)
B4 = jax.ShapeDtypeStruct((4, 8), np.uint8)


# -- per-rule toys -----------------------------------------------------------

def test_clean_toy_has_no_findings_and_full_signature():
    f = jax.jit(lambda p, b: b.astype(np.float32).sum(axis=1) * p)
    sig, findings = sig_and_findings(ProgramSpec('step', f, (P, B4)))
    assert findings == []
    assert sig['batch'] == {'shape': [4, 8], 'dtype': 'uint8'}
    assert sig['out'] == [{'shape': [4], 'dtype': 'float32'}]
    assert sig['batch_donated'] is False
    assert sig['const_bytes'] == 0
    assert sig['num_partitions'] == 1
    assert len(sig['stablehlo_sha256']) == 64


def test_no_f64_rule_catches_planted_promotion():
    with jax.enable_x64(True):
        f = jax.jit(lambda p, b: b.astype(np.float64).sum() * p)
        _, findings = sig_and_findings(ProgramSpec('step', f, (P, B4)))
    assert rules_of(findings) == {'no-f64'}


def test_no_weak_type_rule_catches_scalar_only_epilogue():
    f = jax.jit(lambda p, b: jnp.sin(1.0))
    _, findings = sig_and_findings(ProgramSpec('step', f, (P, B4)))
    assert rules_of(findings) == {'no-weak-type'}


def test_no_host_callback_rule():
    def cb(x):
        return np.asarray(x)

    f = jax.jit(lambda p, b: jax.pure_callback(
        cb, jax.ShapeDtypeStruct(b.shape, np.float32), b))
    _, findings = sig_and_findings(ProgramSpec('step', f, (P, B4)))
    assert 'no-host-callback' in rules_of(findings)


def test_donation_rule_both_directions():
    donated = jax.jit(lambda p, b: b.astype(np.float32).sum() * p,
                      donate_argnums=(1,))
    plain = jax.jit(lambda p, b: b.astype(np.float32).sum() * p)
    # program donates, spec says it must not
    _, findings = sig_and_findings(ProgramSpec('step', donated, (P, B4)))
    assert rules_of(findings) == {'donation'}
    # spec expects donation, program dropped it
    _, findings = sig_and_findings(
        ProgramSpec('step', plain, (P, B4), donate_batch=True))
    assert rules_of(findings) == {'donation'}
    # declared + lowered agree
    sig, findings = sig_and_findings(
        ProgramSpec('step', donated, (P, B4), donate_batch=True))
    assert findings == [] and sig['batch_donated'] is True


def test_shardable_rule_names_indivisible_batch():
    f = jax.jit(lambda p, b: b.astype(np.float32).sum(axis=1) * p)
    odd = jax.ShapeDtypeStruct((3, 8), np.uint8)
    mesh = make_mesh(n_devices=2, time_parallel=1)
    _, findings = sig_and_findings(ProgramSpec('step', f, (P, odd)),
                                   width=2, mesh=mesh)
    assert rules_of(findings) == {'shardable'}
    assert 'cannot shard over 2' in findings[0].message


def test_const_budget_rule_catches_closure_captured_weights():
    weights = np.ones((300_000,), np.float32)          # 1.2 MB closed over
    f = jax.jit(lambda p, b: b.astype(np.float32).sum()
                * jnp.asarray(weights).sum() * p)
    sig, findings = sig_and_findings(ProgramSpec('step', f, (P, B4)))
    assert rules_of(findings) == {'const-budget'}
    assert sig['const_bytes'] >= 1_200_000
    # an explicit budget accepts it (the vft-programs suppression shape)
    _, findings = sig_and_findings(
        ProgramSpec('step', f, (P, B4), const_budget=2 << 20))
    assert findings == []


def test_spec_ok_suppression_mirrors_vft_lint():
    donated = jax.jit(lambda p, b: b.astype(np.float32).sum() * p,
                      donate_argnums=(1,))
    _, findings = sig_and_findings(ProgramSpec(
        'step', donated, (P, B4),
        ok={'donation': 'toy: donation is the point'}))
    assert findings == []


def test_mesh_width_2_signature_records_partitions():
    from video_features_tpu.parallel.mesh import batch_sharding, replicated
    mesh = make_mesh(n_devices=2, time_parallel=1)
    f = jax.jit(lambda p, b: b.astype(np.float32).sum(axis=1) * p)
    pp = jax.ShapeDtypeStruct((), np.float32, sharding=replicated(mesh))
    bb = jax.ShapeDtypeStruct((4, 8), np.uint8,
                              sharding=batch_sharding(mesh))
    sig, findings = sig_and_findings(ProgramSpec('step', f, (pp, bb)),
                                     width=2, mesh=mesh)
    assert findings == []
    assert sig['num_partitions'] == 2


# -- lock semantics on a real family -----------------------------------------

@pytest.fixture(scope='module')
def r21d_live():
    """One r21d build + both mesh-width lowerings, shared by the lock
    tests (the build is the expensive part). float32 lane only: the
    width/merge semantics under test are lane-independent, and the bf16
    lane's own semantics have their targeted tests below — one build
    here instead of two keeps the module inside the tier-1 budget."""
    live, findings = collect(('r21d',), (1, 2), lanes=('float32',))
    assert findings == []
    return live


def test_write_lock_is_idempotent(r21d_live, tmp_path):
    lock = tmp_path / 'lock.json'
    write_lock(lock, r21d_live)
    first = lock.read_text()
    write_lock(lock, r21d_live)
    assert lock.read_text() == first
    doc = json.loads(first)
    assert set(doc['families']) == {'r21d'}
    assert set(doc['families']['r21d']) == {'mesh1', 'mesh2'}


def test_clean_diff_against_own_lock(r21d_live, tmp_path):
    lock = tmp_path / 'lock.json'
    write_lock(lock, r21d_live)
    assert diff_lock(r21d_live, load_lock(lock), ('r21d',)) == []


def test_mesh_width_subset_repin_keeps_other_widths(r21d_live, tmp_path):
    """A --mesh-widths subset re-pin must merge, not drop, the family's
    other widths' pinned signatures — and a subset CHECK must not
    report the unchecked widths as stale."""
    lock = tmp_path / 'lock.json'
    write_lock(lock, r21d_live)
    only_m1 = {'r21d': {'mesh1': r21d_live['r21d']['mesh1']}}
    write_lock(lock, only_m1)
    doc = json.loads(lock.read_text())
    assert set(doc['families']['r21d']) == {'mesh1', 'mesh2'}
    assert diff_lock(r21d_live, load_lock(lock), ('r21d',)) == []
    # width-subset diff: live has only mesh1, lock has both — clean
    assert diff_lock(only_m1, load_lock(lock), ('r21d',),
                     widths=(1,)) == []


def test_injected_dtype_drift_is_reported(r21d_live, tmp_path):
    lock = tmp_path / 'lock.json'
    write_lock(lock, r21d_live)
    doc = json.loads(lock.read_text())
    step = doc['families']['r21d']['mesh1']['programs']['step']
    step['batch']['dtype'] = 'float64'               # the injected drift
    lock.write_text(json.dumps(doc))
    findings = diff_lock(r21d_live, load_lock(lock), ('r21d',))
    assert len(findings) == 1
    f = findings[0]
    assert (f.rule, f.family, f.mesh, f.program) \
        == ('lock-drift', 'r21d', 1, 'step')
    assert 'batch' in f.message and 'float64' in f.message


def test_unknown_family_in_lock_is_reported(r21d_live, tmp_path):
    lock = tmp_path / 'lock.json'
    write_lock(lock, r21d_live)
    doc = json.loads(lock.read_text())
    doc['families']['betamax'] = {'mesh1': {'programs': {}}}
    lock.write_text(json.dumps(doc))
    findings = diff_lock(r21d_live, load_lock(lock), ('r21d',))
    assert len(findings) == 1
    assert findings[0].family == 'betamax'
    assert 'unknown family' in findings[0].message


def test_missing_and_stale_programs_are_both_drift(r21d_live, tmp_path):
    lock = tmp_path / 'lock.json'
    write_lock(lock, r21d_live)
    doc = json.loads(lock.read_text())
    progs = doc['families']['r21d']['mesh1']['programs']
    progs['ghost'] = dict(progs['step'])             # pinned, never lowered
    lock.write_text(json.dumps(doc))
    findings = diff_lock(r21d_live, load_lock(lock), ('r21d',))
    assert [f.program for f in findings] == ['ghost']
    assert 'stale' in findings[0].message
    # and the reverse: a live program the lock has never seen
    doc['families']['r21d']['mesh1']['programs'] = {}
    lock.write_text(json.dumps(doc))
    findings = diff_lock(r21d_live, load_lock(lock), ('r21d',))
    assert any('new program not in the lock' in f.message
               for f in findings)


def test_full_scope_repin_prunes_stale_lock_entries(r21d_live, tmp_path):
    """The bare --write-lock must make the 'unknown family' finding's
    own remediation advice work: stale families (and stale width keys)
    are pruned on a full-scope re-pin, kept on subset re-pins."""
    lock = tmp_path / 'lock.json'
    write_lock(lock, {'betamax': {'mesh9': {'programs': {}}}})
    write_lock(lock, r21d_live)                       # subset: kept
    assert 'betamax' in json.loads(lock.read_text())['families']
    write_lock(lock, r21d_live, prune_families=True,
               replace_widths=True)                   # full scope
    assert set(json.loads(lock.read_text())['families']) == {'r21d'}


def test_const_bytes_recorded_at_every_width(r21d_live):
    """Width-conditional signature fields would make a --mesh-widths
    subset run drift against a full-width lock (review regression)."""
    for mesh in ('mesh1', 'mesh2'):
        assert 'const_bytes' in \
            r21d_live['r21d'][mesh]['programs']['step']


def test_unpinned_family_is_drift(r21d_live):
    findings = diff_lock(r21d_live, {'families': {}}, ('r21d',))
    assert len(findings) == 1 and 'not in the lock' in findings[0].message


# -- CLI exit codes (the CI contract) ----------------------------------------

def test_cli_exit_0_clean_and_2_on_drift(tmp_path, capsys):
    # float32 lane only: each main() builds once per lane, and this test
    # runs three mains — the lane-aware CLI/diff semantics have their
    # own (single-build) coverage above, so doubling every build here
    # would buy nothing but tier-1 wall clock
    lane = ['--lanes', 'float32']
    lock = tmp_path / 'lock.json'
    assert main(['--families', 'resnet', '--write-lock',
                 '--lock', str(lock)] + lane) == EXIT_CLEAN
    assert main(['--families', 'resnet',
                 '--lock', str(lock)] + lane) == EXIT_CLEAN
    doc = json.loads(lock.read_text())
    step = doc['families']['resnet']['mesh1']['programs']['step']
    step['params']['float32']['arrays'] += 1         # injected census drift
    lock.write_text(json.dumps(doc))
    assert main(['--families', 'resnet',
                 '--lock', str(lock)] + lane) == EXIT_FINDINGS
    out = capsys.readouterr().out
    assert 'params drifted' in out


# -- the live-tree gate vs the SHIPPED lock ----------------------------------

def test_shipped_lock_covers_all_families_at_both_widths():
    """Every pinned family — the model families plus the extra shipped
    programs (the feature index's query program) — pins both mesh widths
    on the float32 lane, and every bf16-accepting family
    (registry.BF16_FEATURES) ADDITIONALLY pins both widths of its
    mesh<n>@bfloat16 fast-lane variants — a refusing family (i3d, raft)
    must have none."""
    doc = load_lock(default_lock_path())
    assert set(doc['families']) == set(ALL_PINNED)
    for family, entry in doc['families'].items():
        want = {'mesh1', 'mesh2'}
        if family in BF16_FEATURES:
            want |= {'mesh1@bfloat16', 'mesh2@bfloat16'}
        if family in INT8_FEATURES:
            want |= {'mesh1@int8', 'mesh2@int8'}
        assert set(entry) == want, family
        for mesh in entry.values():
            assert mesh['programs'], family


def test_shipped_bf16_variants_census_is_pure_bf16():
    """The lane's load-bearing acceptance: the committed lock's
    compute_dtype=bfloat16 variants carry ZERO fp32 (or fp64) params —
    proof the transplant-time cast reached every tensor (fp32 lives
    only in activation islands, which a params census never sees)."""
    doc = load_lock(default_lock_path())
    checked = 0
    for family in sorted(BF16_FEATURES):
        for key, entry in doc['families'][family].items():
            if '@bfloat16' not in key:
                continue
            for name, sig in entry['programs'].items():
                census = sig['params']
                assert set(census) == {'bfloat16'}, (family, key, name,
                                                    census)
                assert census['bfloat16']['arrays'] > 0
                checked += 1
    assert checked >= 2 * len(BF16_FEATURES)   # both widths per family


def test_shipped_int8_variants_census_is_int8_majority():
    """The int8 lane's load-bearing acceptance against the committed
    lock: every compute_dtype=int8 variant carries int8 params and its
    DECLARED fp32 minority (biases, norm params, per-channel scales)
    stays strictly under the int8 payload bytes — proof the per-channel
    weight quantization reached the conv/linear bulk of every accepting
    family (CLIP's fused in_proj_weight included, which alone would
    flip the byte majority if missed)."""
    doc = load_lock(default_lock_path())
    checked = 0
    for family in sorted(INT8_FEATURES):
        for key, entry in doc['families'][family].items():
            if '@int8' not in key:
                continue
            for name, sig in entry['programs'].items():
                census = sig['params']
                assert 'int8' in census, (family, key, name, census)
                assert census['int8']['arrays'] > 0
                assert 'float64' not in census, (family, key, name)
                f32 = census.get('float32', {}).get('bytes', 0)
                assert f32 < census['int8']['bytes'], (family, key, name,
                                                       census)
                checked += 1
    assert checked >= 2 * len(INT8_FEATURES)   # both widths per family


def test_lane_helpers_roundtrip():
    assert mesh_key(1, 'float32') == 'mesh1'          # pre-lane keys hold
    assert mesh_key(2, 'bfloat16') == 'mesh2@bfloat16'
    assert mesh_key(2, 'int8') == 'mesh2@int8'
    assert parse_mesh_key('mesh1') == (1, 'float32')
    assert parse_mesh_key('mesh2@bfloat16') == (2, 'bfloat16')
    assert parse_mesh_key('mesh1@int8') == (1, 'int8')
    assert lane_families('float32', FAMILIES) == FAMILIES
    assert set(lane_families('bfloat16', FAMILIES)) == BF16_FEATURES
    assert set(lane_families('int8', FAMILIES)) == INT8_FEATURES


def test_bf16_census_rule_catches_fp32_survivor():
    """A bf16-lane program whose params census still shows float32
    arrays must trip 'bf16-census' — and the same signature on the
    float32 lane must not (fp32 params are that lane's contract)."""
    f = jax.jit(lambda p, b: (b.astype(jnp.bfloat16).sum(axis=1)
                              * p).astype(np.float32))
    spec = ProgramSpec('step', f, (P, B4))      # P is a float32 param
    sig = program_signature(spec)
    bf16_findings = check_program(spec, sig, 'toy', 1, None,
                                  lane='bfloat16')
    assert rules_of(bf16_findings) == {'bf16-census'}
    assert 'float32' in bf16_findings[0].message
    assert '@bfloat16' in bf16_findings[0].render()
    assert check_program(spec, sig, 'toy', 1, None,
                         lane='float32') == []


def test_int8_census_rule_catches_unquantized_params():
    """An int8-lane program must carry int8 params OUTWEIGHING its fp32
    minority: a plain-fp32 toy trips 'int8-census' (nothing quantized),
    a quantized toy with a small fp32 scale rides clean — and the same
    fp32 signature on the float32 lane must not fire (fp32 params are
    that lane's contract)."""
    w8 = jax.ShapeDtypeStruct((64, 8), np.int8)     # 512 int8 bytes
    sc = jax.ShapeDtypeStruct((1, 8), np.float32)   # 32 fp32 bytes
    fq = jax.jit(lambda q, s, b: (b.astype(np.float32)
                                  @ (q.astype(np.float32) * s)))
    b64 = jax.ShapeDtypeStruct((4, 64), np.uint8)
    spec_ok = ProgramSpec('step', fq, (w8, sc, b64))
    sig_ok = program_signature(spec_ok)
    assert check_program(spec_ok, sig_ok, 'toy', 1, None,
                         lane='int8') == []
    # unquantized: fp32-only params on the int8 lane
    f = jax.jit(lambda p, b: b.astype(np.float32).sum(axis=1) * p)
    spec = ProgramSpec('step', f, (P, B4))
    sig = program_signature(spec)
    findings = check_program(spec, sig, 'toy', 1, None, lane='int8')
    assert rules_of(findings) == {'int8-census'}
    assert '@int8' in findings[0].render()
    assert check_program(spec, sig, 'toy', 1, None, lane='float32') == []


def test_bf16_lane_collect_and_lock_roundtrip(tmp_path):
    """One REAL bf16-lane build (vggish — the cheapest family): collect
    places it under mesh<n>@bfloat16, write-lock/diff round-trips clean,
    and a census-drift plant in the bf16 variant is reported with the
    lane named."""
    live, findings = collect(('vggish',), (1,), lanes=('bfloat16',))
    assert findings == []
    assert set(live['vggish']) == {'mesh1@bfloat16'}
    sig = live['vggish']['mesh1@bfloat16']['programs']['step']
    assert set(sig['params']) == {'bfloat16'}
    assert sig['batch']['dtype'] == 'bfloat16'   # halved H2D at the edge
    lock = tmp_path / 'lock.json'
    write_lock(lock, live)
    assert diff_lock(live, load_lock(lock), ('vggish',),
                     widths=(1,)) == []
    doc = json.loads(lock.read_text())
    doc['families']['vggish']['mesh1@bfloat16']['programs']['step'][
        'params'] = {'float32': {'arrays': 1, 'bytes': 4}}
    lock.write_text(json.dumps(doc))
    findings = diff_lock(live, load_lock(lock), ('vggish',), widths=(1,))
    assert len(findings) == 1
    assert findings[0].lane == 'bfloat16'
    assert 'params drifted' in findings[0].message


def test_live_tree_clean_fast_families():
    """Tier-1 slice of the CI programs-check gate: the two cheapest
    builds against the shipped lock (the slow lane + CI run all 8,
    both lanes). resnet runs BOTH lanes (the bf16 variants gate in
    tier-1 too); r21d pins float32 only here — its bf16 build would be
    a third full build and the CI job covers it."""
    assert main(['--families', 'resnet']) == EXIT_CLEAN
    assert main(['--families', 'r21d', '--lanes', 'float32']) == EXIT_CLEAN


@pytest.mark.slow
def test_live_tree_clean_all_families():
    assert main([]) == EXIT_CLEAN


def test_family_lock_hashes_reads_shipped_lock():
    hashes = family_lock_hashes('r21d')
    # a run manifest names its lane's pinned program: the bf16 variants
    # ride the same mapping under their mesh<n>@bfloat16 keys
    assert set(hashes) == {'mesh1', 'mesh2',
                           'mesh1@bfloat16', 'mesh2@bfloat16'}
    assert set(hashes['mesh1']) == {'step'}
    assert len(hashes['mesh1']['step']) == 64
    assert len(hashes['mesh1@bfloat16']['step']) == 64
    assert hashes['mesh1@bfloat16']['step'] != hashes['mesh1']['step']
    assert family_lock_hashes('not-a-family') == {}


def test_manifest_records_programs_lock(tmp_path):
    """configure_obs attaches the family's pinned hashes; the manifest
    document carries them under the 'programs_lock' key."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor
    args = load_config('r21d', overrides={
        'device': 'cpu', 'video_paths': ['x.mp4'],
        'allow_random_weights': True, 'compilation_cache_dir': None,
        'manifest_out': str(tmp_path / 'manifest.json')})
    ex = create_extractor(args)
    doc = ex.manifest.document()
    assert doc['programs_lock'] == {'r21d': family_lock_hashes('r21d')}


# -- float32 boundary parity (the no-f64 satellite) --------------------------

def test_vggish_float32_pin_matches_jax_implicit_downcast():
    """The explicit host-side ``astype(np.float32)`` at the vggish
    device boundary must be byte-identical to the implicit float64
    canonicalization jax used to apply at device_put (x64 disabled) —
    same double→float rounding, so the pin changes nothing."""
    rng = np.random.default_rng(0)
    examples = rng.standard_normal((5, 96, 64)) * 4 - 2   # float64 DSP out
    explicit = examples.astype(np.float32)
    implicit = np.asarray(jax.device_put(examples))
    assert implicit.dtype == np.float32
    np.testing.assert_array_equal(explicit, implicit)


def test_host_transforms_preserve_uint8():
    from video_features_tpu.ops.host_transforms import (
        center_crop_host, frames_match_device_contract, resize_pil,
    )
    frame = np.random.default_rng(1).integers(
        0, 255, (120, 160, 3), dtype=np.uint8)
    for out in (resize_pil(frame, 64), center_crop_host(frame, 96),
                resize_pil(frame, 64, interpolation='bicubic')):
        assert frames_match_device_contract(out), out.dtype
    assert not frames_match_device_contract(frame.astype(np.float64))


class FloatLeakRecipe:
    """Module-level (spawn unpickles by reference): yields one float64
    window — numpy default-dtype math leaking through a transform."""

    def open(self, path):
        def windows():
            yield np.zeros((8, 8, 3), np.float64), 0
        return {}, windows()


def test_farm_worker_rejects_float_windows(tmp_path, caplog):
    """A recipe leaking float windows fails ITS video with the dtype
    contract named (worker 'err' path) — shipped bytes must always
    agree with the in-process decode replay, and jax's silent f64
    downcast would have masked the disagreement."""
    import logging

    from video_features_tpu.farm import DecodeFarm
    from video_features_tpu.parallel.packing import FLUSH, NUDGE, VideoTask

    task = VideoTask(str(tmp_path / 'leak.bin'))
    farm = DecodeFarm(FloatLeakRecipe(), workers=1, ring_bytes=1 << 20)
    with caplog.at_level(logging.WARNING, logger='video_features_tpu'):
        for item in farm.stream(iter([task]), lambda t: True):
            if item is FLUSH or item is NUDGE:
                continue
    assert task.failed
    assert farm.stats()['videos_failed'] == 1
    assert 'must be uint8' in caplog.text
