"""vft-programs: abstract-interpretation contract checker over compiled
JAX programs.

vft-lint (``analysis/checks.py``) enforces the *Python-level* contracts;
the contracts that actually decide TPU behavior — shapes, dtypes,
sharding, donation, what XLA compiles — live one level down, in the
lowered programs, and nothing else pins them: a silent f64 promotion, a
dropped donation, or a weight tensor accidentally captured by closure
(baked into the HLO as a constant) ships invisibly. This module
AOT-lowers every family's *actual* jitted step — the same callable the
hot paths dispatch — at a canonical abstract geometry, on CPU, at mesh
widths {1, 2} (forced host devices) and, for families that accept a
compute_dtype fast lane, on EVERY lane they accept (``mesh<n>`` =
float32 as always; ``mesh<n>@bfloat16`` for ``registry.BF16_FEATURES``,
whose parameter dtype census proves the transplant cast left no fp32
param behind — the ``bf16-census`` rule; ``mesh<n>@int8`` for
``registry.INT8_FEATURES``, whose census proves the weight quantization
ran and fp32 is the declared minority — the ``int8-census`` rule), and

  * extracts an **abstract signature** per program: batch/output avals
    (weak types included), the full parameter dtype census, the declared
    donated-buffer set, data-axis sharding (``mhlo.num_partitions``),
    ``cost_analysis`` FLOPs/bytes, baked-constant bytes, and a sha256
    of the StableHLO text;
  * runs **rule checks** over the lowering (catalog in
    ``docs/static_analysis.md``): no-f64, no-weak-type leak on outputs,
    no host callback in hot programs, donation-as-declared on the batch
    input, batch-dim shardability at every supported mesh width
    (``parallel.mesh.shard_error``), and a baked-constant budget;
  * **diffs** the live signatures against the committed
    ``PROGRAMS.lock.json`` and exits 0 clean / 2 on drift or a new rule
    finding (``--write-lock`` re-pins intentionally) — mirroring
    vft-lint's exit-code conventions. Suppressions mirror vft-lint's
    rationale-at-the-site convention, but live in the family's
    ``program_specs`` (``ProgramSpec(ok={rule: rationale})``) because a
    finding names a *program*, not a source line.

No device execution happens: lowering is trace + StableHLO emission,
and the cost analysis runs on the unoptimized module. The whole check
(8 families × 2 widths) completes in well under two minutes on a laptop
CPU, which is what lets CI gate on it.

Everything here imports jax lazily: the module itself stays importable
in jax-free processes (the manifest's lock-hash recording and the lock
readers below are pure stdlib).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import re
import sys
from pathlib import Path
from typing import Any, Dict, Iterable, List, Mapping, Optional, Tuple

from video_features_tpu.analysis.core import (
    EXIT_CLEAN, EXIT_ERROR, EXIT_FINDINGS,
)
from video_features_tpu.config import KNOWN_FEATURE_TYPES

LOCK_SCHEMA = 'video_features_tpu.programs_lock/1'
DEFAULT_LOCK = 'PROGRAMS.lock.json'           # repo-root, committed

# every family the lock must cover — the ONE registry of feature types
# (config.py), not a second hand-synced list: a new family is a lock
# gap (and a checker 'coverage' finding) the day it lands
FAMILIES = tuple(KNOWN_FEATURE_TYPES)
# non-extractor program providers the lock ALSO pins: the feature
# index's query program is a shipped compiled program like any step
# function, so it rides the same gate (float32 lane only — it is not a
# feature family and never joins registry.BF16_FEATURES)
EXTRA_PROGRAMS = ('index',)
ALL_PINNED = FAMILIES + EXTRA_PROGRAMS
MESH_WIDTHS = (1, 2)

# compute_dtype lanes the lock pins per family: 'float32' entries keep
# their historical mesh<n> keys byte-for-byte (the default path must
# never drift when a lane is added), fast-lane variants land under
# mesh<n>@<lane> for every family in the lane's registry opt-in set —
# their parameter dtype census is the per-lane proof the storage
# transform actually happened: 'bfloat16' (registry.BF16_FEATURES) must
# carry ZERO fp32 params (the bf16-census rule below), 'int8'
# (registry.INT8_FEATURES) must carry int8 weight payloads with float32
# reduced to the DECLARED minority — scales, biases, norm params
# (the int8-census rule below).
LANES = ('float32', 'bfloat16', 'int8')

RULES = ('no-f64', 'no-weak-type', 'no-host-callback', 'donation',
         'shardable', 'const-budget', 'bf16-census', 'int8-census')


def lane_families(lane: str, families: Iterable[str]) -> tuple:
    """The subset of ``families`` that builds on ``lane`` — every family
    for float32; only the lane's registry opt-in set for the fast lanes
    (``BF16_FEATURES`` / ``INT8_FEATURES`` — the rest REFUSE the knob at
    config time, which is itself contract-tested, not a lock gap)."""
    if lane == 'float32':
        return tuple(families)
    from video_features_tpu.registry import BF16_FEATURES, INT8_FEATURES
    accepted = BF16_FEATURES if lane == 'bfloat16' else INT8_FEATURES
    return tuple(f for f in families if f in accepted)


def mesh_key(width: int, lane: str) -> str:
    """Lock entry key for one (mesh width, compute_dtype lane):
    ``mesh<n>`` for float32 (unchanged — pre-lane locks stay valid),
    ``mesh<n>@bfloat16`` for the fast lane."""
    return f'mesh{width}' if lane == 'float32' else f'mesh{width}@{lane}'


def parse_mesh_key(key: str) -> Tuple[int, str]:
    """Inverse of :func:`mesh_key`: ``'mesh2@bfloat16'`` → (2, 'bfloat16')."""
    base, _, lane = key.partition('@')
    try:
        width = int(base.replace('mesh', '') or 0)
    except ValueError:
        width = 0
    return width, (lane or 'float32')

# default baked-constant budget per program: small epilogue constants
# (normalization mean/std, resize index tables, iota caches) are fine;
# a real weight tensor folded into the HLO is megabytes — the failure
# this rule exists for (closure capture instead of params threading)
CONST_BUDGET = 1 << 20

# StableHLO custom_call targets that mean "the program calls back into
# the host python process" — a hot program stalling on the GIL
_CALLBACK_MARKERS = ('callback', 'py_func')


# -- family build recipes ----------------------------------------------------

# overrides that make every family buildable on a jax-CPU host with no
# checkpoints and no video files: the lock pins PROGRAM signatures, and
# random weights have exactly the shapes/dtypes real checkpoints
# transplant to (tests/test_transplant.py holds that equivalence)
_BASE_OVERRIDES: Dict[str, Any] = {
    'device': 'cpu',
    'video_paths': ['__programs_check__.mp4'],
    'allow_random_weights': True,
    'compilation_cache_dir': None,
}
_FAMILY_OVERRIDES: Dict[str, Dict[str, Any]] = {
    # the registry arch the timm lane is tuned around; pretrained=False
    # skips the pip-timm download path (shapes come from the native init)
    'timm': {'model_name': 'vit_base_patch16_224', 'pretrained': False},
    # lm.yml ships a 48 B-parameter model (192 GB): the lock pins the
    # step's contract at a trunk a host can build — every kind of layer
    # once more than once (1 dense + 2 expert layers), a share of the
    # experts held, windows of 256 ids — not at the published widths
    'lm': {'vocab_size': 1024, 'hidden_size': 128, 'num_hidden_layers': 3,
           'intermediate_size': 256, 'moe_intermediate_size': 64,
           'n_routed_experts': 16, 'n_experts_held': 8,
           'num_experts_per_tok': 4, 'num_attention_heads': 4,
           'q_lora_rank': 96, 'kv_lora_rank': 64, 'qk_nope_head_dim': 32,
           'qk_rope_head_dim': 16, 'v_head_dim': 32, 'stack_size': 4,
           'step_size': 4, 'patch_grid': 8},
}


def build_family(feature_type: str, compute_dtype: str = 'float32'):
    """The real extractor, built exactly like production builds it
    (``registry.create_extractor`` over the merged config) — so the
    lowered programs ARE the shipped programs, closures included.
    ``compute_dtype`` selects the lane (``'bfloat16'`` builds the fast
    lane's extractor: bf16 params from the transplant cast, bf16
    activations — whose lowering the mesh<n>@bfloat16 lock variants
    pin)."""
    if feature_type == 'index':
        # the feature index's query program: no extractor, no weights —
        # the provider lowers the SAME jitted callable the serve query
        # path dispatches, at the canonical lock geometry
        from video_features_tpu.index.search import IndexPrograms
        return IndexPrograms()
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor
    overrides = dict(_BASE_OVERRIDES)
    overrides.update(_FAMILY_OVERRIDES.get(feature_type, {}))
    if compute_dtype != 'float32':
        overrides['compute_dtype'] = compute_dtype
    return create_extractor(load_config(feature_type, overrides=overrides))


# -- program specs -----------------------------------------------------------

class ProgramSpec:
    """One abstract AOT program a family exposes to the checker.

    ``jitted`` must be the SAME jit-wrapped callable the hot path
    dispatches (not a re-wrap): the baked-constant rule exists precisely
    to catch what the real callable closes over. ``args``/``kwargs``
    are abstract (``jax.ShapeDtypeStruct``) inputs at the family's
    canonical lock geometry; ``batch_argnum`` names the positional arg
    that is the device batch (donation + shardability anchor on it).
    ``ok`` maps accepted rule ids to their rationale — the vft-programs
    analog of vft-lint's inline ``# vft-lint: ok=<rule>`` suppression,
    living in the family source next to the spec it excuses.
    """

    __slots__ = ('name', 'jitted', 'args', 'kwargs', 'batch_argnum',
                 'donate_batch', 'const_budget', 'ok')

    def __init__(self, name: str, jitted, args: Tuple, kwargs=None, *,
                 batch_argnum: int = 1, donate_batch: bool = False,
                 const_budget: int = CONST_BUDGET,
                 ok: Optional[Mapping[str, str]] = None) -> None:
        self.name = name
        self.jitted = jitted
        self.args = tuple(args)
        self.kwargs = dict(kwargs or {})
        self.batch_argnum = int(batch_argnum)
        self.donate_batch = bool(donate_batch)
        self.const_budget = int(const_budget)
        self.ok = dict(ok or {})


class Finding:
    """One rule violation or lock drift at
    ``family/mesh<n>[@lane]/program``."""

    __slots__ = ('rule', 'family', 'mesh', 'program', 'message', 'lane')

    def __init__(self, rule: str, family: str, mesh: int, program: str,
                 message: str, lane: str = 'float32') -> None:
        self.rule = rule
        self.family = family
        self.mesh = int(mesh)
        self.program = program
        self.message = message
        self.lane = lane

    def render(self) -> str:
        lane = '' if self.lane == 'float32' else f'@{self.lane}'
        return (f'{self.family}/mesh{self.mesh}{lane}/{self.program}: '
                f'[{self.rule}] {self.message}')


# -- shared abstract-lowering seam (obs/manifest.py reuses this) -------------

def stablehlo_sha256(text: str) -> str:
    """The byte-deterministic program identity: sha256 over the lowered
    StableHLO text. The ONE home of the hashing convention — the lock
    entries pin it per (family, mesh-width, lane), and the executable
    store (``aot/runtime.py``) keys its persisted compiled executables
    by the same identity, which is what makes an unchanged lock imply a
    compile-free boot. (The store hashes the PRODUCTION lowering, which
    additionally bakes the ambient matmul-precision context and the
    live args' shardings that the checker's abstract lowering carries
    no opinion on — same identity space, same determinism guarantee.)"""
    return hashlib.sha256(text.encode()).hexdigest()


def abstract_lowering(jitted, *args, **kwargs):
    """AOT-lower ``jitted`` at the abstract shapes of ``args``/``kwargs``
    — concrete arrays are mapped to ``ShapeDtypeStruct`` in place, avals
    pass through. The one home of the ``jitted.lower(...)`` seam: the
    run manifest's cost analysis and the vft-programs signature
    extraction both go through here."""
    import jax
    shaped = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if hasattr(x, 'shape') and not isinstance(x, jax.ShapeDtypeStruct)
        else x, (args, kwargs))
    return jitted.lower(*shaped[0], **shaped[1])


def lowering_cost(lowered) -> Optional[Dict[str, float]]:
    """FLOPs / bytes-accessed of a lowering (unoptimized-module cost
    analysis — no compile). None when the backend doesn't support it."""
    try:
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        if not cost:
            return None
        out = {}
        for key in ('flops', 'bytes accessed'):
            if key in cost:
                out[key.replace(' ', '_')] = float(cost[key])
        return out or None
    except Exception:
        # vft-lint: ok=swallowed-exception — cost analysis is an
        # optimization report, never a requirement (manifest contract)
        return None


# -- signature extraction ----------------------------------------------------

def _aval_doc(aval) -> Dict[str, Any]:
    doc: Dict[str, Any] = {'shape': [int(d) for d in aval.shape],
                           'dtype': str(aval.dtype)}
    if getattr(aval, 'weak_type', False):
        doc['weak_type'] = True
    return doc


def _param_census(tree) -> Dict[str, Dict[str, int]]:
    """dtype → {arrays, bytes} over every array leaf of ``tree`` — the
    full parameter dtype census the precision lanes diff against."""
    import jax
    import numpy as np
    census: Dict[str, Dict[str, int]] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        if not hasattr(leaf, 'shape'):
            continue
        dt = str(leaf.dtype)
        rec = census.setdefault(dt, {'arrays': 0, 'bytes': 0})
        rec['arrays'] += 1
        rec['bytes'] += int(np.prod(leaf.shape, dtype=np.int64)
                            * np.dtype(leaf.dtype).itemsize)
    return census


def _donated_flags(lowered) -> List[bool]:
    """Per-positional-arg declared donation (True when ANY leaf of the
    arg is donated). ``args_info`` reflects the jit's declaration even
    on backends that drop donation at compile time (CPU). Its structure
    mirrors the call: ``(args, kwargs)``."""
    import jax
    info = lowered.args_info
    positional = info[0] if (isinstance(info, tuple) and len(info) == 2
                             and isinstance(info[1], dict)) else info
    flags = []
    for arg in positional:
        leaves = jax.tree_util.tree_leaves(
            arg, is_leaf=lambda x: hasattr(x, 'donated'))
        flags.append(any(getattr(leaf, 'donated', False)
                         for leaf in leaves))
    return flags


_NUM_PARTITIONS_RE = re.compile(r'mhlo.num_partitions = (\d+)')


def program_signature(spec: ProgramSpec) -> Dict[str, Any]:
    """The abstract signature of one program — everything the lock pins.

    One trace, one lowering: ``jitted.trace(...)`` (the jax AOT stage)
    respects the jit's static argnames — which ``jax.eval_shape`` /
    ``jax.make_jaxpr`` would not — and its ClosedJaxpr carries both the
    weak-typed output avals and the closed-over consts."""
    import jax
    import numpy as np
    traced = spec.jitted.trace(*spec.args, **spec.kwargs)
    lowered = traced.lower()
    text = lowered.as_text()
    batch = spec.args[spec.batch_argnum]
    donated = _donated_flags(lowered)
    m = _NUM_PARTITIONS_RE.search(text)
    sig: Dict[str, Any] = {
        'batch': _aval_doc(batch),
        'params': _param_census(spec.args[0]),
        'out': [_aval_doc(a) for a in traced.jaxpr.out_avals],
        'out_tree': str(jax.tree_util.tree_structure(traced.out_info)),
        'batch_donated': bool(donated[spec.batch_argnum]
                              if spec.batch_argnum < len(donated) else False),
        'donated_args': [i for i, d in enumerate(donated) if d],
        'num_partitions': int(m.group(1)) if m else 1,
        'stablehlo_sha256': stablehlo_sha256(text),
    }
    cost = lowering_cost(lowered)
    if cost:
        sig['cost'] = {k: int(v) for k, v in cost.items()}
    # bytes the program CLOSES OVER (vs. takes as args): a large value
    # means weights were captured by closure and get baked into the
    # compiled HLO on every geometry. Recorded at EVERY width — the
    # jaxpr is already built, and width-conditional fields would make a
    # --mesh-widths subset run drift against a full-width lock.
    # (size × itemsize, not .nbytes: closed-over numpy arrays arrive as
    # jax TypedNdArray, which has shape/dtype but no nbytes)
    sig['const_bytes'] = int(sum(
        int(c.size) * np.dtype(c.dtype).itemsize
        for c in traced.jaxpr.consts if hasattr(c, 'dtype')))
    # keep the text around for the rule pass without re-lowering
    sig['_text'] = text
    return sig


# -- rule checks -------------------------------------------------------------

def check_program(spec: ProgramSpec, sig: Dict[str, Any], family: str,
                  width: int, mesh, lane: str = 'float32') -> List[Finding]:
    findings: List[Finding] = []
    text = sig['_text']

    def report(rule: str, message: str) -> None:
        if rule not in spec.ok:
            findings.append(Finding(rule, family, width, spec.name,
                                    message, lane=lane))

    if re.search(r'\bf64\b|xf64[>x]', text):
        report('no-f64',
               'lowered program contains f64 ops — a silent float64 '
               'promotion crossed the host/device boundary (pin float32 '
               'at the boundary; the MXU has no f64 path)')
    for i, out in enumerate(sig['out']):
        if out.get('weak_type'):
            report('no-weak-type',
                   f'output leaf {i} has a weak type ({out["dtype"]}) — '
                   f'a python-scalar-only epilogue leaked; downstream '
                   f'dtype promotion becomes context-dependent')
    for marker in _CALLBACK_MARKERS:
        if marker in text:
            report('no-host-callback',
                   f'lowered program contains a host-callback custom '
                   f'call ({marker!r}) — a hot program must never stall '
                   f'device steps on the python GIL')
            break
    if sig['batch_donated'] != spec.donate_batch:
        want = 'donated' if spec.donate_batch else 'NOT donated'
        got = 'donated' if sig['batch_donated'] else 'not donated'
        report('donation',
               f'batch input declared {want} by the family spec but the '
               f'jitted program has it {got} — donation drift changes '
               f'device-memory behavior silently')
    if mesh is not None:
        from video_features_tpu.parallel.mesh import shard_error
        batch_len = sig['batch']['shape'][0]
        err = shard_error(batch_len, mesh)
        if err is not None:
            report('shardable', f'batch dim not shardable at mesh width '
                                f'{width}: {err}')
    if sig.get('const_bytes', 0) > spec.const_budget:
        report('const-budget',
               f'program closes over {sig["const_bytes"]} bytes of '
               f'constants (budget {spec.const_budget}) — weights '
               f'captured by closure get baked into the HLO per '
               f'geometry instead of being passed as params')
    if lane == 'bfloat16':
        # the lane's load-bearing proof: the transplant-time cast left
        # no fp32 (or fp64) PARAM behind — a survivor would silently
        # keep fp32 HBM residency and promote its whole sub-graph back
        # to fp32, defeating the knob while the bench still reports a
        # "bf16" number. fp32 is allowed only in ACTIVATION islands
        # (ops/nn.py), which a params census never sees.
        leaked = sorted(dt for dt in sig['params']
                        if dt in ('float32', 'float64'))
        if leaked:
            detail = ', '.join(
                f'{dt}: {sig["params"][dt]["arrays"]} array(s) / '
                f'{sig["params"][dt]["bytes"]} bytes' for dt in leaked)
            report('bf16-census',
                   f'compute_dtype=bfloat16 program still carries '
                   f'{detail} in its parameter census — the '
                   f'transplant-time cast (torch2jax dtype seam) missed '
                   f'them; bf16 params must be bf16 in HBM')
    if lane == 'int8':
        # the int8 lane's proof, same shape as bf16's but with a
        # DECLARED fp32 minority: weights dominate a model's bytes, so
        # after quantization (ops/quant.py) the census must show int8
        # payloads outweighing the fp32 leftovers (per-channel scales,
        # biases, norm params, embedding tables). fp32 bytes >= int8
        # bytes means the quantizer missed the weights — full-size HBM
        # residency under an "int8" label.
        census = sig['params']
        if 'float64' in census:
            report('int8-census',
                   'compute_dtype=int8 program carries float64 params — '
                   'no lane stores f64')
        if 'int8' not in census:
            report('int8-census',
                   'compute_dtype=int8 program has NO int8 params in '
                   'its census — the transplant-time quantization '
                   '(ops/quant.py via the torch2jax dtype seam) never '
                   'ran')
        else:
            f32 = census.get('float32', {}).get('bytes', 0)
            i8 = census['int8']['bytes']
            if f32 >= i8:
                report('int8-census',
                       f'compute_dtype=int8 program carries more float32 '
                       f'param bytes ({f32}) than int8 ({i8}) — float32 '
                       f'must be the declared minority (scales/biases/'
                       f'norm params); the quantizer missed the weights')
    return findings


# -- collection --------------------------------------------------------------

def _program_mesh(width: int):
    """Data-only mesh of ``width`` host devices (None for width 1 — the
    single-device programs carry no sharding annotations)."""
    if width <= 1:
        return None
    from video_features_tpu.parallel.mesh import make_mesh
    return make_mesh(n_devices=width, time_parallel=1)


def collect(families: Iterable[str], widths: Iterable[int],
            lanes: Iterable[str] = LANES,
            ) -> Tuple[Dict[str, Any], List[Finding]]:
    """Build each family once per lane it supports, lower its programs
    at every width, run the rule checks. Returns (live lock document
    fragment, findings). float32 entries land under the historical
    ``mesh<n>`` keys; bf16-lane entries (``registry.BF16_FEATURES``
    only) under ``mesh<n>@bfloat16``."""
    families = tuple(families)
    live: Dict[str, Any] = {}
    findings: List[Finding] = []
    for family in families:
        live[family] = {}
    for lane in lanes:
        for family in lane_families(lane, families):
            ex = build_family(family, compute_dtype=lane)
            fam_doc = live[family]
            for width in widths:
                mesh = _program_mesh(width)
                specs = ex.program_specs(mesh=mesh)
                if not specs:
                    findings.append(Finding(
                        'coverage', family, width, '-',
                        f'{family} exposes no abstract program specs '
                        f'(BaseExtractor.program_specs) — every family '
                        f'must pin its compiled programs', lane=lane))
                    continue
                progs: Dict[str, Any] = {}
                for spec in specs:
                    sig = program_signature(spec)
                    findings.extend(
                        check_program(spec, sig, family, width, mesh,
                                      lane=lane))
                    sig.pop('_text')
                    progs[spec.name] = sig
                fam_doc[mesh_key(width, lane)] = {'programs': progs}
    return live, findings


# -- the lock ----------------------------------------------------------------

def default_lock_path() -> Path:
    """Repo-root ``PROGRAMS.lock.json`` (the package's parent)."""
    return Path(__file__).resolve().parent.parent.parent / DEFAULT_LOCK


def load_lock(path) -> Dict[str, Any]:
    path = Path(path)
    if not path.exists():
        return {}
    return json.loads(path.read_text() or '{}')


def write_lock(path, live: Dict[str, Any], *,
               prune_families: bool = False,
               replace_widths: bool = False) -> None:
    """Re-pin: replace exactly the checked (family, mesh width) entries,
    keep the rest — a ``--families`` subset must not drop sibling
    families, and a ``--mesh-widths`` subset must not drop the family's
    OTHER widths' pinned signatures.

    A FULL-scope re-pin (the bare ``--write-lock``) also prunes what
    drift findings point at: ``prune_families`` drops lock families that
    are no longer known (so the 'unknown family' finding's own
    remediation advice actually remediates), and ``replace_widths``
    replaces each checked family's entry wholesale (stale ``mesh<n>``
    keys from a retired width don't accrete silently)."""
    doc = load_lock(path)
    families = dict(doc.get('families', {}))
    if prune_families:
        families = {k: v for k, v in families.items() if k in ALL_PINNED}
    for family, fam_doc in live.items():
        if replace_widths:
            families[family] = {k: fam_doc[k] for k in sorted(fam_doc)}
            continue
        merged = dict(families.get(family, {}))
        merged.update(fam_doc)
        families[family] = {k: merged[k] for k in sorted(merged)}
    out = {
        'schema': LOCK_SCHEMA,
        'families': {k: families[k] for k in sorted(families)},
    }
    Path(path).write_text(json.dumps(out, indent=1, sort_keys=True) + '\n')


def family_lock_hashes(feature_type: str,
                       path=None) -> Dict[str, Dict[str, str]]:
    """``{mesh<n>: {program: stablehlo_sha256}}`` for one family from the
    committed lock — pure stdlib (no jax), safe from any process. The
    run manifest records this so a production trace names exactly which
    pinned program ran. ``{}`` when the lock is absent or the family is
    unpinned."""
    try:
        doc = load_lock(path or default_lock_path())
    except Exception:
        # vft-lint: ok=swallowed-exception — telemetry never fails a
        # run: an unreadable/corrupt lock reads as "unpinned"
        return {}
    fam = doc.get('families', {}).get(feature_type, {})
    out: Dict[str, Dict[str, str]] = {}
    for mesh, entry in fam.items():
        progs = entry.get('programs', {})
        hashes = {name: sig.get('stablehlo_sha256', '')
                  for name, sig in progs.items()}
        if hashes:
            out[mesh] = hashes
    return out


# fields whose drift is reported individually (everything else in the
# signature rides along under the stablehlo hash)
_DIFF_FIELDS = ('batch', 'params', 'out', 'out_tree', 'batch_donated',
                'donated_args', 'num_partitions', 'const_bytes', 'cost',
                'stablehlo_sha256')


def diff_lock(live: Dict[str, Any], lock: Dict[str, Any],
              checked: Iterable[str],
              widths: Iterable[int] = MESH_WIDTHS,
              lanes: Iterable[str] = LANES) -> List[Finding]:
    """Field-by-field drift between the live lowerings and the lock.
    Families outside ``checked`` — and mesh widths outside ``widths`` /
    lanes outside ``lanes`` — are skipped (a ``--families`` /
    ``--mesh-widths`` / ``--lanes`` subset run must not report what it
    didn't lower as missing/stale); but a lock family that is not a
    known family at all is always reported. A bf16 lane key is only
    "checked" for families that ACCEPT the lane — a lock carrying
    mesh<n>@bfloat16 for a refusing family is stale and surfaces as a
    live-side-missing program drift once the family joins the lane's
    checked set... until then it is simply never compared (subset
    semantics), so prune it with a full-scope --write-lock."""
    findings: List[Finding] = []
    lanes = tuple(lanes)
    locked = lock.get('families', {})

    def checked_meshes(family: str) -> set:
        return {mesh_key(w, lane) for w in widths for lane in lanes
                if family in lane_families(lane, (family,))}
    for family in sorted(locked):
        if family not in ALL_PINNED:
            findings.append(Finding(
                'lock-drift', family, 0, '-',
                f'lock names unknown family {family!r} — stale entry '
                f'(re-pin with --write-lock)'))
    for family in checked:
        lv = live.get(family, {})
        lk = locked.get(family)
        if lk is None:
            findings.append(Finding(
                'lock-drift', family, 0, '-',
                f'{family} is not in the lock — pin it with '
                f'--write-lock'))
            continue
        for mesh in sorted((set(lv) | set(lk)) & checked_meshes(family)):
            width, lane = parse_mesh_key(mesh)
            lvp = lv.get(mesh, {}).get('programs', {})
            lkp = lk.get(mesh, {}).get('programs', {})
            for name in sorted(set(lvp) | set(lkp)):
                if name not in lkp:
                    findings.append(Finding(
                        'lock-drift', family, width, name,
                        'new program not in the lock (compiled-program '
                        'count changed) — re-pin with --write-lock',
                        lane=lane))
                    continue
                if name not in lvp:
                    findings.append(Finding(
                        'lock-drift', family, width, name,
                        'pinned program no longer lowered by the family '
                        '— stale lock entry (re-pin with --write-lock)',
                        lane=lane))
                    continue
                for field in _DIFF_FIELDS:
                    a, b = lkp[name].get(field), lvp[name].get(field)
                    if a is None and b is None:
                        continue
                    if a != b:
                        findings.append(Finding(
                            'lock-drift', family, width, name,
                            f'{field} drifted: lock={_short(a)} '
                            f'live={_short(b)}', lane=lane))
    return findings


def _short(v: Any, n: int = 120) -> str:
    s = json.dumps(v, sort_keys=True) if not isinstance(v, str) else v
    return s if len(s) <= n else s[:n - 1] + '…'


# -- CLI ---------------------------------------------------------------------

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog='vft-programs',
        description='abstract-interpretation contract checker over every '
                    'compiled JAX program (docs/static_analysis.md)')
    parser.add_argument('--families', help='comma-separated subset '
                        f'(default: all — {",".join(ALL_PINNED)})')
    parser.add_argument('--mesh-widths', default='1,2',
                        help='comma-separated mesh widths to pin '
                        '(default: 1,2 — width 2 needs '
                        '--xla_force_host_platform_device_count=2)')
    parser.add_argument('--lanes', default=','.join(LANES),
                        help='comma-separated compute_dtype lanes to '
                        'check/pin (default: float32,bfloat16,int8 — '
                        'each fast lane covers only its registry opt-in '
                        'set, BF16_FEATURES / INT8_FEATURES)')
    parser.add_argument('--lock', help='lock file path (default: '
                        f'<repo>/{DEFAULT_LOCK})')
    parser.add_argument('--write-lock', action='store_true',
                        help='re-pin: write the live signatures for the '
                        'checked families and exit 0')
    parser.add_argument('--list-rules', action='store_true')
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule in RULES:
            print(rule)
        return EXIT_CLEAN

    families = tuple(args.families.split(',')) if args.families \
        else ALL_PINNED
    unknown = [f for f in families if f not in ALL_PINNED]
    if unknown:
        print(f'vft-programs: unknown families {unknown} '
              f'(known: {", ".join(ALL_PINNED)})', file=sys.stderr)
        return EXIT_ERROR
    widths = tuple(int(w) for w in args.mesh_widths.split(','))
    lanes = tuple(args.lanes.split(','))
    bad_lanes = [lane for lane in lanes if lane not in LANES]
    if bad_lanes:
        print(f'vft-programs: unknown lanes {bad_lanes} '
              f'(known: {", ".join(LANES)})', file=sys.stderr)
        return EXIT_ERROR
    lock_path = Path(args.lock) if args.lock else default_lock_path()

    import jax
    n_local = len(jax.devices())
    if max(widths) > n_local:
        print(f'vft-programs: mesh width {max(widths)} needs '
              f'{max(widths)} host devices but jax sees {n_local} — run '
              f'via tools/vft_programs.py (it forces '
              f'XLA_FLAGS=--xla_force_host_platform_device_count), or '
              f'set the flag before jax initializes', file=sys.stderr)
        return EXIT_ERROR

    try:
        live, findings = collect(families, widths, lanes)
    except Exception as e:                    # noqa: BLE001 — CLI boundary
        import traceback
        traceback.print_exc()
        print(f'vft-programs: analyzer error: {e}', file=sys.stderr)
        return EXIT_ERROR

    if args.write_lock:
        write_lock(lock_path, live,
                   prune_families=set(families) == set(ALL_PINNED),
                   replace_widths=(set(widths) == set(MESH_WIDTHS)
                                   and set(lanes) == set(LANES)))
        n = sum(len(e.get('programs', {}))
                for fam in live.values() for e in fam.values())
        print(f'vft-programs: pinned {n} program signature(s) across '
              f'{len(live)} family(ies) to {lock_path}')
        for f in findings:
            print(f'(unpinnable) {f.render()}', file=sys.stderr)
        return EXIT_CLEAN

    findings.extend(diff_lock(live, load_lock(lock_path), families,
                              widths=widths, lanes=lanes))
    for f in findings:
        print(f.render())
    n_progs = sum(len(e.get('programs', {}))
                  for fam in live.values() for e in fam.values())
    print(f'vft-programs: {len(findings)} finding(s) across {n_progs} '
          f'programs, {len(live)} families, mesh widths '
          f'{list(widths)}, lanes {list(lanes)}', file=sys.stderr)
    return EXIT_FINDINGS if findings else EXIT_CLEAN


if __name__ == '__main__':
    sys.exit(main())
