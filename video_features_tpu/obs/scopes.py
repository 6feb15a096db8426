"""Device scopes: the step's own names for the parts of its device time.

The device trace names an op event by its HLO instruction (``%fusion.1691``),
a name no recompile keeps and no reader understands. The program knows
better: every ``jax.named_scope('<name>')`` a step's code opens is carried,
in order, in the ``op_name`` of each HLO instruction traced under it —
through ``lax.scan`` bodies too — and the COMPILED module still has it
(``metadata={op_name="jit(step)/raft_update/while/body/.../raft_gru/dot"}``).
Only the compiled executable can say that ``%fusion.1691`` came from
``raft_update/raft_gru``, and only the program holds the executable; so
the program exports the map, once per compiled step, and a reader joins
it to the device trace (``benchmark/readers/scope_time.py``; an operator:
``docs/observability.md`` "Device scopes").

``SCOPES`` is the pinned vocabulary, as ``utils.tracing.STAGES`` is the
host's: every ``jax.named_scope`` literal under ``models/`` and
``extract/`` names a member (``tests/test_obs_scopes.py`` holds the tree
to that). A scope is a name for *where device time goes*; it changes
metadata only, never the computation (``PROGRAMS.lock.json`` hashes the
program without locations and does not move when a scope is added).

Nothing here runs, and nothing imports this module, unless the run keeps
a manifest: the map is built from an executable the manifest's cost
analysis compiled anyway (``obs.manifest.xla_cost_analysis``).
"""
from __future__ import annotations

import re
import threading
from typing import Any, Dict, List, Optional

SCOPES = (
    # i3d's fused step (extract/i3d.py, models/raft.py, models/i3d.py)
    'raft_encoders',     # fnet over the unique frames, cnet over the firsts
    'raft_corr',         # the all-pairs correlation pyramid
    'raft_update',       # the refinement scans: both lax.scan calls
    'raft_lookup',       # in the body: the pyramid lookup and its delivery
    'raft_motion',       # in the body: the motion encoder
    'raft_convf1',       # in raft_motion: the 7×7 over the two flow planes
    'raft_coords',       # planes ↔ (B, H, W, 2) conversions of the carry
    'raft_gru',          # in the body: the separable conv GRU
    'raft_flow_head',    # in the body: the flow head, onto the two planes
    'raft_upsample',     # mask head + convex 8× upsample, once
    'flow_quantise',     # crop, clamp ±20, uint8 levels, ±1
    'i3d_towers',        # both I3D towers
    'i3d_stem',          # in i3d_towers: a tower's first convolution
    # the lm family's trunks: the mixers (models/latent_moe.py,
    # retention_trunk.py, hybrid_trunk.py), the feed-forwards
    # (models/token_trunk.py)
    'mla',               # latent attention
    'sparse_mla',        # the same under a learned selection of keys
    'mla_indexer',       # in sparse_mla: the indexer that selects them
    'window_mla',        # latent attention under a window
    'attention',         # grouped-query attention
    'sliding_attention',  # the same under a window (a trunk with both kinds)
    'full_attention',    # and its full causal layers
    'retention',         # gated power retention
    'short_conv',        # gated short convolution
    'mamba',             # the Mamba-2 mixer
    'ssd',               # in mamba: step sizes, decays, the scan, D skip
    'moe',               # routed (and shared) experts
    'dense_mlp',         # a dense SwiGLU
)

# `  [ROOT ]%name = <shape> opcode(`: the name and the opcode
_INSTRUCTION = re.compile(
    r'^\s+(?:ROOT )?(%[\w.\-]+) = .*? ([a-z][a-z\-]*)\(')
# opcodes that never run as an op the device trace times
_NEVER_TIMED = frozenset(('parameter', 'get-tuple-element', 'tuple',
                          'constant', 'bitcast'))
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_MODULE = re.compile(r'^HloModule ([\w.\-]+)', re.M)
_LOC = re.compile(r'loc\("([^"]+)"')
_MEMBERS = frozenset(SCOPES)


def path_of(op_name: str) -> str:
    """``jit(step)/raft_update/while/body/closed_call/raft_gru/dot`` →
    ``raft_update/raft_gru``: the members found, in order. A member that
    follows itself is one (jax repeats the name stack where a scope spans
    a nested jaxpr: ``moe/while/body/moe/…``)."""
    path: List[str] = []
    for part in op_name.split('/'):
        if part in _MEMBERS and path[-1:] != [part]:
            path.append(part)
    return '/'.join(path)


def _parse(hlo_text: str):
    instructions: Dict[str, str] = {}
    bare: List[str] = []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        meta = _OP_NAME.search(line)
        if meta is not None:
            instructions[m.group(1)] = path_of(meta.group(1))
        elif m.group(2) not in _NEVER_TIMED:
            bare.append(m.group(1))
    return instructions, bare


def scope_map(hlo_text: str) -> Dict[str, str]:
    """The optimised module's text (``compiled.as_text()``) →
    ``{'%fusion.1691': 'raft_update/raft_gru', ...}``: for every
    instruction of every computation (fused ones' too — a fusion goes
    where its own, i.e. its root's, ``op_name`` says) the members of
    ``SCOPES`` in its ``op_name``; ``''`` for an instruction with an
    ``op_name`` and no member. Instructions without metadata (parameters,
    tuples, ``copy-start``, the copies layout assignment inserts) are
    left out; :func:`compiled_scopes` names and so counts them."""
    return _parse(hlo_text)[0]


def lowered_scopes(lowered_text: str) -> List[str]:
    """Members of ``SCOPES`` the LOWERED module names
    (``lowered.as_text(debug_info=True)``: its ``loc("jit(step)/…")``
    strings): today's program, whatever a compilation cache serves."""
    seen = set()
    for name in _LOC.findall(lowered_text):
        seen.update(part for part in name.split('/') if part in _MEMBERS)
    return sorted(seen)


def compiled_scopes(lowered_text: str, compiled_text: str) -> Dict[str, Any]:
    """The record a reader needs, from one lowering
    (``lowered.as_text(debug_info=True)``) and its executable
    (``compiled.as_text()``): ``program`` (the name the device trace shows
    the module under), ``instructions`` (:func:`scope_map`),
    ``no_metadata`` (the names of the instructions left out of it that
    can run as ops of their own: ``copy-done``, and the copies layout
    assignment and loop-carry insertion make, which no code of the
    program was traced into) and ``missing``: the members the lowering
    names and the compiled text does not. jax's compilation-cache key
    leaves metadata out, so an executable loaded from a cache written
    before a scope was added carries the OLD ``op_name``s; a non-empty
    ``missing`` says the cache served an older program's metadata, and a
    reader must not trust the map."""
    instructions, bare = _parse(compiled_text)
    module = _MODULE.search(compiled_text)
    seen = {part for path in set(instructions.values())
            for part in path.split('/') if part}
    return {'program': module.group(1) if module else None,
            'instructions': instructions, 'no_metadata': bare,
            'missing': sorted(set(lowered_scopes(lowered_text)) - seen)}


# -- the same-process door (as obs.spans.attached() is for the timeline) ----

_lock = threading.Lock()
_NOTED: Dict[str, Dict[str, Any]] = {}


def note(program: Optional[str], record: Dict[str, Any]) -> None:
    """Keep ``record`` (:func:`compiled_scopes`) for a reader in the same
    process, under the name the device trace shows. A second, different
    executable of one program (another geometry) reuses instruction names
    for other instructions: the first map stays and ``variants`` counts,
    so a reader can refuse rather than guess."""
    if not program:
        return
    with _lock:
        held = _NOTED.get(program)
        if held is None:
            _NOTED[program] = dict(record, variants=1)
        elif held['instructions'] != record['instructions']:
            held['variants'] += 1


def noted() -> Dict[str, Dict[str, Any]]:
    """``{program: record}`` of every executable noted in this process."""
    with _lock:
        return {k: dict(v) for k, v in _NOTED.items()}
