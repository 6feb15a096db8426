"""The jax seam of the executable store: serialize, load, or compile.

``ensure_program`` is the ONE path every consumer goes through — the
lazy per-geometry dispatch (``BaseExtractor.aot_call``), the serve
pre-warm (``BaseExtractor.aot_warm``), and the tests. It traces the
ACTUAL jitted callable the hot path dispatches (the same discipline as
``analysis/programs.py`` — the program identity is the lowering of the
real callable, closures and ambient matmul-precision context included),
takes the StableHLO sha256 of that lowering as the program identity,
and then either

  * **loads** a previously published executable from the
    :class:`aot.store.ExecStore` (PJRT-level deserialization — no XLA
    optimization pass runs; measured ~30x cheaper than a compile on
    CPU, far more on accelerators), or
  * **compiles** the lowering and republishes the serialized executable
    so every future process loads instead.

The store key (``aot.store.exec_digest``) is the program sha plus the
full runtime environment — ``mesh<n>[@dtype]`` lane, jax version,
backend platform, device kind, host ISA, and the exact device ids the
executable is bound to. Any component differing is a SILENT MISS by
construction: a jax upgrade, a different chip generation, or a
placement on different silicon recompiles and republishes under its own
key, never errors. When a miss finds the SAME program published under a
different environment, a structured event names the drift so operators
can see why a boot stopped being compile-free.

Loaded executables produce byte-identical outputs to freshly compiled
ones (same StableHLO, same backend — pinned by tests/test_aot.py),
which is the contract that lets the ``aot_*`` knobs stay out of the
cache fingerprint.
"""
from __future__ import annotations

import logging
import pickle
from typing import Any, Dict, Optional, Tuple

from video_features_tpu.aot.store import ExecStore, exec_digest
from video_features_tpu.obs.events import event

# bump when the payload framing (NOT the executable format — jax/PJRT
# own that, and their versions are in the key) changes incompatibly
PAYLOAD_VERSION = 1


def runtime_environment(devices: Tuple[int, ...]) -> Dict[str, Any]:
    """The environment components of the store key. ``devices`` is the
    sorted tuple of device ids the program's args are committed to —
    PJRT deserialization rebinds by id, so an executable serialized for
    chip d1 must never answer a lookup for chip d0."""
    import platform as _host

    import jax
    dev = jax.devices()[0]
    return {
        'jax': jax.__version__,
        'platform': dev.platform,
        'device_kind': dev.device_kind,
        # XLA:CPU AOT artifacts record the compiling host's CPU feature
        # list (see utils/device.enable_compilation_cache); the ISA in
        # the key keeps a shared aot_dir from serving one host's CPU
        # executable to a different microarchitecture
        'machine': _host.machine(),
        'devices': list(devices),
        'payload_v': PAYLOAD_VERSION,
    }


def arg_devices(args) -> list:
    """The devices ``args`` are committed to, ordered by id — committed
    ``jax.Array`` leaves and sharded ``ShapeDtypeStruct``s both count;
    plain numpy leaves (uncommitted) contribute nothing. Empty means
    'backend default device'. These are both the store key's device ids
    and the ``execution_devices`` a loaded executable is bound to."""
    import jax
    devices = {}
    for leaf in jax.tree_util.tree_leaves(args):
        sharding = getattr(leaf, 'sharding', None)
        for d in getattr(sharding, 'device_set', None) or ():
            devices[d.id] = d
    if not devices:
        d = jax.devices()[0]
        devices[d.id] = d
    return [devices[i] for i in sorted(devices)]


def serialize_compiled(compiled) -> bytes:
    """One self-contained payload for a ``jax.stages.Compiled``: the
    PJRT-serialized executable plus the in/out pytree structure
    (``serialize_executable`` returns the trees separately because
    PyTreeDefs aren't its problem; they pickle fine and the payload
    must be one blob on disk)."""
    from jax.experimental import serialize_executable as se
    payload, in_tree, out_tree = se.serialize(compiled)
    return pickle.dumps((PAYLOAD_VERSION, payload, in_tree, out_tree))


def deserialize_compiled(blob: bytes, devices):
    """Inverse of :func:`serialize_compiled`, bound to ``devices`` (the
    keyed :func:`arg_devices` — left to its default the loader binds
    EVERY local device, wrong for a one-chip program on a four-chip
    host); raises on any mismatch (version skew, foreign pickle,
    truncation) — callers treat every raise as a corrupt entry to evict
    + a compile to fall back on."""
    version, payload, in_tree, out_tree = pickle.loads(blob)
    if version != PAYLOAD_VERSION:
        raise ValueError(f'aot payload version {version} != '
                         f'{PAYLOAD_VERSION}')
    from jax.experimental import serialize_executable as se
    return se.deserialize_and_load(payload, in_tree, out_tree,
                                   backend=devices[0].client,
                                   execution_devices=devices)


class AotProgram:
    """One resident executable + the call convention to reach it.

    ``Compiled`` objects are called with the ARRAY args only — static
    kwargs were baked at trace time — so the program remembers which
    statics it was specialized for (``aot_call`` keys its dispatch
    table on them) and drops them at call time.
    """

    __slots__ = ('name', 'compiled', 'program_sha', 'source')

    def __init__(self, name: str, compiled, program_sha: str,
                 source: str) -> None:
        self.name = name
        self.compiled = compiled
        self.program_sha = program_sha
        self.source = source              # 'loaded' | 'compiled'

    def __call__(self, *arrays):
        return self.compiled(*arrays)


def ensure_program(store: ExecStore, name: str, jitted, args: tuple,
                   statics: Optional[Dict[str, Any]] = None, *,
                   lane: str, feature_type: str = '?',
                   ) -> Tuple[AotProgram, str]:
    """Trace ``jitted`` at ``args``/``statics``, then load-or-compile.

    Returns ``(program, path)`` with ``path`` one of ``'loaded'`` /
    ``'compiled'``. Raises only on a genuine COMPILE failure (the same
    error the jit path would hit); every store-side failure — unreadable
    dir, corrupt payload, failed publish — degrades to the compile path
    with a structured report.
    """
    statics = dict(statics or {})
    lowered = jitted.trace(*args, **statics).lower()
    from video_features_tpu.analysis.programs import stablehlo_sha256
    program_sha = stablehlo_sha256(lowered.as_text())
    devices = arg_devices(args)
    components = {'program_sha': program_sha, 'lane': lane}
    components.update(runtime_environment(tuple(d.id for d in devices)))
    digest = exec_digest(components)

    blob = store.fetch(digest)
    if blob is not None:
        try:
            compiled = deserialize_compiled(blob, devices)
            return (AotProgram(name, compiled, program_sha, 'loaded'),
                    'loaded')
        except Exception:
            # bit-rot below the size check, or an environment the key
            # failed to capture: purge so the next boot doesn't re-fail,
            # and recompile — never serve (or crash on) a bad payload
            store.evict_corrupt(digest)
            event(logging.WARNING,
                  'stored executable failed to deserialize; evicted '
                  'and recompiling', subsystem='aot', exc_info=True,
                  feature_type=feature_type, program=name, lane=lane)
    else:
        _report_environment_miss(store, program_sha, components,
                                 feature_type, name, lane)

    compiled = lowered.compile()
    try:
        store.put(digest, serialize_compiled(compiled),
                  meta={'feature_type': feature_type, 'program': name,
                        **components})
    except Exception:
        from video_features_tpu.aot.store import log_aot_error
        log_aot_error(f'publish for {feature_type}/{name}')
    return AotProgram(name, compiled, program_sha, 'compiled'), 'compiled'


def _report_environment_miss(store: ExecStore, program_sha: str,
                             components: Dict[str, Any],
                             feature_type: str, name: str,
                             lane: str) -> None:
    """A miss for a program the store DOES hold under a different
    environment is the invalidation semantics working as designed (jax
    upgraded, different device kind/ids, host ISA changed) — but an
    operator reading "boot stopped being compile-free" needs the reason
    named, so it gets a structured event instead of indistinguishable
    silence. Never raises; never fires for plain cold stores."""
    try:
        for meta in store.metas_for(program_sha):
            drift = {k: (meta.get(k), components.get(k))
                     for k in ('jax', 'platform', 'device_kind',
                               'machine', 'devices', 'lane', 'payload_v')
                     if meta.get(k) != components.get(k)}
            if drift:
                event(logging.INFO,
                      'executable present under a different runtime '
                      'environment — recompiling (silent-miss '
                      'invalidation)', subsystem='aot',
                      feature_type=feature_type, program=name, lane=lane,
                      drift={k: {'stored': a, 'live': b}
                             for k, (a, b) in drift.items()})
                return
    except Exception:
        # vft-lint: ok=swallowed-exception — best-effort diagnostics on
        # the compile path; the miss itself is already being handled
        pass
