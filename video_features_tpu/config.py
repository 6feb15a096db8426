"""Config system: per-feature YAML defaults merged with dotlist CLI overrides.

Behavior parity with the reference's OmegaConf pipeline (main.py:9-10,
utils/utils.py:77-135) without the OmegaConf dependency: flat key=value YAML
files, CLI ``key=value`` dotlist wins over YAML, then an imperative
``sanity_check`` that validates combinations and rewrites output/tmp paths.
"""
from __future__ import annotations

import os
import random
import warnings
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Tuple, Union

import yaml

CONFIG_DIR = Path(__file__).parent / 'configs'

# The one registry of feature families. Also the coverage set the
# vft-programs contract checker pins PROGRAMS.lock.json against
# (analysis/programs.py) — adding a family here obliges an abstract
# step spec (BaseExtractor.program_specs) and a lock re-pin.
KNOWN_FEATURE_TYPES = ('i3d', 'r21d', 's3d', 'vggish', 'resnet', 'raft', 'clip', 'timm', 'lm')

# -- content-addressed feature cache (cache/; docs/caching.md) ---------------
# Injected into every merged config (CLI dotlist wins, as always) rather
# than copied into each per-feature YAML: one source of truth for the
# namespace, and older user YAMLs pick the knobs up automatically.
CACHE_DEFAULTS: Dict[str, Any] = {
    # consult/publish the content-addressed result store: the second
    # request for any (video content, config, checkpoint) becomes an
    # O(read) hit that skips decode + inference, with byte-identical
    # outputs. Off by default — today's behavior exactly.
    'cache_enabled': False,
    # where entries live (manifest.jsonl + objects/); shared across
    # processes/workers on one host
    'cache_dir': '~/.cache/video_features_tpu/features',
    # LRU size bound in bytes (null = unbounded); enforced inline on
    # publish and offline via tools/cache_gc.py
    'cache_max_bytes': None,
    # fleet shared tier (fleet/tier.py; docs/fleet.md): a directory
    # every fleet host mounts. When set, cache_dir becomes the local L1
    # and this the L2 — puts replicate here, an L1 miss a peer already
    # extracted serves from here byte-identically (no decode) and
    # promotes into L1. null = single-host behavior exactly.
    'cache_l2_dir': None,
}

# -- device-loop pipelining (parallel/packing.py) -----------------------------
# Same injection policy as CACHE_DEFAULTS: one source of truth, older
# user YAMLs pick the knobs up automatically, CLI dotlist wins.
PIPELINE_DEFAULTS: Dict[str, Any] = {
    # in-flight device batches on the output side of the device loop:
    # batch k-1's results are only materialized (D2H + row scatter +
    # save) AFTER batch k has been dispatched, so readback and host
    # finalization overlap device compute. 1 = fully synchronous
    # (today's behavior); each extra unit keeps one more output batch
    # resident on device. Outputs are byte-identical at any depth.
    'inflight': 2,
    # mesh-sharded packed execution (parallel/mesh.py): the packed
    # worklist / serve device loop plans batches at capacity × ndev and
    # shards each stacked batch over the data axis of an N-device mesh
    # (params replicated per chip). 1 = single-device (today's loop);
    # 0 = auto-detect every local device of the platform; N = exactly N
    # chips (a clear error if fewer exist). Outputs are byte-identical
    # at any device count; per-video fault isolation is unchanged. The
    # knob only drives the PACKED paths (pack_across_videos / serve) —
    # the per-video loop keeps data_parallel for in-graph DP.
    'mesh_devices': 1,
    # the precision ladder (ops/precision.py, docs/design.md
    # "precision ladder"): 'float32' (default) is exactly today's
    # numerics; 'bfloat16' casts params to bf16 at transplant time (half
    # the HBM residency + H2D bytes) and runs bf16 activations with fp32
    # accumulation islands; 'int8' quantizes conv/linear weights
    # per-output-channel symmetric int8 at transplant time (a QUARTER of
    # the fp32 param bytes, ops/quant.py) with in-graph dequant and fp32
    # activations. Each lane sits under a measured per-family rel-L2
    # bound (tests/test_precision.py). Orthogonal to the matmul
    # `precision=` knob. Families without a pinned bound REFUSE a lane
    # with a structured build-time error (registry.BF16_FEATURES /
    # registry.INT8_FEATURES); outputs are NOT byte-identical across
    # lanes, so the knob is classified 'both' — artifacts from different
    # lanes never share a cache entry or a warm serve program.
    'compute_dtype': 'float32',
}

# -- decode farm (farm/; docs/decode_farm.md) --------------------------------
# Same injection policy as CACHE_DEFAULTS: one source of truth, older
# user YAMLs pick the knobs up automatically, CLI dotlist wins. Families
# whose YAML already carries decode_workers (i3d ships 2) keep their
# tuned value.
FARM_DEFAULTS: Dict[str, Any] = {
    # host decode/preprocess parallelism. Unset (null, the default):
    # the packed/serve paths decode up to K videos of the worklist at
    # once on in-process threads (decode lanes), K = the usable cores
    # halved, at most 4, never more than the videos of a sized worklist
    # (extract/streaming.py decode_lane_plan); the per-video loop reads
    # unset as 1. 1 = serial in-process decode on every path, exactly as
    # before. >1 on the per-video loop = the in-process transform
    # thread pool; >1 on the packed/serve paths = the multi-process
    # decode farm (N worker processes feeding the packer over
    # shared-memory rings — GIL- and swscale-unbound). Outputs are
    # byte-identical at any value.
    'decode_workers': None,
    # per-worker shared-memory ring size (MiB): bounds decoded bytes in
    # flight per worker; a slow consumer stalls decode instead of
    # growing memory. See docs/decode_farm.md for sizing.
    'decode_farm_ring_mb': 64,
}

# -- persistent executable store (aot/; docs/serving.md "Zero cold start") ---
# Same injection policy as CACHE_DEFAULTS: one source of truth, older
# user YAMLs pick the knobs up automatically, CLI dotlist wins.
AOT_DEFAULTS: Dict[str, Any] = {
    # consult/publish the persistent compiled-executable store: the
    # second process running an unchanged program set LOADS executables
    # (PJRT deserialization, milliseconds) instead of paying XLA
    # compilation. Keyed by the StableHLO identity PROGRAMS.lock.json
    # pins + jax version + backend/device kind + device ids — any
    # mismatch is a silent compile-on-miss, never an error. Outputs of
    # loaded executables are byte-identical to freshly compiled ones
    # (tests/test_aot.py), so these knobs stay out of the cache
    # fingerprint. Off by default — today's behavior exactly.
    'aot_enabled': False,
    # where serialized executables live (manifest.jsonl + objects/);
    # shared across processes on one host. NOTE: on the CPU backend the
    # payloads record the compiling host's ISA, so a network-shared dir
    # only pays off for accelerator backends (same caveat as jax's own
    # compilation cache — utils/device.enable_compilation_cache). TRUST:
    # payloads restore via pickle-based PJRT machinery — whoever can
    # write this dir can run code in every loading process, so keep it
    # writable only by the principals that run the extractors
    # (docs/serving.md "Zero cold start" § trust model).
    'aot_dir': '~/.cache/video_features_tpu/executables',
    # LRU size bound in bytes (null = unbounded); enforced inline on
    # publish and offline via tools/aot_gc.py
    'aot_max_bytes': None,
    # fleet shared artifact tier (fleet/artifacts.py; docs/fleet.md):
    # when set, aot_dir becomes the local L1 and this a shared
    # publish-on-compile / pull-on-miss tier — a freshly provisioned
    # host loads executables a peer compiled and boots compile-free.
    # Same ISA/trust caveats as a network-shared aot_dir (above).
    # null = single-host behavior exactly.
    'aot_l2_dir': None,
}

# -- feature index (index/; docs/feature_index.md) ---------------------------
# Same injection policy as CACHE_DEFAULTS: one source of truth, older
# user YAMLs pick the knobs up automatically, CLI dotlist wins.
INDEX_DEFAULTS: Dict[str, Any] = {
    # serve-side feature index: an ingest worker tails the cache
    # manifest and folds every published framewise feature object into
    # searchable embedding shards (POST /v1/search, loopback 'search').
    # Requires cache_enabled. Off by default — today's behavior exactly.
    'index_enabled': False,
    # where shards + row manifest live; null = <cache_dir>/index (beside
    # the objects the rows point into, outside objects/ so cache GC's
    # orphan sweep never touches it)
    'index_dir': None,
    # shard-file row bound: every shard pads to exactly this many rows
    # at query time, so the AOT store holds ONE query executable per
    # embedding dim regardless of corpus size
    'index_shard_rows': 1024,
    # ingest-poll cadence (seconds) when the cursor has caught up with
    # the cache manifest; behind, the worker re-polls immediately
    'index_poll_s': 0.5,
    # query-batch quantization: query vectors pad to multiples of this,
    # bounding executable geometries on the query side like
    # index_shard_rows does on the shard side
    'index_query_block': 8,
    # the STATIC k the query program compiles with (lax.top_k); requests
    # asking for less get a slice, more is clamped
    'index_k_max': 10,
}

# -- flight recorder (obs/; docs/observability.md) ---------------------------
# Same injection policy as CACHE_DEFAULTS: one source of truth, older
# user YAMLs pick the knobs up automatically, CLI dotlist wins.
OBS_DEFAULTS: Dict[str, Any] = {
    # Chrome trace-event JSON export of the run's span timeline (open in
    # Perfetto / chrome://tracing; validate with tools/trace_view.py).
    # Works on all three paths: one-shot CLI, packed worklists, serve
    # (base override; each worker exports on drain). null = off.
    'trace_out': None,
    # span ring-buffer bound (events): the recorder keeps the most
    # recent window and stamps how many older events were dropped
    'trace_capacity': 200_000,
    # per-run JSON manifest: merged config + config/weights fingerprints,
    # per-video outcomes, aggregate stage table, XLA compile time, and
    # per-executable-identity cost analysis. null = off.
    'manifest_out': None,
    # -- vft-flight (obs/blackbox.py, obs/watchdog.py) -------------------
    # crash-dump black box: on unhandled worker crash, fatal signal, or
    # watchdog trip, a bounded post-mortem bundle (recent spans, event
    # tail, metrics snapshot, manifest fragment) lands here. null = off.
    'postmortem_dir': None,
    # size cap for the whole postmortem/ dir: oldest bundles GC first,
    # the newest always survives
    'postmortem_max_bytes': 64 * (1 << 20),
    # stall watchdog: a worker holding queued work longer than this many
    # seconds without a single stage advance trips a structured event +
    # vft_watchdog_stalls_total{stage} + a black-box dump. null = off.
    'watchdog_stall_s': None,
    # -- vft-scope SLOs (obs/slo.py) -------------------------------------
    # declarative objectives; setting either turns on multi-window 5m/1h
    # burn-rate evaluation over the serve request families, vft_slo_*
    # gauges, and structured obs/events alerts. null = off.
    # "99% of requests complete within this many seconds":
    'slo_latency_p99_s': None,
    # request success-rate objective in (0, 1), e.g. 0.999:
    'slo_availability': None,
}


# -- knob classification registry (vft-lint: knob-classification) -----------
# The ONE declarative answer to "what does this config key change?" along
# the two identity axes consumers key on:
#
#   * the cache CONFIG FINGERPRINT (cache/key.py): does the knob change
#     the extracted BYTES? Excluded knobs don't fragment the cache key
#     space; anything NOT listed here stays IN the fingerprint
#     (fail-closed: an unknown future knob costs a redundant miss, never
#     a wrong hit).
#   * the serve POOL KEY (serve/server.py): does the knob change the
#     compiled program / weights / residency, or the worker's run
#     behavior? Excluded knobs share a warm entry (the FIRST builder's
#     setting wins); anything NOT listed stays IN the key (fail-closed:
#     an unknown knob builds a redundant entry, never shares a wrong one).
#
# Classes:
#   'neither'          — changes neither the bytes nor the program:
#                        excluded from fingerprint AND pool key
#   'pool_only'        — changes the program/residency/run behavior but
#                        never the bytes: excluded from the fingerprint,
#                        IN the pool key
#   'fingerprint_only' — (unused today; supported for completeness)
#   'both'             — relevant everywhere (same as not listing it,
#                        but explicit for injected knobs)
#
# Consumers derive their exclusion sets via knob_exclude() — there are
# deliberately NO hand-maintained copies of these lists anywhere else;
# vft-lint (analysis/, rule 'knob-registry') rejects any that reappear,
# and rule 'knob-classification' rejects any injected *_DEFAULTS knob
# missing from this table. PRs 5-8 each re-fixed a drift between the
# three hand-synced copies this replaces.
KNOB_CLASSIFICATION: Dict[str, str] = {
    # payload / routing: the work list and where outputs land are
    # per-request concerns, never identity
    'video_paths': 'neither',
    'file_with_video_paths': 'neither',
    # the fused-worklist family list (`features=[resnet,clip,...]`) is
    # pure routing: each family still resolves its OWN merged config
    # (resolve_fused_features strips the key before load_config), so it
    # must never fragment a family's fingerprint or pool key — a fused
    # run's cache keys are identical to N sequential runs' by contract
    'features': 'neither',
    'output_path': 'neither',
    # tmp_path is pool-key relevant: loaders read the ENTRY's tmp root,
    # so a request with a different tmp_path must get its own entry
    # rather than silently writing re-encode temps under another
    # request's root
    'tmp_path': 'pool_only',
    'keep_tmp_files': 'pool_only',
    # device & parallelism: where the program runs, not what it computes
    # (numerics are pinned by `precision`, which stays IN both keys)
    'device': 'pool_only',
    'device_ids': 'pool_only',
    'data_parallel': 'pool_only',
    'multihost': 'pool_only',
    'coordinator_address': 'pool_only',
    'num_processes': 'pool_only',
    'process_id': 'pool_only',
    'pack_across_videos': 'pool_only',
    'pack_decode_ahead': 'pool_only',
    # mesh-sharded packed execution: how many chips the batch spreads
    # over, never what each row computes (byte-identical at any device
    # count — tests/test_mesh_packed.py pins it). Pool-key RELEVANT: it
    # changes the compiled program's sharding and how many chips the
    # entry is resident on, so a 1-chip and a 4-chip request each get
    # their own warm entry.
    'mesh_devices': 'pool_only',
    # the bf16 fast lane changes BOTH identities: bf16 features are
    # numerically different bytes (within the pinned bound — a bf16 run
    # must never serve an fp32 cache entry or vice versa), and a bf16
    # entry is a different compiled program with half the params HBM —
    # fp32 and bf16 warm pool entries must coexist, not collide
    'compute_dtype': 'both',
    'compilation_cache_dir': 'pool_only',
    # input-side decode parallelism (decode farm): where decode runs,
    # never the bytes produced (tests/test_farm.py pins byte-identity);
    # the FIRST builder's farm settings win for a shared warm entry
    'decode_workers': 'neither',
    'decode_farm_ring_mb': 'neither',
    # output-side pipelining depth (async device loop): how deep D2H
    # defers behind dispatch, never what the step computes
    # (tests/test_packing.py pins byte-identity); FIRST builder wins
    'inflight': 'neither',
    # observability / debug surfaces: telemetry can't change the bytes,
    # and fragmenting the executable key space on trace settings would
    # transplant + compile twice for a trace_out difference. show_pred
    # and profile change the worker's RUN behavior → pool-key relevant
    # is deliberately NOT claimed for trace knobs, but profile is forced
    # on for the serve metrics surface → excluded from the pool key too.
    'profile': 'neither',
    'profile_dir': 'neither',
    'show_pred': 'pool_only',
    'trace_out': 'neither',
    'trace_capacity': 'neither',
    'manifest_out': 'neither',
    # vft-flight telemetry (black box + watchdog): where crash dumps
    # land and when liveness trips can't change the extracted bytes,
    # and fragmenting the executable key space on a postmortem path
    # would transplant twice for a telemetry difference — same policy
    # as the trace knobs above
    'postmortem_dir': 'neither',
    'postmortem_max_bytes': 'neither',
    'watchdog_stall_s': 'neither',
    # vft-scope SLOs: burn-rate evaluation reads metrics the serving
    # path already records — an objective can't change extracted bytes
    # or executable identity
    'slo_latency_p99_s': 'neither',
    'slo_availability': 'neither',
    # the cache's own namespace must not fragment its key space; pool-key
    # RELEVANT: a worker's extractor publishes/consults the cache
    # configured at build time, so requests with different cache
    # settings must not share an entry
    'cache_enabled': 'pool_only',
    'cache_dir': 'pool_only',
    'cache_max_bytes': 'pool_only',
    # the L2 is part of WHICH store the worker publishes/consults —
    # same pool-key reasoning as cache_dir; and like cache_dir it can
    # never change the bytes an extractor computes
    'cache_l2_dir': 'pool_only',
    # executable store (aot/): where compiled programs are LOADED from
    # can never change the bytes they compute (loaded executables are
    # byte-identical to fresh compiles — tests/test_aot.py pins it), so
    # the fingerprint excludes all three; pool-key RELEVANT for the
    # same reason as cache_*: a worker consults/publishes the store it
    # was built with, so requests naming different stores must not
    # share an entry
    'aot_enabled': 'pool_only',
    'aot_dir': 'pool_only',
    'aot_max_bytes': 'pool_only',
    # same reasoning as aot_dir: names WHERE executables come from,
    # never what they compute
    'aot_l2_dir': 'pool_only',
    # feature index (index/): a serving-side consumer of ALREADY
    # published cache objects — ingest and query never touch what an
    # extractor computes, and no worker binds to these knobs at build
    # time (the IndexService reads them once at boot), so they fragment
    # neither the cache key space nor the warm pool
    'index_enabled': 'neither',
    'index_dir': 'neither',
    'index_shard_rows': 'neither',
    'index_poll_s': 'neither',
    'index_query_block': 'neither',
    'index_k_max': 'neither',
    # covered by the weights fingerprint (checkpoint CONTENT is hashed)
    'allow_random_weights': 'pool_only',
    # serve-side per-request plumbing
    'timeout_s': 'neither',
    'config': 'pool_only',
}

_KNOB_AXIS_EXCLUDES = {
    'fingerprint': ('neither', 'pool_only'),
    'pool_key': ('neither', 'fingerprint_only'),
}


def knob_exclude(axis: str) -> frozenset:
    """The keys excluded from ``axis`` (``'fingerprint'`` |
    ``'pool_key'``), derived from :data:`KNOB_CLASSIFICATION`."""
    excluded_classes = _KNOB_AXIS_EXCLUDES[axis]
    return frozenset(k for k, cls in KNOB_CLASSIFICATION.items()
                     if cls in excluded_classes)


class Config(dict):
    """A flat dict with attribute access — the shape every extractor consumes.

    The reference accepts "any object with the right attributes" (its tests
    patch OmegaConf dicts programmatically, tests/utils.py:51-56); this class
    keeps that duck-typed contract.
    """

    def __getattr__(self, key: str) -> Any:
        try:
            return self[key]
        except KeyError:
            raise AttributeError(key)

    def __setattr__(self, key: str, value: Any) -> None:
        self[key] = value

    def __delattr__(self, key: str) -> None:
        try:
            del self[key]
        except KeyError:
            raise AttributeError(key)

    def copy(self) -> 'Config':
        return Config(self)


def build_cfg_path(feature_type: str) -> Path:
    """Default YAML path for a feature family (reference utils/utils.py:229-240)."""
    return CONFIG_DIR / f'{feature_type}.yml'


def _parse_value(raw: str) -> Any:
    """Parse one CLI value with YAML scalar/list semantics (OmegaConf-like).

    ``null``→None, ``true``→bool, ``3``→int, ``'[a,b]'``→list, else str.
    """
    try:
        return yaml.safe_load(raw)
    except yaml.YAMLError:
        return raw


def parse_dotlist(dotlist: Iterable[str]) -> Config:
    """Parse ``['key=value', ...]`` CLI args into a Config."""
    cfg = Config()
    for item in dotlist:
        if '=' not in item:
            raise ValueError(f'Malformed CLI argument (expected key=value): {item!r}')
        key, _, raw = item.partition('=')
        cfg[key.strip()] = _parse_value(raw)
    return cfg


def load_yaml(path: Union[str, os.PathLike]) -> Config:
    with open(path) as f:
        data = yaml.safe_load(f) or {}
    if not isinstance(data, dict):
        raise ValueError(f'Config file {path} must contain a flat mapping')
    return Config(data)


def load_config(
    feature_type: Optional[str] = None,
    overrides: Optional[Dict[str, Any]] = None,
    run_sanity_check: bool = True,
) -> Config:
    """YAML defaults ← overrides (overrides win), then sanity_check.

    Mirrors reference main.py:9-11: ``OmegaConf.merge(args_yml, args_cli)``
    with CLI priority, followed by ``sanity_check``.
    """
    overrides = dict(overrides or {})
    feature_type = feature_type or overrides.get('feature_type')
    if feature_type is None:
        raise ValueError('feature_type must be given (CLI: feature_type=<name>)')
    cfg_path = build_cfg_path(feature_type)
    if not cfg_path.exists():
        raise NotImplementedError(
            f'Extractor {feature_type!r} is not implemented. '
            f'Known: {", ".join(KNOWN_FEATURE_TYPES)}')
    args = load_yaml(cfg_path)
    for key, value in CACHE_DEFAULTS.items():
        args.setdefault(key, value)
    for key, value in AOT_DEFAULTS.items():
        args.setdefault(key, value)
    for key, value in INDEX_DEFAULTS.items():
        args.setdefault(key, value)
    for key, value in OBS_DEFAULTS.items():
        args.setdefault(key, value)
    for key, value in PIPELINE_DEFAULTS.items():
        args.setdefault(key, value)
    for key, value in FARM_DEFAULTS.items():
        args.setdefault(key, value)
    args.update(overrides)
    if run_sanity_check:
        sanity_check(args)
    return args


def resolve_fused_features(value: Union[str, Iterable[str]]) -> List[str]:
    """Normalize + validate a fused-worklist ``features`` value.

    Accepts a list (the YAML-parsed CLI form ``features=[resnet,clip]``)
    or a comma-separated string; returns the de-duplicated family list in
    user order. Every family must be in :data:`KNOWN_FEATURE_TYPES` —
    ValueError (not assert: user-facing, must survive ``python -O``)
    names the offender. A single-family list is legal and simply routes
    to the ordinary single-family path.
    """
    if isinstance(value, str):
        items = [s.strip() for s in value.split(',') if s.strip()]
    elif isinstance(value, (list, tuple)):
        items = [str(s).strip() for s in value if str(s).strip()]
    else:
        raise ValueError(
            f'features must be a list of family names or a comma-separated '
            f'string (e.g. features=[resnet,clip,timm]); got {value!r}')
    if not items:
        raise ValueError('features must name at least one feature family')
    families: List[str] = []
    for fam in items:
        if fam not in KNOWN_FEATURE_TYPES:
            raise ValueError(
                f'features names unknown family {fam!r} '
                f'(known: {", ".join(KNOWN_FEATURE_TYPES)})')
        if fam not in families:
            families.append(fam)
    return families


def split_fused_overrides(
    overrides: Dict[str, Any], families: Iterable[str],
) -> Tuple[Config, Dict[str, Config]]:
    """Split a fused-run dotlist into (shared, per-family) overrides.

    ``<family>.<knob>=value`` keys (``parse_dotlist`` keeps the dot) are
    family-SCOPED: they reach only that family's merged config — the
    escape hatch for knobs that must differ per family (``timm.
    model_name=vit_base_patch16_224`` while resnet keeps its YAML
    default). The routing keys ``features``/``feature_type`` are dropped
    from the shared set: each family's config is resolved with its own
    ``feature_type``, and ``features`` leaking into a merged config would
    fragment its cache fingerprint vs a sequential run (fail-closed
    unknown keys stay IN the fingerprint).
    """
    fams = list(families)
    shared, scoped = Config(), {f: Config() for f in fams}
    for key, value in dict(overrides or {}).items():
        if key in ('features', 'feature_type'):
            continue
        head, dot, rest = key.partition('.')
        if dot and head in scoped and rest:
            scoped[head][rest] = value
        else:
            shared[key] = value
    return shared, scoped


def load_fused_configs(
    features: Union[str, Iterable[str]],
    overrides: Optional[Dict[str, Any]] = None,
    run_sanity_check: bool = True,
) -> 'Dict[str, Config]':
    """One merged per-family config per requested family, in user order.

    Each family resolves exactly as a sequential ``load_config(family,
    shared + family-scoped overrides)`` run would — same YAML defaults,
    same injected knob defaults, same sanity_check path rewriting
    (``output_path/<family>[/<model_name>]``) — so per-``(family,
    video)`` cache keys, resume sidecars, and output naming are
    byte-for-byte those of N sequential runs. Validation is all-or-
    nothing: any invalid family or per-family config rejects the whole
    fused request before any work starts.
    """
    families = resolve_fused_features(features)
    shared, scoped = split_fused_overrides(dict(overrides or {}), families)
    configs: Dict[str, Config] = {}
    for fam in families:
        fam_overrides = Config(shared)
        fam_overrides.update(scoped[fam])
        configs[fam] = load_config(fam, overrides=fam_overrides,
                                   run_sanity_check=run_sanity_check)
    return configs


def resolve_device(device: str) -> str:
    """Map a user device string onto a JAX platform.

    The reference accepts torch strings ('cuda:0', 'cpu'); we keep accepting
    them for drop-in compatibility: 'cuda*'/'tpu'/'gpu' → the accelerator
    platform. Unlike the reference (utils/utils.py:83-92 maps unavailable
    CUDA → CPU) a missing accelerator RAISES, naming the platforms found:
    every family yml ships ``device: 'tpu'``, and a run that quietly
    carried on on the CPU would report success at a thousandth of the
    speed. ``device=cpu`` is the explicit CPU path.
    """
    from video_features_tpu.utils.device import (
        accelerator_platform, pin_cpu_platform,
    )

    device = str(device).lower()
    if device.startswith(('cuda', 'tpu', 'gpu', 'accel')):
        return accelerator_platform()
    # Pin before backends initialize: probing for accelerators here would
    # take the chip away from the process that needs it
    # (utils/device.pin_cpu_platform).
    pin_cpu_platform()
    return 'cpu'


def sanity_check(args: Config) -> None:
    """Validate the merged config and rewrite output/tmp paths.

    Check-for-check parity with reference utils/utils.py:77-135:
      * legacy ``device_ids`` → single-device warning (:83-89);
      * unavailable accelerator degrades to CPU (:90-92);
      * paths required; unique video stems (:93-95, upstream issue #54);
      * output_path != tmp_path (:96);
      * i3d stack_size >= 10 (:103-106); pwc removed (:107-109);
      * timm model_name required (:113-115); batch_size not None (:116-117);
      * extraction_fps xor extraction_total (:118-120);
      * append ``<feature_type>[/<model_name>]`` ('/'→'_') to output/tmp
        paths (:122-135).
    """
    if 'device_ids' in args:
        warnings.warn(
            'multi-device single-process extraction is not supported. '
            'Scale out by sharding the video list across workers/hosts '
            f'(device_ids={args["device_ids"]} ignored; using one '
            'accelerator).')
        args['device'] = 'tpu'
    args['device'] = resolve_device(args.get('device', 'cpu'))

    from video_features_tpu.utils.device import MATMUL_PRECISIONS
    prec = args.get('precision', 'highest')
    # ValueError, not assert: user-facing validation must survive `python -O`
    # (an invalid value would otherwise surface later as an opaque
    # jax.default_matmul_precision error inside the per-video loop)
    if prec not in MATMUL_PRECISIONS:
        raise ValueError(
            f'precision must be one of {MATMUL_PRECISIONS}; got {prec!r}')
    backend = args.get('decode_backend', 'auto')
    if backend not in ('auto', 'native', 'cv2'):
        raise ValueError(
            f"decode_backend must be 'auto', 'native', or 'cv2'; "
            f'got {backend!r}')

    # bf16 fast lane (ops/precision.py): validate the value AND the
    # family's acceptance at config time — a family without a pinned
    # parity bound refuses the knob with a structured error here, so a
    # serve submit fails its build with the bound named instead of a
    # worker shipping out-of-bound features. ComputeDtypeError is a
    # ValueError — same surface as every other knob rejection.
    from video_features_tpu.ops.precision import check_compute_dtype
    args['compute_dtype'] = check_compute_dtype(
        args.get('feature_type'),
        str(args.get('compute_dtype') or 'float32'))
    if args.get('cache_enabled'):
        if not args.get('cache_dir'):
            raise ValueError('cache_enabled=true requires cache_dir '
                             '(see docs/caching.md)')
        if args.get('cache_max_bytes') is not None:
            args['cache_max_bytes'] = int(args['cache_max_bytes'])
            if args['cache_max_bytes'] < 0:
                raise ValueError('cache_max_bytes must be >= 0 or null; '
                                 f'got {args["cache_max_bytes"]}')
        if args.get('on_extraction') == 'print':
            # nothing reaches disk, so there is nothing to address by
            # content — warn-and-disable (same policy as the packing knob)
            warnings.warn('cache_enabled has no effect with '
                          'on_extraction=print — disabling the cache')
            args['cache_enabled'] = False
    if args.get('cache_l2_dir') is not None:
        # the shared tier rides on the cache: without a local L1 store
        # there is nothing to tier
        args['cache_l2_dir'] = str(args['cache_l2_dir'])
        if not args.get('cache_enabled'):
            raise ValueError('cache_l2_dir requires cache_enabled=true '
                             '(see docs/fleet.md)')

    # executable-store knobs (aot/): the dir coerces to str, the size
    # bound must be a non-negative int. ValueError, not assert —
    # survives `python -O` like every other knob rejection.
    if args.get('aot_enabled'):
        if not args.get('aot_dir'):
            raise ValueError('aot_enabled=true requires aot_dir '
                             '(see docs/serving.md "Zero cold start")')
    if args.get('aot_dir') is not None:
        args['aot_dir'] = str(args['aot_dir'])
    if args.get('aot_max_bytes') is not None:
        args['aot_max_bytes'] = int(args['aot_max_bytes'])
        if args['aot_max_bytes'] < 0:
            raise ValueError('aot_max_bytes must be >= 0 or null; '
                             f'got {args["aot_max_bytes"]}')
    if args.get('aot_l2_dir') is not None:
        args['aot_l2_dir'] = str(args['aot_l2_dir'])
        if not args.get('aot_enabled'):
            raise ValueError('aot_l2_dir requires aot_enabled=true '
                             '(see docs/fleet.md)')

    # feature-index knobs (index/): the ingest worker tails the CACHE
    # manifest, so the index requires the cache; geometry knobs must be
    # positive ints (they size compiled programs). ValueError, not
    # assert — survives `python -O`.
    if args.get('index_enabled'):
        if not args.get('cache_enabled'):
            raise ValueError('index_enabled=true requires '
                             'cache_enabled=true — the index ingests '
                             'published cache objects '
                             '(see docs/feature_index.md)')
    if args.get('index_dir') is not None:
        args['index_dir'] = str(args['index_dir'])
    for key in ('index_shard_rows', 'index_query_block', 'index_k_max'):
        if args.get(key) is not None:
            args[key] = int(args[key])
            if args[key] < 1:
                raise ValueError(f'{key} must be >= 1; got {args[key]}')
    if args.get('index_poll_s') is not None:
        args['index_poll_s'] = float(args['index_poll_s'])
        if args['index_poll_s'] <= 0:
            raise ValueError('index_poll_s must be > 0 (seconds between '
                             'ingest polls when caught up); got '
                             f'{args["index_poll_s"]}')

    # device-loop pipelining: the in-flight depth must be a positive int
    # (1 = synchronous; each extra unit pins one more output batch on
    # device). ValueError, not assert — survives `python -O`.
    if args.get('inflight') is not None:
        args['inflight'] = int(args['inflight'])
        if args['inflight'] < 1:
            raise ValueError(
                f'inflight must be >= 1 (1 = synchronous device loop); '
                f'got {args["inflight"]}')

    # mesh-sharded packed execution: device count must be a non-negative
    # int (0 = auto-detect, 1 = single device). data_parallel owns its
    # own mesh (per-extractor in-graph DP with batch rounding), so the
    # two knobs must not both claim the device set — data_parallel wins
    # as the legacy spelling and mesh_devices degrades with a warning.
    if args.get('mesh_devices') is not None:
        args['mesh_devices'] = int(args['mesh_devices'])
        if args['mesh_devices'] < 0:
            raise ValueError(
                'mesh_devices must be >= 0 (0 = auto-detect local '
                f'devices, 1 = single device); got {args["mesh_devices"]}')
        if args['mesh_devices'] != 1 and args.get('data_parallel'):
            warnings.warn(
                'mesh_devices and data_parallel both requested — '
                'data_parallel already owns the device mesh, so '
                'mesh_devices is ignored (running mesh_devices=1)')
            args['mesh_devices'] = 1

    # decode-farm knobs (farm/): worker count and per-worker SHM ring
    # size must be positive ints. ValueError, not assert — survives -O.
    if args.get('decode_workers') is not None:
        args['decode_workers'] = int(args['decode_workers'])
        if args['decode_workers'] < 1:
            raise ValueError(
                f'decode_workers must be >= 1 (1 = serial in-process '
                f'decode; null = decode lanes from the cores); '
                f'got {args["decode_workers"]}')
    if args.get('decode_farm_ring_mb') is not None:
        args['decode_farm_ring_mb'] = int(args['decode_farm_ring_mb'])
        if args['decode_farm_ring_mb'] < 1:
            raise ValueError(
                'decode_farm_ring_mb must be >= 1 (MiB per worker ring); '
                f'got {args["decode_farm_ring_mb"]}')

    # flight-recorder knobs (obs/): paths coerce to str; the ring-buffer
    # bound must be a positive int or the recorder silently records nothing
    for key in ('trace_out', 'manifest_out'):
        if args.get(key) is not None:
            args[key] = str(args[key])
    if args.get('trace_capacity') is not None:
        args['trace_capacity'] = int(args['trace_capacity'])
        if args['trace_capacity'] < 1:
            raise ValueError('trace_capacity must be >= 1; got '
                             f'{args["trace_capacity"]}')

    # vft-flight knobs (obs/blackbox.py, obs/watchdog.py): the dump dir
    # coerces to str, the size cap and stall deadline must be positive
    # (ValueError, not assert — survives `python -O`)
    if args.get('postmortem_dir') is not None:
        args['postmortem_dir'] = str(args['postmortem_dir'])
    if args.get('postmortem_max_bytes') is not None:
        args['postmortem_max_bytes'] = int(args['postmortem_max_bytes'])
        if args['postmortem_max_bytes'] < 1:
            raise ValueError('postmortem_max_bytes must be >= 1; got '
                             f'{args["postmortem_max_bytes"]}')
    if args.get('watchdog_stall_s') is not None:
        args['watchdog_stall_s'] = float(args['watchdog_stall_s'])
        if args['watchdog_stall_s'] <= 0:
            raise ValueError('watchdog_stall_s must be > 0 (seconds '
                             'without a stage advance before a stall '
                             f'trips); got {args["watchdog_stall_s"]}')

    # vft-scope SLO knobs (obs/slo.py): a latency objective is a positive
    # deadline; availability is a success-rate target strictly inside
    # (0, 1) — 1.0 means a zero error budget and every failure divides
    # by it
    if args.get('slo_latency_p99_s') is not None:
        args['slo_latency_p99_s'] = float(args['slo_latency_p99_s'])
        if args['slo_latency_p99_s'] <= 0:
            raise ValueError('slo_latency_p99_s must be > 0 (the p99 '
                             'latency objective in seconds); got '
                             f'{args["slo_latency_p99_s"]}')
    if args.get('slo_availability') is not None:
        args['slo_availability'] = float(args['slo_availability'])
        if not 0 < args['slo_availability'] < 1:
            raise ValueError('slo_availability must be in (0, 1), e.g. '
                             f'0.999; got {args["slo_availability"]}')

    assert args.get('file_with_video_paths') or args.get('video_paths'), \
        '`video_paths` or `file_with_video_paths` must be specified'
    filenames = [Path(p).stem for p in form_list_from_user_input(
        args.get('video_paths'), args.get('file_with_video_paths'), to_shuffle=False)]
    assert len(filenames) == len(set(filenames)), \
        'Non-unique video filenames (stems collide in the flat output dir)'
    assert os.path.relpath(str(args['output_path'])) != os.path.relpath(str(args['tmp_path'])), \
        'The same path for out & tmp'

    ft = args.get('feature_type')
    if args.get('show_pred') and ft == 'vggish':
        warnings.warn('Showing class predictions is not implemented '
                      'for VGGish')
    if args.get('data_parallel'):
        from video_features_tpu.registry import DATA_PARALLEL_FEATURES
        if ft not in DATA_PARALLEL_FEATURES:
            warnings.warn(
                f'data_parallel is not implemented for {ft} — running '
                'single-device (scale out with multihost=true / sharded '
                'worklists instead)')
            args['data_parallel'] = False
    if args.get('pack_across_videos'):
        from video_features_tpu.registry import PACKED_FEATURES
        # warnings.warn (→ stderr), NOT print: with on_extraction=print the
        # features themselves go to stdout and a WARNING line interleaved
        # there breaks downstream parsers of the feature stream
        if ft not in PACKED_FEATURES:
            warnings.warn(
                f'pack_across_videos is not implemented for {ft} — running '
                'the per-video loop')
            args['pack_across_videos'] = False
        elif args.get('show_pred'):
            # show_pred is a per-video debug surface (it narrates windows in
            # video order); a packed batch interleaves videos
            warnings.warn(
                'show_pred is incompatible with pack_across_videos — '
                'running the per-video loop')
            args['pack_across_videos'] = False
    if ft == 'i3d' and args.get('stack_size') is not None:
        assert args['stack_size'] >= 10, (
            f'I3D does not support inputs shorter than 10 timestamps. '
            f'You have: {args["stack_size"]}')
    if ft == 'pwc' or (ft == 'i3d' and args.get('flow_type') == 'pwc'):
        raise NotImplementedError('PWC flow is not supported; use flow_type=raft')
    if ft == 'timm':
        assert args.get('model_name') is not None, \
            'Please specify `model_name` for timm-style models; e.g. `vit_base_patch16_224`'
    if 'batch_size' in args:
        assert args['batch_size'] is not None, \
            f'Please specify `batch_size`. It is {args["batch_size"]} now'
    if 'extraction_fps' in args and 'extraction_total' in args:
        assert not (args['extraction_fps'] is not None and args['extraction_total'] is not None), \
            '`extraction_fps` and `extraction_total` are mutually exclusive'

    # Append <feature_type>[/<model_name>] to output & tmp paths ('/' → '_').
    subs = [ft] if ft else []
    if args.get('model_name') is not None:
        subs.append(str(args['model_name']))
    out, tmp = str(args['output_path']), str(args['tmp_path'])
    for p in subs:
        out = os.path.join(out, p.replace('/', '_'))
        tmp = os.path.join(tmp, p.replace('/', '_'))
    args['output_path'] = out
    args['tmp_path'] = tmp


# -- serving (python -m video_features_tpu serve) ---------------------------

# Server-level knobs (everything else on the serve command line becomes a
# BASE OVERRIDE merged under every request's config — e.g. device=tpu
# allow_random_weights=true output_path=...). One flat namespace so the
# serve CLI stays the same dotlist as extraction.
SERVE_DEFAULTS: Dict[str, Any] = {
    # local JSON-lines endpoint (requests + metrics); port 0 = ephemeral,
    # printed at startup
    'serve_host': '127.0.0.1',
    'serve_port': 0,
    # admission control: max videos queued-or-in-flight across the server;
    # submits that would exceed it are REJECTED (backpressure), not queued
    'serve_queue_depth': 64,
    # warm-pool bound: distinct (feature_type, geometry, …) executables
    # kept resident; LRU-evicted (gracefully drained) beyond this
    'serve_warm_pool_size': 4,
    # arrival-lull flush: when a worker's request feed is idle this long
    # with windows still pooled, partial batches flush padded so a lone
    # request's tail latency is bounded by this + one device step
    'serve_idle_flush_s': 0.05,
    # liveness bound under CONTINUOUS traffic: even with the queue never
    # idle, partial geometry pools flush at least this often — a lone
    # odd-geometry request can't starve behind a stream of other
    # geometries (trade: more padded slots as this shrinks)
    'serve_max_batch_wait_s': 2.0,
    # default per-request deadline (seconds, null = none): requests whose
    # deadline passes before a video STARTS decoding expire unstarted
    'serve_default_timeout_s': None,
    # optional metrics mirror: the live metrics JSON is atomically
    # rewritten here on every request completion (scrape without a socket)
    'serve_metrics_path': None,
    # priority-class admission (protocol 'priority' field / ingress
    # tenant classes): 'batch' requests only see this fraction of
    # serve_queue_depth, so a saturated queue sheds batch before
    # interactive. 1.0 = no distinction.
    'serve_batch_shed_fraction': 0.5,
    # zero cold start (aot/; docs/serving.md "Zero cold start"): build
    # these warm-pool entries at BOOT, before the first request —
    # a list of 'family' or 'family@lane' specs (e.g.
    # '[resnet,resnet@bfloat16]'), each resolved against the base
    # overrides exactly like a cold submit. With aot_enabled=true in
    # the base overrides, an unchanged program set makes the boot
    # compile-free: every pre-warmed program LOADS from the executable
    # store (builds_loaded in pool stats) instead of compiling. null =
    # no pre-warm (today's behavior: the first request pays the build).
    'serve_prewarm': None,
    # -- ingress (ingress/; docs/ingress.md): the network front door ----
    # HTTP/1.1 + chunked endpoint port: null = DISABLED (loopback-only
    # server, today's behavior), 0 = ephemeral (printed at startup)
    'serve_ingress_port': None,
    'serve_ingress_host': '127.0.0.1',
    # API-key file (JSON/YAML: key → {tenant, priority, rate_rps, burst,
    # max_concurrent}) — REQUIRED when the ingress is enabled; there is
    # deliberately no anonymous mode on a network-facing endpoint
    'serve_ingress_auth_file': None,
    # request-body bound (MiB): oversized bodies get a structured
    # 413-style rejection instead of crashing (or OOMing) the reader
    'serve_ingress_max_body_mb': 64,
    # concurrent-connection bound: excess connects get an immediate 503
    'serve_ingress_max_connections': 64,
}


def split_serve_config(cli_args: Dict[str, Any]) -> Tuple[Config, Config]:
    """Split a serve-command dotlist into (server knobs, base overrides).

    ``serve_*`` keys must be known (a typo'd knob silently becoming a
    per-request override would be maddening to debug); everything else is
    merged under every request's per-feature config via ``load_config``.
    """
    serve, base = Config(SERVE_DEFAULTS), Config()
    for key, value in dict(cli_args).items():
        if key.startswith('serve_'):
            if key not in SERVE_DEFAULTS:
                raise ValueError(
                    f'Unknown serve option {key!r}. '
                    f'Known: {", ".join(sorted(SERVE_DEFAULTS))}')
            serve[key] = value
        else:
            base[key] = value
    for key in ('serve_queue_depth', 'serve_warm_pool_size'):
        serve[key] = int(serve[key])
        if serve[key] < 1:
            raise ValueError(f'{key} must be >= 1; got {serve[key]}')
    serve['serve_port'] = int(serve['serve_port'])
    for key in ('serve_idle_flush_s', 'serve_max_batch_wait_s'):
        serve[key] = float(serve[key])
        if serve[key] <= 0:
            raise ValueError(f'{key} must be > 0')
    if serve['serve_default_timeout_s'] is not None:
        serve['serve_default_timeout_s'] = \
            float(serve['serve_default_timeout_s'])
    if serve['serve_prewarm'] is not None:
        # one spec or a list of 'family[@lane]' specs; validated here so
        # a typo'd family fails the BOOT, not the first request
        specs = serve['serve_prewarm']
        if isinstance(specs, str):
            specs = [specs]
        if not isinstance(specs, (list, tuple)) or not all(
                isinstance(s, str) and s.strip() for s in specs):
            raise ValueError(
                "serve_prewarm must be a 'family[@lane]' spec or a list "
                f'of them (e.g. [resnet,resnet@bfloat16]); got '
                f'{serve["serve_prewarm"]!r}')
        specs = [s.strip() for s in specs]
        # validated against the SERVEABLE set, not KNOWN_FEATURE_TYPES:
        # a family without packed/serving support (vggish, raft) would
        # pass the build but occupy a pool slot no request can reach —
        # the same gate the submit path applies, moved to the boot
        from video_features_tpu.registry import PACKED_FEATURES
        for spec in specs:
            family = spec.split('@', 1)[0]
            # 'index' is the one non-extractor spec: it warms the
            # feature index's query program instead of a pool entry
            if family == 'index':
                continue
            if family not in PACKED_FEATURES:
                raise ValueError(
                    f'serve_prewarm names unknown or unserveable family '
                    f'{family!r} (serveable: index, '
                    f'{", ".join(sorted(PACKED_FEATURES))})')
        serve['serve_prewarm'] = specs
    serve['serve_batch_shed_fraction'] = \
        float(serve['serve_batch_shed_fraction'])
    if not (0 < serve['serve_batch_shed_fraction'] <= 1):
        raise ValueError('serve_batch_shed_fraction must be in (0, 1]; '
                         f'got {serve["serve_batch_shed_fraction"]}')
    if serve['serve_ingress_port'] is not None:
        serve['serve_ingress_port'] = int(serve['serve_ingress_port'])
        if not serve['serve_ingress_auth_file']:
            raise ValueError(
                'serve_ingress_port requires serve_ingress_auth_file '
                '(an API-key file; see docs/ingress.md) — the network '
                'front door has no anonymous mode')
    for key in ('serve_ingress_max_body_mb',
                'serve_ingress_max_connections'):
        serve[key] = int(serve[key])
        if serve[key] < 1:
            raise ValueError(f'{key} must be >= 1; got {serve[key]}')
    return serve, base


# -- fleet router (fleet/; docs/fleet.md) ------------------------------------
# Router-process knobs, NOT extraction config: the `fleet` command takes
# ONLY these (backends own their extraction/serve config), so unlike the
# *_DEFAULTS families above they never merge into per-feature args and
# carry no fingerprint/pool-key classification.
FLEET_DEFAULTS: Dict[str, Any] = {
    # static backend membership: a list of host:port serve daemons
    # (bare ports mean loopback — the simulation/test form). LIVENESS
    # is probed, not configured: unhealthy or draining hosts leave the
    # eligible set without a config change.
    'fleet_hosts': None,
    # the router's own loopback JSON-lines listener (0 = ephemeral)
    'fleet_port': 9310,
    'fleet_host': '127.0.0.1',
    # optional HTTP front door (ingress transport); null = loopback only
    'fleet_http_port': None,
    'fleet_http_host': '127.0.0.1',
    # API-key file for the HTTP front door (required when it's on —
    # same no-anonymous-mode policy as serve_ingress_auth_file)
    'fleet_auth_file': None,
    # health-probe cadence; the probe also reads each backend's
    # `draining` flag for drain-aware membership
    'fleet_probe_interval_s': 2.0,
    # failover bound: how many ring hosts one request may try
    'fleet_max_attempts': 3,
    # backoff between ring hosts (doubles per attempt, capped)
    'fleet_backoff_base_s': 0.05,
    # per-backend connect deadline on the request path
    'fleet_connect_timeout_s': 2.0,
    # virtual nodes per host on the consistent-hash ring
    'fleet_ring_replicas': 64,
    # fleet-level SLOs (obs/slo.py evaluated over the router's routed-
    # request families): always on at the router — /metrics is one
    # scrape target for the whole fleet, so the vft_slo_* gauges must
    # always render. Defaults are generous (video extraction is
    # minutes-scale); tighten per deployment.
    'fleet_slo_latency_p99_s': 30.0,
    'fleet_slo_availability': 0.999,
}


def split_fleet_config(cli_args: Dict[str, Any]) -> Tuple[Config, Config]:
    """Split a fleet-command dotlist into (router knobs, leftovers).

    Same typo discipline as :func:`split_serve_config`; leftovers are
    returned (not merged anywhere) so ``fleet_main`` can refuse them —
    the router forwards requests, it does not own extraction config.
    """
    fleet, extra = Config(FLEET_DEFAULTS), Config()
    for key, value in dict(cli_args).items():
        if key.startswith('fleet_'):
            if key not in FLEET_DEFAULTS:
                raise ValueError(
                    f'Unknown fleet option {key!r}. '
                    f'Known: {", ".join(sorted(FLEET_DEFAULTS))}')
            fleet[key] = value
        else:
            extra[key] = value
    if fleet['fleet_hosts'] is not None:
        hosts = fleet['fleet_hosts']
        if isinstance(hosts, (str, int)):
            hosts = [hosts]
        if not isinstance(hosts, (list, tuple)) or not hosts:
            raise ValueError(
                'fleet_hosts must be a host:port (or bare-port) list, '
                f'e.g. [127.0.0.1:9301,127.0.0.1:9302]; got '
                f'{fleet["fleet_hosts"]!r}')
        fleet['fleet_hosts'] = [str(h) for h in hosts]
    for key in ('fleet_port', 'fleet_max_attempts', 'fleet_ring_replicas'):
        fleet[key] = int(fleet[key])
    if fleet['fleet_port'] < 0:
        raise ValueError(f'fleet_port must be >= 0; got {fleet["fleet_port"]}')
    for key in ('fleet_max_attempts', 'fleet_ring_replicas'):
        if fleet[key] < 1:
            raise ValueError(f'{key} must be >= 1; got {fleet[key]}')
    for key in ('fleet_probe_interval_s', 'fleet_backoff_base_s',
                'fleet_connect_timeout_s', 'fleet_slo_latency_p99_s'):
        fleet[key] = float(fleet[key])
        if fleet[key] <= 0:
            raise ValueError(f'{key} must be > 0; got {fleet[key]}')
    fleet['fleet_slo_availability'] = float(fleet['fleet_slo_availability'])
    if not 0 < fleet['fleet_slo_availability'] < 1:
        raise ValueError('fleet_slo_availability must be in (0, 1), '
                         f'e.g. 0.999; got {fleet["fleet_slo_availability"]}')
    if fleet['fleet_http_port'] is not None:
        fleet['fleet_http_port'] = int(fleet['fleet_http_port'])
        if not fleet['fleet_auth_file']:
            raise ValueError(
                'fleet_http_port requires fleet_auth_file (an API-key '
                'file; see docs/ingress.md) — the fleet front door has '
                'no anonymous mode either')
    return fleet, extra


def form_list_from_user_input(
    video_paths: Union[str, List[str], None] = None,
    file_with_video_paths: Optional[str] = None,
    to_shuffle: bool = True,
) -> List[str]:
    """Normalize user-specified paths into a list (reference utils/utils.py:138-178).

    A file lists one path per line (blank lines dropped). Shuffling randomizes
    the work order so independent shared-filesystem workers rarely collide on
    the same video — the reference's whole multi-worker story (:151-152).
    """
    if file_with_video_paths is None:
        if video_paths is None:
            path_list: List[str] = []
        elif isinstance(video_paths, str):
            path_list = [video_paths]
        else:
            path_list = [str(p) for p in video_paths]
    else:
        with open(file_with_video_paths) as f:
            path_list = [line.strip() for line in f if line.strip()]

    for path in path_list:
        # '.live' paths are VIRTUAL — live-session pseudo-identities
        # (serve/server.submit_live); nothing exists (or should) at them
        if not path.endswith('.live') and not Path(path).exists():
            # obs.events (→ stderr), not print or warnings.warn: the
            # feature stream owns stdout, and this also runs inside
            # serve request handling — where the default warnings
            # filter would dedupe a repeated bad path to ONE report per
            # process, hiding every later tenant's mistake
            import logging

            from video_features_tpu.obs.events import event
            event(logging.WARNING, 'path does not exist',
                  video=str(path))

    if to_shuffle:
        random.shuffle(path_list)
    return path_list
