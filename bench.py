"""Flagship benchmark: fused I3D two-stream (RAFT-backed) clips/sec/chip.

One stack window (stack_size consecutive frames → RAFT flow → I3D rgb ∥
I3D flow → (2048,) feature) is one "clip" — the unit of the north-star
metric (BASELINE.md: Kinetics-400 val clips/sec/chip). The reference fork's
only timing datapoint is ~4 s/video at stack 16 / step 16 @ 25 fps
(reference Test3.ipynb cells 0,2) ≈ 3.75 clips/s on its unspecified GPU;
``vs_baseline`` is measured against that.

Two rungs, both at a PARITY-GRADE precision (the metric name stamps it):

  * ``e2e`` — video file → decoded frames → device → features, the
    pipeline a user actually runs (native decoder when built, cv2
    otherwise; prefetch + overlapped H2D on).
  * ``ingraph_cli_geom`` — the HEADLINE: the fused graph on
    device-resident batches at the geometry the CLI actually runs
    (short-side-256 decode → 256×340 frames, RAFT over the full padded
    frame, 224 crop in-graph — like the reference pipeline behind its
    3.75 clips/s anecdote), timed INSIDE one jit call (``lax.scan`` over
    distinct input batches, result fetched) — in-graph iteration
    amortizes the per-call dispatch, and the value fetch is the sync
    point. A secondary 224² crop-first rung
    (``ingraph_*_224px``) keeps cross-round comparability with the
    round-3/4 headline geometry.

Per-family rungs (s3d / resnet50 / clip / vggish / standalone raft at
native flow resolution — the production steps from
tools/family_precision_study.py) record every BASELINE config's measured
rate in ``rungs`` at the same precision stamp.

The corpus-scale trio: ``worklist_clips_per_sec`` runs the per-video
outer loop over a multi-video worklist (resume contract + prefetch live),
``worklist_packed_clips_per_sec`` runs the SAME worklist batch-major
(``pack_across_videos=true`` — device batches fill across video
boundaries, parallel/packing.py) with the device loop pinned SYNCHRONOUS
(``inflight=1``: D2H after every dispatch), and
``worklist_async_clips_per_sec`` repeats it with the deferred-D2H async
loop (``inflight=2``: batch k-1's readback + scatter + save overlap the
device computing batch k) — the packed/async delta isolates the
readback-overlap win, every rung records its ``inflight`` depth, and
``worklist_packed_batch_occupancy`` records how full the compiled step
actually ran. ``worklist_mesh_clips_per_sec`` repeats the async rung
with the device loop mesh-sharded over N chips (``mesh_devices=N``:
batches plan at capacity × N and shard over the data axis,
parallel/mesh.py) — the pod-scale rung, expected to scale
near-linearly with ``worklist_mesh_devices``.

The serving rung (``serve_*``): the same worklist submitted as dynamic
per-video requests over the warm-pool daemon's socket (serve/) —
sustained warm clips/sec vs the cold-start rate a one-shot CLI pays,
plus p50/p99 request latency and the warm-pool hit rate (asserted > 0,
or the "warm" number is mislabeled). ``BENCH_SERVE=0/1`` overrides the
accelerator-only default.

The cache rung (``cache_*``): the same worklist run twice with the
content-addressed feature cache on (cache/) — cold clips/s with publish
overhead vs warm-hit clips/s (pure O(read) materialization, no decode or
inference), plus per-video hit latency and the store hit rate (asserted
to cover the worklist). ``BENCH_CACHE=0/1`` overrides the
accelerator-only default.

The zero-cold-start rung (``serve_boot_first_feature_s`` /
``serve_boot_first_feature_cold_s`` / ``aot_hit_rate``): boot-to-first-
feature wall time for a pre-warmed daemon (``serve_prewarm`` +
``aot_enabled``, aot/) against a cold vs warm persistent executable
store — the warm boot loads serialized executables instead of compiling
(``builds_compiled == 0`` asserted). ``BENCH_AOT=0/1`` overrides the
accelerator-only default.

The fleet rung (``fleet_warm_clips_per_sec`` / ``fleet_cache_hit_rate``
/ ``fleet_cold_host_first_feature_s`` / ``fleet_metrics_scrape_ms``):
two daemons sharing an L2 feature tier and an AOT artifact tier behind
the content-hash router (fleet/) — host A extracts cold and publishes;
host B boots with empty local stores, pre-warms compile-free off the
artifact tier (``builds_compiled == 0`` asserted), and serves A's
features from the shared L2 without decoding; the warm rate re-serves
the worklist through the router across both hosts, and the scrape
rung times the router's fleet-aggregated ``metrics_prom`` (vft-scope —
the cost of the one-scrape-target design). ``BENCH_FLEET=0/1``
overrides the accelerator-only default.

The precision-ladder rungs (``*_bf16_*`` / ``*_int8_*``): the bf16 fast
lane and the int8 weight lane each get a framewise in-graph rung, a
packed-worklist rung and a serve-warm rung vs their fp32 sibling at
otherwise identical knobs — and EVERY ladder rung records its measured
``*_max_abs_error`` / ``*_rel_l2_error`` beside the speedup (never a
speedup without its cost; the rel-L2 numbers are checkable against the
pinned ``BF16_REL_L2_BOUNDS`` / ``INT8_REL_L2_BOUNDS``). The int8 serve
rung additionally parks the WHOLE ladder — fp32, bf16 and int8 warm
entries — in one daemon (pool size asserted ≥ 3). ``BENCH_BF16`` /
``BENCH_BF16_SERVE`` / ``BENCH_INT8`` / ``BENCH_INT8_SERVE`` override
the accelerator-only defaults.

Default precision is 'mixed' (ops/precision.py): ambient 3-pass bf16 with
the drift-tolerant sub-graphs on 1-pass — measured ≤1e-3 feature drift vs
float32 on the fused path (tools/precision_study.py), i.e. the fastest
setting that still meets the reference-parity bar. BENCH_PRECISION
overrides (e.g. 'highest' for the float32 ladder rung, 'default' for the
no-parity speed ceiling).

The SECOND north-star model, R(2+1)D (BASELINE.md names both), gets its
own in-graph + e2e rungs (``r21d_ingraph_*`` / ``r21d_e2e_*``) at the
same precision stamp; its ladder lives in tools/r21d_precision_study.py
(at 'mixed' the drift vs float32 is 2.0e-4 — parity-grade).

Prints exactly ONE JSON line (all diagnostics — random-weights warnings,
decoder chatter, cache notes — go to stderr). The headline value is the
in-graph rung by policy; every measured rung is recorded in ``rungs``,
and ``BENCH_MODE=e2e`` promotes the e2e rung to headline.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

# Reference anecdote: ~4 s/video, ~15 stacks/video at stack 16 step 16 @25fps
BASELINE_CLIPS_PER_SEC = 3.75


def bench_ingraph(jax, precision, pins, device, platform, params,
                  stack, h, w, batch, iters):
    """Device-only fused-graph clips/sec (in-graph scan, value fetch) at
    an arbitrary frame geometry.

    The CLI-geometry rung feeds the decode geometry the real pipeline
    produces (short-side 256 → the sample's 256×340; RAFT sees the FULL
    frame padded to /8, crop 224 happens in-graph after flow — reference
    models/i3d/extract_i3d.py:38-62,143-164). The square-224 rung is the
    crop-first ceiling the pipeline never runs; it stays as a secondary
    rung only."""
    import jax.numpy as jnp
    from jax import lax

    from video_features_tpu.extract.i3d import fused_two_stream_step
    from video_features_tpu.models import raft as raft_model

    rng = np.random.RandomState(0)
    # uint8 device residents, cast in-graph: what production ships (the
    # extractors keep frames uint8 until on device), and 4x less HBM for
    # the iters-deep input buffer — the fp32 buffer pushed the v5e-8's
    # 16G HBM over capacity at CLI geometry
    all_stacks = jax.device_put(
        rng.randint(0, 255, size=(iters, batch, stack + 1, h, w, 3))
        .astype(np.uint8), device)
    pads = tuple(raft_model.pad_to_multiple(
        np.zeros((1, h, w, 1), np.float32))[1])
    kwargs = dict(pads=pads, streams=('rgb', 'flow'),
                  crop_size=min(224, h, w), platform=platform, pins=pins)

    def chained(p, xs):
        # per-stream checksums double as the finiteness guard (any NaN/Inf
        # element propagates into its stream's sum) without compiling a
        # second full-graph executable
        def body(acc, stacks):
            with jax.default_matmul_precision(precision):
                o = fused_two_stream_step(p, jnp.asarray(stacks, jnp.float32),
                                          **kwargs)
            return {k: acc[k] + o[k].sum() for k in acc}, None
        acc, _ = lax.scan(
            body, {k: jnp.float32(0) for k in kwargs['streams']}, xs)
        return acc

    jitted = jax.jit(chained)
    warm = jax.tree_util.tree_map(float, jitted(params, all_stacks))
    for s, v in warm.items():                      # compile + warmup + guard
        assert np.isfinite(v), f'{s} checksum not finite'

    t0 = time.perf_counter()
    checksum = jax.tree_util.tree_map(float, jitted(params, all_stacks))
    elapsed = time.perf_counter() - t0             # value fetch = real time
    assert all(np.isfinite(v) for v in checksum.values()), checksum
    return batch * iters / elapsed


def bench_family_ingraph(jax, ambient, device, init_fn, step_fn,
                         batch_shape, input_map, count_per_batch, iters,
                         transplant):
    """One family's device-only in-graph rate (scan + checksum fetch) —
    the shared timing harness for every per-family rung, fed by
    tools/family_precision_study._family_specs so bench.py and the
    precision-ladder tool measure the identical production step."""
    from jax import lax

    params = jax.device_put(transplant(init_fn()), device)
    rng = np.random.RandomState(0)
    raw = rng.randint(0, 255,
                      size=(iters,) + batch_shape).astype(np.float32)
    if input_map is not None:
        raw = input_map(raw).astype(np.float32)
    frames = jax.device_put(raw, device)

    def chained(p, xs):
        def body(acc, batch):
            with jax.default_matmul_precision(ambient):
                return acc + step_fn(p, batch).sum(), None
        acc, _ = lax.scan(body, jax.numpy.float32(0), xs)
        return acc

    jitted = jax.jit(chained)
    assert np.isfinite(float(jitted(params, frames)))   # compile + guard
    t0 = time.perf_counter()
    checksum = float(jitted(params, frames))
    elapsed = time.perf_counter() - t0
    assert np.isfinite(checksum)
    count = (count_per_batch if count_per_batch is not None
             else batch_shape[0])
    return count * iters / elapsed


def bench_serve(precision: str, batch: int, stack: int, tmp_dir: str,
                platform: str, wl_paths: list) -> dict:
    """The serving rung: sustained clips/sec + p50/p99 request latency
    through the warm-pool service (serve/), against the SAME worklist the
    cold-CLI rungs measure.

    Two passes of per-video requests over the live socket: the COLD pass
    pays transplant + compile inside its first request (what a cold CLI
    invocation pays every time); the WARM pass is the steady state a
    resident server actually serves — its pool hit rate must be > 0 or
    the measurement is mislabeled (asserted). Fresh output roots per pass
    keep the resume contract from turning pass 2 into an all-skip no-op.
    """
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer
    from video_features_tpu.utils.output import make_path

    base = {
        'device': platform, 'precision': precision,
        'stack_size': stack, 'step_size': stack, 'batch_size': batch,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'tmp_path': os.path.join(tmp_dir, 'serve_tmp'),
    }
    server = ExtractionServer(
        base_overrides=base,
        queue_depth=max(64, 4 * len(wl_paths))).start()
    try:
        client = ServeClient(port=server.port)

        def one_pass(tag):
            out_root = os.path.join(tmp_dir, f'serve_out_{tag}')
            t0 = time.perf_counter()
            # one request per video: dynamic arrivals, packed across
            # requests by the server — NOT one batch-submitted worklist
            rids = [client.submit('i3d', [p],
                                  overrides={'output_path': out_root})
                    for p in wl_paths]
            for rid in rids:
                st = client.wait(rid, timeout_s=900)
                assert st['state'] == 'done', f'serve pass {tag}: {st}'
            return out_root, time.perf_counter() - t0

        _, cold_s = one_pass('cold')
        warm_root, warm_s = one_pass('warm')

        clips = 0
        for p in wl_paths:
            # sanity_check appends <feature_type> to each request's root
            arr = np.load(make_path(os.path.join(warm_root, 'i3d'),
                                    p, 'rgb', '.npy'))
            clips += arr.shape[0]
        assert clips > 0, 'serve warm pass produced no clips'
        m = client.metrics()
        assert m['warm_pool']['hit_rate'] > 0, \
            'warm pass never hit the warm pool — rung mislabeled'
        return {
            'serve_clips_per_sec': round(clips / warm_s, 3),
            'serve_cold_clips_per_sec': round(clips / cold_s, 3),
            'serve_p50_latency_s': m['latency']['p50_s'],
            'serve_p99_latency_s': m['latency']['p99_s'],
            'serve_warm_hit_rate': round(m['warm_pool']['hit_rate'], 4),
        }
    finally:
        server.drain(wait=True, grace_s=120)


def bench_serve_ingress(tmp_dir: str, platform: str,
                        wl_paths: list) -> dict:
    """The ingress rung (ingress/): the HTTP front door's overhead vs
    the loopback socket, plus one real segment query driven through it.

    One resnet segment request goes through the whole network path
    (auth → quota → admission → windower range filter → saved files),
    then the SAME completed request is status-polled N times over each
    surface — ingress ``GET /v1/requests/<id>`` vs loopback ``status``
    — one connection per call on both sides (the ingress speaks one
    request per connection by design, so the loopback comparator must
    pay its connect too or the diff measures connection reuse, not the
    HTTP layer). Reports p50/p99 RTT for both.
    """
    import http.client

    from video_features_tpu.ingress.auth import ApiKeyAuth, Tenant
    from video_features_tpu.ingress.gateway import IngressGateway
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer

    base = {
        'device': platform, 'model_name': 'resnet18', 'batch_size': 8,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'tmp_path': os.path.join(tmp_dir, 'ing_tmp'),
        'output_path': os.path.join(tmp_dir, 'ing_out'),
    }
    server = ExtractionServer(base_overrides=base, queue_depth=64).start()
    gateway = IngressGateway(
        server, auth=ApiKeyAuth({'bench': Tenant('bench')})).start()
    try:
        def api(method, path, body=None):
            c = http.client.HTTPConnection('127.0.0.1', gateway.port,
                                           timeout=600)
            c.request(method, path,
                      body=json.dumps(body) if body is not None else None,
                      headers={'Authorization': 'Bearer bench'})
            r = c.getresponse()
            out = json.loads(r.read())
            c.close()
            assert r.status == 200, (r.status, out)
            return out

        # one real segment query end-to-end through the front door
        doc = api('POST', '/v1/extract', {
            'feature_type': 'resnet', 'video_paths': [wl_paths[0]],
            'range': [0.0, 0.4]})
        rid = doc['request_id']
        while api('GET', f'/v1/requests/{rid}')['state'] == 'running':
            time.sleep(0.05)

        n = int(os.environ.get('BENCH_INGRESS_RTT_N', '100'))
        ing_rtts, loop_rtts = [], []
        for _ in range(n):
            t0 = time.perf_counter()
            api('GET', f'/v1/requests/{rid}')
            ing_rtts.append(time.perf_counter() - t0)
        client = ServeClient(port=server.port)
        for _ in range(n):
            t0 = time.perf_counter()
            client.status(rid)
            loop_rtts.append(time.perf_counter() - t0)

        def pct(xs, p):
            return round(float(np.percentile(xs, p)), 6)

        return {
            'serve_ingress_p50_latency_s': pct(ing_rtts, 50),
            'serve_ingress_p99_latency_s': pct(ing_rtts, 99),
            'serve_ingress_loopback_p50_latency_s': pct(loop_rtts, 50),
            'serve_ingress_loopback_p99_latency_s': pct(loop_rtts, 99),
        }
    finally:
        server.drain(wait=True, grace_s=120)


def bench_aot_boot(tmp_dir: str, platform: str, wl_paths: list) -> dict:
    """The zero-cold-start rung (aot/): boot-to-first-feature wall time
    for a pre-warmed daemon (``serve_prewarm`` + ``aot_enabled``)
    against a COLD executable store — the boot pays XLA compiles and
    publishes them — vs a WARM store, where every pre-warmed program
    LOADS (PJRT deserialization) and the boot must be compile-free
    (``builds_compiled == 0`` asserted, or the rung is mislabeled).
    Both numbers cover ExtractionServer construction, pre-warm, and one
    request completing end to end — the latency a deploy/restart
    actually adds before the first feature lands."""
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer

    base = {
        'device': platform, 'model_name': 'resnet18', 'batch_size': 8,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'tmp_path': os.path.join(tmp_dir, 'aot_tmp'),
        'aot_enabled': True, 'aot_dir': os.path.join(tmp_dir, 'aot_store'),
    }

    def boot(tag):
        t0 = time.perf_counter()
        server = ExtractionServer(base_overrides=base,
                                  queue_depth=64).start()
        try:
            server.prewarm(['resnet'])
            client = ServeClient(port=server.port)
            rid = client.submit('resnet', [wl_paths[0]], overrides={
                'output_path': os.path.join(tmp_dir, f'aot_out_{tag}')})
            st = client.wait(rid, timeout_s=900)
            assert st['state'] == 'done', f'aot boot {tag}: {st}'
            first_s = time.perf_counter() - t0
            m = client.metrics()
        finally:
            server.drain(wait=True, grace_s=120)
        return first_s, m

    cold_s, _ = boot('cold')
    warm_s, m_warm = boot('warm')
    pool = m_warm['warm_pool']
    assert pool['builds_compiled'] == 0 and pool['builds_loaded'] >= 1, \
        f'warm-store boot was not compile-free — rung mislabeled: {pool}'
    # per-boot program hit rate (the store counters are process-global
    # and would fold the cold boot's misses in): loaded / all programs
    # this boot resolved
    aot = m_warm['aot']
    programs = aot['programs_loaded'] + aot['programs_compiled']
    return {
        'serve_boot_first_feature_s': round(warm_s, 3),
        'serve_boot_first_feature_cold_s': round(cold_s, 3),
        'aot_hit_rate': round(aot['programs_loaded'] / max(programs, 1),
                              4),
    }


def bench_index(tmp_dir: str, platform: str, wl_paths: list) -> dict:
    """The feature-index rung (index/): a daemon with ``index_enabled``
    extracts a small worklist, the ingest worker folds the published
    cache objects in (lag polled to zero), then every indexed row is
    queried back through the loopback ``search`` command. Reports
    sustained queries/sec and recall@10 — the search is EXACT (batched
    matmul + top-k over every shard), so each row's own identity must
    sit in its top-10 at cosine 1.0 and recall pins to 1.0; anything
    less is an indexing bug, not a quality tradeoff."""
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer

    cache_dir = os.path.join(tmp_dir, 'index_cache')
    base = {
        'device': platform, 'model_name': 'resnet18', 'batch_size': 8,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'tmp_path': os.path.join(tmp_dir, 'index_tmp'),
        'output_path': os.path.join(tmp_dir, 'index_out'),
        'cache_enabled': True, 'cache_dir': cache_dir,
        'index_enabled': True,
    }
    server = ExtractionServer(base_overrides=base, queue_depth=64).start()
    try:
        client = ServeClient(port=server.port)
        rid = client.submit('resnet', wl_paths[:2])
        st = client.wait(rid, timeout_s=900)
        assert st['state'] == 'done', f'index rung extract: {st}'
        deadline = time.time() + 120
        while True:
            idx = client.index_status()
            if idx['rows_live'] > 0 and idx['ingest_lag_bytes'] == 0:
                break
            assert time.time() < deadline, f'ingest never converged: {idx}'
            time.sleep(0.05)
        # query every indexed row back (bounded) through the loopback
        # command — the full wire + merge path, not just the matmul
        from video_features_tpu.index.service import resolve_index_dir
        from video_features_tpu.index.shards import IndexStore
        store = IndexStore.get(resolve_index_dir(base))
        rows = []
        for arr, _mask, metas in store.shard_views(
                store.group_for('resnet')):
            rows.extend((arr[i], m) for i, m in enumerate(metas)
                        if m is not None)
        n = min(len(rows), int(os.environ.get('BENCH_INDEX_QUERIES',
                                              '32')))
        assert n > 0, 'index rung: no rows indexed'
        self_hits = 0
        t0 = time.perf_counter()
        for vec, m in rows[:n]:
            out = client.search(family='resnet',
                                vector=[float(x) for x in vec], k=10)
            self_hits += any(h['key'] == m['key']
                             and h['t_ms'] == m['t_ms']
                             for h in out['hits'])
        wall = time.perf_counter() - t0
        return {
            'index_queries_per_sec': round(n / wall, 3),
            'index_recall_at_10': round(self_hits / n, 4),
            'index_rows_live': idx['rows_live'],
        }
    finally:
        server.drain(wait=True, grace_s=120)


def bench_fleet(tmp_dir: str, platform: str, wl_paths: list) -> dict:
    """The fleet rung (fleet/): two daemons sharing an L2 feature tier
    and an AOT artifact tier behind the content-hash router
    (fleet/router.py). Host A extracts the worklist cold — compiling
    and publishing executables to the artifact tier and features to
    the L2. Host B then boots with EMPTY local stores: its pre-warm
    must be compile-free (``builds_compiled == 0`` asserted — every
    program pulls from the artifact tier) and its first feature is the
    peer's L2 publish, served without decoding (admission-time
    ``cached`` status asserted). The warm number is the fleet-wide
    re-serve rate through the router, one submit per video so the ring
    spreads them across both hosts — every video must come back
    ``cached`` or the rung is mislabeled."""
    from video_features_tpu.fleet.router import FleetRouter
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer
    from video_features_tpu.utils.output import make_path

    shared = os.path.join(tmp_dir, 'fleet_shared')

    def host_overrides(tag):
        return {
            'device': platform, 'model_name': 'resnet18', 'batch_size': 8,
            'allow_random_weights': True, 'on_extraction': 'save_numpy',
            'tmp_path': os.path.join(tmp_dir, f'fleet_tmp_{tag}'),
            'cache_enabled': True,
            'cache_dir': os.path.join(tmp_dir, f'fleet_l1_{tag}'),
            'cache_l2_dir': os.path.join(shared, 'features'),
            'aot_enabled': True,
            'aot_dir': os.path.join(tmp_dir, f'fleet_aot_{tag}'),
            'aot_l2_dir': os.path.join(shared, 'artifacts'),
        }

    host_a = ExtractionServer(base_overrides=host_overrides('a'),
                              queue_depth=64).start()
    host_b = None
    router = None
    try:
        # cold pass: A owns the whole worklist, compiles, publishes
        ca = ServeClient(port=host_a.port)
        rid = ca.submit('resnet', wl_paths, overrides={
            'output_path': os.path.join(tmp_dir, 'fleet_out_cold')})
        st = ca.wait(rid, timeout_s=900)
        assert st['state'] == 'done', f'fleet cold pass: {st}'

        # cold-host boot-to-first-feature: B joins with empty local
        # stores, pulls A's executables (zero compiles) and serves A's
        # first video from the shared L2 with zero decode
        t0 = time.perf_counter()
        host_b = ExtractionServer(base_overrides=host_overrides('b'),
                                  queue_depth=64).start()
        report = host_b.prewarm(['resnet'])
        assert report['errors'] == [], f'fleet cold-host prewarm: {report}'
        cb = ServeClient(port=host_b.port)
        rid_b = cb.submit('resnet', wl_paths[:1], overrides={
            'output_path': os.path.join(tmp_dir, 'fleet_out_boot')})
        st_b = cb.wait(rid_b, timeout_s=300)
        cold_host_s = time.perf_counter() - t0
        assert st_b['state'] == 'done', f'fleet cold host: {st_b}'
        assert st_b['videos'][wl_paths[0]] == 'cached', \
            f'cold host decoded instead of serving the peer L2: {st_b}'
        wm = host_b.metrics()['warm_pool']
        assert wm['builds_compiled'] == 0, \
            f'cold host compiled — artifact tier missed: {wm}'

        # warm fleet pass: one submit per video through the router, so
        # the ring spreads the worklist across both hosts
        router = FleetRouter(
            [f'127.0.0.1:{host_a.port}', f'127.0.0.1:{host_b.port}'],
            port=0, probe_interval_s=30.0).start()
        cr = ServeClient(port=router.port)
        warm_out = os.path.join(tmp_dir, 'fleet_out_warm')
        t0 = time.perf_counter()
        rids = [cr.submit('resnet', [p],
                          overrides={'output_path': warm_out})
                for p in wl_paths]
        for p, r in zip(wl_paths, rids):
            st = cr.wait(r, timeout_s=300)
            assert st['state'] == 'done', f'fleet warm pass: {st}'
            assert st['videos'][p] == 'cached', \
                f'warm pass missed the shared tier — rung mislabeled: {st}'
        warm_s = time.perf_counter() - t0

        # vft-scope: the aggregated scrape is the fleet's one metrics
        # hop — time it end-to-end (scrape both backends under the
        # probe deadline, relabel host=, merge, SLO tick)
        t0 = time.perf_counter()
        prom = cr.metrics_prom()
        scrape_ms = (time.perf_counter() - t0) * 1000.0
        assert 'vft_fleet_routed_total{host=' in prom, \
            'aggregated exposition missing fleet families'
        assert 'vft_slo_latency_burn_rate{window="5m"}' in prom, \
            'aggregated exposition missing SLO gauges'

        clips = 0
        for p in wl_paths:
            arr = np.load(make_path(
                os.path.join(warm_out, 'resnet', 'resnet18'),
                p, 'resnet', '.npy'))
            clips += arr.shape[0]
        assert clips > 0, 'fleet warm pass produced no clips'
        hits = misses = 0
        for srv in (host_a, host_b):
            cst = srv.metrics()['cache']
            hits += cst['hits']
            misses += cst['misses']
        return {
            'fleet_warm_clips_per_sec': round(clips / warm_s, 3),
            'fleet_cache_hit_rate': round(hits / max(1, hits + misses), 4),
            'fleet_cold_host_first_feature_s': round(cold_host_s, 3),
            'fleet_metrics_scrape_ms': round(scrape_ms, 2),
        }
    finally:
        if router is not None:
            router.stop()
        for srv in (host_a, host_b):
            if srv is not None:
                try:
                    srv.drain(wait=True, grace_s=120)
                except Exception:
                    pass


def bench_cache(precision: str, batch: int, stack: int, tmp_dir: str,
                platform: str, wl_paths: list) -> dict:
    """The content-addressed cache rung (cache/): the SAME worklist run
    twice with ``cache_enabled=true`` — the cold pass pays decode +
    inference and publishes, the warm pass materializes every video from
    the store (fresh output root, so the resume contract can't mask the
    measurement). Reports cold vs warm-hit clips/s, the per-video hit
    latency, and the store's hit rate (hits must cover the worklist or
    the rung is mislabeled — asserted)."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor
    from video_features_tpu.utils.output import make_path

    cache_dir = os.path.join(tmp_dir, 'feature_cache')

    def one_pass(tag):
        args = load_config('i3d', overrides={
            'video_paths': wl_paths,
            'device': platform, 'precision': precision,
            'stack_size': stack, 'step_size': stack, 'batch_size': batch,
            'allow_random_weights': True, 'on_extraction': 'save_numpy',
            'output_path': os.path.join(tmp_dir, f'cache_out_{tag}'),
            'tmp_path': os.path.join(tmp_dir, 'cache_tmp'),
            'cache_enabled': True, 'cache_dir': cache_dir,
        })
        ex = create_extractor(args)
        t0 = time.perf_counter()
        for p in wl_paths:
            ex._extract(p)
        return ex, time.perf_counter() - t0

    ex_cold, cold_s = one_pass('cold')
    ex_warm, warm_s = one_pass('warm')

    clips = 0
    for p in wl_paths:
        arr = np.load(make_path(ex_warm.output_path, p, 'rgb', '.npy'))
        clips += arr.shape[0]
    assert clips > 0, 'cache warm pass produced no clips'
    st = ex_warm.cache.stats()
    assert st['hits'] >= len(wl_paths), \
        f'cache warm pass missed the store — rung mislabeled: {st}'
    return {
        'cache_cold_clips_per_sec': round(clips / cold_s, 3),
        'cache_hit_clips_per_sec': round(clips / warm_s, 3),
        'cache_hit_latency_s': round(warm_s / len(wl_paths), 4),
        'cache_hit_rate': round(st['hit_rate'], 4),
        'cache_bytes_saved': int(st['bytes_saved']),
    }


def _feature_file_errors(root_a: str, root_b: str) -> dict:
    """max-abs + rel-L2 error between two output roots' FEATURE files
    (matched by relative path; fps/timestamps sidecars excluded — they
    are identical across lanes and would dilute the rel-L2 denominator).
    The honest error a *_bf16_* rung records next to its speedup."""
    from video_features_tpu.ops.precision import rel_l2

    def feature_files(root):
        return {p.relative_to(root): p for p in Path(root).rglob('*.npy')
                if not p.name.endswith(('_fps.npy',
                                        '_timestamps_ms.npy'))}

    a_files, b_files = feature_files(root_a), feature_files(root_b)
    # symmetric: an extra/renamed bf16 output is itself a divergence the
    # rung must surface, not silently ignore
    assert set(a_files) == set(b_files), (
        f'lanes produced different output sets: only-fp32='
        f'{sorted(set(a_files) - set(b_files))} only-bf16='
        f'{sorted(set(b_files) - set(a_files))}')
    refs, cands = [], []
    for rel, pa in sorted(a_files.items()):
        refs.append(np.load(pa).ravel())
        cands.append(np.load(b_files[rel]).ravel())
    assert refs, 'no feature files to compare'
    ref = np.concatenate(refs)
    cand = np.concatenate(cands)
    return {
        'max_abs_error': round(float(np.max(np.abs(ref - cand))), 6),
        'rel_l2_error': round(rel_l2(ref, cand), 6),
    }


def bench_bf16_framewise(jax, device, iters: int, on_accel: bool) -> dict:
    """The framewise in-graph bf16 rung: the SAME resnet step (the
    production ``ExtractResNet._forward``) timed fp32 vs bf16 on
    device-resident uint8 batches — bf16 params from the transplant cast
    (half the HBM), bf16 activations with the ops/nn.py fp32 islands —
    plus the measured error of one batch. The framewise families are the
    bandwidth-bound end (2500+ frames/s) where bf16 storage pays most."""
    from functools import partial

    import jax.numpy as jnp
    from jax import lax

    from video_features_tpu.extract.resnet import ExtractResNet
    from video_features_tpu.models import resnet as resnet_model
    from video_features_tpu.ops.precision import param_np_dtype, rel_l2
    from video_features_tpu.transplant.torch2jax import transplant

    arch = 'resnet50' if on_accel else 'resnet18'
    size = 224 if on_accel else 64
    batch = 32 if on_accel else 2
    sd = resnet_model.init_state_dict(arch=arch)
    rng = np.random.RandomState(0)
    frames = jax.device_put(
        rng.randint(0, 255, (iters, batch, size, size, 3))
        .astype(np.uint8), device)
    one = jax.device_put(
        rng.randint(0, 255, (batch, size, size, 3)).astype(np.uint8),
        device)

    rates, outs = {}, {}
    for lane in ('float32', 'bfloat16'):
        params = jax.device_put(
            transplant(sd, dtype=param_np_dtype(lane)), device)
        step = partial(
            ExtractResNet._forward, arch=arch,
            dtype=jnp.bfloat16 if lane == 'bfloat16' else jnp.float32)

        def chained(p, xs):
            def body(acc, x):
                return acc + step(p, x).sum(), None
            acc, _ = lax.scan(body, jnp.float32(0), xs)
            return acc

        jitted = jax.jit(chained)
        assert np.isfinite(float(jitted(params, frames)))  # compile+guard
        t0 = time.perf_counter()
        checksum = float(jitted(params, frames))
        rates[lane] = batch * iters / (time.perf_counter() - t0)
        assert np.isfinite(checksum)
        outs[lane] = np.asarray(jax.jit(step)(params, one))

    err = float(np.max(np.abs(outs['float32'] - outs['bfloat16'])))
    return {
        'resnet_ingraph_bf16_frames_per_sec': round(rates['bfloat16'], 3),
        'resnet_ingraph_bf16_fp32_frames_per_sec': round(
            rates['float32'], 3),
        'resnet_ingraph_bf16_speedup': round(
            rates['bfloat16'] / rates['float32'], 3),
        'resnet_ingraph_bf16_max_abs_error': round(err, 6),
        'resnet_ingraph_bf16_rel_l2_error': round(
            rel_l2(outs['float32'], outs['bfloat16']), 6),
    }


def bench_int8_framewise(jax, device, iters: int, on_accel: bool) -> dict:
    """The framewise in-graph int8 rung: the SAME resnet step timed fp32
    vs the int8 weight lane on device-resident uint8 batches — int8
    params from transplant-time quantization (a QUARTER of the fp32 HBM
    and H2D bytes; ops/quant.py), fp32 activations after the in-graph
    dequant — plus the measured error of one batch, recorded beside the
    speedup so a committed int8 number is checkable against
    ``INT8_REL_L2_BOUNDS``. Weight-only quantization pays in residency
    and transfer, not FLOPs, so the honest expectation on a compute-rich
    chip is speedup ~1.0 with quarter-size params — the error columns
    are the rung's real payload."""
    from functools import partial

    import jax.numpy as jnp
    from jax import lax

    from video_features_tpu.extract.resnet import ExtractResNet
    from video_features_tpu.models import resnet as resnet_model
    from video_features_tpu.ops.precision import param_np_dtype, rel_l2

    from video_features_tpu.transplant.torch2jax import transplant

    arch = 'resnet50' if on_accel else 'resnet18'
    size = 224 if on_accel else 64
    batch = 32 if on_accel else 2
    sd = resnet_model.init_state_dict(arch=arch)
    rng = np.random.RandomState(0)
    frames = jax.device_put(
        rng.randint(0, 255, (iters, batch, size, size, 3))
        .astype(np.uint8), device)
    one = jax.device_put(
        rng.randint(0, 255, (batch, size, size, 3)).astype(np.uint8),
        device)

    rates, outs = {}, {}
    for lane in ('float32', 'int8'):
        params = jax.device_put(
            transplant(sd, dtype=param_np_dtype(lane)), device)
        # int8 lane activates in float32 (compute_jnp_dtype): the only
        # delta vs the fp32 lane is quantized weights + in-graph dequant
        step = partial(ExtractResNet._forward, arch=arch,
                       dtype=jnp.float32)

        def chained(p, xs):
            def body(acc, x):
                return acc + step(p, x).sum(), None
            acc, _ = lax.scan(body, jnp.float32(0), xs)
            return acc

        jitted = jax.jit(chained)
        assert np.isfinite(float(jitted(params, frames)))  # compile+guard
        t0 = time.perf_counter()
        checksum = float(jitted(params, frames))
        rates[lane] = batch * iters / (time.perf_counter() - t0)
        assert np.isfinite(checksum)
        outs[lane] = np.asarray(jax.jit(step)(params, one))

    err = float(np.max(np.abs(outs['float32'] - outs['int8'])))
    return {
        'resnet_ingraph_int8_frames_per_sec': round(rates['int8'], 3),
        'resnet_ingraph_int8_fp32_frames_per_sec': round(
            rates['float32'], 3),
        'resnet_ingraph_int8_speedup': round(
            rates['int8'] / rates['float32'], 3),
        'resnet_ingraph_int8_max_abs_error': round(err, 6),
        'resnet_ingraph_int8_rel_l2_error': round(
            rel_l2(outs['float32'], outs['int8']), 6),
    }


def bench_serve_bf16(precision: str, tmp_dir: str, platform: str,
                     wl_paths: list) -> dict:
    """The serve-warm bf16 rung: the same worklist served twice per lane
    (cold then warm) through ONE daemon — fp32 and bf16 requests build
    DISTINCT warm pool entries (compute_dtype is pool-key relevant;
    asserted via the pool size), and the warm-pass rates give the
    steady-state speedup a resident bf16 entry actually delivers, with
    the measured error of the warm outputs recorded beside it."""
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer
    from video_features_tpu.utils.output import make_path

    base = {
        'device': platform, 'precision': precision,
        'model_name': 'resnet18', 'batch_size': 8,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'tmp_path': os.path.join(tmp_dir, 'sbf_tmp'),
    }
    server = ExtractionServer(
        base_overrides=base,
        queue_depth=max(64, 4 * len(wl_paths))).start()
    try:
        client = ServeClient(port=server.port)

        def one_pass(tag, lane):
            out_root = os.path.join(tmp_dir, f'sbf_out_{tag}')
            t0 = time.perf_counter()
            rids = [client.submit('resnet', [p], overrides={
                        'output_path': out_root,
                        'compute_dtype': lane})
                    for p in wl_paths]
            for rid in rids:
                st = client.wait(rid, timeout_s=900)
                assert st['state'] == 'done', f'serve bf16 {tag}: {st}'
            return out_root, time.perf_counter() - t0

        one_pass('f32_cold', 'float32')
        f32_root, f32_s = one_pass('f32_warm', 'float32')
        one_pass('bf16_cold', 'bfloat16')
        bf16_root, bf16_s = one_pass('bf16_warm', 'bfloat16')

        clips = 0
        for p in wl_paths:
            arr = np.load(make_path(os.path.join(bf16_root, 'resnet',
                                                 'resnet18'),
                                    p, 'resnet', '.npy'))
            clips += arr.shape[0]
        assert clips > 0, 'serve bf16 warm pass produced no clips'
        m = client.metrics()
        # distinct warm entries per lane — the pool-key split the knob's
        # 'both' classification promises (never a shared program)
        assert m['warm_pool']['size'] >= 2, m['warm_pool']
        errs = _feature_file_errors(f32_root, bf16_root)
        return {
            'serve_bf16_clips_per_sec': round(clips / bf16_s, 3),
            'serve_bf16_fp32_clips_per_sec': round(clips / f32_s, 3),
            'serve_bf16_speedup': round(f32_s / bf16_s, 3),
            'serve_bf16_max_abs_error': errs['max_abs_error'],
            'serve_bf16_rel_l2_error': errs['rel_l2_error'],
        }
    finally:
        server.drain(wait=True, grace_s=120)


def bench_serve_int8(precision: str, tmp_dir: str, platform: str,
                     wl_paths: list) -> dict:
    """The serve-warm int8 rung, and the full precision ladder resident
    in ONE daemon: fp32, bf16 and int8 requests for the same family
    build THREE distinct warm pool entries (compute_dtype is pool-key
    relevant on every rung of the ladder; asserted via the pool size),
    the int8 warm-pass rate gives the steady-state throughput a resident
    quarter-size entry delivers, and the measured error of the int8 warm
    outputs vs the fp32 warm outputs rides beside it."""
    from video_features_tpu.serve.client import ServeClient
    from video_features_tpu.serve.server import ExtractionServer
    from video_features_tpu.utils.output import make_path

    base = {
        'device': platform, 'precision': precision,
        'model_name': 'resnet18', 'batch_size': 8,
        'allow_random_weights': True, 'on_extraction': 'save_numpy',
        'tmp_path': os.path.join(tmp_dir, 'si8_tmp'),
        'serve_warm_pool_size': 4,      # three lanes must fit warm
    }
    server = ExtractionServer(
        base_overrides=base,
        queue_depth=max(64, 4 * len(wl_paths))).start()
    try:
        client = ServeClient(port=server.port)

        def one_pass(tag, lane):
            out_root = os.path.join(tmp_dir, f'si8_out_{tag}')
            t0 = time.perf_counter()
            rids = [client.submit('resnet', [p], overrides={
                        'output_path': out_root,
                        'compute_dtype': lane})
                    for p in wl_paths]
            for rid in rids:
                st = client.wait(rid, timeout_s=900)
                assert st['state'] == 'done', f'serve int8 {tag}: {st}'
            return out_root, time.perf_counter() - t0

        one_pass('f32_cold', 'float32')
        f32_root, f32_s = one_pass('f32_warm', 'float32')
        one_pass('bf16_cold', 'bfloat16')           # third ladder rung
        one_pass('int8_cold', 'int8')
        int8_root, int8_s = one_pass('int8_warm', 'int8')

        clips = 0
        for p in wl_paths:
            arr = np.load(make_path(os.path.join(int8_root, 'resnet',
                                                 'resnet18'),
                                    p, 'resnet', '.npy'))
            clips += arr.shape[0]
        assert clips > 0, 'serve int8 warm pass produced no clips'
        m = client.metrics()
        # the WHOLE ladder resident at once: three distinct warm entries
        # for one family, one per compute_dtype — the pool-key split
        # extended down to int8 (never a shared program across lanes)
        assert m['warm_pool']['size'] >= 3, m['warm_pool']
        errs = _feature_file_errors(f32_root, int8_root)
        return {
            'serve_int8_clips_per_sec': round(clips / int8_s, 3),
            'serve_int8_fp32_clips_per_sec': round(clips / f32_s, 3),
            'serve_int8_speedup': round(f32_s / int8_s, 3),
            'serve_int8_max_abs_error': errs['max_abs_error'],
            'serve_int8_rel_l2_error': errs['rel_l2_error'],
            'serve_int8_warm_pool_size': m['warm_pool']['size'],
        }
    finally:
        server.drain(wait=True, grace_s=120)


def _bench_video(tmp_dir: str, seconds: str = None) -> str:
    """A local benchmark clip: the reference sample if present, else a
    synthetic one (tools/make_sample_video.py). ``BENCH_VIDEO=synthetic``
    forces the synthetic clip and ``seconds`` (default
    ``BENCH_E2E_SECONDS``) its length — the contract smoke test uses a
    1-stack clip so the e2e path stays cheap on CPU. Also the ONE source
    of clip selection for tools/worklist_bench.py, so the e2e and
    worklist rungs always measure the same content."""
    ref = Path('/root/reference/sample/v_GGSY1Qvo990.mp4')
    if ref.exists() and os.environ.get('BENCH_VIDEO') != 'synthetic':
        return str(ref)
    if seconds is None:
        seconds = os.environ.get('BENCH_E2E_SECONDS', '10')
    out = Path(tmp_dir) / 'synth' / 'sample_moving_pattern.mp4'
    if not out.exists():
        import subprocess
        import sys
        # child fds bypass redirect_stdout — pin the subprocess's stdout to
        # stderr so its 'wrote ...' chatter can't break the one-line contract
        subprocess.run(
            [sys.executable, str(Path(__file__).parent / 'tools' /
                                 'make_sample_video.py'),
             '--out', str(out.parent), '--seconds', seconds, '--fps', '25',
             '--size', '340x256'],
            check=True, stdout=sys.stderr)
    return str(out)


def bench_e2e(precision: str, batch: int, stack: int, tmp_dir: str,
              platform: str, feature_type: str = 'i3d', key: str = 'rgb'):
    """File → features clips/sec through the real extractor (decode,
    prefetch, overlapped H2D, fused device step, feature fetch).
    Returns ``(rate, stage_report)`` — the production Tracer's wall-time
    split over the timed runs rides into the bench record
    (``stage_reports``) so a BENCH_*.json explains its own number."""
    from video_features_tpu.config import load_config
    from video_features_tpu.registry import create_extractor

    video = _bench_video(tmp_dir)
    args = load_config(feature_type, overrides={
        'video_paths': video,
        'device': platform,
        'precision': precision,
        'stack_size': stack, 'step_size': stack,
        'batch_size': batch,
        'allow_random_weights': True,
        'profile': True,           # per-stage Tracer feeds stage_reports
        'on_extraction': 'print',  # extraction only; no disk write timing
        'output_path': os.path.join(tmp_dir, 'out'),
        'tmp_path': os.path.join(tmp_dir, 'tmp'),
    })
    ex = create_extractor(args)
    warm = ex.extract(video)                   # compile + cache warm
    clips = warm[key].shape[0]
    assert clips > 0 and np.isfinite(warm[key]).all()
    ex.tracer.reset()                          # timed runs only
    # median of independent runs: one stalled transfer can triple one
    # run's wall time, and the median is the steady state a user sees
    runs = int(os.environ.get('BENCH_E2E_RUNS', 3))
    rates = []
    for _ in range(runs):
        t0 = time.perf_counter()
        out = ex.extract(video)
        rates.append(clips / (time.perf_counter() - t0))
        assert out[key].shape[0] == clips
    from video_features_tpu.utils.tracing import round_report
    return float(np.median(rates)), round_report(ex.tracer.report())


def run() -> dict:
    """Measure all rungs; returns the one-line record. Anything this (or
    the libraries it calls) prints is expected on stderr only — main()
    enforces that by redirecting stdout around the whole measurement."""
    import tempfile

    import jax

    # Local smoke runs: BENCH_PLATFORM=cpu keeps the process off the chip.
    if os.environ.get('BENCH_PLATFORM'):
        jax.config.update('jax_platforms', os.environ['BENCH_PLATFORM'])

    from video_features_tpu.models import i3d as i3d_model
    from video_features_tpu.models import raft as raft_model
    from video_features_tpu.ops.precision import (
        MIXED_AMBIENT, MIXED_PINS,
    )
    from video_features_tpu.transplant.torch2jax import transplant
    from video_features_tpu.utils.device import (
        enable_compilation_cache, jax_device,
    )

    platform = jax.devices()[0].platform
    on_accel = platform != 'cpu'
    # Parity-grade default: 'mixed' meets the ≤1e-3 bar at ~the 3-pass
    # speed (tools/precision_study.py); stamp whatever runs into the metric.
    precision = os.environ.get('BENCH_PRECISION', 'mixed')
    ambient, pins = ((MIXED_AMBIENT, MIXED_PINS) if precision == 'mixed'
                     else (precision, None))
    stack = int(os.environ.get('BENCH_STACK', 16))
    # Headline geometry = what the real CLI runs: short-side-256 decode of
    # the reference sample → 256×340 frames, RAFT on the full padded frame,
    # crop 224 in-graph (VERDICT r4 task 2 — the reference's ~3.75 clips/s
    # anecdote ran THIS geometry, so vs_baseline must too). BENCH_SIZE
    # overrides with a square geometry for smoke runs.
    if os.environ.get('BENCH_SIZE'):
        size = int(os.environ['BENCH_SIZE'])
        cli_h, cli_w = size, size
    else:
        cli_h, cli_w = (256, 340) if on_accel else (64, 86)
    # batch sweep on v5e (lanes lookup): 8 → 26.9, 16 → 28.4, 32 → 28.8
    # clips/s; 16 takes nearly all of the win at half the HBM footprint
    batch = int(os.environ.get('BENCH_BATCH', 16 if on_accel else 1))
    iters = int(os.environ.get('BENCH_ITERS', 8 if on_accel else 2))
    enable_compilation_cache('auto', platform)

    device = jax_device(platform)
    params = jax.device_put({
        'rgb': transplant(i3d_model.init_state_dict(modality='rgb')),
        'flow': transplant(i3d_model.init_state_dict(modality='flow')),
        'raft': transplant(raft_model.init_state_dict()),
    }, device)

    rungs = {}
    # a BENCH_SIZE square override is NOT the CLI geometry — don't stamp
    # it as such (the metric name would launder a crop-first number into
    # the reconciled headline)
    headline_key = (f'ingraph_cli_geom_{precision}'
                    if not os.environ.get('BENCH_SIZE')
                    else f'ingraph_{precision}')
    rungs[headline_key] = round(
        bench_ingraph(jax, ambient, pins, device, platform, params,
                      stack, cli_h, cli_w, batch, iters), 3)
    if on_accel and not os.environ.get('BENCH_SIZE'):
        # secondary crop-first ceiling at 224² (the round-3/4 headline
        # geometry, kept for cross-round comparability)
        try:
            rungs[f'ingraph_{precision}_224px'] = round(
                bench_ingraph(jax, ambient, pins, device, platform, params,
                              stack, 224, 224, batch, iters), 3)
        except Exception as e:
            rungs['ingraph_224px_error'] = f'{type(e).__name__}: {e}'

    # Per-family rungs through ONE shared harness (bench_family_ingraph),
    # specs from tools/family_precision_study so bench and ladder tool
    # measure the identical production steps. R(2+1)D is the second
    # north-star model (BASELINE.md; ladder: 'mixed' drift 2.0e-4 ✅ /
    # 'default' 3.1e-3 ✗) and always runs; the remaining BASELINE configs
    # (s3d / resnet50 / clip / vggish + standalone raft at native flow
    # resolution — VERDICT r4 task 6) run on accelerators by default,
    # BENCH_FAMILIES=0/1 overrides.
    sys.path.insert(0, str(Path(__file__).parent))
    from tools.family_precision_study import _family_specs
    all_families = (os.environ.get('BENCH_FAMILIES',
                                   '1' if on_accel else '0') == '1')
    for fam, spec in _family_specs(on_accel).items():
        if fam != 'r21d' and not all_families:
            continue
        try:
            init_fn, step_fn, bshape, unit, imap, count = spec
            key = (f'r21d_ingraph_{precision}' if fam == 'r21d' else
                   f'{fam}_ingraph_{precision}_{unit.split("/")[0]}')
            rungs[key] = round(
                bench_family_ingraph(jax, ambient, device, init_fn,
                                     step_fn, bshape, imap, count, iters,
                                     transplant), 3)
        except Exception as e:
            rungs[f'{fam}_ingraph_error'] = f'{type(e).__name__}: {e}'

    # the bf16 fast lane (compute_dtype=bfloat16, ops/precision.py):
    # device-only framewise speedup + measured error vs the fp32
    # sibling, always recorded together so a committed bf16 number is
    # checkable against its family's pinned bound. BENCH_BF16=0/1
    # overrides the accelerator-only default.
    run_bf16 = os.environ.get('BENCH_BF16',
                              '1' if on_accel else '0') == '1'
    if run_bf16:
        try:
            rungs.update(bench_bf16_framewise(jax, device, iters,
                                              on_accel))
        except Exception as e:
            rungs['bf16_ingraph_error'] = f'{type(e).__name__}: {e}'

    # the int8 weight lane (compute_dtype=int8, ops/quant.py): same
    # shape as the bf16 rung — speedup AND measured error, always
    # together, checkable against INT8_REL_L2_BOUNDS. BENCH_INT8=0/1
    # overrides the accelerator-only default.
    run_int8 = os.environ.get('BENCH_INT8',
                              '1' if on_accel else '0') == '1'
    if run_int8:
        try:
            rungs.update(bench_int8_framewise(jax, device, iters,
                                              on_accel))
        except Exception as e:
            rungs['int8_ingraph_error'] = f'{type(e).__name__}: {e}'

    # per-rung Tracer stage reports (decode/h2d/model/save split) ride
    # along in the record so tools/bench_diff.py users can see WHERE a
    # regression landed, not just that one did
    stage_reports = {}
    mode = os.environ.get('BENCH_MODE', 'both' if on_accel else 'ingraph')
    if mode in ('both', 'e2e'):
        with tempfile.TemporaryDirectory() as tmp_dir:
            try:
                rate, rep = bench_e2e(precision, min(batch, 8), stack,
                                      tmp_dir, platform)
                rungs[f'e2e_{precision}'] = round(rate, 3)
                stage_reports[f'e2e_{precision}'] = rep
            except Exception as e:
                rungs['e2e_error'] = f'{type(e).__name__}: {e}'
            try:
                rate, rep = bench_e2e(precision, min(batch, 8), stack,
                                      tmp_dir, platform,
                                      feature_type='r21d', key='r21d')
                rungs[f'r21d_e2e_{precision}'] = round(rate, 3)
                stage_reports[f'r21d_e2e_{precision}'] = rep
            except Exception as e:
                rungs['r21d_e2e_error'] = f'{type(e).__name__}: {e}'
            # Sustained multi-video worklist (resume contract + prefetch
            # + decode overlap live — the corpus-scale number, VERDICT r4
            # task 5); BENCH_WORKLIST=0/1 overrides.
            wl_paths = None
            # the family the worklist trio measures: i3d (the flagship)
            # by default; CPU smoke lanes (contract tests, the CI
            # bench-diff job) override to a cheap family so the rung
            # KEYS stay exercised without paying RAFT-on-CPU minutes
            wl_feature = os.environ.get('BENCH_WORKLIST_FEATURE', 'i3d')
            if os.environ.get('BENCH_WORKLIST',
                              '1' if on_accel else '0') == '1':
                try:
                    from tools.worklist_bench import (
                        make_worklist, run_worklist,
                    )
                    wl_paths = make_worklist(tmp_dir, 4 if on_accel else 2,
                                             10 if on_accel else 2)
                    wrec = run_worklist(wl_feature, wl_paths, tmp_dir,
                                        tmp_dir, platform,
                                        batch_size=min(batch, 8),
                                        stack=stack, precision=precision)
                    rungs[f'worklist_videos_per_min_{precision}'] = \
                        wrec['videos_per_min']
                    rungs[f'worklist_clips_per_sec_{precision}'] = \
                        wrec['clips_per_sec']
                    stage_reports[f'worklist_{precision}'] = wrec['stages']
                except Exception as e:
                    rungs['worklist_error'] = f'{type(e).__name__}: {e}'
                # The SAME worklist object, batch-major
                # (pack_across_videos=true): batches fill across video
                # boundaries (parallel/packing.py) so the compiled step
                # stops running padded tails per video — measured in the
                # same session, with its own output root (the unpacked
                # pass's files would otherwise make it an all-skip no-op).
                # inflight=1 pins the SYNCHRONOUS device loop so the
                # async rung below is a clean A/B over one knob.
                if wl_paths is not None:
                    try:
                        # decode_workers=1 pins the input side in-process
                        # (single decode process) so the packed → async →
                        # farm ladder attributes each delta to one knob
                        wrec_packed = run_worklist(
                            wl_feature, wl_paths,
                            os.path.join(tmp_dir, 'packed'),
                            tmp_dir, platform, batch_size=min(batch, 8),
                            stack=stack, precision=precision, packed=True,
                            inflight=1, decode_workers=1)
                        rungs[f'worklist_packed_clips_per_sec_{precision}'] \
                            = wrec_packed['clips_per_sec']
                        rungs['worklist_packed_inflight'] = \
                            wrec_packed['inflight']
                        stage_reports[f'worklist_packed_{precision}'] = \
                            wrec_packed['stages']
                        if wrec_packed.get('batch_occupancy') is not None:
                            rungs['worklist_packed_batch_occupancy'] = \
                                wrec_packed['batch_occupancy']
                    except Exception as e:
                        rungs['worklist_packed_error'] = \
                            f'{type(e).__name__}: {e}'
                # The async device loop (inflight=2): packed_step only
                # dispatches, D2H + scatter + save of batch k-1 overlap
                # the device computing batch k (parallel/packing.py) —
                # same worklist, own output root, byte-identical outputs
                # (tests/test_packing.py pins parity); the delta vs the
                # inflight=1 rung above is the deferred-readback win.
                if wl_paths is not None:
                    try:
                        wrec_async = run_worklist(
                            wl_feature, wl_paths,
                            os.path.join(tmp_dir, 'async'),
                            tmp_dir, platform, batch_size=min(batch, 8),
                            stack=stack, precision=precision, packed=True,
                            inflight=2, decode_workers=1)
                        rungs[f'worklist_async_clips_per_sec_{precision}'] \
                            = wrec_async['clips_per_sec']
                        rungs['worklist_async_inflight'] = \
                            wrec_async['inflight']
                        stage_reports[f'worklist_async_{precision}'] = \
                            wrec_async['stages']
                        if wrec_async.get('batch_occupancy') is not None:
                            rungs['worklist_async_batch_occupancy'] = \
                                wrec_async['batch_occupancy']
                    except Exception as e:
                        rungs['worklist_async_error'] = \
                            f'{type(e).__name__}: {e}'
                # The decode farm (farm/): same worklist, same async
                # loop, but decode runs in N worker PROCESSES feeding
                # the packer over shared-memory rings — the full
                # pipeline, and the rung the host-decode wall shows up
                # on. Outputs stay byte-identical (tests/test_farm.py);
                # the delta vs the async rung is the farm's win.
                if wl_paths is not None:
                    try:
                        from tools.worklist_bench import \
                            bench_decode_workers
                        n_decode = bench_decode_workers(on_accel)
                        wrec_farm = run_worklist(
                            wl_feature, wl_paths,
                            os.path.join(tmp_dir, 'farm'),
                            tmp_dir, platform, batch_size=min(batch, 8),
                            stack=stack, precision=precision, packed=True,
                            inflight=2, decode_workers=n_decode)
                        rungs[f'worklist_farm_clips_per_sec_{precision}'] \
                            = wrec_farm['clips_per_sec']
                        rungs['worklist_farm_decode_workers'] = \
                            wrec_farm['decode_workers']
                        stage_reports[f'worklist_farm_{precision}'] = \
                            wrec_farm['stages']
                        if wrec_farm.get('batch_occupancy') is not None:
                            rungs['worklist_farm_batch_occupancy'] = \
                                wrec_farm['batch_occupancy']
                    except Exception as e:
                        rungs['worklist_farm_error'] = \
                            f'{type(e).__name__}: {e}'
                # The mesh rung (parallel/mesh.py): same async loop,
                # same in-process decode, but the packed batches plan at
                # capacity × N and shard over the data axis of an
                # N-chip mesh — serve/worklist throughput should scale
                # near-linearly with N, with byte-identical outputs
                # (tests/test_mesh_packed.py pins parity). On a
                # single-device host the rung runs at N=1 and the
                # worklist_mesh_devices metadata says so; CPU CI forces
                # 2 virtual host devices to exercise the sharded path.
                if wl_paths is not None:
                    try:
                        from tools.worklist_bench import bench_mesh_devices
                        wrec_mesh = run_worklist(
                            wl_feature, wl_paths,
                            os.path.join(tmp_dir, 'mesh'),
                            tmp_dir, platform, batch_size=min(batch, 8),
                            stack=stack, precision=precision, packed=True,
                            inflight=2, decode_workers=1,
                            mesh_devices=bench_mesh_devices())
                        rungs[f'worklist_mesh_clips_per_sec_{precision}'] \
                            = wrec_mesh['clips_per_sec']
                        rungs['worklist_mesh_devices'] = \
                            wrec_mesh['mesh_devices']
                        stage_reports[f'worklist_mesh_{precision}'] = \
                            wrec_mesh['stages']
                        if wrec_mesh.get('batch_occupancy') is not None:
                            rungs['worklist_mesh_batch_occupancy'] = \
                                wrec_mesh['batch_occupancy']
                    except Exception as e:
                        rungs['worklist_mesh_error'] = \
                            f'{type(e).__name__}: {e}'
                # The bf16 fast-lane rung (compute_dtype=bfloat16): the
                # same worklist, packed, on an accepting family
                # (BENCH_BF16_FEATURE, default resnet — the framewise
                # bandwidth-bound end) — one fp32 sibling pass + one
                # bf16 pass at OTHERWISE IDENTICAL knobs (inflight=1,
                # in-process decode), so the delta is the lane alone,
                # with the measured output error recorded next to the
                # speedup (never a speedup without its cost).
                if wl_paths is not None and run_bf16:
                    try:
                        bf_feature = os.environ.get('BENCH_BF16_FEATURE',
                                                    'resnet')
                        wrec_f32 = run_worklist(
                            bf_feature, wl_paths,
                            os.path.join(tmp_dir, 'bf16_f32'),
                            tmp_dir, platform, batch_size=min(batch, 8),
                            stack=stack, precision=precision,
                            packed=True, inflight=1, decode_workers=1,
                            compute_dtype='float32')
                        wrec_bf16 = run_worklist(
                            bf_feature, wl_paths,
                            os.path.join(tmp_dir, 'bf16'),
                            tmp_dir, platform, batch_size=min(batch, 8),
                            stack=stack, precision=precision,
                            packed=True, inflight=1, decode_workers=1,
                            compute_dtype='bfloat16')
                        errs = _feature_file_errors(
                            os.path.join(tmp_dir, 'bf16_f32', 'out'),
                            os.path.join(tmp_dir, 'bf16', 'out'))
                        rungs['worklist_packed_bf16_clips_per_sec'] = \
                            wrec_bf16['clips_per_sec']
                        rungs['worklist_packed_bf16_fp32_clips_per_sec'] \
                            = wrec_f32['clips_per_sec']
                        rungs['worklist_packed_bf16_speedup'] = round(
                            wrec_bf16['clips_per_sec']
                            / max(wrec_f32['clips_per_sec'], 1e-9), 3)
                        rungs['worklist_packed_bf16_max_abs_error'] = \
                            errs['max_abs_error']
                        rungs['worklist_packed_bf16_rel_l2_error'] = \
                            errs['rel_l2_error']
                        rungs['worklist_bf16_compute_dtype'] = \
                            wrec_bf16['compute_dtype']
                        stage_reports['worklist_packed_bf16'] = \
                            wrec_bf16['stages']
                    except Exception as e:
                        rungs['worklist_bf16_error'] = \
                            f'{type(e).__name__}: {e}'
                # The int8 weight-lane rung (compute_dtype=int8): the
                # same packed worklist, one fp32 sibling pass + one int8
                # pass at OTHERWISE IDENTICAL knobs, so the delta is the
                # lane alone — quarter-size params + in-graph dequant —
                # with the measured output error recorded next to the
                # speedup (never a speedup without its cost).
                if wl_paths is not None and run_int8:
                    try:
                        i8_feature = os.environ.get('BENCH_INT8_FEATURE',
                                                    'resnet')
                        wrec_f32 = run_worklist(
                            i8_feature, wl_paths,
                            os.path.join(tmp_dir, 'int8_f32'),
                            tmp_dir, platform, batch_size=min(batch, 8),
                            stack=stack, precision=precision,
                            packed=True, inflight=1, decode_workers=1,
                            compute_dtype='float32')
                        wrec_i8 = run_worklist(
                            i8_feature, wl_paths,
                            os.path.join(tmp_dir, 'int8'),
                            tmp_dir, platform, batch_size=min(batch, 8),
                            stack=stack, precision=precision,
                            packed=True, inflight=1, decode_workers=1,
                            compute_dtype='int8')
                        errs = _feature_file_errors(
                            os.path.join(tmp_dir, 'int8_f32', 'out'),
                            os.path.join(tmp_dir, 'int8', 'out'))
                        rungs['worklist_packed_int8_clips_per_sec'] = \
                            wrec_i8['clips_per_sec']
                        rungs['worklist_packed_int8_fp32_clips_per_sec'] \
                            = wrec_f32['clips_per_sec']
                        rungs['worklist_packed_int8_speedup'] = round(
                            wrec_i8['clips_per_sec']
                            / max(wrec_f32['clips_per_sec'], 1e-9), 3)
                        rungs['worklist_packed_int8_max_abs_error'] = \
                            errs['max_abs_error']
                        rungs['worklist_packed_int8_rel_l2_error'] = \
                            errs['rel_l2_error']
                        rungs['worklist_int8_compute_dtype'] = \
                            wrec_i8['compute_dtype']
                        stage_reports['worklist_packed_int8'] = \
                            wrec_i8['stages']
                    except Exception as e:
                        rungs['worklist_int8_error'] = \
                            f'{type(e).__name__}: {e}'
                # The fused multi-family rung (features=[...]): ONE
                # decode + ONE sha256 pass per video feeding N families
                # (run_packed_fused) vs N sequential per-family passes —
                # the wall-clock speedup plus the decode / hash
                # amortization ratios behind it (both → N when decode
                # dominates). Outputs are byte-parity-checked against
                # the sequential passes before any rate is recorded.
                # BENCH_FUSED=0/1 overrides; BENCH_FUSED_FEATURES picks
                # the family set (default resnet,clip,timm).
                if wl_paths is not None and os.environ.get(
                        'BENCH_FUSED', '1' if on_accel else '0') == '1':
                    try:
                        from tools.worklist_bench import (
                            bench_fused_features, run_worklist_fused,
                        )
                        frec = run_worklist_fused(
                            bench_fused_features(), wl_paths,
                            os.path.join(tmp_dir, 'fused'), tmp_dir,
                            platform, batch_size=min(batch, 8),
                            precision=precision)
                        rungs[f'worklist_fused_clips_per_sec_'
                              f'{precision}'] = frec['clips_per_sec']
                        rungs['worklist_fused_speedup'] = \
                            frec['fused_speedup']
                        rungs['worklist_fused_decode_amortization'] = \
                            frec['decode_amortization']
                        rungs['worklist_fused_hash_amortization'] = \
                            frec['hash_amortization']
                        # which family set produced the number — config
                        # metadata, never gated
                        rungs['worklist_fused_families'] = \
                            ','.join(frec['families'])
                        stage_reports[f'worklist_fused_{precision}'] = \
                            frec['stages']
                    except Exception as e:
                        rungs['worklist_fused_error'] = \
                            f'{type(e).__name__}: {e}'
            # The serving rung (serve/): the same worklist content
            # submitted as dynamic per-video requests against the
            # warm-pool daemon — sustained warm clips/sec, the cold-start
            # rate a one-shot CLI pays, and request-latency percentiles.
            # Independent of BENCH_WORKLIST (it builds its own worklist
            # when that rung was skipped); BENCH_SERVE=0/1 overrides.
            if os.environ.get('BENCH_SERVE',
                              '1' if on_accel else '0') == '1':
                try:
                    if wl_paths is None:
                        from tools.worklist_bench import make_worklist
                        wl_paths = make_worklist(
                            tmp_dir, 4 if on_accel else 2,
                            10 if on_accel else 2)
                    srec = bench_serve(precision, min(batch, 8), stack,
                                       tmp_dir, platform, wl_paths)
                    rungs[f'serve_clips_per_sec_{precision}'] = \
                        srec['serve_clips_per_sec']
                    rungs[f'serve_cold_clips_per_sec_{precision}'] = \
                        srec['serve_cold_clips_per_sec']
                    rungs['serve_p50_latency_s'] = \
                        srec['serve_p50_latency_s']
                    rungs['serve_p99_latency_s'] = \
                        srec['serve_p99_latency_s']
                    rungs['serve_warm_hit_rate'] = \
                        srec['serve_warm_hit_rate']
                except Exception as e:
                    rungs['serve_error'] = f'{type(e).__name__}: {e}'
            # The zero-cold-start rung (aot/): boot-to-first-feature
            # for a pre-warmed daemon against a cold vs warm persistent
            # executable store — the warm boot must be compile-free.
            # BENCH_AOT=0/1 overrides the accelerator-only default.
            if os.environ.get('BENCH_AOT',
                              '1' if on_accel else '0') == '1':
                try:
                    if wl_paths is None:
                        from tools.worklist_bench import make_worklist
                        wl_paths = make_worklist(
                            tmp_dir, 4 if on_accel else 2,
                            10 if on_accel else 2)
                    rungs.update(bench_aot_boot(tmp_dir, platform,
                                                wl_paths))
                except Exception as e:
                    rungs['serve_aot_error'] = f'{type(e).__name__}: {e}'
            # The ingress rung (ingress/): the HTTP front door's RTT
            # percentiles vs the loopback socket, through one real
            # segment query. BENCH_INGRESS=0/1 overrides.
            if os.environ.get('BENCH_INGRESS',
                              '1' if on_accel else '0') == '1':
                try:
                    if wl_paths is None:
                        from tools.worklist_bench import make_worklist
                        wl_paths = make_worklist(
                            tmp_dir, 4 if on_accel else 2,
                            10 if on_accel else 2)
                    irec = bench_serve_ingress(tmp_dir, platform, wl_paths)
                    rungs.update(irec)
                except Exception as e:
                    rungs['serve_ingress_error'] = \
                        f'{type(e).__name__}: {e}'
            # The content-addressed cache rung (cache/): cold extraction
            # vs warm O(read) hits over the same worklist — the dedupe
            # win a corpus with repeated/duplicated videos sees per
            # repeat. BENCH_CACHE=0/1 overrides.
            if os.environ.get('BENCH_CACHE',
                              '1' if on_accel else '0') == '1':
                try:
                    if wl_paths is None:
                        from tools.worklist_bench import make_worklist
                        wl_paths = make_worklist(
                            tmp_dir, 4 if on_accel else 2,
                            10 if on_accel else 2)
                    crec = bench_cache(precision, min(batch, 8), stack,
                                       tmp_dir, platform, wl_paths)
                    rungs[f'cache_cold_clips_per_sec_{precision}'] = \
                        crec['cache_cold_clips_per_sec']
                    rungs[f'cache_hit_clips_per_sec_{precision}'] = \
                        crec['cache_hit_clips_per_sec']
                    rungs['cache_hit_latency_s'] = \
                        crec['cache_hit_latency_s']
                    rungs['cache_hit_rate'] = crec['cache_hit_rate']
                    rungs['cache_bytes_saved'] = crec['cache_bytes_saved']
                except Exception as e:
                    rungs['cache_error'] = f'{type(e).__name__}: {e}'
            # The feature-index rung (index/): serve-side ingest to lag
            # zero, then every row queried back over the loopback search
            # command — queries/sec plus recall@10, which exact search
            # pins to 1.0. BENCH_INDEX=0/1 overrides.
            if os.environ.get('BENCH_INDEX',
                              '1' if on_accel else '0') == '1':
                try:
                    if wl_paths is None:
                        from tools.worklist_bench import make_worklist
                        wl_paths = make_worklist(
                            tmp_dir, 4 if on_accel else 2,
                            10 if on_accel else 2)
                    rungs.update(bench_index(tmp_dir, platform, wl_paths))
                except Exception as e:
                    rungs['index_error'] = f'{type(e).__name__}: {e}'
            # The fleet rung (fleet/): two daemons sharing an L2 feature
            # tier + AOT artifact tier behind the content-hash router —
            # compile-free cold-host boot, peer-published warm serves.
            # BENCH_FLEET=0/1 overrides the accelerator-only default.
            if os.environ.get('BENCH_FLEET',
                              '1' if on_accel else '0') == '1':
                try:
                    if wl_paths is None:
                        from tools.worklist_bench import make_worklist
                        wl_paths = make_worklist(
                            tmp_dir, 4 if on_accel else 2,
                            10 if on_accel else 2)
                    rungs.update(bench_fleet(tmp_dir, platform, wl_paths))
                except Exception as e:
                    rungs['fleet_error'] = f'{type(e).__name__}: {e}'
            # The serve-warm bf16 rung: fp32 and bf16 entries resident
            # side by side in ONE daemon (distinct pool keys), warm
            # rates + measured error. BENCH_BF16_SERVE=0/1 overrides.
            if os.environ.get('BENCH_BF16_SERVE',
                              '1' if on_accel else '0') == '1':
                try:
                    if wl_paths is None:
                        from tools.worklist_bench import make_worklist
                        wl_paths = make_worklist(
                            tmp_dir, 4 if on_accel else 2,
                            10 if on_accel else 2)
                    rungs.update(bench_serve_bf16(precision, tmp_dir,
                                                  platform, wl_paths))
                except Exception as e:
                    rungs['serve_bf16_error'] = f'{type(e).__name__}: {e}'
            # The serve-warm int8 rung + the full ladder in one daemon:
            # fp32/bf16/int8 as three resident pool entries, int8 warm
            # rate + measured error. BENCH_INT8_SERVE=0/1 overrides.
            if os.environ.get('BENCH_INT8_SERVE',
                              '1' if on_accel else '0') == '1':
                try:
                    if wl_paths is None:
                        from tools.worklist_bench import make_worklist
                        wl_paths = make_worklist(
                            tmp_dir, 4 if on_accel else 2,
                            10 if on_accel else 2)
                    rungs.update(bench_serve_int8(precision, tmp_dir,
                                                  platform, wl_paths))
                except Exception as e:
                    rungs['serve_int8_error'] = f'{type(e).__name__}: {e}'
    if mode == 'e2e' and f'e2e_{precision}' in rungs:
        headline_key = f'e2e_{precision}'

    # Headline = the in-graph rung; the e2e rung is recorded in `rungs`
    # and BENCH_MODE=e2e promotes it.
    value = rungs[headline_key]
    return {
        'metric': f'i3d_two_stream_{headline_key}_clips_per_sec_'
                  f'{platform}_stack{stack}_{cli_h}x{cli_w}',
        'value': value,
        'unit': 'clips/sec/chip',
        'vs_baseline': round(value / BASELINE_CLIPS_PER_SEC, 3),
        'rungs': rungs,
        # rung name → per-stage Tracer report for every instrumented rung
        # (empty dict on in-graph-only runs)
        'stage_reports': stage_reports,
    }


def main() -> None:
    # The driver contract: stdout carries exactly ONE JSON line. Libraries
    # along the e2e path print diagnostics (random-weights warnings,
    # cv2/ffmpeg chatter, cache notes) — shunt ALL of it to stderr and emit
    # the record on the real stdout afterwards.
    stdout = sys.stdout
    with contextlib.redirect_stdout(sys.stderr):
        record = run()
    print(json.dumps(record), file=stdout)


if __name__ == '__main__':
    main()
