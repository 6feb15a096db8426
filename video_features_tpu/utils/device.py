"""Device resolution helpers shared by extractors."""
from __future__ import annotations

import os
import warnings
from pathlib import Path
from typing import Optional

import jax

MATMUL_PRECISIONS = ('default', 'high', 'highest', 'mixed',
                     'bfloat16', 'tensorfloat32', 'float32')

# ``compilation_cache_dir: 'auto'`` (the yml default) resolves here: ONE
# fixed directory inside the checkout (resolved like io/native.py resolves
# native/). The directory is part of jax's cache key, so it must never
# carry a pid, a time or a temporary name — a cache that moves never hits.
REPO_XLA_CACHE_DIR = Path(__file__).resolve().parents[2] / '.xla_cache'


def resolve_compilation_cache_dir(cache_dir, device: str) -> Optional[str]:
    """Where jax's persistent compilation cache lives for this run, or
    None for no cache — the ONE decision, shared by the extractors, the
    bench, the tools and ``chip_smoke.py``.

    ``JAX_COMPILATION_CACHE_DIR`` set: the cache was placed from outside
    (a sealed machine whose home directory is thrown away keeps it
    wherever the operator mounted it) — that directory itself, on every
    device, whatever the config says. Unset: XLA:CPU gets NO cache (its
    AOT entries record the compiling machine's CPU feature list and the
    loader rejects — or SIGILLs on — any mismatch; CPU compiles are
    seconds); accelerators use ``cache_dir`` — ``'auto'`` = the fixed
    in-checkout :data:`REPO_XLA_CACHE_DIR`, an explicit path wins, falsy
    disables.
    """
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    if not cache_dir or device == 'cpu':
        return None
    if cache_dir == 'auto':
        return str(REPO_XLA_CACHE_DIR)
    return os.path.expanduser(str(cache_dir))


def enable_compilation_cache(cache_dir, device: str) -> Optional[str]:
    """Point jax's persistent compilation cache at the directory
    :func:`resolve_compilation_cache_dir` picks, and return it.

    The fused extraction graphs take minutes to compile; the cache makes
    every process after the first skip straight to execution. ``device``
    is the resolved config device ('cpu'/'tpu') — passed rather than
    asking jax, which would initialize backends before a CPU run pins its
    platform. With ``JAX_COMPILATION_CACHE_DIR`` set jax already reads
    it, and this function touches nothing: no redirect, no clearing, no
    per-platform sub-directory. Safe to call repeatedly.
    """
    path = resolve_compilation_cache_dir(cache_dir, device)
    current = jax.config.jax_compilation_cache_dir
    if os.environ.get('JAX_COMPILATION_CACHE_DIR') or path == current:
        return path
    if path is not None:
        os.makedirs(path, exist_ok=True)
    jax.config.update('jax_compilation_cache_dir', path)
    if current:
        # the setting is process-global, and jax binds the directory at
        # the first compile that uses it — without the reset a CPU
        # extractor built after an accelerator one would keep persisting
        # host-ISA-bound XLA:CPU entries into the accelerator's directory
        warnings.warn(
            f'compilation cache moved from {current} to {path} for a '
            f'device={device!r} extractor — the setting is process-'
            'global, earlier extractors in this process follow it')
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    return path


def pin_cpu_platform() -> None:
    """Restrict jax to the CPU platform BEFORE backends initialize.

    ``device=cpu`` is the explicit CPU path, and on a machine that holds
    a chip it must not take it: jax initializes every installed platform
    on first device access, a TPU belongs to one process at a time, and a
    CPU test or tool that merely probed the chip would hold it against
    the process that needs it (or fail at start-up because that process
    already holds it). No-op if backends are already up (the update then
    fails harmlessly).
    """
    try:
        jax.config.update('jax_platforms', 'cpu')
    except Exception:
        # vft-lint: ok=swallowed-exception — documented no-op when
        # backends are already up (the update fails harmlessly)
        pass


def accelerator_platform() -> str:
    """The platform name of this process's accelerator ('tpu', 'gpu').
    Raises, naming the platforms found, when there is none: an
    accelerator run never carries on on the CPU — ``device=cpu`` is how a
    CPU run is asked for."""
    platforms = sorted({d.platform for d in jax.devices()})
    accel = [p for p in platforms if p != 'cpu']
    if not accel:
        raise RuntimeError(
            'an accelerator device was requested but jax found only '
            f'platform(s) {platforms} (JAX_PLATFORMS='
            f'{os.environ.get("JAX_PLATFORMS")!r}) — pass device=cpu to '
            'run on the CPU explicitly')
    return accel[0]


def jax_device(device: str) -> jax.Device:
    """Map a resolved config device string ('cpu'/'tpu') to a jax.Device.

    'cpu' explicitly targets the CPU backend rather than the default
    device (and pins the platform first — see :func:`pin_cpu_platform`);
    anything else is the accelerator, and raises when there is none.

    Always a LOCAL device: under the multi-process runtime
    (``multihost=true``) ``jax.devices()`` is the pod-GLOBAL list and its
    [0] is process 0's chip — committing a non-rank-0 extractor there makes
    every value fetch raise 'spans non-addressable devices' (caught by
    tests/test_multihost_integration.py).
    """
    if str(device).lower() == 'cpu':
        pin_cpu_platform()
        platform = 'cpu'
    else:
        platform = accelerator_platform()
    return jax.local_devices(backend=platform)[0]


def jax_devices_all(device: str) -> list:
    """All LOCAL devices of the platform :func:`jax_device` resolves to —
    the device set an in-process data-parallel mesh spans.

    Local, not global: under the multi-host runtime each host runs its own
    video shard (shared-nothing contract), so the in-graph mesh must stay on
    this host's addressable chips — a pod-global mesh would have every host
    deadlocking in collectives over different data.
    """
    first = jax_device(device)
    return [d for d in jax.local_devices() if d.platform == first.platform]
