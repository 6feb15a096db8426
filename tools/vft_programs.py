#!/usr/bin/env python3
"""vft-programs launcher: ``python tools/vft_programs.py [flags]``.

A thin wrapper over ``python -m video_features_tpu.analysis.programs``
that works from a source checkout without installation and pins the
analysis environment BEFORE jax initializes:

  * ``JAX_PLATFORMS=cpu`` — the checker lowers programs abstractly; it
    must never take the chip (a TPU belongs to one process at a time,
    and the committed lock is the CPU-pinned lowering);
  * ``--xla_force_host_platform_device_count=2`` (appended to
    ``XLA_FLAGS`` unless the caller already forces a count) — the
    mesh-width-2 lock variants need two host devices to build their
    data mesh.

Exit codes (shared contract, analysis/core.py): 0 clean, 1 analyzer
error, 2 lock drift or a new rule finding. Unlike vft-lint there is no
exit 3 — this tool NEEDS jax by design; its purity bar is "no device
execution", which lowering guarantees structurally.
"""
import os

from _bootstrap import add_repo_root

# unconditional, not setdefault: a host-wide JAX_PLATFORMS=tpu export
# would otherwise lower on real hardware — different StableHLO than the
# CPU-pinned committed lock (spurious drift) AND a chip held against the
# process that needs it. A deliberate non-cpu check can call
# `-m ...analysis.programs` directly.
os.environ['JAX_PLATFORMS'] = 'cpu'
_xla_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _xla_flags:
    os.environ['XLA_FLAGS'] = (
        _xla_flags + ' --xla_force_host_platform_device_count=2').strip()

add_repo_root()

from video_features_tpu.analysis.programs import main  # noqa: E402

if __name__ == '__main__':
    import sys
    sys.exit(main())
