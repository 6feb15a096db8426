"""The entry point refuses to measure without the chip: non-zero exit, no
result line (nothing at all on stdout), before any work."""
import os
import subprocess
import sys

from .conftest import REPO


def _run(args, **env):
    full = dict(os.environ, JAX_PLATFORMS='cpu', **env)
    return subprocess.run(
        [sys.executable, 'benchmark/run.py', *args], cwd=REPO, env=full,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_no_metric_line():
    proc = _run(['--workload', 'resnet50.corpus', '--seed', '2147483659',
                 '--seconds', '1', '--trace', '0'], BENCH_RUN='7')
    assert proc.returncode != 0
    assert proc.stdout == ''
    assert 'Nothing was measured' in proc.stderr


def test_unknown_workload_is_refused():
    proc = _run(['--workload', 'no.such.cell', '--seed', '1', '--seconds',
                 '1', '--trace', '0'])
    assert proc.returncode != 0 and proc.stdout == ''
    assert 'unknown workload' in proc.stderr
