"""Tests of the benchmark's own yardstick (``benchmark/``), on the CPU.

``benchmark/`` is not a package: its entry point puts the directory on
``sys.path``; so does this file, for the tests."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / 'benchmark'
for p in (str(BENCH), str(BENCH / 'references')):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope='session')
def bench_json():
    import json
    return json.loads((REPO / 'BENCHMARK.json').read_text())
