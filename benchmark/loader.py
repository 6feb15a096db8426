"""Finds the benchmark's files by the names in ``BENCHMARK.json``.

Whatever belongs to one configuration, one traffic mix, one driver or one
per-layer metric sits in a file of its own; a later PR adds files and edits
none. This module is the only place that knows the directory layout.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent

for _p in (str(BENCH), str(BENCH / 'references')):
    if _p not in sys.path:
        sys.path.insert(0, _p)

_modules: dict = {}


def load_json(kind: str, name: str) -> dict:
    """``benchmark/<kind>/<name>.json``."""
    path = BENCH / kind / f'{name}.json'
    if not path.is_file():
        raise FileNotFoundError(f'no file {path.relative_to(REPO)}')
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold '-', '.')."""
    key = (kind, name)
    if key not in _modules:
        path = BENCH / kind / f'{name}.py'
        if not path.is_file():
            raise FileNotFoundError(f'no file {path.relative_to(REPO)}')
        mod_name = f'bench_{kind}_' + ''.join(
            c if c.isalnum() else '_' for c in name)
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        _modules[key] = mod
    return _modules[key]


def benchmark_json() -> dict:
    return json.loads((REPO / 'BENCHMARK.json').read_text())
