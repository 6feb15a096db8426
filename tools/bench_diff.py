#!/usr/bin/env python3
"""Diff two bench records (BENCH_*.json) rung by rung.

The driver stamps one ``BENCH_r{N}.json`` per round; this tool turns two
of them into an honest regression report instead of eyeballing JSON:

    python tools/bench_diff.py BENCH_r05.json BENCH_r06.json
    python tools/bench_diff.py old.json new.json --fail-on-regression 10

Direction-aware: throughput-like rungs (``*clips_per_sec*``,
``*videos_per_min*``, ``*hit_rate*``, ``*occupancy*``, ``value``,
``vs_baseline``, ``*_speedup``, and the fused worklist's
``*_amortization`` ratios — decode/hash passes amortized across
families, → N when fusion works) regress when they DROP;
latency/duration-like rungs (``*latency*``, ``*_s`` suffixed) regress
when they RISE. Numeric MEASURED-ERROR rungs (``*_error*`` fields the
precision-ladder lanes record — bf16 and int8 alike:
``*_max_abs_error`` / ``*_rel_l2_error``) are
lower-is-better for display but FLAGGED-NEVER-GATED like config
metadata — drift there is bounded by tests/test_precision.py's pinned
per-family bounds, not by a cross-round percentage (random-weight
magnitudes make percent-of-error noise). Non-numeric rungs (exception
strings) and rungs present on only one side are listed but never
counted as regressions — an absent rung usually means a different
BENCH_* env, not a slowdown. Config-metadata rungs (``*_inflight``,
``*_decode_workers``, ``*_mesh_devices``, ``*_families`` — they name
the loop configuration or family set a number ran under) are flagged
``config-changed`` when they differ, never counted as regressions.

``--fail-on-regression PCT`` exits 1 if any shared numeric rung
regressed by more than PCT percent (CI gate); exit 0 otherwise; exit 2
on usage/IO errors.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Tuple

# 'boot_first_feature' names the zero-cold-start rungs
# (serve_boot_first_feature[_cold]_s): boot-to-first-feature is a
# latency even though the name doesn't say so; the '_s' suffix rule
# would catch it too, but direction must not hinge on a suffix
# convention alone for a rung CI gates on
LOWER_IS_BETTER_MARKERS = ('latency', 'resume_pass', 'boot_first_feature')

# rungs that NAME the loop configuration a number was measured under
# (async depth, decode-farm worker count, mesh width, fused family set)
# rather than measuring anything — a change there is a config change to
# flag, never a perf regression
CONFIG_METADATA_SUFFIXES = ('_inflight', '_decode_workers',
                            '_mesh_devices', '_compute_dtype',
                            '_families')


def is_config_metadata(name: str) -> bool:
    return name.endswith(CONFIG_METADATA_SUFFIXES)


def is_error_rung(name: str) -> bool:
    """Numeric measured-error rungs (the precision ladder's
    ``*_max_abs_error`` / ``*_rel_l2_error`` fields — every bf16 and
    int8 rung records them). Lower is better, but NEVER gated:
    their absolute bound lives in tests/test_precision.py — a
    percentage diff across rounds (different weights, geometry,
    platform) is noise, not signal. Suffix-matched exactly: a future
    numeric rung that merely CONTAINS 'error' (an error-rate counter,
    say) must still gate like any other measurement. The ``*_error``
    exception-string rungs are non-numeric and already fall out as
    n/a."""
    return name.endswith(('_max_abs_error', '_rel_l2_error'))


def load_record(path: str) -> Dict[str, Any]:
    """A bench record in any of its shipped shapes: the one-JSON-line
    file the driver contract produces, a raw (possibly pretty-printed)
    record dict, or the driver's round wrapper (``BENCH_r{N}.json``:
    ``{n, cmd, rc, tail, parsed}`` with the record under ``parsed``)."""
    with open(path) as f:
        text = f.read().strip()
    if not text:
        rec: Any = {}
    else:
        try:
            rec = json.loads(text)            # whole file (pretty or flat)
        except json.JSONDecodeError:
            rec = json.loads(text.splitlines()[0])   # one-line contract
    if isinstance(rec, dict) and 'rungs' not in rec \
            and isinstance(rec.get('parsed'), dict):
        rec = rec['parsed']                   # driver round wrapper
    if not isinstance(rec, dict):
        raise ValueError(f'{path}: not a JSON object')
    return rec


def flatten_rungs(rec: Dict[str, Any]) -> Dict[str, Any]:
    """Headline value + every rung, one flat comparable namespace."""
    out: Dict[str, Any] = {}
    if isinstance(rec.get('value'), (int, float)):
        out['value'] = rec['value']
    if isinstance(rec.get('vs_baseline'), (int, float)):
        out['vs_baseline'] = rec['vs_baseline']
    for k, v in (rec.get('rungs') or {}).items():
        out[k] = v
    return out


def lower_is_better(name: str) -> bool:
    if any(m in name for m in LOWER_IS_BETTER_MARKERS):
        return True
    if is_error_rung(name):
        return True
    return name.endswith('_s') and 'per_sec' not in name


def compare(old: Dict[str, Any], new: Dict[str, Any]
            ) -> List[Tuple[str, Any, Any, Optional[float]]]:
    """(name, old, new, regression_pct|None) per rung; regression_pct is
    positive when the rung got WORSE (direction-aware), None when the
    rung is not comparable (non-numeric, one-sided, old == 0)."""
    rows = []
    for name in sorted(set(old) | set(new)):
        a, b = old.get(name), new.get(name)
        reg: Optional[float] = None
        if isinstance(a, (int, float)) and isinstance(b, (int, float)) \
                and not isinstance(a, bool) and not isinstance(b, bool) \
                and a != 0 and not is_config_metadata(name):
            change = (b - a) / abs(a) * 100.0
            reg = change if lower_is_better(name) else -change
        rows.append((name, a, b, reg))
    return rows


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('old', help='baseline bench JSON')
    ap.add_argument('new', help='candidate bench JSON')
    ap.add_argument('--fail-on-regression', type=float, metavar='PCT',
                    default=None,
                    help='exit 1 if any shared numeric rung regressed '
                         'by more than PCT percent')
    args = ap.parse_args(argv)

    try:
        old = flatten_rungs(load_record(args.old))
        new = flatten_rungs(load_record(args.new))
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f'bench_diff: {e}', file=sys.stderr)
        return 2

    rows = compare(old, new)
    width = max((len(r[0]) for r in rows), default=4)
    print(f'{"rung".ljust(width)} | {"old":>12} | {"new":>12} | change')
    regressions = []
    for name, a, b, reg in rows:
        if reg is None:
            note = ('only-old' if name not in new
                    else 'only-new' if name not in old
                    else 'config-changed' if is_config_metadata(name)
                    and a != b else
                    'config' if is_config_metadata(name) else 'n/a')
            print(f'{name.ljust(width)} | {str(a):>12} | {str(b):>12} '
                  f'| {note}')
            continue
        arrow = 'WORSE' if reg > 0 else 'better' if reg < 0 else 'same'
        # measured-error rungs are flagged, never gated (their absolute
        # bound is test-pinned; cross-round percentages are noise)
        flag = ' (error rung: never gated)' if is_error_rung(name) else ''
        # reg is worsening%; report the signed raw change for readability
        change = (b - a) / abs(a) * 100.0
        print(f'{name.ljust(width)} | {a:>12.4g} | {b:>12.4g} '
              f'| {change:+7.2f}% {arrow}{flag}')
        if args.fail_on_regression is not None \
                and reg > args.fail_on_regression \
                and not is_error_rung(name):
            regressions.append((name, reg))

    if regressions:
        for name, reg in regressions:
            print(f'bench_diff: REGRESSION {name}: {reg:.2f}% worse '
                  f'(threshold {args.fail_on_regression}%)',
                  file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
