"""From a profiler trace to numbers: the benchmark's own reduction.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but ``jax.profiler.ProfileData``, into plain Python::

    {'planes': [{'name': '/device:TPU:0',
                 'lines': [{'name': 'XLA Ops',
                            'events': [(name, start_ns, duration_ns), ...]}]}]}

and everything else here works on that structure, so it is tested on small
synthetic traces (tests/bench/test_trace_reduce.py) and reads the same way
whatever the program does.

What the TPU planes hold (looked at by hand, my chip run, PR 24): one plane per
chip, ``/device:TPU:<n>``; the line ``XLA Modules`` has one event per executed
program (named ``jit_<function>(<fingerprint>)``), the line ``XLA Ops`` one
event per HLO operation of those programs (fusions, convolutions, custom
calls, copies), and ``Steps`` one per program run. Busy time is the union of
the intervals of ``XLA Ops`` (of ``XLA Modules`` where a plane has no op line):
ops of one core do not overlap, but the union is what "an operation ran" means
and stays right if they ever do.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
DEVICE_PLANE = re.compile(r'^/device:(TPU|GPU):(\d+)')


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, 'plugins', 'profile', '*',
                                          '*.xplane.pb')))
    if not found:
        raise FileNotFoundError(f'no .xplane.pb under {log_dir}')
    return found[-1]


def load_xplane(path: str) -> Dict:
    """Device planes of the trace as plain lists (host planes are dropped:
    nothing here reads them, and they are most of a trace's events)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines.append({'name': line.name, 'events': [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events]})
        planes.append({'name': plane.name, 'lines': lines})
    return {'planes': planes}


def device_planes(trace: Dict) -> List[Dict]:
    return [p for p in trace['planes'] if DEVICE_PLANE.match(p['name'])]


def line_events(plane: Dict, line_name: str) -> List[Event]:
    for line in plane['lines']:
        if line['name'] == line_name:
            return line['events']
    return []


def op_events(plane: Dict) -> List[Event]:
    """The finest device events a plane has: its ops, else its modules."""
    return line_events(plane, OPS_LINE) or line_events(plane, MODULES_LINE)


def busy_union_ns(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    total, end = 0.0, None
    for a, b in spans:
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(events: Iterable[Event], t0: float, t1: float
         ) -> List[Tuple[float, float, str]]:
    """Idle intervals of [t0, t1] as (start, length, name of the event that
    ended last before the gap — '(window start)' for a leading gap)."""
    spans = sorted((s, s + d, n) for n, s, d in events if d > 0)
    out, end, last = [], t0, '(window start)'
    for a, b, name in spans:
        if a > end:
            out.append((end, a - end, last))
        if b > end:
            end, last = b, name
    if t1 > end:
        out.append((end, t1 - end, last))
    return out


def sum_by_name(events: Iterable[Event]) -> Dict[str, float]:
    sums: Dict[str, float] = {}
    for name, _, dur in events:
        sums[name] = sums.get(name, 0.0) + dur
    return sums


def sum_matching(events: Iterable[Event], pattern: str) -> Tuple[float, int]:
    """(total ns, count) of the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    total, n = 0.0, 0
    for name, _, dur in events:
        if rx.search(name):
            total += dur
            n += 1
    return total, n


def window_of(trace: Dict) -> Tuple[float, float]:
    """First start and last end over all device events."""
    starts, ends = [], []
    for plane in device_planes(trace):
        for line in plane['lines']:
            for _, s, d in line['events']:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        raise ValueError('no device event in the trace')
    return min(starts), max(ends)


_HLO = re.compile(r'^(%[\w.\-]+) = .*? ([a-z][a-z\-]*)\(')


def short_op(name: str) -> str:
    """An op event is named by its whole HLO instruction (hundreds of
    characters); ``%fusion.12 = f32[..]{..} fusion(..), kind=..`` →
    ``%fusion.12 fusion``."""
    m = _HLO.match(name)
    return f'{m.group(1)} {m.group(2)}' if m else name[:80]


def strip_fingerprint(name: str) -> str:
    """``jit_step(1234567890)`` → ``jit_step``; op names stay as they are."""
    return re.sub(r'\(\d+\)$', '', name)


def reduce(trace: Dict, window_s: float, top: int = 10) -> Dict:
    """Everything the harness prints from one traced window.

    ``window_s`` is the traced window by the host's clock (trace start to
    trace stop); the device's clock has no common zero with it, so busy time
    is taken over the whole trace and divided by that length."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError('the trace has no device plane')
    busy, modules, ops, gap_sums = [], {}, {}, {}
    t0, t1 = window_of(trace)
    for plane in planes:
        events = op_events(plane)
        busy.append(busy_union_ns(events))
        for name, ns in sum_by_name(line_events(plane, OPS_LINE)).items():
            ops[short_op(name)] = ops.get(short_op(name), 0.0) + ns
        for name, ns in sum_by_name(
                line_events(plane, MODULES_LINE)).items():
            key = strip_fingerprint(name)
            modules[key] = modules.get(key, 0.0) + ns
        mods = line_events(plane, MODULES_LINE) or events
        for _, length, after in gaps(mods, t0, t1):
            key = 'after ' + strip_fingerprint(after)
            gap_sums[key] = gap_sums.get(key, 0.0) + length
    n = len(planes)

    def ranked(sums):
        return [[k, v / n / 1e9] for k, v in
                sorted(sums.items(), key=lambda kv: -kv[1])[:top]]

    return {
        'chips': n,
        'busy_s': sum(busy) / n / 1e9,
        'window_s': float(window_s),
        'module_s': {k: v / n / 1e9 for k, v in modules.items()},
        'modules_total_s': sum(modules.values()) / n / 1e9,
        'device_ops': ranked(ops),
        'idle_gaps': ranked(gap_sums),
        'op_events': sum(len(line_events(p, OPS_LINE)) for p in planes),
    }
