"""Device-idle time by what the host was doing, in % of the traced window.

The device trace says *when* the chip stood idle; the program's span timeline
(``video_features_tpu.obs.spans``) says what each host thread did when. The
two clocks share nothing but the steps themselves: the k-th ``model`` span of
program P on the host (dispatch start ``t_disp``; its ``device_wait`` span ends
at ``t_ready``; both carry ``step=<ordinal>, program=P``) is the k-th ``XLA
Modules`` event named P on the device (``start``, ``end``), matched from the
tail: every step of the window has ended when the trace stops, warm-up steps
precede it. For the offset ``o`` with host = device + ``o``::

    max_k(t_disp_k - start_k)  <=  o  <=  min_k(t_ready_k - end_k)

(a step cannot start before it is dispatched, nor be seen ready before it
ends). Device times are counted from the trace's first event, so ``o`` is that
event's time on the host clock. The bracket's midpoint moves the idle gaps
between programs onto the host clock; its width is the join's error and is
logged. The upper bound is tight wherever the host stood waiting for a step
(to the wake-up of ``block_until_ready``: the 42 readings of an i3d window lie
within 0.4 ms, the 5 of a resnet50 window within 0.2 ms). The lower bound is
only as tight as a dispatch call is long: a ``model`` span starts before the
call's own host work and the device starts as the call returns — 2.2 to 2.5 ms
in the i3d cell, 7.3 to 11.4 ms in the resnet50 cell (1.3 to 1.6 ms where one
call of a window happened to be short). And in 2 of 13 resnet50 windows the two bounds
CONFLICT, by 0.3 and 4.0 ms: one step starts on the device before its own
``model`` span does, by the other bound (my chip runs, PR 25; cause not found,
``PERF.md`` section 7). A conflict is an error of one side's timestamps as a
wide bracket is an uncertainty, so both are treated alike: ``|hi - lo|`` is the
join's error, the midpoint is used, and ``MAX_BRACKET_S`` refuses it beyond
25 ms — twice the widest reading, and under a tenth of either cell's typical
gap (0.3 to 0.4 s): a larger error moves every gap's ends by more than the
table can bear.

Each idle instant is then put down to one name by the blocking chain: the
dispatch thread's innermost span if that is not ``input_wait`` (``save``,
``d2h``, ``model``, ``video`` ...: the host itself kept the device waiting);
else a ``pack`` or ``h2d`` span covering it; else a ``decode+preprocess`` /
``decode`` span covering it; else ``unexplained``. The metric's ``spans`` lists
the rows it sums (``[]``: the unexplained row).

No number, never a guess, when: the program has no ``attached()`` recorder or
no ordinal on its spans (a parent commit), the step counts of the two sides
differ, the bounds lie more than ``MAX_BRACKET_S`` apart (either way round), or
the recorder dropped events.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

MAX_BRACKET_S = 25e-3
UNEXPLAINED = 'unexplained'
PRODUCER_CHAIN = (('pack',), ('h2d',), ('decode+preprocess', 'decode'))

Interval = Tuple[float, float]
_memo = {'trace': None, 'table': None}     # the five metrics share one table


def read(ctx) -> Optional[float]:
    if _memo['trace'] is not ctx['trace']:
        _memo['trace'], _memo['table'] = ctx['trace'], _table(ctx)
    table = _memo['table']
    if table is None:
        return None
    names = ctx['metric']['spans'] or [UNEXPLAINED]
    return 100.0 * sum(table.get(n, 0.0) for n in names) \
        / ctx['reduced']['window_s']


def _table(ctx) -> Optional[Dict[str, float]]:
    log = ctx['log']
    recorder = _newest_recorder()
    if recorder is None:
        log('idle_by_span: the program attached no span recorder; no number')
        return None
    result = attribute(ctx['trace'], recorder.snapshot(),
                       dropped=recorder.dropped)
    if 'refused' in result:
        log(f'idle_by_span: no number: {result["refused"]}')
        return None
    reduced = ctx['reduced']
    window_s, idle_s = reduced['window_s'], \
        reduced['window_s'] - reduced['busy_s']
    edges_s = window_s - result['trace_span_s']
    rows = sorted(result['seconds'].items(), key=lambda kv: -kv[1])
    rows += [('(gaps between ops inside a program)', result['in_program_s']),
             ('(window edges: window_s less the trace\'s span)', edges_s)]
    width_us = (result['hi'] - result['lo']) * 1e6
    log(f'idle_by_span: {result["steps"]} steps matched on both sides; '
        f'offset bracket [{result["lo"]:.6f}, {result["hi"]:.6f}] s, '
        + (f'width {width_us:.1f} us' if width_us >= 0 else
           f'the bounds CONFLICT by {-width_us:.1f} us (a step starts on the '
           f'device before its dispatch)')
        + f'; {recorder.dropped} events dropped')
    for name, seconds in rows:
        log(f'idle_by_span: {seconds:9.4f} s {100 * seconds / window_s:6.2f} %'
            f'  {name}')
    total = sum(s for _, s in rows)
    log(f'idle_by_span: rows sum to {total:.4f} s = '
        f'{100 * total / window_s:.2f} % of the window; device_idle of this '
        f'run is {idle_s:.4f} s = {100 * idle_s / window_s:.2f} %')
    return result['seconds']


def _newest_recorder():
    try:
        from video_features_tpu.obs import spans
    except ImportError:
        return None
    attached = getattr(spans, 'attached', None)     # a parent has none
    recorders = attached() if attached is not None else []
    return recorders[-1] if recorders else None


# -- the join and the attribution, on plain data (tests/bench) ---------------

def attribute(trace: Dict, host_events: Sequence[Dict], dropped: int = 0
              ) -> Dict:
    """``trace`` as ``trace_reduce.load_xplane`` gives it; ``host_events`` as
    ``SpanRecorder.snapshot()`` gives them (Chrome trace events: ``ts`` and
    ``dur`` in microseconds on the program's clock). Returns ``{'refused':
    why}`` or the table: ``seconds`` (name → idle seconds between programs,
    the mean over device planes), ``lo``/``hi`` (the offset's bounds, those
    furthest apart over planes; ``lo > hi`` where they conflict), ``steps``,
    ``in_program_s``, ``trace_span_s``."""
    if dropped:
        return {'refused': f'the recorder dropped {dropped} events'}
    steps = _host_steps(host_events)
    if not steps:
        return {'refused': 'no model/device_wait pair carries a step ordinal'}
    planes = trace_reduce.device_planes(trace)
    if not planes:
        return {'refused': 'the trace has no device plane'}
    t0, t1 = trace_reduce.window_of(trace)
    tables, brackets, matched, in_program = [], [], 0, 0.0
    for plane in planes:
        modules = trace_reduce.line_events(plane, trace_reduce.MODULES_LINE)
        joined = _join(steps, modules, t0)
        if 'refused' in joined:
            return joined
        lo, hi, n = joined['lo'], joined['hi'], joined['steps']
        if abs(hi - lo) > MAX_BRACKET_S:
            return {'refused': f'the bounds of the offset lie '
                               f'{1e3 * (hi - lo):.3f} ms apart (lo {lo:.6f}, '
                               f'hi {hi:.6f}), over {1e3 * MAX_BRACKET_S:g} ms'}
        offset = (lo + hi) / 2
        gaps = [((s - t0) / 1e9 + offset, (s - t0 + d) / 1e9 + offset)
                for s, d, _ in trace_reduce.gaps(modules, t0, t1)]
        tables.append(_chain(gaps, host_events, joined['dispatch_tids']))
        brackets.append((lo, hi))
        matched += n
        in_program += (trace_reduce.busy_union_ns(modules)
                       - trace_reduce.busy_union_ns(
                           trace_reduce.op_events(plane))) / 1e9
    seconds: Dict[str, float] = {}
    for table in tables:
        for name, s in table.items():
            seconds[name] = seconds.get(name, 0.0) + s / len(planes)
    lo, hi = max(brackets, key=lambda b: abs(b[1] - b[0]))
    return {'seconds': seconds, 'lo': lo, 'hi': hi,
            'steps': matched // len(planes),
            'in_program_s': in_program / len(planes),
            'trace_span_s': (t1 - t0) / 1e9}


def _host_steps(events: Sequence[Dict]) -> List[Dict]:
    """One record a dispatched step that has both its spans, by ordinal."""
    model, ready = {}, {}
    for e in events:
        args = e.get('args') or {}
        if e.get('ph') != 'X' or 'step' not in args:
            continue
        if e['name'] == 'model':
            model[args['step']] = e
        elif e['name'] == 'device_wait':
            ready[args['step']] = e
    return [{'step': k, 'program': model[k]['args'].get('program'),
             'tid': model[k]['tid'], 't_disp': model[k]['ts'] / 1e6,
             't_ready': (ready[k]['ts'] + ready[k]['dur']) / 1e6}
            for k in sorted(model) if k in ready]


def _join(steps: List[Dict], modules, t0: float) -> Dict:
    """Match the device's events of the host's programs, from the tail, to the
    host's steps; the bracket of the clock offset, in seconds (device times
    taken from ``t0`` on, so that nanoseconds since some epoch keep their
    digits)."""
    programs = {s['program'] for s in steps}
    device = sorted((s, s + d, trace_reduce.strip_fingerprint(n))
                    for n, s, d in modules
                    if trace_reduce.strip_fingerprint(n) in programs)
    if not device:
        return {'refused': f'no device event is named {sorted(programs)}'}
    if len(device) > len(steps):
        return {'refused': f'{len(device)} steps on the device, '
                           f'{len(steps)} on the host'}
    early, tail = steps[:-len(device)], steps[-len(device):]
    ordinals = [s['step'] for s in tail]
    if ordinals != list(range(ordinals[0], ordinals[0] + len(tail))):
        return {'refused': 'a step is missing on the host: the ordinals of '
                           'the matched steps are not consecutive'}
    los, his = [], []
    for step, (start, end, name) in zip(tail, device):
        if step['program'] != name:
            return {'refused': f'step {step["step"]} is {step["program"]} on '
                               f'the host and {name} on the device'}
        los.append(step['t_disp'] - (start - t0) / 1e9)
        his.append(step['t_ready'] - (end - t0) / 1e9)
    lo, hi = max(los), min(his)
    # a host step left over must have ended before the trace began (warm-up);
    # one that was still running then has no event: a step missing on the
    # device, which the bracket alone cannot tell from a warm-up step
    first_start = (device[0][0] - t0) / 1e9 + hi
    if any(s['t_ready'] > first_start for s in early):
        return {'refused': 'a step is missing on the device: a host step '
                           'before the matched ones ends inside the trace'}
    return {'lo': lo, 'hi': hi, 'steps': len(device),
            'dispatch_tids': {s['tid'] for s in tail}}


def _chain(gaps: List[Interval], events: Sequence[Dict], dispatch_tids
           ) -> Dict[str, float]:
    """Seconds of ``gaps`` (host clock) by the blocking chain's last name."""
    if not gaps:
        return {}
    first, last = gaps[0][0], gaps[-1][1]
    by_tid: Dict[int, list] = {}
    by_name: Dict[str, list] = {}
    for e in events:
        if e.get('ph') != 'X':
            continue
        a, b = e['ts'] / 1e6, (e['ts'] + e['dur']) / 1e6
        if b <= first or a >= last:
            continue
        if e['tid'] in dispatch_tids:
            by_tid.setdefault(e['tid'], []).append((a, b, e['name']))
        else:
            by_name.setdefault(e['name'], []).append((a, b))
    out: Dict[str, float] = {}
    remaining = gaps
    # 1. what the dispatch thread itself was inside of
    blocked: Dict[str, List[Interval]] = {}
    for spans in by_tid.values():
        for a, b, name in _innermost(spans):
            if name != 'input_wait':
                blocked.setdefault(name, []).append((a, b))
    # 2., 3. else the producer side: batch assembly and transfer, then decode
    levels = [(name, _union(ivs)) for name, ivs in blocked.items()]
    levels += [(names[0], _union([iv for n in names
                                  for iv in by_name.get(n, [])]))
               for names in PRODUCER_CHAIN]
    for name, cover in levels:
        taken, remaining = _take(remaining, cover)
        if taken:
            out[name] = out.get(name, 0.0) + taken
    out[UNEXPLAINED] = sum(b - a for a, b in remaining)
    return out


def _innermost(spans: List[Tuple[float, float, str]]
               ) -> List[Tuple[float, float, str]]:
    """Disjoint segments of one thread's nested spans, each under the name of
    the innermost span that covers it."""
    out, stack, cursor = [], [], 0.0

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close_until(a)
        if stack:
            if a > cursor:
                out.append((cursor, a, stack[-1][1]))
            b = min(b, stack[-1][0])      # a child never outlives its parent
        cursor = max(cursor, a)
        stack.append((b, name))
    close_until(float('inf'))
    return out


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        elif b > a:
            out.append((a, b))
    return out


def _take(remaining: List[Interval], cover: List[Interval]
          ) -> Tuple[float, List[Interval]]:
    """(seconds of ``remaining`` that ``cover`` covers, what is left); both
    lists sorted and disjoint."""
    if not cover:
        return 0.0, remaining
    starts = [a for a, _ in cover]
    taken, left = 0.0, []
    for a, b in remaining:
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        at = a
        while i < len(cover) and cover[i][0] < b:
            ca, cb = cover[i]
            if cb > at:
                if ca > at:
                    left.append((at, ca))
                taken += min(cb, b) - max(ca, at)
                at = min(cb, b)
            i += 1
        if at < b:
            left.append((at, b))
    return taken, left
