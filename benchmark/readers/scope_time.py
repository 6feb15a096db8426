"""Device time under one of the program's own scopes, in ms a unit saved.

The device trace names an op event by its HLO instruction (``%fusion.1691 =
...``); the program knows which ``jax.named_scope`` each instruction of each
compiled step was traced under and says so, once per executable
(``video_features_tpu.obs.scopes``: ``noted()`` → ``{program: {'instructions':
{'%fusion.1691': 'raft_update/raft_gru', ...}, 'no_metadata': ['%copy.782',
...], 'missing': [...]}}``). This reader joins the two:

* an ``XLA Ops`` event lies under the ``XLA Modules`` event that covers its
  start; that module's name less its fingerprint is the program; the event's
  first word is the instruction; the program's map gives its scope path;
* events on the ``XLA Ops`` line NEST (a ``%while`` event covers its body's
  events), so an event counts with its **self time**: its duration less the
  union of the events it contains. Self times sum to the union of the ops;
* a scope's seconds are the self times of every event whose path contains it
  (``raft_update`` contains ``raft_update/raft_gru``); ``(unscoped)`` those of
  events whose instruction is in the map under no scope; ``(no op_name)``
  those of instructions the module has and the program never wrote: the copies
  the compiler's layout assignment and loop-carry insertion make, ``copy-done``.
  They are the compiler's, no scope can name them, and they are a row, not a
  fault; an event whose instruction the module does NOT have is one.

The metric's ``scope`` names the row; its value is seconds under the scope ×
1000 ÷ units saved in the window: a cost, not a share, so one part getting
faster does not make the others read worse. The metrics of one trace share one
table, logged whole; ``device_ops`` hands the harness the same table's rows by
scope path for ``breakdown.device_ops``, names a recompile keeps.

No number, never a guess, when: no map is noted for a program that ran (a
parent commit, a synthetic trace); a map's ``missing`` is not empty (the
compile cache served an executable with an older program's metadata) or one
program has several executables noted; events not found in the map hold over
1 % of the self time; the self times do not sum to the ops' union within 1 %;
no unit was saved.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional

import trace_reduce

UNSCOPED = '(unscoped)'
NO_OP_NAME = '(no op_name: inserted by the compiler)'
UNMAPPED = '(instruction not in the map)'
MAX_UNMAPPED = 0.01
MAX_SUM_ERROR = 0.01

_memo: Dict = {}     # a trace's join, and the table its metrics share


def read(ctx) -> Optional[float]:
    memo = _joined(ctx['trace'])
    if 'table' not in memo:
        memo['table'] = _table(ctx, memo['result'])
    table = memo['table']
    if table is None or ctx['metric']['scope'] not in table:
        return None
    return 1e3 * table[ctx['metric']['scope']] / ctx['units']


def device_ops(trace: Dict, top: int = 10) -> Optional[List[List]]:
    """The ``top`` rows of the table by scope path, ``[[path, seconds], ...]``
    with the leftover rows among them, or ``None`` where ``attribute``
    refuses or the programs that ran open no scope (then the harness keeps
    the HLO names, which say more than one ``(unscoped)`` row)."""
    result = _joined(trace)['result']
    if 'refused' in result or not result['paths']:
        return None
    rows = dict(result['paths'])
    rows.update({UNSCOPED: result['scopes'][UNSCOPED],
                 NO_OP_NAME: result['no_op_name_s'],
                 UNMAPPED: result['unmapped_s']})
    ranked = sorted(rows.items(), key=lambda kv: -kv[1])[:top]
    return [[name, seconds] for name, seconds in ranked if seconds > 0]


def _joined(trace: Dict) -> Dict:
    if _memo.get('trace') is not trace:
        _memo.clear()
        _memo.update(trace=trace, result=attribute(trace, _noted()))
    return _memo


def _table(ctx, result: Dict) -> Optional[Dict[str, float]]:
    log, units = ctx['log'], ctx['units']
    if 'refused' not in result and not units:
        result = {'refused': 'no unit was saved in the window'}
    if 'refused' in result:
        log(f'scope_time: no number: {result["refused"]}')
        return None
    modules_s = ctx['reduced']['modules_total_s']

    def row(name: str, seconds: float) -> None:
        log(f'scope_time: {seconds:9.4f} s {1e3 * seconds / units:9.3f} '
            f'ms/unit {100 * seconds / modules_s:6.2f} %  {name}')

    log(f'scope_time: {result["events"]} op events under '
        f'{sorted(result["programs"])}; self time by scope path:')
    rows = sorted(result['paths'].items(), key=lambda kv: -kv[1])
    rows += [(UNSCOPED, result['scopes'][UNSCOPED]),
             (NO_OP_NAME, result['no_op_name_s']),
             (UNMAPPED, result['unmapped_s'])]
    for name, seconds in rows:
        row(name, seconds)
    total = sum(s for _, s in rows)
    log(f'scope_time: rows sum to {total:.4f} s = '
        f'{100 * total / modules_s:.2f} % of modules_total_s '
        f'{modules_s:.4f} s; in no row: {result["in_program_gaps_s"]:.4f} s '
        f'of gaps between ops inside a program, {result["outside_s"]:.4f} s '
        f'of ops outside every module event')
    log('scope_time: by scope (an event counts under every scope of its '
        'path):')
    for name, seconds in sorted(result['scopes'].items(),
                                key=lambda kv: -kv[1]):
        row(name, seconds)
    return result['scopes']


def _noted() -> Dict[str, Dict]:
    try:
        from video_features_tpu.obs import scopes
    except ImportError:                     # a parent commit has none
        return {}
    return scopes.noted()


# -- the join, on plain data (tests/bench/test_scope_time.py) ---------------

def self_times(events: List[trace_reduce.Event]) -> List[List]:
    """[name, start, self time] of each event of one line: the duration less
    the union of the events that start inside it. An event that outlives the
    one it starts in keeps its whole duration; the outer one loses the part
    they share."""
    out: List[List] = []
    stack: List[List] = []        # [end, covered until, index into out]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            end, cursor, i = stack[-1]
            b = min(start + dur, end)
            if b > cursor:
                out[i][2] -= b - max(start, cursor)
                stack[-1][1] = b
        out.append([name, start, dur])
        if dur > 0:
            stack.append([start + dur, start, len(out) - 1])
    return out


def attribute(trace: Dict, noted: Dict[str, Dict]) -> Dict:
    """``trace`` as ``trace_reduce.load_xplane`` gives it, ``noted`` as
    ``obs.scopes.noted()``. Returns ``{'refused': why}`` or the table, in
    seconds, the mean over device planes: ``paths`` (scope path → self time),
    ``scopes`` (scope → self time of every event whose path contains it, and
    ``(unscoped)``), ``no_op_name_s``, ``unmapped_s``, ``in_program_gaps_s``,
    ``outside_s``, ``programs``, ``events``."""
    planes = trace_reduce.device_planes(trace)
    if not planes:
        return {'refused': 'the trace has no device plane'}
    paths: Dict[str, float] = {}
    no_map: Dict[str, float] = {}
    not_found = bare = outside = self_total = union = modules_ns = 0.0
    programs, events = set(), 0
    bare_names = {program: frozenset(record.get('no_metadata') or ())
                  for program, record in noted.items()}
    instruction_of: Dict[str, str] = {}    # a few thousand names, 1e6 events
    for plane in planes:
        modules = sorted(
            (s, s + d, trace_reduce.strip_fingerprint(n)) for n, s, d in
            trace_reduce.line_events(plane, trace_reduce.MODULES_LINE))
        starts = [m[0] for m in modules]
        modules_ns += sum(b - a for a, b, _ in modules)
        ops = trace_reduce.line_events(plane, trace_reduce.OPS_LINE)
        union += trace_reduce.busy_union_ns(ops)
        events += len(ops)
        for name, start, own in self_times(ops):
            self_total += own
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start >= modules[i][1]:
                outside += own
                continue
            program = modules[i][2]
            programs.add(program)
            record = noted.get(program)
            if record is None:
                no_map[program] = no_map.get(program, 0.0) + own
                continue
            instruction = instruction_of.get(name)
            if instruction is None:
                instruction = instruction_of[name] = \
                    trace_reduce.short_op(name).split(' ')[0]
            path = record['instructions'].get(instruction)
            if path is not None:
                paths[path] = paths.get(path, 0.0) + own
            elif instruction in bare_names[program]:
                bare += own
            else:
                not_found += own
    if not self_total:
        return {'refused': 'the trace has no op event'}
    unmapped = not_found + sum(no_map.values())
    if unmapped - not_found > MAX_UNMAPPED * self_total:
        return {'refused': f'no scope map is noted for {sorted(no_map)} '
                           f'(the program exports none, or lowered none)'}
    for program in sorted(programs & set(noted)):
        record = noted[program]
        if record.get('missing'):
            return {'refused': f'{program}: the lowering names '
                               f'{record["missing"]} and the compiled text '
                               f'does not: the compile cache served an '
                               f'older program\'s metadata'}
        if record.get('variants', 1) > 1:
            return {'refused': f'{program}: {record["variants"]} different '
                               f'executables noted, their instruction names '
                               f'collide'}
    if unmapped > MAX_UNMAPPED * self_total:
        return {'refused': f'events not found in the map hold '
                           f'{100 * unmapped / self_total:.2f} % of the '
                           f'self time'}
    if abs(self_total - union) > MAX_SUM_ERROR * union:
        return {'refused': f'self times sum to {self_total / 1e9:.6f} s, '
                           f'the ops\' union is {union / 1e9:.6f} s'}
    n = len(planes) * 1e9
    scopes: Dict[str, float] = {UNSCOPED: paths.pop('', 0.0) / n}
    for path, ns in paths.items():
        for scope in path.split('/'):
            scopes[scope] = scopes.get(scope, 0.0) + ns / n
    return {'paths': {k: v / n for k, v in paths.items()}, 'scopes': scopes,
            'no_op_name_s': bare / n, 'unmapped_s': unmapped / n,
            'outside_s': outside / n,
            'in_program_gaps_s': (modules_ns - (self_total - outside)) / n,
            'programs': programs, 'events': events}
