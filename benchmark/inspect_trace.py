#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, and the event names that took
the most time on each line.

    python3 benchmark/inspect_trace.py <dir with plugins/profile/.. or .xplane.pb> [top] [substring of the names to print in full]
"""
import sys

if __name__ == '__main__':
    import os
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_reduce
    from jax.profiler import ProfileData
    path = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 25
    grep = sys.argv[3] if len(sys.argv) > 3 else None   # print these in full
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f'PLANE {plane.name}')
        for line in plane.lines:
            sums, n = {}, 0
            for e in line.events:
                sums[e.name] = sums.get(e.name, 0.0) + e.duration_ns
                n += 1
            print(f'  LINE {line.name!r}: {n} events, {len(sums)} names, '
                  f'{sum(sums.values()) / 1e9:.3f} s')
            for name, ns in sorted(sums.items(), key=lambda kv: -kv[1])[:top]:
                print(f'    {ns / 1e9:10.4f} s  {name[:150]}')
            if grep:
                for name, ns in sums.items():
                    if grep in name:
                        print(f'    FULL {ns / 1e9:10.4f} s  {name}')
