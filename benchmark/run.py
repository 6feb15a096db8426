#!/usr/bin/env python3
"""Entry point of the benchmark (see ``harness.py`` and ``README.md``).

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The body lives under the ``__main__`` check and jax is imported inside it:
decode-farm workers are spawned and re-import the entry module; they must
neither run the benchmark again nor touch jax (the chip belongs to one
process).
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

if __name__ == '__main__':
    import os
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    sys.path.insert(0, os.path.dirname(here))     # the program's package
    import harness
    raise SystemExit(harness.main(t_start=T_START))
