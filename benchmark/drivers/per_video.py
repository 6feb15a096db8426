"""Driver ``per_video``: the body of ``cli.main``'s loop — one client, closed
loop, ``extractor._extract(path)`` video after video. Every video pays its own
tail batch; the program's read-ahead (``decode_workers`` threads, ``inflight``
device batches) is whatever its configuration ships."""
from __future__ import annotations

import math


def run_pass(extractor, items) -> None:
    for item in items:
        extractor._extract(item['path'])


warm = run_pass


def batch_slots(extractor, rows_per_video) -> int:
    """Device-batch slots the loop spends on videos of these row counts:
    each video's last batch is padded to the compiled batch."""
    bs = int(extractor.batch_size)
    return sum(math.ceil(r / bs) * bs for r in rows_per_video)
