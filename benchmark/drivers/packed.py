"""Driver ``packed``: corpus mode — one client, closed loop,
``extractor.extract_packed(worklist)`` as ``cli.main`` calls it under
``pack_across_videos=true``: device batches fill across video boundaries and
a worklist has one tail batch."""
from __future__ import annotations

import math


# what cli.main passes: the yml's pack_decode_ahead (2 in every family's yml)
DECODE_AHEAD = 2


def run_pass(extractor, items) -> None:
    extractor.extract_packed([i['path'] for i in items],
                             decode_ahead=DECODE_AHEAD)


warm = run_pass


def batch_slots(extractor, rows_per_video) -> int:
    """Slots of one worklist: full batches and one padded tail."""
    bs = int(extractor.packed_batch_size())
    return math.ceil(sum(rows_per_video) / bs) * bs
