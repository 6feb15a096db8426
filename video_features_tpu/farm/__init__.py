"""Decode farm: multi-process decoder workers feeding the packer.

One in-process decoder is capped by the GIL and one process's swscale.
This subsystem runs N decoder worker PROCESSES — each driving
the exact decode + host-transform stack the in-process path runs
(``io/video.py`` + ``ops/host_transforms.py``) — and ships decoded
windows to the packed scheduler through bounded shared-memory byte
rings, so pixel data never takes the pickle hop.

Entry point: :class:`DecodeFarm` (``farm/farm.py``), consumed by
``parallel.packing.run_packed`` when ``decode_workers > 1`` and the
extractor publishes a picklable decode recipe (``farm/recipes.py``).
Contract: the farm's window stream is drop-in for
``extract.streaming.stream_windows_across_videos`` — same
``(task, window, meta)`` items, FLUSH/NUDGE sentinels, per-video fault
isolation, and ``task.emitted``/``exhausted`` accounting — so outputs
are byte-identical to ``decode_workers=1`` at any worker count.

See docs/decode_farm.md for architecture, SHM sizing, and knobs.
"""
from video_features_tpu.farm.farm import (  # noqa: F401
    DecodeFarm, FarmUnavailable, farm_available,
)
from video_features_tpu.farm.recipes import (  # noqa: F401
    FramewiseRecipe, StackRecipe,
)
