"""Frame-wise families on the packed path hand frames over as they decode.

A ``VideoLoader`` batch is gathered whole before any frame of it is
yielded, so the packed loaders (in-process, farm, fused) read the decode
lanes' chunk (``streaming.CHUNK_WINDOWS``) a batch, not the device batch:
a lane's first chunk then waits for at most that many decoded frames, not
for the whole video. The per-video loop keeps ``batch_size``, where a
loader batch is one device step. The lanes time each video's first chunk
(``first_chunk_s``) under the tracer.
"""
import time
import types

import numpy as np
import pytest

from tools.make_sample_video import write_noise_clip
from video_features_tpu.config import load_config
from video_features_tpu.extract.streaming import CHUNK_WINDOWS
from video_features_tpu.io.video import VideoLoader
from video_features_tpu.registry import create_extractor

N_FRAMES = 100                # longer than two chunks; 25 fps


@pytest.fixture(scope='module')
def long_clip(tmp_path_factory):
    d = tmp_path_factory.mktemp('handover')
    return write_noise_clip(d / 'long.mp4', N_FRAMES, seed=7)


@pytest.fixture(scope='module')
def extractor(long_clip, tmp_path_factory):
    """A frame-wise extractor at a device batch far above the clip, as
    the packed corpus cell runs it; nothing here steps the device."""
    d = tmp_path_factory.mktemp('handover_out')
    return create_extractor(load_config('resnet', overrides=dict(
        video_paths=[long_clip], device='cpu', model_name='resnet18',
        batch_size=1024, allow_random_weights=True,
        on_extraction='save_numpy', output_path=str(d / 'out'),
        tmp_path=str(d / 'tmp'))))


@pytest.fixture
def decoded(monkeypatch):
    """How many frames the loaders have decoded so far."""
    count = [0]
    frames = VideoLoader._retimed_frames

    def counting(self):
        for frame in frames(self):
            count[0] += 1
            yield frame

    monkeypatch.setattr(VideoLoader, '_retimed_frames', counting)
    return count


@pytest.mark.parametrize('segment, frames', [
    (None, N_FRAMES),
    ((0.0, 1.0), 25),            # [0, 25): the early stop ends the decode
])
def test_packed_windows_yield_after_at_most_one_lane_chunk(
        extractor, long_clip, decoded, segment, frames):
    """The first window comes after at most ``CHUNK_WINDOWS`` decoded
    frames, and a segment query stops decoding within one chunk of the
    range's end; the windows are the per-video loader's frames, in
    order, with the same timestamps."""
    task = types.SimpleNamespace(path=long_clip, info={}, segment=segment)
    windows = extractor.packed_windows(task)
    first = next(windows)
    assert 0 < decoded[0] <= CHUNK_WINDOWS
    got = [first] + list(windows)
    assert decoded[0] <= frames + CHUNK_WINDOWS
    assert task.info['fps'] == pytest.approx(25.0)

    loader = extractor._make_loader(long_clip)
    try:
        want = [(f, t) for batch, times, _ in loader
                for f, t in zip(batch, times)][:frames]
    finally:
        loader.close()
    assert len(got) == len(want) == frames
    for (frame, t_ms), (ref, ref_t) in zip(got, want):
        assert t_ms == ref_t
        np.testing.assert_array_equal(frame, ref)


def test_packed_loaders_open_at_the_chunk_and_per_video_at_the_batch(
        extractor, long_clip, monkeypatch):
    """In-process packed windows, the farm's recipe and the fused recipe
    read ``CHUNK_WINDOWS`` frames a loader batch; the per-video loop's
    loader keeps the extractor's ``batch_size``."""
    from video_features_tpu.parallel.packing import build_fused_recipe
    assert extractor.farm_recipe().batch_size == CHUNK_WINDOWS
    assert build_fused_recipe({'resnet': extractor}).batch_size == \
        CHUNK_WINDOWS
    loader = extractor._make_loader(long_clip)
    loader.close()
    assert loader.batch_size == extractor.batch_size == 1024

    opened = []
    make = type(extractor)._make_loader

    def spy(self, path, *args):
        opened.append(make(self, path, *args))
        return opened[-1]

    monkeypatch.setattr(type(extractor), '_make_loader', spy)
    task = types.SimpleNamespace(path=long_clip, info={}, segment=None)
    windows = extractor.packed_windows(task)
    next(windows)
    windows.close()
    assert [lo.batch_size for lo in opened] == [CHUNK_WINDOWS]


class _Task:
    def __init__(self, path):
        self.path, self.emitted = path, 0
        self.exhausted = self.failed = False


def test_lane_first_chunk_seconds_time_each_videos_first_chunk():
    """Tracer on: ``first_chunk_s`` sums, over a lane's videos, the seconds
    from opening a video to handing over its first chunk — the open's
    wait is in it, the later chunks' are not, so it stays under
    ``busy_s``. Tracer off it stays 0."""
    from video_features_tpu.extract import streaming
    from video_features_tpu.obs.spans import SpanRecorder
    from video_features_tpu.utils.tracing import Tracer
    open_s, late_s = 0.03, 0.002

    def open_windows(task):
        time.sleep(open_s)                  # probe, open, first decode
        for i in range(CHUNK_WINDOWS + 40):
            if i >= CHUNK_WINDOWS:
                time.sleep(late_s)          # the later chunks' decode
            yield np.zeros((4,), np.uint8), i

    tasks = [_Task(f'v{i}') for i in range(4)]
    stats = []
    out = list(streaming.stream_windows_across_lanes(
        iter(tasks), open_windows, 2,
        tracer=Tracer(enabled=True, recorder=SpanRecorder()), stats=stats))
    assert len(out) == 4 * (CHUNK_WINDOWS + 40)
    assert sum(s['videos'] for s in stats) == 4
    for s in (s for s in stats if s['videos']):
        assert s['first_chunk_s'] >= s['videos'] * open_s
        assert 0 < s['first_chunk_s'] < s['busy_s']
        assert s['busy_s'] - s['first_chunk_s'] >= s['videos'] * 40 * late_s

    quiet = []
    list(streaming.stream_windows_across_lanes(
        iter([_Task('q')]), open_windows, 2, stats=quiet))
    assert all(s['first_chunk_s'] == 0.0 == s['busy_s'] for s in quiet)
