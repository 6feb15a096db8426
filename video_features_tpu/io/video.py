"""Video decode & batching layer.

Re-design of reference utils/io.py (VideoLoader, 176 LoC) for a TPU pipeline:

  * frames are yielded as **stacked NumPy arrays** (B, H, W, 3) ready for a
    single host→HBM transfer, not Python lists of per-frame tensors;
  * fps retargeting has two backends — an exact ffmpeg re-encode (reference
    io.py:14-36) used when an ffmpeg binary exists, and a pure
    frame-index-resampling path (ffmpeg's ``fps=`` filter semantics: for each
    output slot at time k/fps pick the nearest source frame) used otherwise;
  * the decode backend is pluggable: cv2 today, the native C++ libav service
    later, behind the same ``FrameDecoder`` protocol.

Contract parity with the reference loader:
  * iteration yields ``(batch, times_ms, indices)``;
  * ``timestamp_ms = index / fps * 1000`` (reference io.py:132);
  * first batch has ``batch_size`` frames, later ones read
    ``batch_size - overlap`` new frames and reuse ``overlap`` cached ones
    (reference io.py:109-154); the final batch may be short;
  * ``len(loader)`` is the total frame count;
  * temporary re-encodes are deleted unless ``keep_tmp`` (reference io.py:159-165).
"""
from __future__ import annotations

import hashlib
import itertools
import logging
import os
import shutil
import subprocess
from pathlib import Path
from typing import Callable, Dict, Iterator, List, Optional, Tuple, Union

import cv2
import numpy as np

# memoized which_ffmpeg result; None = not probed yet ('' = no binary).
# Reset to None in tests that monkeypatch the PATH.
_FFMPEG_PATH: Optional[str] = None

_REENCODE_SEQ = itertools.count()


def reencode_out_path(video_path: Union[str, os.PathLike],
                      tmp_path: Union[str, os.PathLike]) -> str:
    """Collision-free re-encode target in ``tmp_path``. The stem alone
    is not enough: decode-farm worker processes (and the threaded
    decode-ahead pool) re-encode CONCURRENTLY into one shared tmp_path,
    so same-stem videos — or the same video open in two processes —
    would clobber each other's tmp file mid-read and delete each
    other's on close(). Path digest separates same-stem sources; pid +
    a per-process counter separate concurrent opens of one source."""
    digest = hashlib.sha1(
        os.path.abspath(os.fspath(video_path)).encode()).hexdigest()[:8]
    return os.path.join(
        os.fspath(tmp_path),
        f'{Path(video_path).stem}_{digest}_{os.getpid()}'
        f'_{next(_REENCODE_SEQ)}_new_fps.mp4')


def which_ffmpeg() -> str:
    """Path to an ffmpeg binary, or '' (reference utils/utils.py:181-194).

    ``shutil.which``, memoized: the old ``subprocess.run(['which', ...])``
    probe spawned a process per VideoLoader (twice when fps retiming was
    requested) and broke on hosts without a ``which`` binary.
    """
    global _FFMPEG_PATH
    if _FFMPEG_PATH is None:
        _FFMPEG_PATH = shutil.which('ffmpeg') or ''
    return _FFMPEG_PATH


def get_video_props(path: Union[str, os.PathLike]) -> Dict[str, float]:
    """fps / num_frames / height / width via cv2 (reference io.py:167-176)."""
    cap = cv2.VideoCapture(str(path))
    try:
        props = dict(
            fps=cap.get(cv2.CAP_PROP_FPS),
            num_frames=int(cap.get(cv2.CAP_PROP_FRAME_COUNT)),
            height=int(cap.get(cv2.CAP_PROP_FRAME_HEIGHT)),
            width=int(cap.get(cv2.CAP_PROP_FRAME_WIDTH)),
        )
    finally:
        cap.release()
    return props


def reencode_video_with_diff_fps(video_path: str, tmp_path: str,
                                 extraction_fps: float) -> str:
    """ffmpeg CFR re-encode to ``extraction_fps`` (reference io.py:14-36).

    Raises ``RuntimeError`` when ffmpeg exits non-zero or writes no
    output — the old ``subprocess.call`` ignored the exit code and the
    missing file surfaced later as an opaque cv2 probe error; the caller
    (``VideoLoader``) degrades to index resampling instead.
    """
    ffmpeg = which_ffmpeg()
    assert ffmpeg != '', 'ffmpeg is not installed'
    os.makedirs(tmp_path, exist_ok=True)
    new_path = reencode_out_path(video_path, tmp_path)
    cmd = [ffmpeg, '-hide_banner', '-loglevel', 'panic', '-y', '-i', video_path,
           '-filter:v', f'fps=fps={extraction_fps}', new_path]
    rc = subprocess.call(cmd)
    if rc != 0 or not os.path.isfile(new_path):
        raise RuntimeError(
            f'ffmpeg re-encode of {video_path} exited {rc} '
            f'({"no output written" if not os.path.isfile(new_path) else new_path})')
    return new_path


def resample_frame_indices(num_src_frames: int, src_fps: float,
                           target_fps: float) -> np.ndarray:
    """Source-frame index per output slot for CFR retiming to ``target_fps``.

    Pure-host equivalent of ffmpeg's ``fps=`` filter with 'near' rounding:
    output slot k sits at time k/target_fps and takes the nearest source
    frame, duplicating (upsampling) or dropping (downsampling) as needed.
    """
    if num_src_frames <= 0:
        return np.zeros((0,), dtype=np.int64)
    duration = num_src_frames / src_fps
    num_out = max(int(round(duration * target_fps)), 1)
    k = np.arange(num_out)
    src_idx = np.round(k * src_fps / target_fps).astype(np.int64)
    return np.clip(src_idx, 0, num_src_frames - 1)


class Cv2FrameDecoder:
    """Sequential RGB frame decoder over cv2.VideoCapture.

    Yields (source_index, frame HWC uint8 RGB). Handles the cv2 quirk where
    frame 0 occasionally fails to decode (reference io.py:99-107).
    """

    def __init__(self, path: str):
        self.path = path
        self.cap: Optional[cv2.VideoCapture] = None

    def __iter__(self) -> Iterator[Tuple[int, np.ndarray]]:
        self.cap = cv2.VideoCapture(self.path)
        ok, first = self.cap.read()
        if ok:
            # frame 0 decodes fine → restart from the beginning
            self.cap.release()
            self.cap = cv2.VideoCapture(self.path)
        else:
            # structured channel, not print: decode chatter must never
            # interleave with the on_extraction=print feature stream
            from video_features_tpu.obs.events import event
            event(logging.WARNING,
                  'first frame failed to decode (cv2 missing-frame '
                  'quirk); continuing from the next readable frame',
                  video=self.path)
        idx = 0
        while True:
            ok, bgr = self.cap.read()
            if not ok:
                break
            yield idx, cv2.cvtColor(bgr, cv2.COLOR_BGR2RGB)
            idx += 1
        self.release()

    def release(self) -> None:
        if self.cap is not None:
            self.cap.release()
            self.cap = None


class VideoLoader:
    """Batched streaming frame iterator.

    Args:
        path: video file path.
        batch_size: frames per yielded batch.
        fps: retarget to this frame rate (mutually exclusive with ``total``).
        total: retarget so the whole video yields ~``total`` frames.
        tmp_path: where ffmpeg re-encodes land (ffmpeg backend only).
        keep_tmp: keep the re-encoded temp file.
        transform: per-frame callable (HWC uint8 RGB → anything). When None,
            raw frames are returned and batches arrive stacked as one
            (B, H, W, 3) uint8 array.
        transform_workers: >1 runs the transform over a thread pool,
            pipelined ahead of the consumer (PIL/cv2 release the GIL in
            their core loops, so host preprocessing scales with threads —
            it is the usual bottleneck once the device is fast).
        overlap: frames shared between consecutive batches (flow pairing).
        use_ffmpeg: force (True)/forbid (False) the ffmpeg-binary re-encode
            backend. Default (None): the binary when present (exact
            reference parity) → the in-process native re-encoder (same
            fps-filter + libx264-default semantics, no binary needed) →
            pure index resampling.
        backend: frame decode backend — 'native' (C++ libav service),
            'cv2', or 'auto' (native when buildable, else cv2).
    """

    def __init__(
        self,
        path: Union[str, os.PathLike],
        batch_size: int = 1,
        fps: Optional[float] = None,
        total: Optional[int] = None,
        tmp_path: Union[str, os.PathLike] = 'tmp',
        keep_tmp: bool = False,
        transform: Optional[Callable] = None,
        transform_workers: int = 1,
        overlap: int = 0,
        use_ffmpeg: Optional[bool] = None,
        backend: str = 'auto',
    ):
        assert isinstance(batch_size, int) and batch_size > 0
        assert isinstance(overlap, int) and 0 <= overlap < batch_size
        assert isinstance(transform_workers, int) and transform_workers >= 1
        if fps is not None and total is not None:
            raise ValueError("'fps' and 'total' are mutually exclusive")

        assert backend in ('auto', 'native', 'cv2'), backend
        self.batch_size = batch_size
        self.transform = transform
        self.transform_workers = transform_workers if transform else 1
        self.overlap = overlap
        self.keep_tmp = keep_tmp
        self.backend = backend
        self._tmp_file: Optional[str] = None

        path = str(path)
        if not os.path.isfile(path):
            # probe failures otherwise surface as opaque downstream errors
            # (e.g. cv2 reporting negative frame counts)
            raise FileNotFoundError(f'video does not exist: {path}')
        props = self._probe_props(path)
        self.height, self.width = props['height'], props['width']
        src_fps, src_frames = props['fps'], props['num_frames']

        if total is not None:
            fps = total * src_fps / max(src_frames, 1)

        # Retiming backend resolution: the ffmpeg binary when present
        # (exact reference parity), else the in-process native re-encoder
        # (same fps-filter semantics + libx264 at the CLI defaults —
        # native/vfdecode.cc vf_reencode_fps), else pure index resampling.
        native_reencode = False
        if use_ffmpeg is None:
            use_ffmpeg = which_ffmpeg() != ''
            if not use_ffmpeg:
                from video_features_tpu.io import native as native_mod
                native_reencode = native_mod.available()

        self._index_map: Optional[np.ndarray] = None
        self._decoder = None
        reencoded = None
        if fps is not None and use_ffmpeg:
            # a failed ffmpeg run (non-zero exit, no output) degrades to
            # index resampling like a host without the binary would —
            # the old code ignored the exit code and the missing output
            # surfaced downstream as an opaque cv2 probe error
            try:
                reencoded = reencode_video_with_diff_fps(
                    path, str(tmp_path), fps)
            except (RuntimeError, OSError) as e:
                from video_features_tpu.obs.events import event
                event(logging.WARNING,
                      f'ffmpeg fps re-encode failed ({e}); falling back '
                      'to index resampling', video=str(path))
        elif fps is not None and native_reencode:
            # The native encoder hard-rejects inputs it can't handle (e.g.
            # non-yuv420p); degrade to index resampling like a host with
            # neither backend would, rather than killing extraction.
            from video_features_tpu.io.native import reencode_fps_native
            try:
                reencoded = reencode_fps_native(path, str(tmp_path), fps)
            except (RuntimeError, OSError) as e:
                from video_features_tpu.obs.events import event
                event(logging.WARNING,
                      f'native fps re-encode failed ({e}); falling back '
                      'to index resampling', video=str(path))
        if fps is None:
            self.path = path
            self.fps = src_fps
            self.num_frames = src_frames
        elif reencoded is not None:
            self.path = reencoded
            self._tmp_file = self.path
            new_props = get_video_props(self.path)
            self.fps = new_props['fps']
            self.num_frames = new_props['num_frames']
            self.height, self.width = new_props['height'], new_props['width']
        else:
            self.path = path
            self.fps = fps
            self._index_map = resample_frame_indices(src_frames, src_fps, fps)
            self.num_frames = len(self._index_map)

    # -- iteration ----------------------------------------------------------

    def __iter__(self):
        self._frames = self._retimed_frames()
        self._pre_transformed = False
        if self.transform_workers > 1:
            self._frames = _parallel_map(self.transform, self._frames,
                                         self.transform_workers)
            self._pre_transformed = True
        self._cache: List = []
        self._cache_times: List[float] = []
        self._cache_indices: List[int] = []
        self._out_idx = 0
        self._exhausted = False
        return self

    def _probe_props(self, path: str) -> Dict[str, float]:
        """Stream properties from whichever probe understands the file:
        the native service first (when selected), cv2 otherwise — each can
        demux containers the other's build may lack."""
        if self.backend != 'cv2':
            from video_features_tpu.io import native
            props = native.get_video_props_native(path)
            if props is not None and props['num_frames'] > 0:
                return props
            if self.backend == 'native' and props is None and \
                    not native.available():
                raise RuntimeError('native decode backend unavailable '
                                   '(libvfdecode.so failed to build/load)')
        return get_video_props(path)

    def _make_decoder(self):
        if self.backend != 'cv2':
            from video_features_tpu.io import native
            if native.available():
                decoder = native.NativeFrameDecoder(self.path)
                if self.backend == 'native':
                    return decoder
                try:  # auto: per-file fallback — libav may lack a demuxer
                    return decoder.open()
                except IOError:
                    pass
            elif self.backend == 'native':
                raise RuntimeError('native decode backend unavailable '
                                   '(libvfdecode.so failed to build/load)')
        return Cv2FrameDecoder(self.path)

    def _retimed_frames(self) -> Iterator[np.ndarray]:
        """Decoded frames in output order, honoring the index map (dup/drop).

        try/finally, not an exhausted-path-only ``release()``: a consumer
        that abandons iteration mid-stream (generator ``close()`` or GC)
        must still release the decoder handle, or every early-stopped
        video leaks a demuxer/codec context until interpreter exit.
        """
        decoder = self._make_decoder()
        self._decoder = decoder
        try:
            if self._index_map is None:
                for _, frame in decoder:
                    yield frame
                return
            # index map is sorted; stream the source once, dup/dropping.
            pos = 0
            n = len(self._index_map)
            for src_idx, frame in decoder:
                while pos < n and self._index_map[pos] == src_idx:
                    yield frame
                    pos += 1
                if pos >= n:
                    return
        finally:
            decoder.release()
            self._decoder = None

    def __next__(self):
        if self._exhausted:
            raise StopIteration

        batch = list(self._cache)
        times = list(self._cache_times)
        indices = list(self._cache_indices)

        new_frames = 0
        while len(batch) < self.batch_size:
            try:
                frame = next(self._frames)
            except StopIteration:
                self._exhausted = True
                break
            idx = self._out_idx
            self._out_idx += 1
            times.append(idx / self.fps * 1000)
            indices.append(idx)
            if self.transform is not None and not self._pre_transformed:
                frame = self.transform(frame)
            batch.append(frame)
            new_frames += 1

        # a batch of only cached overlap frames carries no new information
        if new_frames == 0:
            raise StopIteration

        if self.overlap:
            self._cache = batch[-self.overlap:]
            self._cache_times = times[-self.overlap:]
            self._cache_indices = indices[-self.overlap:]

        if self.transform is None:
            return np.stack(batch), times, indices
        return batch, times, indices

    def __len__(self) -> int:
        return self.num_frames

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        """Release the decoder handle and delete the re-encode temp file.

        Idempotent and safe at any point of iteration; ``with
        VideoLoader(...) as loader:`` and the decode-farm workers call it
        deterministically instead of waiting on ``__del__`` (GC timing is
        an unreliable place to hold codec contexts and tmp-file cleanup).
        """
        frames = getattr(self, '_frames', None)
        if frames is not None and hasattr(frames, 'close'):
            # runs the generator's finally → decoder.release()
            frames.close()
            self._frames = None
        decoder = getattr(self, '_decoder', None)
        if decoder is not None:
            decoder.release()
            self._decoder = None
        if getattr(self, '_tmp_file', None) and not self.keep_tmp:
            try:
                os.remove(self._tmp_file)
            except OSError:
                pass
            self._tmp_file = None

    def __enter__(self) -> 'VideoLoader':
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            # vft-lint: ok=swallowed-exception — context-exit close is
            # best-effort; decode errors already surfaced on the iterator
            pass


def iter_frame_batches(loader: VideoLoader) -> Iterator[Tuple[np.ndarray, List[float], List[int]]]:
    """Convenience: iterate a loader yielding stacked (B,H,W,3) uint8 batches."""
    for batch, times, indices in loader:
        if isinstance(batch, list):
            batch = np.stack(batch)
        yield batch, times, indices


def _parallel_map(fn, iterable, workers: int):
    """Ordered parallel map with bounded lookahead (host preprocessing).

    Keeps ``2·workers`` frames in flight on a thread pool; PIL/cv2 release
    the GIL in their core loops so per-frame transforms scale with threads.
    """
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=workers) as pool:
        pending = deque()
        for item in iterable:
            pending.append(pool.submit(fn, item))
            if len(pending) > 2 * workers:
                yield pending.popleft().result()
        while pending:
            yield pending.popleft().result()


def prefetch(iterable, depth: int = 2):
    """Run ``iterable`` on a background thread, buffering ``depth`` items.

    Host-side software pipelining (SURVEY.md §7 design stance 2): while the
    device computes on batch k, the decode thread fills batch k+1 — the
    single-host analog of a double-buffered infeed. Exceptions from the
    producer re-raise at the consuming site; the thread shuts down with the
    iterator (``close()`` or garbage collection of the generator).
    """
    import queue
    import threading

    q: 'queue.Queue' = queue.Queue(maxsize=max(depth, 1))
    _END = object()
    stop = threading.Event()

    def put_or_abort(item) -> bool:
        """Blocking put that gives up once the consumer is gone."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def producer():
        try:
            for item in iterable:
                if not put_or_abort(item):
                    return
            put_or_abort(_END)
        # vft-lint: ok=swallowed-exception — shipped, not swallowed:
        # the consumer re-raises whatever the producer thread posts
        except BaseException as e:  # re-raised by the consumer
            put_or_abort(e)
        finally:
            # an abandoned source is closed HERE, on the thread that ran
            # it, not whenever its last reference goes: the lane windower
            # joins its decode threads and closes their loaders there
            close = getattr(iterable, 'close', None)
            if close is not None:
                close()

    thread = threading.Thread(target=producer, daemon=True)
    thread.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
