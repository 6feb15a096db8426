"""ctypes binding for the native C++ decode service (native/vfdecode.cc).

The reference's decode path crosses a process boundary per re-encode and a
Python call per frame (reference utils/io.py:96-154 via cv2, utils/
utils.py:181-226 via ffmpeg subprocesses). The native service decodes
through the FFmpeg C libraries directly into preallocated numpy chunks —
one C call per ``CHUNK`` frames — and is the default ``VideoLoader``
backend when buildable; cv2 remains the fallback.

The shared library is compiled on first use (g++ + pkg-config, cached next
to the source); environments without a toolchain or libav dev packages
transparently fall back.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path
from typing import Iterator, Optional, Tuple

import numpy as np

NATIVE_DIR = Path(__file__).resolve().parents[2] / 'native'
LIB_PATH = NATIVE_DIR / 'libvfdecode.so'

_lib = None
_lib_lock = threading.Lock()
_build_failed = False

# frames decoded per C call: amortizes FFI overhead, bounds memory
# (CHUNK × H × W × 3 bytes; 32 × 1080p ≈ 200 MB worst case, typical ≪)
CHUNK = 32


def _build() -> bool:
    try:
        proc = subprocess.run(['make', '-C', str(NATIVE_DIR)],
                              capture_output=True, timeout=120)
        return proc.returncode == 0 and LIB_PATH.exists()
    except (OSError, subprocess.TimeoutExpired):
        return False


def load_library() -> Optional[ctypes.CDLL]:
    """The bound library, building it if needed; None if unavailable."""
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        # Always run make: a no-op when the cached .so is fresh, a rebuild
        # when vfdecode.cc or the tables header is newer (stale libs would
        # otherwise miss newer symbols or carry old tables). If make is
        # unavailable but a prebuilt .so exists, still try it.
        if not _build() and not LIB_PATH.exists():
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(str(LIB_PATH))
        except OSError:
            _build_failed = True
            return None
        try:
            _bind(lib)
        except AttributeError:
            # missing symbol: a stale prebuilt .so that make couldn't
            # refresh — treat as unavailable rather than crash callers
            _build_failed = True
            return None
        _lib = lib
        return _lib


def _bind(lib: ctypes.CDLL) -> None:
    lib.vf_open.restype = ctypes.c_void_p
    lib.vf_open.argtypes = [ctypes.c_char_p]
    lib.vf_last_error.restype = ctypes.c_char_p
    lib.vf_props.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.vf_read.restype = ctypes.c_long
    lib.vf_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                            ctypes.c_long]
    lib.vf_rotation.restype = ctypes.c_int
    lib.vf_rotation.argtypes = [ctypes.c_void_p]
    lib.vf_close.argtypes = [ctypes.c_void_p]
    lib.vf_audio_open.restype = ctypes.c_void_p
    lib.vf_audio_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.vf_audio_rate.restype = ctypes.c_int
    lib.vf_audio_rate.argtypes = [ctypes.c_void_p]
    lib.vf_audio_read.restype = ctypes.c_long
    lib.vf_audio_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                  ctypes.c_long]
    lib.vf_audio_close.argtypes = [ctypes.c_void_p]
    lib.vf_reencode_fps.restype = ctypes.c_int
    lib.vf_reencode_fps.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                    ctypes.c_double]


def available() -> bool:
    return load_library() is not None


def reencode_fps_native(video_path: str, tmp_path: str,
                        extraction_fps: float) -> str:
    """CFR re-encode to ``extraction_fps`` — the reference's
    ``ffmpeg -filter:v fps=fps=N`` stage (reference utils/io.py:14-36)
    without the binary: native fps filter (round=near zero-order hold) +
    libx264 at the CLI's defaults (crf 23, preset medium). Same output
    naming contract as io.video.reencode_video_with_diff_fps.

    Runs in a short-lived subprocess (io/reencode_cli.py) so the encode
    is byte-deterministic regardless of host-process state — libx264's
    rate control measurably changes its decisions after e.g. XLA:CPU jit
    initialization in the same process; a fresh process matches the
    reference's ffmpeg-CLI execution model exactly."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    if load_library() is None:   # build once here; child just dlopens
        raise RuntimeError('native decode library unavailable')
    os.makedirs(tmp_path, exist_ok=True)
    from video_features_tpu.io.video import reencode_out_path
    new_path = reencode_out_path(video_path, tmp_path)
    # The package may not be pip-installed: make the child resolve THIS
    # checkout's package regardless of the caller's cwd. Invoking the
    # entry point by file path puts the io/ dir (no package inside) at
    # sys.path[0], so the PYTHONPATH entry below deterministically wins
    # even when cwd contains a different video_features_tpu checkout.
    pkg_parent = str(Path(__file__).resolve().parents[2])
    env = dict(os.environ)
    env['PYTHONPATH'] = os.pathsep.join(
        [pkg_parent] + ([env['PYTHONPATH']] if env.get('PYTHONPATH') else []))
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).with_name('reencode_cli.py')),
         str(video_path), new_path, repr(float(extraction_fps))],
        capture_output=True, text=True, env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f'native re-encode failed: {proc.stderr.strip()}')
    return new_path


class NativeFrameDecoder:
    """Sequential RGB frame decoder over the C++ service.

    Same protocol as io.video.Cv2FrameDecoder: iterating yields
    ``(source_index, frame HWC uint8 RGB)``. Frames are decoded in CHUNK-
    sized batches into a fresh numpy array per chunk; yielded frames are
    views into it, safe for callers that hold references.
    """

    def __init__(self, path: str):
        self.path = path
        self._handle: Optional[int] = None

    def open(self) -> 'NativeFrameDecoder':
        lib = load_library()
        if lib is None:
            raise RuntimeError('native decode service unavailable')
        handle = lib.vf_open(os.fsencode(self.path))
        if not handle:
            raise IOError(
                f'vfdecode: {lib.vf_last_error().decode()} ({self.path})')
        self._handle = handle
        fps = ctypes.c_double()
        n = ctypes.c_long()
        w = ctypes.c_int()
        h = ctypes.c_int()
        lib.vf_props(handle, ctypes.byref(fps), ctypes.byref(n),
                     ctypes.byref(w), ctypes.byref(h))
        self.fps = fps.value
        self.num_frames = n.value
        # display geometry: vfdecode applies display-matrix rotation (like
        # cv2's auto-rotate), so width/height already reflect it
        self.width = w.value
        self.height = h.value
        self.rotation = lib.vf_rotation(handle)
        return self

    def __iter__(self) -> Iterator[Tuple[int, np.ndarray]]:
        if self._handle is None:
            self.open()
        lib = load_library()
        idx = 0
        try:
            while True:
                chunk = np.empty((CHUNK, self.height, self.width, 3), np.uint8)
                got = lib.vf_read(self._handle, chunk.ctypes.data, CHUNK)
                if got < 0:
                    raise IOError(f'vfdecode: decode error {got} ({self.path})')
                for i in range(got):
                    yield idx, chunk[i]
                    idx += 1
                if got < CHUNK:
                    break
        finally:
            self.release()

    def release(self) -> None:
        if self._handle is not None:
            load_library().vf_close(self._handle)
            self._handle = None

    def __del__(self):
        self.release()


def get_video_props_native(path: str) -> Optional[dict]:
    """fps/num_frames/height/width via the C++ service; None if unavailable."""
    if not available():
        return None
    dec = NativeFrameDecoder(str(path))
    try:
        dec.open()
    except (IOError, RuntimeError):
        return None
    props = dict(fps=dec.fps, num_frames=dec.num_frames,
                 height=dec.height, width=dec.width)
    dec.release()
    return props


def read_audio_native(path: str, target_sr: int = 0) -> 'tuple':
    """Decode a file's audio track to mono float32 via the C++ service.

    Returns ``(waveform (T,) float32 in [-1, 1], sample_rate)``. With
    ``target_sr`` > 0 libswresample converts to that rate in-process —
    replacing the reference's mp4 → aac → wav ffmpeg-subprocess chain
    (reference utils/utils.py:197-226) with zero temp files. Raises IOError
    when the file has no audio track (matching the ffmpeg path's behavior)
    or RuntimeError when the native service is unavailable.
    """
    lib = load_library()
    if lib is None:
        raise RuntimeError('native decode service unavailable')
    handle = lib.vf_audio_open(os.fsencode(str(path)), int(target_sr))
    if not handle:
        raise IOError(f'vfdecode audio: {lib.vf_last_error().decode()} ({path})')
    try:
        rate = lib.vf_audio_rate(handle)
        chunk = 1 << 18
        buf = np.empty(chunk, np.float32)
        parts = []
        while True:
            n = lib.vf_audio_read(handle, buf.ctypes.data, chunk)
            if n < 0:
                raise IOError(f'vfdecode audio: decode error {n} ({path})')
            if n == 0:
                break
            parts.append(buf[:n].copy())
        data = (np.concatenate(parts) if parts
                else np.zeros((0,), np.float32))
        return data, rate
    finally:
        lib.vf_audio_close(handle)
