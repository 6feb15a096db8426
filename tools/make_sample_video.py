#!/usr/bin/env python3
"""Generate synthetic sample media for demos/tests (no downloads needed).

The reference ships sample .mp4 clips; this repo generates equivalents on
demand: a moving-pattern video (exercises decode, resize, optical flow —
the pattern translates at a known velocity, so RAFT output is visually
checkable) and a tone .wav for the vggish path. Also writes
``sample_video_paths.txt`` in the output directory (the
``file_with_video_paths`` input format: one path per line).

Usage:
    python tools/make_sample_video.py --out ./sample \
        [--seconds 4] [--fps 25] [--size 320x240]
"""
from __future__ import annotations

import argparse
import wave
from pathlib import Path

import numpy as np


def write_noise_clip(path, n_frames: int, w: int = 64, h: int = 48,
                     seed: int = 0) -> str:
    """A deterministic little mp4: a noise card scrolling horizontally.

    The ONE tiny-fixture clip writer shared by the test suites — a
    codec/fps tweak here reaches every consumer at once.
    """
    import cv2

    wr = cv2.VideoWriter(str(path), cv2.VideoWriter_fourcc(*'mp4v'),
                         25.0, (w, h))
    rng = np.random.RandomState(seed)
    base = (rng.rand(h, w, 3) * 255).astype(np.uint8)
    for t in range(n_frames):
        wr.write(np.roll(base, t * 3, axis=1))
    wr.release()
    return str(path)


def write_video(path: Path, seconds: float, fps: float, w: int, h: int) -> None:
    import cv2

    rng = np.random.RandomState(0)
    # random blobs on a gradient background; the whole field translates at
    # (2, 1) px/frame so flow ≈ constant and visually verifiable
    base_h, base_w = h * 2, w * 2
    yy, xx = np.mgrid[0:base_h, 0:base_w]
    base = ((xx * 255 / base_w + yy * 128 / base_h) % 255).astype(np.uint8)
    base = np.stack([base, np.roll(base, 37, 0), np.roll(base, 91, 1)], -1)
    for _ in range(40):
        cy, cx = rng.randint(0, base_h), rng.randint(0, base_w)
        r = rng.randint(8, 32)
        color = rng.randint(0, 255, 3).tolist()
        cv2.circle(base, (cx, cy), r, color, -1)

    writer = cv2.VideoWriter(str(path), cv2.VideoWriter_fourcc(*'mp4v'),
                             fps, (w, h))
    n = int(seconds * fps)
    for t in range(n):
        dy, dx = (t * 1) % h, (t * 2) % w
        frame = np.roll(np.roll(base, -dy, 0), -dx, 1)[:h, :w]
        writer.write(frame)
    writer.release()


def write_tone(path: Path, seconds: float = 3.0, sr: int = 16000,
               freq: float = 440.0) -> None:
    t = np.arange(int(sr * seconds)) / sr
    samples = (np.sin(2 * np.pi * freq * t) * 0.5 * 32767).astype('<i2')
    with wave.open(str(path), 'wb') as f:
        f.setnchannels(1)
        f.setsampwidth(2)
        f.setframerate(sr)
        f.writeframes(samples.tobytes())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--out', required=True)
    ap.add_argument('--seconds', type=float, default=4.0)
    ap.add_argument('--fps', type=float, default=25.0)
    ap.add_argument('--size', default='320x240')
    ns = ap.parse_args()

    out = Path(ns.out)
    out.mkdir(parents=True, exist_ok=True)
    w, h = (int(v) for v in ns.size.split('x'))

    video = out / 'sample_moving_pattern.mp4'
    tone = out / 'sample_tone.wav'
    write_video(video, ns.seconds, ns.fps, w, h)
    write_tone(tone)
    (out / 'sample_video_paths.txt').write_text(f'{video.resolve()}\n')
    print(f'wrote {video}\nwrote {tone}\nwrote {out / "sample_video_paths.txt"}')
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
