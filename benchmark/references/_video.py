"""The references' own way from a video file to uint8 RGB frames: OpenCV's
decoder and Pillow's resize, as the published pipelines use them. Nothing of
the program is imported."""
from __future__ import annotations

import numpy as np


def read_frames(path: str, upto: int | None = None) -> np.ndarray:
    """Frames [0, upto) of ``path`` (all when None) as (n, H, W, 3) uint8
    RGB, decoded in order from the start (seeking an mp4v stream lands on
    key frames only)."""
    import cv2
    cap = cv2.VideoCapture(str(path))
    if not cap.isOpened():
        raise IOError(f'cannot open {path}')
    frames = []
    while upto is None or len(frames) < upto:
        ok, bgr = cap.read()
        if not ok:
            break
        frames.append(cv2.cvtColor(bgr, cv2.COLOR_BGR2RGB))
    cap.release()
    if not frames:
        raise IOError(f'no frame decoded from {path}')
    return np.stack(frames)


def resize_short_side(frame: np.ndarray, size: int) -> np.ndarray:
    """Pillow bilinear resize of the shorter side to ``size``, aspect kept,
    the longer side ``int(size * long / short)``; untouched when the short
    side already has that size (torchvision ``Resize(int)`` on a PIL image,
    and the fork's ``ResizeImproved``)."""
    from PIL import Image
    h, w = frame.shape[:2]
    if min(h, w) == size:
        return frame
    if w < h:
        ow, oh = size, int(size * h / w)
    else:
        oh, ow = size, int(size * w / h)
    return np.asarray(Image.fromarray(frame).resize((ow, oh), Image.BILINEAR))
