"""The one traffic generator: a data file of parameters in, video files out.

``benchmark/traffic/<name>.json`` describes a corpus of clips — how many, how
long, what geometry, codec and rate — and this module writes it from
``--seed``. A later PR adds a traffic mix by adding such a file; it never adds
code here.

Every seed gets the *same clips*: the lengths are the file's ``frames`` list
cycled over ``clips``, and the pictures and their motion are drawn from the
file's ``content_seed`` and the clip's index, not from ``--seed``. The seed
decides the order of the worklist (and, in the harness, the weights). That is
deliberate: what a decode costs depends on the picture and on how it moves,
and with pictures drawn from the seed the six seeds of a decode-bound cell
kept their rank across two sets of runs, 6 % apart, while two runs of one
seed differed by under 1 % (my chip runs, PR 24) — the seed was changing the
work. A driver runs whole passes over the worklist; a clip that comes round
again in a later pass has a fresh name (a hard link), so the program's skip
of finished outputs never turns work into no work.

Content follows the repo's ``tools/make_sample_video.py::write_video`` (blobs
on a gradient, the whole field translating at a constant velocity): it
compresses like video, and a flow model sees a smooth field of a few pixels.
"""
from __future__ import annotations

import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Dict, List

import numpy as np

REQUIRED = ('kind', 'clips', 'frames', 'width', 'height', 'fps', 'fourcc',
            'content_seed')


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, *stream]))


def clip_lengths(params: Dict) -> List[int]:
    frames = list(params['frames'])
    return [int(frames[i % len(frames)]) for i in range(int(params['clips']))]


def write_clip(path: str, n_frames: int, w: int, h: int, fps: float,
               fourcc: str, rng: np.random.Generator) -> str:
    import cv2
    bh, bw = 2 * h, 2 * w
    yy, xx = np.mgrid[0:bh, 0:bw]
    base = ((xx * 255 / bw + yy * 128 / bh + rng.integers(0, 255))
            % 255).astype(np.uint8)
    base = np.stack([base, np.roll(base, 37, 0), np.roll(base, 91, 1)], -1)
    base = np.ascontiguousarray(base)
    for _ in range(40):
        cy, cx = int(rng.integers(0, bh)), int(rng.integers(0, bw))
        color = [int(c) for c in rng.integers(0, 255, 3)]
        cv2.circle(base, (cx, cy), int(rng.integers(8, 32)), color, -1)
    # one tile to the right and below, so a window of (h, w) at any offset
    # inside the base is a plain slice
    tiled = np.tile(base, (2, 2, 1))
    # every clip moves at one speed, (±2, ±1) or (±1, ±2) pixels a frame
    vx, vy = [(2, 1), (1, 2)][int(rng.integers(0, 2))]
    vx *= int(rng.choice([-1, 1]))
    vy *= int(rng.choice([-1, 1]))
    writer = cv2.VideoWriter(str(path), cv2.VideoWriter_fourcc(*fourcc),
                             float(fps), (w, h))
    if not writer.isOpened():
        raise IOError(f'cv2.VideoWriter cannot open {path} ({fourcc})')
    for t in range(n_frames):
        dy, dx = (t * vy) % bh, (t * vx) % bw
        writer.write(np.ascontiguousarray(tiled[dy:dy + h, dx:dx + w]))
    writer.release()
    return str(path)


def generate(params: Dict, seed: int, out_dir: str, threads: int = 8) -> Dict:
    """Write the corpus; return ``{'clips': [{'path','frames'}], 'order'}``.
    ``order`` is the seed's permutation of the clip indices."""
    missing = [k for k in REQUIRED if k not in params]
    if missing:
        raise KeyError(f'traffic file lacks {missing}')
    if params['kind'] != 'corpus':
        raise ValueError(f'unknown traffic kind {params["kind"]!r}')
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    lengths = clip_lengths(params)
    w, h = int(params['width']), int(params['height'])

    def one(i):
        path = out / f'clip{i:03d}.mp4'
        write_clip(str(path), lengths[i], w, h, params['fps'],
                   params['fourcc'], _rng(params['content_seed'], 1, i))
        return {'path': str(path), 'frames': lengths[i]}

    with ThreadPoolExecutor(max_workers=threads) as pool:
        clips = list(pool.map(one, range(len(lengths))))
    order = [int(i) for i in _rng(seed, 2).permutation(len(clips))]
    return {'clips': clips, 'order': order, 'dir': str(out)}


def pass_paths(corpus: Dict, tag: str) -> List[Dict]:
    """The worklist of one pass, in the seed's order, each clip under a
    name no earlier pass has used: ``<tag>_clipNNN.mp4`` hard-linked (or,
    where the file system refuses, copied) beside the original."""
    items = []
    for i in corpus['order']:
        src = corpus['clips'][i]['path']
        dst = os.path.join(corpus['dir'], f'{tag}_{os.path.basename(src)}')
        if not os.path.exists(dst):
            try:
                os.link(src, dst)
            except OSError:
                shutil.copyfile(src, dst)
        items.append({'path': dst, 'frames': corpus['clips'][i]['frames'],
                      'clip': i})
    return items
