"""The comparison that decides ``correct``.

What is compared is what the timed window itself saved: the ``.npy`` files of
the window's videos, read back from disk once the window has closed. Against
them stands the plain reference, run from the *video files* with its own
decode (``references/<config>.py``), on the seed's weights. So one comparison
covers decode, host resize/crop, H2D, the whole device step at the timed batch,
D2H, the scatter of batch rows back to videos, un-padding of tail batches, and
save.

Numbers (each printed beside its limit; the limits live in the cell's file,
``workloads/<cell>.json`` → ``limits``, and ``PERF.md`` gives the readings they
were set from):

* ``videos_failed``   videos of the window without a loadable output. Limit 0.
* ``rows_off``        videos whose saved row count is not the reference's
  count for that many frames (a dropped or doubled tail, a lost batch). Limit 0.
* ``nonfinite``       non-finite numbers in the sampled rows. Limit 0.
* ``rel_l2``          ‖P − R‖ / ‖R‖ over all sampled rows together: the steady
  number, which the lower-precision control has to fail.
* ``row_rel_l2_max``  the worst single sampled row by the same measure: one
  altered, shifted or swapped row shows here even where thousands are right.

The sample is drawn from the seed over *all* videos the window finished:
``sample.videos`` of them, the first and the last finished always among them,
and in each ``sample.rows`` rows, the first and the last row (the padded tail
batch) always among them. The reference runs in blocks of ``sample.block``
rows, after the program's state is freed.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import numpy as np

NOTHING_COMPARED = 1e30


def collect(passes: List[List[Dict]], saved_path: Callable[[str], str],
            reference) -> List[Dict]:
    """Look at every video handed over in the window: does its output load,
    and how many rows does it hold?"""
    videos = []
    for items in passes:
        for item in items:
            path = saved_path(item['path'])
            rec = {'video': item['path'], 'frames': item['frames'],
                   'out': path, 'saved': False, 'rows': 0,
                   'rows_expected': reference.rows_of(item['frames'])}
            try:
                arr = np.load(path, mmap_mode='r')
                rec['saved'] = True
                rec['rows'] = int(arr.shape[0])
                rec['shape'] = tuple(arr.shape)
                rec['dtype'] = str(arr.dtype)
            except Exception as e:     # a missing or torn file is a failure
                rec['error'] = f'{type(e).__name__}: {e}'
            videos.append(rec)
    return videos


def _first_last_and_some(n: int, k: int, rng) -> List[int]:
    """k of range(n): the first, the last, the rest drawn."""
    ends = sorted({0, n - 1})
    rest = [i for i in range(n) if i not in ends]
    rng.shuffle(rest)
    return sorted((ends + rest)[:max(k, 1)])


def draw_sample(videos: List[Dict], sample: Dict, seed: int):
    """[(video record, [row, ...])] drawn from the seed."""
    rng = np.random.Generator(np.random.PCG64(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 3]))
    good = [v for v in videos if v['saved'] and v['rows'] > 0
            and v['rows'] == v['rows_expected']]
    if not good:
        return []
    picked = _first_last_and_some(len(good), int(sample['videos']), rng)
    return [(good[i], _first_last_and_some(good[i]['rows'],
                                           int(sample['rows']), rng))
            for i in picked]


def reference_rows(reference, ckpts: Dict[str, str], units: np.ndarray,
                   block: int, mode: str = 'highest') -> np.ndarray:
    """The reference's rows for ``units``, block by block."""
    import jax
    import weights
    from _layers import Ops
    params = {k: weights.load(p) for k, p in ckpts.items()}
    ops = Ops(mode)
    fn = jax.jit(lambda p, u: reference.forward(ops, p, u))
    out = []
    for i in range(0, len(units), block):
        chunk = units[i:i + block]
        n = len(chunk)
        if n < block:      # one compiled shape: pad the last block
            chunk = np.concatenate(
                [chunk, np.repeat(chunk[-1:], block - n, axis=0)])
        out.append(np.asarray(fn(params, chunk))[:n])
    return np.concatenate(out)


def rel_l2(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def row_rel_l2(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return (np.linalg.norm(got - want, axis=1)
            / np.linalg.norm(want, axis=1))


def compare(videos: List[Dict], reference, ckpts: Dict[str, str],
            workload: Dict, seed: int, mode: str = 'highest'):
    """({name: {'value', 'limit', 'ok'}} for the five numbers above, the
    number of rows compared)."""
    limits = workload['limits']
    sample_spec = workload['sample']
    failed = sum(1 for v in videos if not v['saved'])
    rows_off = sum(1 for v in videos
                   if v['saved'] and v['rows'] != v['rows_expected'])
    numbers = {'videos_failed': float(failed), 'rows_off': float(rows_off)}

    sample = draw_sample(videos, sample_spec, seed)
    if sample:
        got, units = [], []
        for v, rows in sample:
            got.append(np.load(v['out'])[rows])
            units.append(reference.load_units(v['video'], rows))
        got = np.concatenate(got).astype(np.float32)
        want = reference_rows(reference, ckpts, np.concatenate(units),
                              int(sample_spec['block']), mode)
        finite = np.isfinite(got)
        numbers['nonfinite'] = float(got.size - int(finite.sum()))
        safe = np.where(finite, got, 0.0)
        numbers['rel_l2'] = rel_l2(safe, want)
        numbers['row_rel_l2_max'] = float(row_rel_l2(safe, want).max())
        numbers['sampled_rows'] = float(len(got))
    else:
        # nothing to compare is not correct: the window saved no usable row
        # (a large finite number: the result line has to stay plain JSON)
        numbers.update(nonfinite=0.0, rel_l2=NOTHING_COMPARED,
                       row_rel_l2_max=NOTHING_COMPARED, sampled_rows=0.0)

    out = {}
    for name in ('videos_failed', 'rows_off', 'nonfinite', 'rel_l2',
                 'row_rel_l2_max'):
        limit = float(limits[name])
        value = numbers[name]
        out[name] = {'value': value, 'limit': limit,
                     'ok': bool(np.isfinite(value) and value <= limit)}
    return out, int(numbers['sampled_rows'])
