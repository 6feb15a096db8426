"""The traffic generator: the same seed gives the same corpus, every seed the
same amount of work, and a clip that comes round again has a fresh name."""
import hashlib
import json
import os

import pytest

import traffic_gen
from .conftest import BENCH

TINY = {'kind': 'corpus', 'clips': 5, 'frames': [9, 17, 12], 'width': 64,
        'height': 48, 'fps': 25, 'fourcc': 'mp4v', 'content_seed': 7}
BIG_SEED = 2 ** 31 + 12345          # more than 32 signed bits hold


def digest(path):
    return hashlib.sha256(open(path, 'rb').read()).hexdigest()


@pytest.fixture(scope='module')
def corpus(tmp_path_factory):
    return traffic_gen.generate(TINY, BIG_SEED,
                                str(tmp_path_factory.mktemp('corpus')))


def test_lengths_cycle_over_the_clips(corpus):
    assert [c['frames'] for c in corpus['clips']] == [9, 17, 12, 9, 17]
    assert sorted(corpus['order']) == list(range(5))


def test_clips_decode_to_their_length_and_geometry(corpus):
    from _video import read_frames
    for clip in corpus['clips']:
        frames = read_frames(clip['path'])
        assert frames.shape == (clip['frames'], 48, 64, 3)
    # the picture moves: consecutive frames differ
    assert (frames[0] != frames[1]).any()


def test_same_seed_same_corpus(corpus, tmp_path):
    again = traffic_gen.generate(TINY, BIG_SEED, str(tmp_path / 'again'))
    assert again['order'] == corpus['order']
    assert [digest(c['path']) for c in again['clips']] == \
        [digest(c['path']) for c in corpus['clips']]


def test_another_seed_same_clips_in_another_order(corpus, tmp_path):
    """The seed must not change the work: pictures and motion come from the
    file's content_seed, the seed only orders the worklist."""
    other = traffic_gen.generate(TINY, BIG_SEED + 1, str(tmp_path / 'other'))
    assert [digest(c['path']) for c in other['clips']] == \
        [digest(c['path']) for c in corpus['clips']]
    pictures = traffic_gen.generate(dict(TINY, content_seed=8), BIG_SEED,
                                    str(tmp_path / 'pictures'))
    assert digest(pictures['clips'][0]['path']) != \
        digest(corpus['clips'][0]['path'])
    orders = {tuple(traffic_gen.generate(
        dict(TINY, clips=8, frames=[2]), BIG_SEED + k,
        str(tmp_path / f'o{k}'))['order']) for k in range(4)}
    assert len(orders) > 1


def test_a_later_pass_has_fresh_names_for_the_same_clips(corpus):
    first = traffic_gen.pass_paths(corpus, 'p0')
    second = traffic_gen.pass_paths(corpus, 'p1')
    assert [i['clip'] for i in first] == corpus['order']
    assert [i['clip'] for i in second] == corpus['order']
    assert not {i['path'] for i in first} & {i['path'] for i in second}
    for a, b in zip(first, second):
        assert os.path.samefile(a['path'], b['path']) or \
            digest(a['path']) == digest(b['path'])
        assert a['frames'] == b['frames']


@pytest.mark.parametrize('missing', ['clips', 'frames', 'fourcc',
                                     'content_seed'])
def test_a_traffic_file_without_a_key_is_refused(tmp_path, missing):
    params = {k: v for k, v in TINY.items() if k != missing}
    with pytest.raises(KeyError):
        traffic_gen.generate(params, 1, str(tmp_path))


@pytest.mark.parametrize('name,clips,frames,stacks', [
    ('corpus-8', 8, 2390, 144),
    ('corpus-32', 32, 9560, 576),
])
def test_the_committed_mixes(name, clips, frames, stacks):
    import loader
    params = json.loads((BENCH / 'traffic' / f'{name}.json').read_text())
    lengths = traffic_gen.clip_lengths(params)
    assert len(lengths) == clips and sum(lengths) == frames
    ref = loader.load_module('references', 'i3d-two-stream-raft')
    assert sum(ref.rows_of(n) for n in lengths) == stacks
    assert (params['width'], params['height']) == (340, 256)
