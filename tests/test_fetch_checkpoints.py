"""tools/fetch_checkpoints.py: offline-verifiable provisioning paths.

Network downloads can't run in CI; the URL machinery is exercised through
``file://`` URLs and the bundled-blob path through a fake reference
checkout. The URL/hash table itself mirrors the reference sources
(clip_src/clip.py:32-43, extract_resnet.py:38-40, vggish_slim.py:119-131).
"""
import hashlib
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

_spec = importlib.util.spec_from_file_location(
    'fetch_checkpoints',
    Path(__file__).parent.parent / 'tools' / 'fetch_checkpoints.py')
fc = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(fc)


def test_expected_hash_conventions():
    # full sha256 (CLIP style)
    art = {'name': 'ViT-B-32.pt', 'sha256': 'ab' * 32}
    assert fc.expected_hash(art) == 'ab' * 32
    # torch-hub filename prefix (torchvision style)
    art = {'name': 'resnet50-0676ba61.pth', 'sha256': 'filename'}
    assert fc.expected_hash(art) == '0676ba61'


def test_registry_covers_every_family():
    from video_features_tpu.config import KNOWN_FEATURE_TYPES
    # timm weights come via the pip-timm bridge, not this tool; lm reads a
    # converted .npz of a trunk's share (the published model is 48 B
    # parameters: nothing this tool should pull)
    assert set(fc.SOURCES) == set(KNOWN_FEATURE_TYPES) - {'timm', 'lm'}


def test_file_url_download_and_verify(tmp_path):
    blob = tmp_path / 'mirror' / 'weights' / 'model-aaaa.pth'
    blob.parent.mkdir(parents=True)
    blob.write_bytes(b'weights-bytes')
    sha = hashlib.sha256(b'weights-bytes').hexdigest()
    art = {'kind': 'url', 'name': 'model-aaaa.pth',
           'url': 'https://example.com/weights/model-aaaa.pth',
           'sha256': sha}
    out = tmp_path / 'out'
    got = fc.fetch_artifact(art, out, url_base=f'file://{tmp_path}/mirror')
    assert got.read_bytes() == b'weights-bytes'
    # second call: checksum-verified skip (corrupt the mirror to prove it)
    blob.write_bytes(b'changed')
    assert fc.fetch_artifact(
        art, out, url_base=f'file://{tmp_path}/mirror') == got


def test_checksum_mismatch_raises_and_removes(tmp_path):
    blob = tmp_path / 'mirror' / 'w' / 'model-bbbb.pth'
    blob.parent.mkdir(parents=True)
    blob.write_bytes(b'tampered')
    art = {'kind': 'url', 'name': 'model-bbbb.pth',
           'url': 'https://example.com/w/model-bbbb.pth',
           'sha256': hashlib.sha256(b'original').hexdigest()}
    with pytest.raises(RuntimeError, match='sha256 mismatch'):
        fc.fetch_artifact(art, tmp_path / 'out',
                          url_base=f'file://{tmp_path}/mirror')
    assert not (tmp_path / 'out' / 'model-bbbb.pth').exists()


def test_bundled_copy_requires_checkout(tmp_path):
    art = fc.SOURCES['raft'][0]
    with pytest.raises(RuntimeError, match='from-checkout'):
        fc.fetch_artifact(art, tmp_path / 'out')


def test_bundled_copy_and_npz_conversion(tmp_path):
    torch = pytest.importorskip('torch')
    checkout = tmp_path / 'checkout'
    src = checkout / 'models/raft/checkpoints/raft-sintel.pth'
    src.parent.mkdir(parents=True)
    sd = {'module.fnet.conv1.weight': torch.zeros(4, 3, 3, 3),
          'module.fnet.conv1.bias': torch.arange(4.0)}
    torch.save(sd, src)

    art = fc.SOURCES['raft'][0]
    got = fc.fetch_artifact(art, tmp_path / 'out', checkout=checkout)
    npz = fc.convert_artifact(got, art['convert'])
    assert npz.suffix == '.npz'

    from video_features_tpu.transplant.torch2jax import load_torch_checkpoint
    params = load_torch_checkpoint(str(npz))  # torch-free load path
    # DataParallel prefix stripped + conv laid out channels-last
    assert params['fnet']['conv1']['weight'].shape == (3, 3, 3, 4)
    np.testing.assert_array_equal(params['fnet']['conv1']['bias'],
                                  np.arange(4.0, dtype=np.float32))


def test_main_rejects_unknown_family(tmp_path, monkeypatch):
    monkeypatch.setattr(sys, 'argv',
                        ['fetch_checkpoints.py', 'nope', '--out',
                         str(tmp_path)])
    with pytest.raises(SystemExit):
        fc.main()


def test_main_happy_path_offline(tmp_path, monkeypatch, capsys):
    """CLI end-to-end with the bundled-blob source + --no-convert (the
    offline provisioning path)."""
    torch = pytest.importorskip('torch')
    checkout = tmp_path / 'checkout'
    for rel in ['models/raft/checkpoints/raft-sintel.pth',
                'models/raft/checkpoints/raft-kitti.pth']:
        p = checkout / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        torch.save({'w': torch.zeros(2)}, p)
    monkeypatch.setattr(sys, 'argv', [
        'fetch_checkpoints.py', 'raft', '--out', str(tmp_path / 'out'),
        '--no-convert', '--from-checkout', str(checkout)])
    assert fc.main() == 0
    assert (tmp_path / 'out' / 'raft-sintel.pth').exists()
    assert (tmp_path / 'out' / 'raft-kitti.pth').exists()
    assert '2 artifacts ready' in capsys.readouterr().out
