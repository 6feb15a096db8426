"""What must not hide a missing or failing chip: device resolution raises
instead of carrying on on the CPU, the Pallas lookups are interpreted only
on 'cpu', a failed video shows in the CLI's exit code, and the compile
cache goes where the environment put it (utils/device.py)."""
import os
from pathlib import Path

import jax
import numpy as np
import pytest

from video_features_tpu import registry
from video_features_tpu.config import Config, resolve_device
from video_features_tpu.extract.base import BaseExtractor
from video_features_tpu.utils import device as device_mod
from video_features_tpu.utils.output import make_path

from tools.make_sample_video import write_noise_clip

REPO_ROOT = Path(__file__).resolve().parent.parent


# -- no fallback --------------------------------------------------------------

def test_accelerator_request_raises_naming_platforms():
    """This lane has no accelerator (conftest pins cpu): asking for one
    raises and names what jax found — resolve_device and jax_device."""
    for ask in ('tpu', 'cuda:0', 'gpu'):
        with pytest.raises(RuntimeError, match=r"platform\(s\) \['cpu'\]"):
            resolve_device(ask)
    with pytest.raises(RuntimeError, match='device=cpu'):
        device_mod.jax_device('tpu')
    assert resolve_device('cpu') == 'cpu'
    assert device_mod.jax_device('cpu').platform == 'cpu'


def test_raft_refine_raises_on_unknown_platform():
    """Only 'cpu' means interpret; a platform string the dispatch does not
    know is an error, not an interpreted production kernel."""
    from video_features_tpu.models import raft
    assert raft._pallas_interpret('tpu') is False
    assert raft._pallas_interpret('cpu') is True
    with pytest.raises(ValueError, match="unknown platform 'gpu'"):
        raft._pallas_interpret('gpu')
    fmap = np.zeros((1, 8, 8, 256), np.float32)
    cnet = np.zeros((1, 8, 8, 256), np.float32)
    with pytest.raises(ValueError, match="unknown platform 'bogus'"):
        raft._refine({'update_block': {}}, fmap, fmap, cnet, iters=1,
                     platform='bogus')


# -- exit code ----------------------------------------------------------------

@pytest.mark.parametrize('packed', [False, True],
                         ids=['per-video', 'packed'])
def test_cli_returns_1_when_a_video_failed(packed, tmp_path):
    """Fault isolation keeps the worklist going — the readable video is
    saved — but the exit code says a video failed."""
    from video_features_tpu.cli import main
    good = write_noise_clip(tmp_path / 'good.mp4', 4, seed=7)
    bad = tmp_path / 'gone.mp4'          # never created
    out = tmp_path / 'out'
    argv = ['feature_type=resnet', 'model_name=resnet18', 'device=cpu',
            'batch_size=4', 'allow_random_weights=true',
            'on_extraction=save_numpy', f'video_paths=[{bad},{good}]',
            f'pack_across_videos={str(packed).lower()}',
            f'output_path={out}', f'tmp_path={tmp_path / "tmp"}']
    assert main(list(argv)) == 1
    final = str(out / 'resnet' / 'resnet18')
    assert np.load(make_path(final, good, 'resnet', '.npy')).shape == (4, 512)
    assert not Path(make_path(final, str(bad), 'resnet', '.npy')).exists()
    # the good video alone (now a resume skip) is a clean run again
    assert main([a for a in argv if not a.startswith('video_paths=')]
                + [f'video_paths=[{good}]']) == 0


# -- compile cache placement ----------------------------------------------------

class StubExtractor(BaseExtractor):
    """Builds without touching a device, so ``device='tpu'`` can be faked
    on this CPU-only lane."""

    def __init__(self, args) -> None:
        super().__init__(feature_type=args['feature_type'],
                         on_extraction='print', tmp_path='tmp',
                         output_path='out', keep_tmp_files=False,
                         device=args['device'])


def _build_stub(monkeypatch, device, cache_dir='auto'):
    monkeypatch.setitem(registry.EXTRACTORS, 'stub',
                        (__name__, 'StubExtractor'))
    return registry.create_extractor(Config(
        feature_type='stub', device=device, compilation_cache_dir=cache_dir))


@pytest.fixture
def cache_config():
    """Save/restore jax's process-global cache dir around a test (no test
    compiles while it is set, so nothing is ever written there)."""
    from jax.experimental.compilation_cache import compilation_cache
    saved = jax.config.jax_compilation_cache_dir
    made = not device_mod.REPO_XLA_CACHE_DIR.exists()
    yield
    jax.config.update('jax_compilation_cache_dir', saved)
    compilation_cache.reset_cache()
    if made and device_mod.REPO_XLA_CACHE_DIR.exists():
        os.rmdir(device_mod.REPO_XLA_CACHE_DIR)


@pytest.mark.parametrize('device', ['cpu', 'tpu'])
def test_cache_dir_from_environment_is_left_alone(device, monkeypatch,
                                                  cache_config, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: that directory itself, on every
    device — no redirect, no clearing, no platform sub-directory."""
    placed = str(tmp_path / 'placed')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', placed)
    # jax reads the variable at import; mirror that for this process
    jax.config.update('jax_compilation_cache_dir', placed)
    for cache_dir in ('auto', None, str(tmp_path / 'explicit')):
        _build_stub(monkeypatch, device, cache_dir)
        assert jax.config.jax_compilation_cache_dir == placed
        assert device_mod.resolve_compilation_cache_dir(
            cache_dir, device) == placed
    assert not Path(placed).exists()      # jax makes it, not the program


def test_cache_dir_unset_is_one_fixed_in_checkout_path(monkeypatch,
                                                       cache_config,
                                                       tmp_path):
    """Unset: an accelerator run resolves to the SAME fixed directory
    inside the checkout every time (the path is part of jax's cache key);
    XLA:CPU gets none; an explicit path wins; null disables."""
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    fixed = str(REPO_ROOT / '.xla_cache')
    for _ in range(2):
        _build_stub(monkeypatch, 'tpu')
        assert jax.config.jax_compilation_cache_dir == fixed
    resolve = device_mod.resolve_compilation_cache_dir
    assert resolve('auto', 'tpu') == fixed
    assert resolve('auto', 'cpu') is None
    assert resolve(None, 'tpu') is None
    assert resolve(str(tmp_path / 'mine'), 'tpu') == str(tmp_path / 'mine')
    # a CPU extractor built after an accelerator one must not inherit its
    # directory (XLA:CPU entries are host-ISA-bound) — and says so
    with pytest.warns(UserWarning, match='compilation cache moved'):
        _build_stub(monkeypatch, 'cpu')
    assert jax.config.jax_compilation_cache_dir is None
