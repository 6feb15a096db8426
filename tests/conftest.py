"""Test env: force JAX onto CPU with 8 virtual devices so sharding tests run
without TPU hardware. Must run before jax is imported anywhere.

``VFT_TEST_PLATFORM=native`` leaves the process's real backend alone —
required for the ``tpu``-marked hardware lane (``VFT_TEST_PLATFORM=native
pytest -m tpu``), which would otherwise see the forced-CPU backend and
skip itself on every host."""
import os
import sys
from pathlib import Path

_PLAT = os.environ.get('VFT_TEST_PLATFORM', 'cpu')
if _PLAT not in ('cpu', 'native'):
    raise SystemExit(
        f'VFT_TEST_PLATFORM={_PLAT!r} is not recognized: use "cpu" (the '
        f'default hermetic 8-virtual-device environment) or "native" '
        f'(real hardware, for the `-m tpu` lane)')
_NATIVE = _PLAT == 'native'
if _NATIVE:
    print('conftest: VFT_TEST_PLATFORM=native — running on the REAL '
          'backend (no CPU pin, no 8-device virtual mesh); intended for '
          'the `-m tpu` hardware lane only', file=sys.stderr)
if not _NATIVE:
    os.environ['JAX_PLATFORMS'] = 'cpu'
    xla_flags = os.environ.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in xla_flags:
        os.environ['XLA_FLAGS'] = (
            xla_flags + ' --xla_force_host_platform_device_count=8').strip()

# The env var above is too late if something imported jax before this
# conftest (the config reads it at import): force the runtime config too,
# before any backend initializes, so the hermetic lane never takes the
# chip on a machine that has one.
import jax  # noqa: E402

if not _NATIVE:
    jax.config.update('jax_platforms', 'cpu')

# Pretrained blobs are not bundled: the suite intentionally runs random
# weights (parity tests transplant seeded torch modules instead). The
# production path hard-errors without this escape — tests/test_weights.py
# unsets it to assert that.
os.environ.setdefault('VFT_ALLOW_RANDOM_WEIGHTS', '1')

REPO_ROOT = Path(__file__).parent.parent
REFERENCE_ROOT = Path('/root/reference')

if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Keep the two lanes apart. Native mode runs ONLY the hardware lane:
    everything not tpu-marked assumes the hermetic 8-virtual-device CPU
    backend and would hard-fail (mesh size) or silently compile against
    real hardware. The hermetic lane skips the tpu-marked tests — which
    therefore never skip themselves: in native mode a missing TPU is a
    failure, not a skip."""
    native_only = pytest.mark.skip(
        reason='VFT_TEST_PLATFORM=native runs only the `-m tpu` lane')
    needs_chip = pytest.mark.skip(
        reason='hardware lane: VFT_TEST_PLATFORM=native pytest -m tpu, '
               'on a TPU host')
    for item in items:
        # the marker, not item.keywords: a parametrize id like [tpu] is a
        # keyword too
        hardware = item.get_closest_marker('tpu') is not None
        if hardware != _NATIVE:
            item.add_marker(needs_chip if hardware else native_only)


@pytest.fixture(scope='session')
def sample_video() -> str:
    """The reference repo's sample clip (read-only)."""
    path = REFERENCE_ROOT / 'sample' / 'v_GGSY1Qvo990.mp4'
    if not path.exists():
        pytest.skip('sample video unavailable')
    return str(path)


@pytest.fixture(scope='session')
def sample_video_2() -> str:
    path = REFERENCE_ROOT / 'sample' / 'v_ZNVhz7ctTq0.mp4'
    if not path.exists():
        pytest.skip('sample video unavailable')
    return str(path)


@pytest.fixture(scope='session')
def short_video(tmp_path_factory) -> str:
    """A ~48-frame clip cut from the sample video (keeps CPU E2E tests fast)."""
    import cv2

    src = REFERENCE_ROOT / 'sample' / 'v_ZNVhz7ctTq0.mp4'
    if not src.exists():
        pytest.skip('sample video unavailable')
    out = str(tmp_path_factory.mktemp('vids') / 'short_clip.mp4')
    cap = cv2.VideoCapture(str(src))
    fps = cap.get(cv2.CAP_PROP_FPS)
    w = int(cap.get(cv2.CAP_PROP_FRAME_WIDTH))
    h = int(cap.get(cv2.CAP_PROP_FRAME_HEIGHT))
    writer = cv2.VideoWriter(out, cv2.VideoWriter_fourcc(*'mp4v'), fps, (w, h))
    for _ in range(48):
        ok, frame = cap.read()
        if not ok:
            break
        writer.write(frame)
    writer.release()
    cap.release()
    return out


def _clip_from_sample(tmp_path_factory, n_frames: int, tag: str) -> str:
    """First ``n_frames`` of the reference sample, re-encoded via cv2."""
    import cv2

    src = REFERENCE_ROOT / 'sample' / 'v_ZNVhz7ctTq0.mp4'
    if not src.exists():
        pytest.skip('sample video unavailable')
    out = str(tmp_path_factory.mktemp(tag) / f'clip{n_frames}.mp4')
    cap = cv2.VideoCapture(str(src))
    fps = cap.get(cv2.CAP_PROP_FPS)
    w = int(cap.get(cv2.CAP_PROP_FRAME_WIDTH))
    h = int(cap.get(cv2.CAP_PROP_FRAME_HEIGHT))
    writer = cv2.VideoWriter(out, cv2.VideoWriter_fourcc(*'mp4v'), fps, (w, h))
    for _ in range(n_frames):
        ok, frame = cap.read()
        if not ok:
            break
        writer.write(frame)
    writer.release()
    cap.release()
    return out


@pytest.fixture(scope='session')
def video_33(tmp_path_factory) -> str:
    """A 33-frame clip: exactly two stack_size=16 windows (2·16+1 frames)
    for the end-to-end golden parity tests."""
    return _clip_from_sample(tmp_path_factory, 33, 'vids33')


@pytest.fixture(scope='session')
def video_65(tmp_path_factory) -> str:
    """A 65-frame clip: exactly one stack_size=64 window (64+1 frames) —
    upstream's documented default stack (reference docs/models/i3d.md:15-18),
    for the published-geometry golden."""
    return _clip_from_sample(tmp_path_factory, 65, 'vids65')


@pytest.fixture(scope='session')
def reference_repo() -> Path:
    """Path to the reference implementation, importable for parity tests only."""
    if not REFERENCE_ROOT.exists():
        pytest.skip('reference repo unavailable')
    if str(REFERENCE_ROOT) not in sys.path:
        sys.path.insert(0, str(REFERENCE_ROOT))
    return REFERENCE_ROOT
