"""RAFT: numerical parity vs the reference torch net (20-iteration GRU)."""
import numpy as np
import pytest
import torch

from video_features_tpu.models import raft as raft_model
from video_features_tpu.transplant.torch2jax import transplant

pytestmark = pytest.mark.slow  # parity/e2e/sharding: full lane only



@pytest.fixture(scope='module')
def torch_raft(reference_repo):
    from models.raft.raft_src.raft import RAFT
    torch.manual_seed(0)
    model = RAFT()
    model.eval()
    return model


def test_parity_flow(torch_raft):
    """Same random weights + input pair → same flow after 20 GRU iterations.

    The iterative structure gives numerical drift little room: agreement here
    means the encoders, corr pyramid, bilinear lookup, GRU, and convex
    upsampling all match (SURVEY.md §7 hard-part #1).
    """
    params = transplant(torch_raft.state_dict())
    rng = np.random.RandomState(0)
    # 128x128: smallest corr-pyramid level is 2x2 — the torch reference
    # divides by (H-1) when normalizing grid coords and NaNs on 1-pixel
    # levels, so anything smaller is outside its operating envelope
    f1 = rng.randint(0, 256, (1, 128, 128, 3)).astype(np.float32)
    f2 = np.clip(f1 + rng.randn(1, 128, 128, 3) * 8, 0, 255).astype(np.float32)

    with torch.no_grad():
        ref = torch_raft(
            torch.from_numpy(f1).permute(0, 3, 1, 2),
            torch.from_numpy(f2).permute(0, 3, 1, 2),
        ).permute(0, 2, 3, 1).numpy()

    import jax
    with jax.default_matmul_precision('highest'):
        ours = np.asarray(raft_model.forward(params, f1, f2))

    assert ours.shape == ref.shape == (1, 128, 128, 2)
    l2 = np.linalg.norm(ours - ref) / max(np.linalg.norm(ref), 1e-12)
    assert l2 < 1e-3, f'relative L2 {l2}'
    np.testing.assert_allclose(ours, ref, atol=2e-3)


def test_bilinear_sample_matches_grid_sample():
    rng = np.random.RandomState(0)
    img = rng.rand(2, 6, 7, 1).astype(np.float32)
    # include out-of-range coords to exercise zeros padding
    coords = (rng.rand(2, 11, 2).astype(np.float32) * 10) - 2

    ours = np.asarray(raft_model.bilinear_sample(img, coords))

    timg = torch.from_numpy(img).permute(0, 3, 1, 2)
    x = torch.from_numpy(coords[..., 0])
    y = torch.from_numpy(coords[..., 1])
    grid = torch.stack([2 * x / (7 - 1) - 1, 2 * y / (6 - 1) - 1], dim=-1)
    ref = torch.nn.functional.grid_sample(
        timg, grid.unsqueeze(2), align_corners=True).squeeze(-1).permute(0, 2, 1).numpy()
    np.testing.assert_allclose(ours, ref, atol=1e-6)


def test_pad_unpad_roundtrip():
    x = np.random.RandomState(0).rand(1, 61, 125, 3).astype(np.float32)
    padded, pads = raft_model.pad_to_multiple(x)
    assert padded.shape[1] % 8 == 0 and padded.shape[2] % 8 == 0
    back = np.asarray(raft_model.unpad(padded, pads))
    np.testing.assert_array_equal(back, x)


def test_coords_grid_xy_order():
    g = np.asarray(raft_model.coords_grid(1, 3, 4))
    assert g.shape == (1, 3, 4, 2)
    assert g[0, 2, 3, 0] == 3  # x = column
    assert g[0, 2, 3, 1] == 2  # y = row


def test_forward_consecutive_matches_pairwise():
    """Frame-deduplicated encoding must equal the stacked-pair forward —
    same math, each interior frame's fnet encoding computed once."""
    import jax

    from video_features_tpu.transplant.torch2jax import transplant
    params = transplant(raft_model.init_state_dict(seed=0))
    rng = np.random.RandomState(3)
    frames = rng.randint(0, 255, (5, 48, 64, 3)).astype(np.float32)

    with jax.default_matmul_precision('highest'):
        ref = np.asarray(raft_model.forward(
            params, frames[:-1], frames[1:], iters=3))
        got = np.asarray(raft_model.forward_consecutive(
            params, frames, iters=3))
    assert got.shape == ref.shape == (4, 48, 64, 2)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_forward_stack_pairs_matches_pairwise():
    """The fused-I3D stack form: (B, S+1) frames → (B, S) within-stack
    flows, equal to pairwise forward on each stack's consecutive pairs."""
    import jax

    from video_features_tpu.transplant.torch2jax import transplant
    params = transplant(raft_model.init_state_dict(seed=0))
    rng = np.random.RandomState(4)
    B, S = 2, 3
    stacks = rng.randint(0, 255, (B, S + 1, 48, 64, 3)).astype(np.float32)

    with jax.default_matmul_precision('highest'):
        f1 = stacks[:, :-1].reshape(B * S, 48, 64, 3)
        f2 = stacks[:, 1:].reshape(B * S, 48, 64, 3)
        ref = np.asarray(raft_model.forward(params, f1, f2, iters=3))
        got = np.asarray(raft_model.forward_stack_pairs(
            params, stacks, iters=3))
    assert got.shape == (B, S, 48, 64, 2)
    np.testing.assert_allclose(got.reshape(B * S, 48, 64, 2), ref,
                               rtol=1e-4, atol=1e-4)
