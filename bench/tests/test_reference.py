"""The plain reference's merge over a fleet held in blocks: a ring split
into blocks takes its halo rows from the neighbouring blocks and merges
as one block would, up to the order of summation."""
import jax
import numpy as np
import pytest

from bench import harness
from bench import reference as ref

D, H, M = 16, 4, 6
SPLITS = {"one": [16], "two": [8, 8], "four": [4, 4, 4, 4], "unequal": [3, 7, 2, 4]}


def _state(seed):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((D, H, 3 * H)).astype(np.float32)
    p = np.linalg.inv(a @ a.transpose(0, 2, 1) + np.eye(H, dtype=np.float32))
    beta = rng.standard_normal((D, H, M)).astype(np.float32)
    mask = rng.random(D) < 0.75
    return p.astype(np.float32), beta, mask


def _merged(sizes, topology, hops, seed):
    p, beta, mask = _state(seed)
    edges = np.cumsum([0] + sizes)
    blocks = [(p[lo:hi], beta[lo:hi]) for lo, hi in zip(edges[:-1], edges[1:])]
    fleet = ref.Fleet(blocks, [jax.devices()[0]] * len(blocks),
                      activation="identity", ridge=1e-3)
    fleet.merge(mask, topology, hops)
    return fleet.host_state()


@pytest.mark.parametrize("hops", [1, 2])
@pytest.mark.parametrize("split", sorted(SPLITS))
def test_ring_over_blocks_is_one_blocks_band_sum(split, hops):
    want_p, want_b = _merged([D], "ring", hops, seed=hops)
    got_p, got_b = _merged(SPLITS[split], "ring", hops, seed=hops)
    assert harness._rel(got_p, want_p) < 1e-6
    assert harness._rel(got_b, want_b) < 1e-6
    # the merge moved the participants and left the others as they were
    p0, b0, mask = _state(hops)
    assert not np.allclose(got_b[mask], b0[mask])
    np.testing.assert_array_equal(got_b[~mask], b0[~mask])


def test_star_over_blocks_is_one_block():
    want_p, want_b = _merged([D], "star", 0, seed=5)
    got_p, got_b = _merged(SPLITS["unequal"], "star", 0, seed=5)
    assert harness._rel(got_p, want_p) < 1e-6
    assert harness._rel(got_b, want_b) < 1e-6


def test_band_sum_halo_is_band_sum():
    x = jax.random.normal(jax.random.PRNGKey(0), (D, 3))
    cpu = [jax.devices()[0]] * 3
    got = np.concatenate(ref.band_sums([x[:5], x[5:7], x[7:]], 2, cpu))
    np.testing.assert_allclose(got, ref.band_sum(x, hops=2), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("sizes", [[1, 15], [8, 7, 1]])
def test_ring_block_smaller_than_hops_is_an_error(sizes):
    with pytest.raises(ValueError, match="fewer than hops=2"):
        _merged(sizes, "ring", 2, seed=0)
