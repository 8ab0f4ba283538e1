"""Kernel-vs-reference parity for the fused topology-merge path.

The Pallas ``topology_mix`` family and the fused ``from_uv_solve`` /
``banded_merge_solve`` kernels must match ``Topology.mix`` +
``fleet_from_uv`` (Cholesky) to ≤1e-5 for all four topologies, under
interpret=True on CPU and with odd D/Ñ tile remainders (nothing
aligned to the (8, 128) f32 tile)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleet import (
    all_to_all,
    fleet_from_uv,
    fleet_merge,
    fleet_merge_kernel,
    fleet_to_uv,
    fleet_train,
    fleet_train_rounds,
    hierarchical,
    init_fleet,
    ring,
    sharded_merge,
    star,
)
from repro.core import UV
from repro.kernels import (
    banded_merge_solve,
    banded_mix,
    dense_mix,
    from_uv_solve,
    segment_broadcast,
    segment_sum_mix,
    topology_mix,
)
from repro.launch.sharding import fleet_stack_spec, shard_fleet

# odd everywhere: D, R, C all miss the (8, 128) tile grid
D_ODD, R_ODD, C_ODD = 13, 10, 37
RIDGE = 1e-3

TOPO_FNS = {
    "all_to_all": all_to_all,
    "star": star,
    "ring2": lambda n: ring(n, hops=2),
    "ring_closed": lambda n: ring(n, hops=(n + 1) // 2),
    "hierarchical": lambda n: hierarchical(n, 3),
    "hierarchical_isolated": lambda n: hierarchical(n, 3, head_exchange=False),
}


def _rand(seed, shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


@pytest.fixture(scope="module")
def small_fleet():
    d, feat, hid = 12, 24, 8
    key = jax.random.PRNGKey(0)
    x_init = jax.random.uniform(key, (d, 2 * hid, feat))
    fleet = init_fleet(key, d, feat, hid, x_init, activation="identity", ridge=RIDGE)
    streams = jax.random.uniform(jax.random.PRNGKey(1), (d, 16, feat))
    return fleet_train(fleet, streams), d


@pytest.mark.parametrize("topo_name", sorted(TOPO_FNS))
@pytest.mark.parametrize("d,r,c", [(D_ODD, R_ODD, C_ODD), (16, 8, 128)])
def test_topology_mix_kernel_matches_reference(topo_name, d, r, c):
    """Pallas mix == dense-matrix einsum == Topology.mix, ragged and
    tile-aligned shapes."""
    topo = TOPO_FNS[topo_name](d)
    x = _rand(d * r + c, (d, r, c))
    want = jnp.einsum("ij,j...->i...", jnp.asarray(topo.dense_matrix()), x)
    got_xla = topo.mix(x)
    got_kernel = topology_mix(x, topo, interpret=True)
    np.testing.assert_allclose(np.asarray(got_xla), np.asarray(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_kernel), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_banded_mix_rejects_overwide_band():
    x = _rand(0, (5, 8, 16))
    with pytest.raises(ValueError, match="band"):
        banded_mix(x, 3, interpret=True)


def test_segment_kernels_roundtrip():
    """segment_sum_mix + segment_broadcast == segment_sum + gather."""
    cids = np.array([0] * 4 + [1] * 6 + [2] * 3, np.int32)
    x = _rand(2, (13, R_ODD, C_ODD))
    sums = segment_sum_mix(x, cids, 3, interpret=True)
    want = jax.ops.segment_sum(x, jnp.asarray(cids), num_segments=3)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(want), rtol=1e-5, atol=1e-5)
    back = segment_broadcast(sums, cids, interpret=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(want)[cids], rtol=1e-5, atol=1e-5)


def test_segment_sum_mix_rejects_unsorted_ids():
    """The kernel accumulates contiguous cluster runs; unsorted ids
    would silently drop partial sums, so they must be rejected."""
    x = _rand(8, (3, 8, 16))
    with pytest.raises(ValueError, match="sorted"):
        segment_sum_mix(x, np.array([0, 1, 0], np.int32), 2, interpret=True)


def test_dense_mix_tile_remainders():
    """Tiled dense kernel == einsum on shapes straddling block edges."""
    d, r, c = 33, 5, 29
    m = (np.random.default_rng(0).random((d, d)) < 0.3).astype(np.float32)
    np.fill_diagonal(m, 1.0)
    x = _rand(3, (d, r, c))
    got = dense_mix(x, m, interpret=True)
    want = jnp.einsum("ij,j...->i...", jnp.asarray(m), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_from_uv_solve_matches_cholesky():
    """Fused Gauss-Jordan kernel == invert_u/solve_beta (Cholesky) to
    ≤1e-5, odd Ñ and odd device count."""
    dn, n, m = D_ODD, R_ODD, 23
    h = _rand(4, (dn, 5 * n, n))
    u = jnp.einsum("dkn,dkm->dnm", h, h)
    v = _rand(5, (dn, n, m))
    p, beta = from_uv_solve(u, v, ridge=RIDGE, interpret=True)
    ureg = u + RIDGE * jnp.eye(n)
    np.testing.assert_allclose(
        np.asarray(p), np.asarray(jnp.linalg.inv(ureg)), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(beta), np.asarray(jnp.linalg.solve(ureg, v)), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("hops", [1, 2])
def test_banded_merge_solve_fuses_mix_and_solve(hops):
    """One kernel: neighbor-sum + ridge-add + solve == roll-sum then
    Cholesky."""
    dn, n, m = D_ODD, R_ODD, 23
    h = _rand(6, (dn, 5 * n, n))
    u = jnp.einsum("dkn,dkm->dnm", h, h)
    v = _rand(7, (dn, n, m))
    w = jnp.concatenate([u, v], axis=2)
    p, beta = banded_merge_solve(w, hops, ridge=RIDGE, interpret=True)
    wm = sum(jnp.roll(w, o, axis=0) for o in range(-hops, hops + 1))
    ureg = wm[:, :, :n] + RIDGE * jnp.eye(n)
    np.testing.assert_allclose(
        np.asarray(p), np.asarray(jnp.linalg.inv(ureg)), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(beta), np.asarray(jnp.linalg.solve(ureg, wm[:, :, n:])),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("hops,lo,n_local", [(0, 2, 4), (1, 0, 4), (2, 3, 5), (2, D_ODD - 4, 4)])
def test_banded_merge_solve_halo_block_equals_wrapped_centre(hops, lo, n_local):
    """The halo form on one shard's block extended by ``hops`` rows each
    side (wrapping round the ring) solves exactly the wrapped kernel's
    rows of that block."""
    dn, n, m = D_ODD, R_ODD, 23
    h = _rand(8, (dn, 5 * n, n))
    w = jnp.concatenate(
        [jnp.einsum("dkn,dkm->dnm", h, h), _rand(9, (dn, n, m))], axis=2)
    ext = jnp.take(w, np.arange(lo - hops, lo + n_local + hops) % dn, axis=0)
    p, beta = banded_merge_solve(ext, hops, ridge=RIDGE, halo=True, interpret=True)
    p_all, beta_all = banded_merge_solve(w, hops, ridge=RIDGE, interpret=True)
    rows = np.arange(lo, lo + n_local) % dn
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_all)[rows])
    np.testing.assert_array_equal(np.asarray(beta), np.asarray(beta_all)[rows])
    with pytest.raises(ValueError, match="no centre rows"):
        banded_merge_solve(ext[:2 * hops], hops, ridge=RIDGE, halo=True, interpret=True)


@pytest.mark.parametrize("topo_name", sorted(TOPO_FNS))
def test_fleet_merge_kernel_matches_fleet_merge(small_fleet, topo_name):
    """End-to-end: the Pallas merge (mix kernels + fused solve) equals
    the XLA fleet_merge for every topology."""
    fleet, d = small_fleet
    topo = TOPO_FNS[topo_name](d)
    ref = fleet_merge(fleet, topo, ridge=RIDGE)
    got = fleet_merge_kernel(fleet, topo, ridge=RIDGE, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got.beta), np.asarray(ref.beta), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(got.p), np.asarray(ref.p), rtol=1e-5, atol=1e-5
    )


def test_fleet_merge_matches_mix_plus_from_uv(small_fleet):
    """The structure-aware merge (cluster-level solves) is exactly the
    naive mix-then-solve-per-device reference."""
    fleet, d = small_fleet
    for topo in (star(d), hierarchical(d, 3), hierarchical(d, 3, head_exchange=False)):
        uv = fleet_to_uv(fleet, ridge=RIDGE)
        mixed = UV(u=topo.mix(uv.u), v=topo.mix(uv.v))
        ref = fleet_from_uv(fleet, mixed, ridge=RIDGE)
        got = fleet_merge(fleet, topo, ridge=RIDGE)
        np.testing.assert_allclose(
            np.asarray(got.beta), np.asarray(ref.beta), rtol=1e-4, atol=1e-5
        )


def test_n_clusters_precomputed():
    """Satellite: n_clusters is frozen at construction, not re-derived
    from cluster_ids.max() on every mix call."""
    assert hierarchical(12, 5).n_clusters == 5
    assert star(9).n_clusters == 1
    assert hierarchical(12, 5, head_exchange=False).n_clusters == 5


def test_fleet_train_rounds_warns_on_truncation(small_fleet, caplog):
    """Satellite: steps % rounds != 0 drops the tail and logs it."""
    fleet, d = small_fleet
    streams = jax.random.uniform(jax.random.PRNGKey(2), (d, 17, 24))
    with caplog.at_level("WARNING", logger="repro.fleet.fleet"):
        out = fleet_train_rounds(fleet, streams, star(d), rounds=4, ridge=RIDGE)
    assert any("dropping the tail" in r.message for r in caplog.records)
    # truncation is exact: equals training on the first 16 steps only
    caplog.clear()
    with caplog.at_level("WARNING", logger="repro.fleet.fleet"):
        ref = fleet_train_rounds(fleet, streams[:, :16], star(d), rounds=4, ridge=RIDGE)
    assert not caplog.records
    np.testing.assert_allclose(
        np.asarray(out.beta), np.asarray(ref.beta), rtol=1e-5, atol=1e-6
    )


def test_fleet_train_rounds_scan_matches_python_loop(small_fleet):
    """The compile-once lax.scan equals the old train/merge round loop."""
    fleet, d = small_fleet
    streams = jax.random.uniform(jax.random.PRNGKey(3), (d, 16, 24))
    topo = ring(d, hops=2)
    got = fleet_train_rounds(fleet, streams, topo, rounds=4, ridge=RIDGE)
    st = fleet
    chunks = streams.reshape(d, 4, 4, 24)
    for r in range(4):
        st = fleet_train(st, chunks[:, r])
        st = fleet_merge(st, topo, ridge=RIDGE)
    np.testing.assert_allclose(
        np.asarray(got.beta), np.asarray(st.beta), rtol=1e-4, atol=1e-5
    )


def test_sharded_merge_single_shard(small_fleet):
    """psum-of-segment-sums merge on a 1-shard mesh equals fleet_merge
    for every cluster-wise-constant topology; the open ring now takes
    the ppermute halo-exchange path (its 1-shard degenerate form is the
    circular wrap); arbitrary sparse dense-mask topologies stay
    rejected."""
    from repro.fleet.topology import Topology

    fleet, d = small_fleet
    mesh = jax.make_mesh((1,), ("data",))
    assert fleet_stack_spec(("data",)) == jax.sharding.PartitionSpec(("data",))
    fleet_s = shard_fleet(fleet, mesh)
    for topo in (all_to_all(d), star(d), hierarchical(d, 3),
                 hierarchical(d, 3, head_exchange=False), ring(d, hops=d // 2),
                 ring(d, hops=1), ring(d, hops=2)):
        ref = fleet_merge(fleet, topo, ridge=RIDGE)
        fn, operands = sharded_merge(topo, mesh, ("data",), RIDGE, kernel=False)
        got = jax.jit(fn)(fleet_s, np.ones(d, np.float32), *operands)
        np.testing.assert_allclose(
            np.asarray(got.beta), np.asarray(ref.beta), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(got.p), np.asarray(ref.p), rtol=1e-4, atol=1e-5
        )
    m = np.eye(d, dtype=np.float32)
    m[0, 1] = m[1, 0] = 1.0  # sparse custom mask: not cluster-wise constant
    custom = Topology(name="custom", n_devices=d, kind="dense", matrix=m)
    with pytest.raises(NotImplementedError, match="neighbor sets"):
        sharded_merge(custom, mesh, ("data",), RIDGE, kernel=False)
