"""Cohort-paged arena tests: host arena round-trips, schedule
rotation, the two-tier merge's ≤1e-5 agreement with the flat resident
merge on every claimed topology (both kernel paths), tier-traffic
accounting, and the ``CohortFleetRuntime`` vs ``FleetRuntime``
tick-by-tick differential (the paged runtime must be an implementation
detail, not a semantics change)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleet import (
    CohortMerger,
    CohortSchedule,
    FleetArena,
    cohort_round_cost,
    cohort_tree_reduce,
    fleet_merge_masked,
    hierarchical,
    init_arena,
    init_fleet,
    ring,
    star,
)
from repro.fleet.topology import Topology, all_to_all
from repro.runtime import (
    CohortFleetRuntime,
    DetectorConfig,
    FleetRuntime,
    GovernorConfig,
    RuntimeConfig,
)

D, C, F, NH, B = 32, 8, 8, 4, 4
RIDGE = 1e-2
N_INIT = 16


@pytest.fixture(scope="module")
def fleet():
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, (D, N_INIT, F)) * 0.5
    return init_fleet(jax.random.PRNGKey(1), D, F, NH, x0, ridge=RIDGE)


def _arena(fleet) -> FleetArena:
    return FleetArena.from_fleet(fleet)


def _config(topology, **kw) -> RuntimeConfig:
    return RuntimeConfig(
        topology=topology, ridge=RIDGE,
        detector=DetectorConfig(warmup=4, warmup_skip=1),
        governor=GovernorConfig(merge_every=3),
        use_ingest_kernel=True, ingest_backend="xla", **kw,
    )


# ------------------------------------------------------------------ arena


def test_arena_from_fleet_roundtrip(fleet):
    arena = _arena(fleet)
    assert (arena.n_devices, arena.n_hidden, arena.n_out) == (D, NH, F)
    assert arena.alpha.shape == (F, NH)  # stored ONCE, not (D, F, NH)
    back = arena.to_fleet()
    np.testing.assert_array_equal(np.asarray(back.p), np.asarray(fleet.p))
    np.testing.assert_array_equal(np.asarray(back.beta), np.asarray(fleet.beta))
    np.testing.assert_array_equal(
        np.asarray(back.params.alpha), np.asarray(fleet.params.alpha)
    )
    # nbytes: basis once + per-device (P, β)
    expect = arena.alpha.nbytes + arena.bias.nbytes + 4 * D * (NH * NH + NH * F)
    assert arena.nbytes == expect


def test_arena_page_is_a_view(fleet):
    arena = _arena(fleet)
    page = arena.page(8, 16)
    assert page.p.shape == (8, NH, NH)
    assert page.params.alpha.ndim == 2  # unstacked shared basis
    assert np.shares_memory(page.p, arena.p)  # zero-copy
    arena.write_page(8, 16, np.zeros((8, NH, NH)), np.zeros((8, NH, F)),
                     where=np.arange(8) < 2)
    assert np.all(arena.p[8:10] == 0) and not np.all(arena.p[10:16] == 0)


def test_arena_rejects_per_device_bases(fleet):
    bad = fleet.replace(
        params=fleet.params._replace(alpha=fleet.params.alpha.at[0].add(1.0))
    )
    with pytest.raises(ValueError, match="share"):
        FleetArena.from_fleet(bad)


def test_init_arena_matches_per_device_init():
    """Paged init is Eq. 13 per device — identical to the resident
    ``init_fleet`` given the same key and boot chunks.

    The two programs batch the Cholesky solves differently, so they
    agree to about κ(P)·u·|P|. The fixture is ridged with 1.0: at this
    file's RIDGE=1e-2 the sigmoid boot chunks leave κ(P) = 457 and
    |P| = 12.6 (observed |ΔP| 9.9e-5), at 1.0 κ(P) = 22.5 and |P| =
    0.71 (observed 7.8e-7)."""
    ridge = 1.0
    key = jax.random.PRNGKey(3)
    x0 = np.asarray(jax.random.normal(key, (D, N_INIT, F))) * 0.5
    arena = init_arena(
        jax.random.PRNGKey(4), D, F, NH, lambda lo, hi: x0[lo:hi],
        cohort_size=C, ridge=ridge,
    )
    resident = init_fleet(
        jax.random.PRNGKey(4), D, F, NH, jnp.asarray(x0), ridge=ridge
    )
    np.testing.assert_allclose(
        arena.p, np.asarray(resident.p), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        arena.beta, np.asarray(resident.beta), rtol=1e-4, atol=1e-5
    )
    with pytest.raises(ValueError, match="bottleneck"):
        init_arena(key, D, F, F, lambda lo, hi: x0[lo:hi], cohort_size=C)


# --------------------------------------------------------------- schedule


def test_schedule_validation():
    with pytest.raises(ValueError, match="divisible"):
        CohortSchedule(32, 5)
    with pytest.raises(ValueError, match="cohort_size"):
        CohortSchedule(32, 64)
    with pytest.raises(ValueError, match="active_per_tick"):
        CohortSchedule(32, 8, active_per_tick=5)
    s = CohortSchedule(32, 8)
    assert s.n_cohorts == 4
    assert s.bounds(2) == (16, 24)
    assert s.bounds() == [(0, 8), (8, 16), (16, 24), (24, 32)]


def test_schedule_round_robin_covers_all_cohorts():
    s = CohortSchedule(32, 8, active_per_tick=2)
    assert s.active(0) == [0, 1]
    assert s.active(1) == [2, 3]
    served = set()
    for t in range(2):
        served.update(s.active(t))
    assert served == {0, 1, 2, 3}
    # active_per_tick=None serves everyone
    assert CohortSchedule(32, 8).active(7) == [0, 1, 2, 3]


# -------------------------------------------------------- two-tier merges

CLAIMED_TOPOLOGIES = [
    star(D),
    all_to_all(D),
    ring(D, hops=2),
    ring(D, hops=9),
    ring(D, hops=D // 2),  # closed band → fleet-wide constant
    hierarchical(D, 4),    # head exchange → global
    hierarchical(D, 4, head_exchange=False),   # nests evenly in cohorts
    hierarchical(D, 6, head_exchange=False),   # straddles cohort bounds
    hierarchical(D, 16, head_exchange=False),  # two clusters per cohort
]


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize(
    "topology", CLAIMED_TOPOLOGIES, ids=lambda t: t.name
)
def test_two_tier_merge_matches_flat(fleet, topology, kernel):
    """Eq. 8 through the cohort tree == the flat resident merge ≤1e-5
    under a participation mask, for every claimed topology and both
    tier-1 lowerings."""
    rng = np.random.default_rng(42)
    mask = rng.random(D) > 0.25
    mask[:2] = True  # keep every run a real merge
    arena = _arena(fleet)
    merger = CohortMerger(
        topology, CohortSchedule(D, C), ridge=RIDGE, kernel=kernel
    )
    cost = merger.merge(arena, mask)
    flat = fleet_merge_masked(
        fleet, topology, jnp.asarray(mask, jnp.float32), ridge=RIDGE
    )
    np.testing.assert_allclose(
        arena.beta, np.asarray(flat.beta), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        arena.p, np.asarray(flat.p), rtol=1e-5, atol=1e-5
    )
    # non-participants bit-for-bit untouched
    skip = ~mask
    np.testing.assert_array_equal(
        arena.beta[skip], np.asarray(fleet.beta)[skip]
    )
    assert cost.bytes_total > 0


def test_star_merge_collapses_fleet_to_one_state(fleet):
    """A full-participation star round solves ONE global (ΣU, ΣV) and
    broadcasts it: every device row must land bit-identical, across
    cohort pages — the scatter-back can't fragment the consensus."""
    arena = _arena(fleet)
    merger = CohortMerger(star(D), CohortSchedule(D, C), ridge=RIDGE)
    merger.merge(arena, np.ones(D, bool))
    np.testing.assert_array_equal(arena.p, np.broadcast_to(arena.p[:1], arena.p.shape))
    np.testing.assert_array_equal(
        arena.beta, np.broadcast_to(arena.beta[:1], arena.beta.shape)
    )


def test_merger_compile_once_across_pages_and_masks(fleet):
    arena = _arena(fleet)
    merger = CohortMerger(
        hierarchical(D, 4, head_exchange=False),
        CohortSchedule(D, C), ridge=RIDGE,
    )
    rng = np.random.default_rng(0)
    for _ in range(3):
        merger.merge(arena, rng.random(D) > 0.3)
    assert all(v <= 1 for v in merger.jit_cache_sizes().values()), (
        merger.jit_cache_sizes()
    )


def test_merger_rejects_undecomposable_topologies():
    # unsorted cluster ids: the paged segment sums assume contiguity
    cids = np.array([0, 1] * (D // 2), np.int32)
    scrambled = Topology(
        name="scrambled", n_devices=D, kind="segment",
        cluster_ids=cids, n_clusters=2, head_exchange=False,
        payloads_per_round=2 * D,
    )
    with pytest.raises(ValueError, match="sorted"):
        CohortMerger(scrambled, CohortSchedule(D, C))
    # a dense topology that is NOT fleet-wide constant cannot decompose
    dense = Topology(
        name="arbitrary_dense", n_devices=D, kind="dense",
        matrix=np.eye(D, dtype=np.float32), payloads_per_round=0,
    )
    with pytest.raises(NotImplementedError):
        CohortMerger(dense, CohortSchedule(D, C))
    merger = CohortMerger(star(D), CohortSchedule(D, C))
    with pytest.raises(ValueError, match="mask"):
        merger.merge(_arena_of_zeros(), np.ones(D + 1, bool))


def _arena_of_zeros() -> FleetArena:
    return FleetArena(
        alpha=np.zeros((F, NH), np.float32), bias=np.zeros(NH, np.float32),
        p=np.stack([np.eye(NH, dtype=np.float32)] * D),
        beta=np.zeros((D, NH, F), np.float32),
    )


def test_cohort_tree_reduce_matches_sum():
    rng = np.random.default_rng(1)
    for n in (1, 2, 3, 5, 8):
        stack = rng.normal(size=(n, NH, NH + F)).astype(np.float32)
        out = cohort_tree_reduce(jnp.asarray(stack))
        np.testing.assert_allclose(
            np.asarray(out), stack.sum(axis=0), rtol=1e-5, atol=1e-5
        )


# ----------------------------------------------------------- tier costs


def test_tier_cost_accounting():
    sched = CohortSchedule(D, C)  # 4 cohorts
    # global mode: devices↔cohort head, then a head tree
    c = cohort_round_cost(star(D), sched, NH, F)
    assert (c.tier1_payloads, c.tier2_payloads) == (2 * (D - 4), 2 * 3)
    assert c.bytes_total == c.bytes_tier1 + c.bytes_tier2
    # clusters nested evenly in cohorts: NOTHING crosses the overlay
    c = cohort_round_cost(hierarchical(D, 4, head_exchange=False), sched, NH, F)
    assert c.tier2_payloads == 0
    # straddling clusters pay exactly their extra cohort incidences
    c = cohort_round_cost(hierarchical(D, 6, head_exchange=False), sched, NH, F)
    assert c.tier2_payloads > 0
    assert c.tier2_payloads < 2 * 6 * sched.n_cohorts
    # open ring: the halo is 2·hops per boundary, O(cohorts)
    c = cohort_round_cost(ring(D, hops=2), sched, NH, F)
    assert c.tier2_payloads == 2 * 2 * sched.n_cohorts
    # tier 2 stays O(cohorts) while tier 1 carries the O(D) bulk
    assert c.tier1_payloads > c.tier2_payloads


# ------------------------------------------------- paged runtime (tentpole)


def _tick_batches(n_ticks: int, seed: int = 7, drift_dev: int | None = None,
                  drift_from: int = 10**9):
    rng = np.random.default_rng(seed)
    for t in range(n_ticks):
        batch = rng.normal(scale=0.5, size=(D, B, F)).astype(np.float32)
        if drift_dev is not None and t >= drift_from:
            batch[drift_dev] += 2.0
        yield batch


@pytest.mark.parametrize(
    "topology",
    [star(D), hierarchical(D, 6, head_exchange=False), ring(D, hops=2)],
    ids=lambda t: t.name,
)
def test_paged_runtime_matches_resident(fleet, topology):
    """The ISSUE's differential: the paged runtime's TickReport stream
    is the resident runtime's, tick by tick — losses, drift flags,
    fresh detections, merge decisions — through merge rounds, a
    post-merge rebase tick, and a drift detection."""
    cfg = _config(topology)
    resident = FleetRuntime(fleet, cfg)
    paged = CohortFleetRuntime(_arena(fleet), cfg, cohort_size=C)
    for t, batch in enumerate(_tick_batches(12, drift_dev=3, drift_from=8)):
        r1 = resident.tick(batch)
        r2 = paged.tick(batch)
        np.testing.assert_allclose(
            r1.losses, r2.losses, rtol=1e-5, atol=1e-6
        )
        assert np.array_equal(r1.drifted, r2.drifted), t
        assert np.array_equal(r1.fresh_detections, r2.fresh_detections), t
        assert r1.decision == r2.decision, (t, r1.decision, r2.decision)
        assert (r1.merge_seconds is None) == (r2.merge_seconds is None)
    assert resident.governor.state.merges > 0  # the stream merged
    np.testing.assert_allclose(
        np.asarray(resident.states.beta), paged.arena.beta,
        atol=5e-5, rtol=0,
    )
    assert paged.detections_total == resident.detections_total
    assert list(paged.detections) == list(resident.detections)
    paged.assert_compile_once()


def test_paged_runtime_served_mask_and_callable_batch(fleet):
    """Un-served devices keep state bit-for-bit; a callable batch
    source deals per-cohort slices and never materializes (D, B, F)."""
    cfg = _config(star(D))
    paged = CohortFleetRuntime(_arena(fleet), cfg, cohort_size=C)
    p0 = paged.arena.p.copy()
    det0 = jax.tree_util.tree_map(np.asarray, paged.det)
    served = np.ones(D, bool)
    served[5] = served[20] = False
    rng = np.random.default_rng(0)
    batch = rng.normal(size=(D, B, F)).astype(np.float32)
    calls = []

    def batch_fn(lo, hi):
        calls.append((lo, hi))
        return batch[lo:hi]

    rep = paged.tick(batch_fn, served=served)
    assert calls == CohortSchedule(D, C).bounds()
    np.testing.assert_array_equal(paged.arena.p[5], p0[5])
    np.testing.assert_array_equal(paged.arena.p[20], p0[20])
    assert np.asarray(paged.det.count)[5] == det0.count[5]
    assert not np.array_equal(paged.arena.p[6], p0[6])
    np.testing.assert_array_equal(rep.served, served)


def test_paged_runtime_cohort_rotation(fleet):
    """active_per_tick < n_cohorts: inactive cohorts report NaN losses
    and keep model + detector state; rotation serves everyone across
    the window."""
    cfg = _config(star(D))
    paged = CohortFleetRuntime(
        _arena(fleet), cfg, cohort_size=C, active_per_tick=2
    )
    p0 = paged.arena.p.copy()
    batch = np.random.default_rng(0).normal(size=(D, B, F)).astype(np.float32)
    rep = paged.tick(batch)
    # tick 0 serves cohorts {0, 1} = devices [0, 16)
    assert np.isfinite(rep.losses[:16]).all()
    assert np.isnan(rep.losses[16:]).all()
    np.testing.assert_array_equal(rep.served, np.arange(D) < 16)
    np.testing.assert_array_equal(paged.arena.p[16:], p0[16:])
    assert (np.asarray(paged.det.count)[16:] == 0).all()
    rep = paged.tick(batch)  # tick 1 serves cohorts {2, 3}
    assert np.isnan(rep.losses[:16]).all()
    assert np.isfinite(rep.losses[16:]).all()
    assert (np.asarray(paged.det.count) == 1).all()


def test_paged_runtime_rejects_unsupported_config(fleet):
    from repro.fleet import FaultInjector, RobustConfig, StalenessSchedule

    arena = _arena(fleet)
    base = dict(topology=star(D), ridge=RIDGE)
    for bad in (
        dict(staleness=StalenessSchedule.random(D, max_lag=2, seed=0)),
        dict(robust=RobustConfig()),
        dict(faults=FaultInjector(n_devices=D, specs=())),
        dict(payload_precision="int8"),
        dict(snapshot_every=4, snapshot_dir="/tmp/nope"),
    ):
        with pytest.raises(ValueError):
            CohortFleetRuntime(
                arena, RuntimeConfig(**base, **bad), cohort_size=C
            )
    with pytest.raises(ValueError, match="cohort_size"):
        CohortFleetRuntime(arena, RuntimeConfig(**base))
    with pytest.raises(ValueError, match="topology"):
        CohortFleetRuntime(
            arena, RuntimeConfig(topology=star(D * 2), ridge=RIDGE),
            cohort_size=C,
        )


def test_paged_runtime_telemetry_gauges(fleet, tmp_path):
    from repro.obs import TelemetryConfig

    cfg = _config(star(D), telemetry=TelemetryConfig(dir=tmp_path))
    paged = CohortFleetRuntime(_arena(fleet), cfg, cohort_size=C)
    for batch in _tick_batches(3):
        paged.tick(batch)
    tel = paged.telemetry
    assert tel.ticks.value == 3
    assert tel.cohort_pages.value == 3 * (D // C)
    assert tel.arena_bytes.value == paged.arena.nbytes
    assert tel.arena_resident_devices.value == D
    assert tel.merge_rounds.value == paged.merge_round > 0
    tiers = {k: c.value for k, c in tel.merge_tier_bytes.children.items()}
    assert tiers.get(("intra",), 0) > tiers.get(("inter",), 0) > 0
    summary = paged.finalize_telemetry()
    assert summary["ticks"] == 3
