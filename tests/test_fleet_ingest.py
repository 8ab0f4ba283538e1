"""Parity + dispatch tests for the fused fleet-ingest kernel family.

The Pallas ingest kernel (interpret=True on CPU) must match the
``_fleet_train`` vmap-of-scan reference to ≤1e-5 under odd device/Ñ/T
remainders and a forgetting factor λ<1, the fused XLA Woodbury lowering
must match its (real-arithmetic-exact) sequential chain, padded sample
slots must be exact identity steps, and the ``kernel=`` dispatches on
``fleet_train`` / ``fleet_train_rounds`` / ``oselm_train_sequential`` /
``FleetRuntime`` must reproduce their XLA baselines — the runtime tick
to identical ``TickReport``s with zero retracing.

NB on tolerances: RLS parity in f32 degrades as κ(P)² — fixtures use
identity activations or well-ridged sigmoids so the comparison tests
the kernels, not the conditioning (same convention as the merge-kernel
parity tests).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ae_score,
    init_oselm,
    init_slfn,
    oselm_step_k1,
    oselm_train_sequential,
)
from repro.fleet import (
    fleet_merge,
    fleet_train,
    fleet_train_rounds,
    init_fleet,
    ring,
    sharded_ingest,
    sharded_merge,
    star,
)
from repro.fleet.fleet import _fleet_train
from repro.kernels.fleet_ingest import (
    fleet_ingest,
    fleet_ingest_kernel,
    fleet_ingest_xla,
    ingest_padding,
)
from repro.launch.sharding import shard_fleet
from repro.runtime import FleetRuntime, RuntimeConfig

# odd everywhere: D misses the block_d grid, T the sublane tile, Ñ the
# lane/sublane tiles, F the lane tile
D_ODD, T_ODD, F_ODD, NH_ODD = 13, 17, 37, 10
RIDGE = 1e-3


def _fleet(d=D_ODD, f=F_ODD, nh=NH_ODD, *, activation="identity",
           forget=1.0, ridge=RIDGE, seed=0):
    key = jax.random.PRNGKey(seed)
    x_init = jax.random.uniform(key, (d, 4 * nh, f))
    return init_fleet(
        key, d, f, nh, x_init, activation=activation, ridge=ridge, forget=forget
    )


def _window(d=D_ODD, t=T_ODD, f=F_ODD, seed=1):
    return jax.random.uniform(jax.random.PRNGKey(seed), (d, t, f))


def _assert_state_close(got, ref, *, rtol=1e-5, atol=1e-5):
    np.testing.assert_allclose(np.asarray(got.p), np.asarray(ref.p),
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(got.beta), np.asarray(ref.beta),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("activation,forget", [
    ("sigmoid", 1.0),       # paper default
    ("identity", 0.95),     # forgetting factor λ<1
    ("identity", 1.0),
])
def test_ingest_kernel_matches_scan_reference(activation, forget):
    """Pallas ingest == _fleet_train + pre-train score, ≤1e-5, odd
    D/T/Ñ/F remainders (D=13 with block_d=4 leaves a ragged device
    block; T=17 pads to the sublane tile)."""
    ridge = 5e-2 if activation == "sigmoid" else RIDGE
    fleet = _fleet(activation=activation, forget=forget, ridge=ridge)
    win = _window()
    ref = _fleet_train(fleet, win)
    ref_loss = jax.vmap(lambda s, xb: jnp.mean(ae_score(s, xb)))(fleet, win)
    got, loss = fleet_ingest_kernel(fleet, win, block_d=4, interpret=True)
    _assert_state_close(got, ref)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_loss),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("forget", [1.0, 0.95])
@pytest.mark.parametrize("block_t", [5, 17, 32])
def test_ingest_xla_matches_scan_reference(forget, block_t):
    """Fused Woodbury lowering == the sequential chain, ragged tail
    blocks (17 % 5 != 0) included. The c×c Cholesky reorders the f32
    accumulation, so the bound is a touch wider than the Pallas
    kernel's — the identity is exact in real arithmetic."""
    fleet = _fleet(forget=forget)
    win = _window()
    ref = _fleet_train(fleet, win)
    ref_loss = jax.vmap(lambda s, xb: jnp.mean(ae_score(s, xb)))(fleet, win)
    got, loss = fleet_ingest_xla(fleet, win, block_t=block_t)
    _assert_state_close(got, ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_loss),
                               rtol=1e-5, atol=1e-7)


def test_ingest_padding_is_identity():
    """Padded sample slots are masked identity steps: a T=17 window
    (pallas pads to 24 slots, xla's 8-block to 24) gives bit-identical
    results to processing exactly those 17 samples — and the padding
    accounting helper reports what each lowering pads."""
    fleet = _fleet(forget=0.9)   # λ<1 would expose a pad slot decaying P
    win = _window(t=17)
    ref = _fleet_train(fleet, win)
    got_k, _ = fleet_ingest_kernel(fleet, win, block_d=4, interpret=True)
    got_x, _ = fleet_ingest_xla(fleet, win, block_t=8)
    _assert_state_close(got_k, ref)
    _assert_state_close(got_x, ref, rtol=2e-4, atol=2e-5)
    # sublane pad / block pad (block_t caps at T: one block, no pad)
    assert ingest_padding(17) == (7, 0)
    assert ingest_padding(17, block_t=8) == (7, 7)
    assert ingest_padding(32) == (0, 0)


def test_ingest_supervised_targets():
    """The optional targets window (m != n) matches the supervised
    sequential chain — the path oselm_train_sequential(kernel=True)
    rides."""
    f, nh, m, t = 21, 6, 9, 13
    key = jax.random.PRNGKey(0)
    params = init_slfn(key, f, nh)
    x0 = jax.random.uniform(key, (3 * nh, f))
    t0 = jax.random.uniform(jax.random.PRNGKey(5), (3 * nh, m))
    st = init_oselm(params, x0, t0, activation="identity", ridge=1e-2, forget=0.9)
    xs = jax.random.uniform(jax.random.PRNGKey(1), (t, f))
    ts = jax.random.uniform(jax.random.PRNGKey(2), (t, m))
    ref = oselm_train_sequential(st, xs, ts)
    for kw in (dict(backend="pallas", interpret=True), dict(backend="xla")):
        got = oselm_train_sequential(st, xs, ts, kernel=True, **kw)
        _assert_state_close(got, ref, rtol=1e-4, atol=1e-5)


def test_oselm_step_k1_kernel_wired():
    """Satellite: the (previously orphaned) fused single-step kernel is
    reachable through core.oselm's kernel= flag and matches the plain
    step."""
    f, nh = F_ODD, NH_ODD
    key = jax.random.PRNGKey(3)
    params = init_slfn(key, f, nh)
    x0 = jax.random.uniform(key, (4 * nh, f))
    st = init_oselm(params, x0, x0, activation="sigmoid", ridge=1e-2)
    x = jax.random.uniform(jax.random.PRNGKey(4), (f,))
    ref = oselm_step_k1(st, x, x)
    got = oselm_step_k1(st, x, x, kernel=True, interpret=True)
    _assert_state_close(got, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_fleet_train_kernel_dispatch(backend):
    """fleet_train(kernel=True) == fleet_train, both backends."""
    fleet = _fleet()
    win = _window()
    ref = fleet_train(fleet, win)
    got = fleet_train(fleet, win, kernel=True, backend=backend)
    tol = dict(rtol=1e-5, atol=1e-5) if backend == "pallas" else dict(rtol=2e-4, atol=2e-5)
    _assert_state_close(got, ref, **tol)


def test_fleet_train_rounds_kernel_dispatch(caplog):
    """fleet_train_rounds(kernel=True) == the XLA rounds loop, and the
    padded per-round window logs the masked-identity padding warning on
    top of the existing tail-truncation warning."""
    d = D_ODD
    fleet = _fleet(d=d)
    streams = _window(d=d, t=19, seed=7)   # 19 = 4 rounds of 4 + tail 3
    topo = ring(d, hops=2)
    with caplog.at_level("WARNING", logger="repro.fleet.fleet"):
        ref = fleet_train_rounds(fleet, streams, topo, rounds=4, ridge=RIDGE)
        got = fleet_train_rounds(
            fleet, streams, topo, rounds=4, ridge=RIDGE, kernel=True
        )
        # the pallas lowering pads each 4-sample round window to the
        # 8-row sublane tile → the masked-identity padding warning
        got_p = fleet_train_rounds(
            fleet, streams, topo, rounds=4, ridge=RIDGE,
            kernel=True, backend="pallas",
        )
    _assert_state_close(got, ref, rtol=1e-4, atol=1e-5)
    _assert_state_close(got_p, ref, rtol=1e-4, atol=1e-5)
    msgs = [r.message for r in caplog.records]
    assert any("dropping the tail" in m for m in msgs)
    assert any("masked identity slots" in m for m in msgs)


def test_fleet_ingest_dispatcher_validates_backend():
    fleet = _fleet(d=4)
    with pytest.raises(ValueError, match="backend"):
        fleet_ingest(fleet, _window(d=4), backend="cuda")


def test_sharded_ingest_single_shard_matches_unsharded():
    """shard_map'd ingest over a 1-shard mesh == fleet_train, both the
    scan and kernel paths (the 8-real-shard equality lives in
    tests/test_distribution.py as a subprocess test)."""
    d = 12
    fleet = _fleet(d=d)
    win = _window(d=d)
    mesh = jax.make_mesh((1,), ("data",))
    fleet_s = shard_fleet(fleet, mesh)
    ref = fleet_train(fleet, win)
    _, ref_losses = fleet_ingest(fleet, win, backend="xla")
    got, losses = jax.jit(sharded_ingest(mesh, ("data",), kernel=False))(fleet_s, win)
    _assert_state_close(got, ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(losses), np.asarray(ref_losses),
                               rtol=1e-5, atol=1e-6)
    got_k, losses_k = jax.jit(sharded_ingest(
        mesh, ("data",), kernel=True, backend="xla"))(fleet_s, win)
    _assert_state_close(got_k, ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(losses_k), np.asarray(ref_losses),
                               rtol=1e-5, atol=1e-6)


def test_halo_ring_merge_single_shard_matches_fleet_merge():
    """Open-ring halo-exchange merge (1-shard degenerate: the circular
    wrap) == fleet_merge; an over-wide band is rejected with the
    shards-adjacency error."""
    d = 12
    fleet = _fleet(d=d)
    fleet = fleet_train(fleet, _window(d=d))
    mesh = jax.make_mesh((1,), ("data",))
    fleet_s = shard_fleet(fleet, mesh)
    # hops=0 is the degenerate self-merge band: no halo may be shipped
    # (w[-0:] is the WHOLE shard block, not an empty halo)
    for hops in (0, 1, 2):
        ref = fleet_merge(fleet, ring(d, hops=hops), ridge=RIDGE)
        fn, operands = sharded_merge(ring(d, hops=hops), mesh, ("data",), RIDGE,
                                     kernel=False)
        got = jax.jit(fn)(fleet_s, np.ones(d, np.float32), *operands)
        _assert_state_close(got, ref, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------ runtime parity


def _mk_runtime(fleet, topo, **kw):
    return FleetRuntime(fleet, RuntimeConfig(topology=topo, ridge=RIDGE, **kw))


@pytest.mark.parametrize("ingest_backend", ["xla", "pallas"])
def test_runtime_tick_parity_kernel_vs_xla_ingest(ingest_backend):
    """Kernel ingest ↔ XLA ingest produce identical TickReports (same
    losses, same detector flags, same merge decisions) and the kernel
    runtime stays compile-once."""
    from repro.runtime import GovernorConfig

    d, f, nh, b = 12, 24, 8, 8
    topo = star(d)
    gov = GovernorConfig(merge_every=3)
    rt_ref = _mk_runtime(_fleet(d=d, f=f, nh=nh), topo, governor=gov)
    rt_k = _mk_runtime(
        _fleet(d=d, f=f, nh=nh), topo, governor=gov,
        use_ingest_kernel=True, ingest_backend=ingest_backend,
    )
    rng = np.random.default_rng(0)
    merges = 0
    for _ in range(8):
        batch = rng.random((d, b, f), np.float32)
        rep_ref = rt_ref.tick(batch)
        rep_k = rt_k.tick(batch)
        np.testing.assert_allclose(rep_k.losses, rep_ref.losses,
                                   rtol=1e-5, atol=1e-7)
        assert np.array_equal(rep_k.drifted, rep_ref.drifted)
        assert np.array_equal(rep_k.fresh_detections, rep_ref.fresh_detections)
        assert rep_k.decision.merge == rep_ref.decision.merge
        assert rep_k.decision.participants == rep_ref.decision.participants
        merges += rep_ref.decision.merge
    assert merges > 0, "soak never merged — parity test lost its teeth"
    sizes = rt_k.assert_compile_once()
    assert sizes["ingest_detect"] == 1
    _assert_state_close(rt_k.states, rt_ref.states, rtol=1e-4, atol=1e-5)


# --------------------------------------------------------- history/regression


def test_bench_history_record_and_gate(tmp_path):
    """Satellite: BENCH_history.jsonl appends entries per run and the
    gate fails only on a >25% wall-clock regression vs the previous
    same-backend baseline (first run seeds it). A failing run is still
    recorded (artifact-first) but marked regressed, so it never becomes
    the baseline a re-run would silently pass against."""
    import json

    from benchmarks.history import check_regression, record, record_and_gate

    path = tmp_path / "hist.jsonl"
    assert record("b1", {"x_us": 100.0}, path=path) is None     # seeds
    prev = record("b1", {"x_us": 110.0}, path=path)             # +10%: fine
    assert prev is not None and prev["metrics"]["x_us"] == 100.0
    assert check_regression(prev, {"x_us": 110.0}) == []
    assert check_regression(prev, {"x_us": 130.0}) != []        # +30%: fails
    # non-_us keys and new keys never gate
    assert check_regression(prev, {"x_us": 101.0, "aux": 9e9, "new_us": 5}) == []
    with pytest.raises(AssertionError, match="regression"):
        record_and_gate("b1", {"x_us": 200.0}, path=path)
    # the failing run was recorded (artifact-first), flagged regressed...
    entries = [json.loads(line) for line in path.read_text().splitlines()]
    assert entries[-1]["metrics"]["x_us"] == 200.0
    assert entries[-1]["regressed"] is True
    # ...and did NOT ratchet the baseline: the next run still gates
    # against the last GOOD entry (110), so re-running the regressed
    # timing fails again instead of self-healing
    with pytest.raises(AssertionError, match="regression"):
        record_and_gate("b1", {"x_us": 200.0}, path=path)
    assert record("b1", {"x_us": 1.0}, path=path)["metrics"]["x_us"] == 110.0


def test_ingest_rejects_per_device_bases():
    """A fleet stacked from independent per-device SLFN bases cannot be
    fused-ingested (the kernel projects through ONE shared basis) —
    validated at every concrete entry point instead of silently using
    device 0's basis."""
    from repro.core import init_autoencoder

    d, f, nh = 6, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(0), d)
    x_init = jax.random.uniform(jax.random.PRNGKey(1), (d, 4 * nh, f))
    per_dev = [init_autoencoder(k, f, nh, x0, activation="identity", ridge=1e-2)
               for k, x0 in zip(keys, x_init)]
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_dev)
    win = _window(d=d, t=8, f=f)
    with pytest.raises(ValueError, match="shared SLFN basis"):
        fleet_ingest(stacked, win)
    with pytest.raises(ValueError, match="shared SLFN basis"):
        fleet_train_rounds(stacked, win, star(d), rounds=2, kernel=True)
    with pytest.raises(ValueError, match="shared SLFN basis"):
        FleetRuntime(stacked, RuntimeConfig(topology=star(d),
                                            use_ingest_kernel=True))
    # the reference scan path handles per-device bases fine
    fleet_train(stacked, win)


def test_sharded_ingest_compile_once():
    """The sharded ingest is the tick's hot path: jitted once with its
    outputs laid out as its inputs (as ``FleetRuntime`` does), repeated
    ticks reuse ONE trace instead of re-tracing per call."""
    from jax.sharding import NamedSharding, PartitionSpec

    d = 8
    fleet = _fleet(d=d)
    mesh = jax.make_mesh((1,), ("data",))
    fleet_s = shard_fleet(fleet, mesh)
    ingest = jax.jit(sharded_ingest(mesh, ("data",), kernel=False),
                     out_shardings=NamedSharding(mesh, PartitionSpec("data")))
    sizes = []
    for seed in (2, 3, 4, 5):
        fleet_s, _ = ingest(fleet_s, _window(d=d, seed=seed))
        sizes.append(ingest._cache_size())
    # the device_put input and the jit-output sharding may compile once
    # each; after that the trace count must be FLAT across ticks
    assert sizes[-1] == sizes[1] <= 2, sizes
