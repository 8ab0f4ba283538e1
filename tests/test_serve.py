"""Serving front-end tests: request/ack protocol, dynamic batcher
(whole-request windows, padding, served masks), write-ahead log
durability + contiguity, admission policy branches, degraded-ladder
hysteresis, and the async ServeFrontend end-to-end — including
in-process crash-recovery equivalence (snapshot + WAL replay restores
the exact pre-crash fleet) and the skip-merge governor veto."""
import asyncio
import time

import jax
import numpy as np
import pytest

try:  # property-based in CI; deterministic sweep where hypothesis is absent
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.fleet import init_fleet, ring
from repro.obs import TelemetryConfig, spans_between
from repro.runtime import FleetRuntime, GovernorConfig, RuntimeConfig
from repro.serve import (
    AdmissionConfig,
    AdmissionController,
    DegradedLadder,
    LadderConfig,
    Mode,
    SampleRequest,
    ServeConfig,
    ServeFrontend,
    WindowBuilder,
    WriteAheadLog,
)

D, F, H, B = 8, 6, 4, 3
RIDGE = 1e-3


def _rng(seed=0):
    return np.random.default_rng(seed)


def _req(device=0, k=1, client="c", seed=1):
    return SampleRequest(
        device=device,
        x=_rng(seed).normal(size=(k, F)).astype(np.float32),
        client=client,
    )


def _runtime(tmp_path=None, *, snapshot_every=None, merge_every=4, d=D):
    rng = _rng(0)
    x_init = rng.normal(size=(d, 2 * H, F)).astype(np.float32)
    fleet = init_fleet(
        jax.random.PRNGKey(0), d, F, H, x_init,
        activation="identity", ridge=RIDGE,
    )
    return FleetRuntime(fleet, RuntimeConfig(
        topology=ring(d, hops=1),
        governor=GovernorConfig(merge_every=merge_every),
        snapshot_dir=None if tmp_path is None else str(tmp_path / "snap"),
        snapshot_every=snapshot_every,
        telemetry=TelemetryConfig(trace=False),
    ))


# ------------------------------------------------------------------ protocol


def test_request_promotes_1d_and_validates():
    r = SampleRequest(device=1, x=np.zeros(F, np.float32))
    assert r.x.shape == (1, F) and r.n_samples == 1
    with pytest.raises(ValueError, match="k>=1"):
        SampleRequest(device=0, x=np.zeros((0, F), np.float32))
    with pytest.raises(ValueError):
        SampleRequest(device=0, x=np.zeros((2, 2, F), np.float32))


def test_request_ids_unique():
    ids = {_req(seed=i).request_id for i in range(32)}
    assert len(ids) == 32


# ------------------------------------------------------------------- batcher


def _builder():
    return WindowBuilder(D, B, np.zeros((D, F), np.float32))


def test_batcher_window_shapes_and_served_mask():
    wb = _builder()
    wb.add(_req(device=2, k=2, seed=1))
    wb.add(_req(device=5, k=1, seed=2))
    w = wb.close(0)
    assert w.batch.shape == (D, B, F)
    assert w.served.tolist() == [d in (2, 5) for d in range(D)]
    assert w.n_requests == 2 and w.n_samples == 3
    assert wb.depth == 0
    # un-served rows padded with the fallback (zeros here)
    np.testing.assert_array_equal(w.batch[0], 0.0)
    # partially-filled served rows pad by cycling their own samples
    np.testing.assert_array_equal(w.batch[2][2], w.batch[2][0])


def test_batcher_takes_whole_requests_only():
    wb = _builder()
    wb.add(_req(device=1, k=2, seed=1))
    wb.add(_req(device=1, k=2, seed=2))  # 2+2 > B=3: must wait a window
    w = wb.close(0)
    assert w.n_requests == 1 and w.n_samples == 2
    assert wb.depth == 1
    w2 = wb.close(1)
    assert w2.n_requests == 1
    assert wb.close(2) is None  # empty: no window


def test_batcher_fallback_tracks_last_served_sample():
    wb = _builder()
    r = _req(device=3, k=2, seed=5)
    wb.add(r)
    wb.close(0)
    np.testing.assert_array_equal(wb.fallback[3], r.x[1])


def test_batcher_rejects_misfits():
    wb = _builder()
    with pytest.raises(ValueError, match="does not fit"):
        wb.add(_req(device=D, k=1))       # device out of range
    with pytest.raises(ValueError, match="does not fit"):
        wb.add(_req(device=0, k=B + 1))   # burst over budget
    assert not wb.can_fit(
        SampleRequest(device=0, x=np.zeros((1, F + 1), np.float32))
    )


# ----------------------------------------------------------------------- wal


def test_wal_roundtrip_and_gc(tmp_path):
    wal = WriteAheadLog(tmp_path)
    wb = _builder()
    for seq in range(3):
        wb.add(_req(device=seq, k=1, seed=seq))
        wal.append(wb.close(seq))
    assert wal.entries() == [0, 1, 2]
    batch, served, allow = wal.load(1)
    assert batch.shape == (D, B, F) and served[1] and allow
    assert wal.gc(before=2) == 2
    assert wal.entries() == [2]


def test_wal_contiguity_gap_raises(tmp_path):
    wal = WriteAheadLog(tmp_path)
    wb = _builder()
    for seq in (4, 5, 7):  # hole at 6
        wb.add(_req(device=0, k=1, seed=seq))
        wal.append(wb.close(seq))
    with pytest.raises(RuntimeError, match="gap"):
        wal.replayable(4)
    # entries below from_seq are covered by the snapshot: not a gap
    assert wal.replayable(7) == [7]


def test_wal_cleans_stale_tmp(tmp_path):
    (tmp_path / "wal_00000009.npz.123.tmp").write_bytes(b"torn")
    wal = WriteAheadLog(tmp_path)
    assert wal.entries() == []
    assert not list(tmp_path.glob("*.tmp"))


# ----------------------------------------------------------------- admission


def test_admission_policy_branches():
    cfg = AdmissionConfig(
        max_queue_per_device=2, client_cap=4, depth_high_frac=0.5,
        slo_p99_s=0.1, slo_min_depth_frac=0.25, budget_defer_frac=0.9,
    )
    ctl = AdmissionController(cfg, capacity=16)
    base = dict(
        mode=Mode.NORMAL, device_depth=0, client_inflight=0,
        total_depth=0, tick_p99_s=None, budget_utilization=0.0,
    )
    req = _req()
    assert ctl.decide(req, **base) == ("admit", "admit")
    assert ctl.decide(req, **{**base, "mode": Mode.SHED}) == ("shed", "degraded")
    assert ctl.decide(req, **{**base, "mode": Mode.STALE_SCORES}) == (
        "stale", "degraded")
    assert ctl.decide(req, **{**base, "device_depth": 2}) == (
        "defer", "queue_full")
    assert ctl.decide(req, **{**base, "client_inflight": 4}) == (
        "defer", "client_cap")
    assert ctl.decide(req, **{**base, "total_depth": 8}) == (
        "defer", "backpressure")
    # p99 breach alone (quiet queue) does NOT defer — no deadlock
    assert ctl.decide(req, **{**base, "tick_p99_s": 0.2}) == ("admit", "admit")
    assert ctl.decide(req, **{**base, "tick_p99_s": 0.2, "total_depth": 4}) == (
        "defer", "slo")
    assert ctl.decide(req, **{**base, "budget_utilization": 0.95}) == (
        "defer", "comm_budget")
    shed_cfg = AdmissionConfig(max_queue_per_device=2, overflow="shed")
    shed_ctl = AdmissionController(shed_cfg, capacity=16)
    assert shed_ctl.decide(req, **{**base, "device_depth": 2}) == (
        "shed", "queue_full")
    with pytest.raises(ValueError, match="defer|shed"):
        AdmissionConfig(overflow="drop")


# -------------------------------------------------------------------- ladder


def test_ladder_hysteresis_one_rung_at_a_time():
    ladder = DegradedLadder(LadderConfig(escalate_after=2, recover_after=3))
    assert ladder.check(True) == Mode.NORMAL      # 1 strike: no move
    assert ladder.check(True) == Mode.SKIP_MERGE  # 2 strikes: one rung
    assert ladder.check(True) == Mode.SKIP_MERGE
    assert ladder.check(True) == Mode.STALE_SCORES
    ladder.check(True), ladder.check(True)
    assert ladder.mode == Mode.SHED
    ladder.check(True)
    assert ladder.mode == Mode.SHED               # ceiling holds
    for _ in range(2):
        ladder.check(False)
    assert ladder.mode == Mode.SHED               # 2 calm < recover_after
    assert ladder.check(False) == Mode.STALE_SCORES
    ladder.check(True)                            # pressure resets calm run
    for _ in range(3):
        ladder.check(False)
    assert ladder.mode == Mode.SKIP_MERGE
    for _ in range(3):
        ladder.check(False)
    assert ladder.mode == Mode.NORMAL
    assert len(ladder.transitions) == 6


# ------------------------------------------------------------------ frontend


def _frontend(rt, **kw):
    kw.setdefault("batch", B)
    kw.setdefault("max_delay_s", 0.003)
    kw.setdefault("close_at_requests", 4)
    kw.setdefault("warmup", False)  # tiny fleets compile in ms
    return ServeFrontend(rt, ServeConfig(**kw))


def test_frontend_serves_and_acks_every_request():
    rt = _runtime()
    fe = _frontend(rt)
    rng = _rng(3)

    async def drive():
        await fe.start()
        acks = await asyncio.gather(*[
            fe.submit_with_retries(SampleRequest(
                device=int(rng.integers(D)),
                x=rng.normal(size=(1, F)).astype(np.float32),
                client=f"c{i % 3}",
            )) for i in range(24)
        ])
        await fe.stop()
        return acks

    acks = asyncio.run(drive())
    assert all(a.ok for a in acks), {a.status for a in acks}
    assert all(a.score is not None and a.latency_s > 0 for a in acks)
    ing = rt.telemetry.summary()["ingress"]
    assert ing["accepted"] == 24
    assert ing["acked"] == 24
    assert rt.tick_no > 0
    rt.assert_compile_once()
    assert not fe._futures and not fe._client_inflight  # nothing leaked


def test_frontend_rejects_malformed_without_crashing():
    rt = _runtime()
    fe = _frontend(rt)

    async def drive():
        await fe.start()
        bad_dev = await fe.submit(_req(device=D + 3))
        bad_burst = await fe.submit(_req(device=0, k=B + 2))
        ok = await fe.submit(_req(device=0, k=1))
        await fe.stop()
        return bad_dev, bad_burst, ok

    bad_dev, bad_burst, ok = asyncio.run(drive())
    assert bad_dev.status == "shed" and "out of range" in bad_dev.reason
    assert bad_burst.status == "shed"
    assert ok.ok


def test_frontend_crash_recovery_restores_exact_state(tmp_path):
    """Snapshot + WAL replay reconstructs the pre-crash fleet exactly:
    a fresh runtime recovered from disk matches the original's model
    and detector state bit-for-bit, with telemetry continuous."""
    rt = _runtime(tmp_path, snapshot_every=4)
    fe = _frontend(rt, wal_dir=str(tmp_path / "wal"))
    rng = _rng(9)

    async def drive():
        await fe.start()
        for _ in range(6):  # several windows: snapshots + WAL-only tail
            await asyncio.gather(*[
                fe.submit_with_retries(SampleRequest(
                    device=int(rng.integers(D)),
                    x=rng.normal(size=(1, F)).astype(np.float32),
                )) for _ in range(6)
            ])
        await fe.stop()

    asyncio.run(drive())
    assert rt.tick_no > 4  # at least one snapshot plus a WAL tail
    beta_ref = np.asarray(rt.states.beta)
    ewma_ref = np.asarray(rt.det.ewma)
    ticks_ref = rt.tick_no

    # "crash": the original objects are simply never consulted again
    rt2 = _runtime(tmp_path, snapshot_every=4)
    fe2 = _frontend(rt2, wal_dir=str(tmp_path / "wal"))
    restored, replayed = fe2.recover()
    assert restored < ticks_ref and replayed == ticks_ref - restored
    assert rt2.tick_no == ticks_ref
    np.testing.assert_array_equal(np.asarray(rt2.states.beta), beta_ref)
    np.testing.assert_array_equal(np.asarray(rt2.det.ewma), ewma_ref)
    # counters rode the snapshot and advanced through the replay
    assert int(rt2.telemetry.ticks.value) == ticks_ref
    assert int(rt2.telemetry.ingress_replayed.value) == replayed


def test_frontend_skip_merge_vetoes_governor():
    rt = _runtime(merge_every=2)
    # recover_after astronomically high: the pinned degraded mode stays
    # pinned no matter how many calm watchdog checks accumulate
    fe = _frontend(rt, ladder=LadderConfig(recover_after=10**9))
    fe.ladder.mode = Mode.SKIP_MERGE  # pin the ladder: windows veto merges

    async def drive():
        await fe.start()
        for _ in range(8):
            await asyncio.gather(*[
                fe.submit(_req(device=d, k=1, seed=d)) for d in range(D)
            ])
        await fe.stop()

    asyncio.run(drive())
    assert rt.governor.state.merges == 0
    assert rt.governor.state.deferred_degraded > 0
    assert rt.tick_no >= 4  # ticks kept flowing while merges were vetoed


def test_frontend_stall_counts_stall_checks_before_the_ladder_moves():
    """A worker stalled past the deadline shows up as cause=stall
    pressure checks, at least ``escalate_after`` of them by the time the
    ladder leaves NORMAL, and as no other cause."""
    rt = _runtime()
    fe = _frontend(
        rt, tick_deadline_s=0.05, watchdog_interval_s=0.005,
        ladder=LadderConfig(escalate_after=3, recover_after=10**9),
        pre_tick=lambda w: time.sleep(0.4) if w.seq == 0 else None,
    )
    stall = rt.telemetry.ingress_pressure_checks.labels(cause="stall")
    seen = []
    check = fe.ladder.check

    def watched(pressured):
        mode = check(pressured)
        seen.append((mode, stall.value))
        return mode

    fe.ladder.check = watched

    async def drive():
        await fe.start()
        ack = await fe.submit(_req(device=0, k=1))
        await fe.stop()
        return ack

    assert asyncio.run(drive()).ok  # the stalled tick still finished
    first = next(i for i, (mode, _) in enumerate(seen) if mode != Mode.NORMAL)
    assert seen[first][1] >= 3
    ing = rt.telemetry.ingress_stats()
    assert ing["pressure_checks"]["stall"] >= 3
    assert ing["pressure_checks"].get("p99", 0) == 0
    assert ing["pressure_checks"].get("depth", 0) == 0
    assert ing["degraded_transitions"].get("skip_merge") == 1


def test_frontend_records_ingress_spans_per_window():
    """Each window records ingress.close (its requests and summed
    admission time), ingress.queued and ingress.complete, with the
    window's tick number as seq — the number the acks carry — and no
    span per request."""
    rt = _runtime()
    fe = _frontend(rt, close_at_requests=12, max_delay_s=0.05)
    rng = _rng(5)

    async def drive():
        await fe.start()
        acks = await asyncio.gather(*[
            fe.submit(SampleRequest(
                device=i % D, x=rng.normal(size=(1, F)).astype(np.float32),
                client=f"c{i}",
            )) for i in range(24)
        ])
        await fe.stop()
        return acks

    t0 = time.perf_counter()
    acks = asyncio.run(drive())
    spans = spans_between(t0, time.perf_counter())
    assert all(a.ok for a in acks)
    ticks = sorted({a.tick for a in acks})
    closes = [s for s in spans if s.name == "ingress.close" and "n" in s.attrs]
    assert sorted(s.seq for s in closes) == ticks
    assert sum(s.attrs["n"] for s in closes) == 24
    assert all(s.attrs["admit_s"] > 0 for s in closes)
    for name in ("ingress.queued", "ingress.complete"):
        assert sorted(s.seq for s in spans if s.name == name) == ticks, name
    assert sum(s.attrs["n"] for s in spans if s.name == "ingress.complete") == 24
    assert sorted(s.seq for s in spans if s.name == "tick") == ticks
    by_seq = {s.seq: s for s in closes}
    for s in spans:
        if s.name == "ingress.queued":  # close, then the worker's pickup
            assert s.start == by_seq[s.seq].end and s.end >= s.start
    n_ingress = sum(s.name.startswith("ingress.") for s in spans)
    n_closes = sum(s.name == "ingress.close" for s in spans)
    assert n_ingress == n_closes + 2 * len(ticks)


def _closes_with_ticks(fe):
    """Wrap the window cut to note the ticks the tick histogram had
    observed at each close, in close order."""
    seen = []
    close = fe.builder.close

    def watched(*args, **kw):
        seen.append(fe.telemetry.tick_seconds.count)
        return close(*args, **kw)

    fe.builder.close = watched
    return seen


def test_frontend_slo_defers_under_load_and_counts_p99_evals():
    """With an SLO set and every tick over it, submits past the SLO's
    minimum load answer busy/slo end to end; each window's p99_evals is
    the p99s its submits computed, at most one per tick observed."""
    rt = _runtime()
    fe = _frontend(
        rt, close_at_requests=64, max_delay_s=0.01,
        admission=AdmissionConfig(slo_p99_s=1e-9),
        # keep the watchdog's own p99 reads and the ladder out of it
        watchdog_interval_s=10.0,
        ladder=LadderConfig(escalate_after=10**9),
    )
    observed = _closes_with_ticks(fe)
    rng = _rng(11)

    def wave(n):
        return asyncio.gather(*[
            fe.submit(SampleRequest(
                device=i % D, x=rng.normal(size=(1, F)).astype(np.float32),
                client=f"c{i}",
            )) for i in range(n)
        ])

    async def drive():
        await fe.start()
        first = await wave(4)   # no tick observed yet: no p99 to read
        loaded = await wave(40)
        await fe.stop()
        return first, loaded

    t0 = time.perf_counter()
    first, loaded = asyncio.run(drive())
    spans = spans_between(t0, time.perf_counter())
    assert all(a.ok for a in first)
    # capacity 8 x 8 = 64; slo_min_depth_frac 0.25: 16 admitted, then slo
    statuses = [(a.status, a.reason) for a in loaded]
    assert statuses.count(("busy", "slo")) == 24
    assert sum(a.ok for a in loaded) == 16
    assert rt.telemetry.ingress_stats()["deferred"] == {"slo": 24}
    closes = [s for s in spans if s.name == "ingress.close" and "n" in s.attrs]
    evals = [int(s.attrs["p99_evals"]) for s in sorted(closes, key=lambda s: s.seq)]
    assert evals == [0, 1]  # 44 submits, one tick between them: one p99
    assert len(observed) == len(evals)
    for k, n in enumerate(evals):
        since = observed[k] - (observed[k - 1] if k else 0)
        assert n <= since
    assert rt.telemetry.tick_seconds.evals == 1


def test_frontend_without_slo_reads_no_p99_and_decides_the_same():
    """A served run with no SLO computes no tick p99 on the submit path
    (p99_evals 0 on every window), and its decisions and acks are those
    of the same run with a never-breached SLO, which reads the p99 on
    every submit."""

    def run(admission):
        rt = _runtime(merge_every=2)
        fe = _frontend(rt, close_at_requests=D, max_delay_s=1.0,
                       admission=admission,
                       watchdog_interval_s=10.0)  # no p99 reads of its own
        decisions = []
        decide = fe.admission.decide

        def watched(req, **kw):
            verdict = decide(req, **kw)
            decisions.append((verdict, kw["tick_p99_s"] is not None))
            return verdict

        fe.admission.decide = watched
        rng = _rng(13)

        async def drive():
            await fe.start()
            acks = []
            for _ in range(6):  # whole windows, one request per device
                acks += await asyncio.gather(*[
                    fe.submit(SampleRequest(
                        device=d, x=rng.normal(size=(1, F)).astype(np.float32),
                        client=f"c{d}",
                    )) for d in range(D)
                ])
            await fe.stop()
            return acks

        t0 = time.perf_counter()
        acks = asyncio.run(drive())
        spans = spans_between(t0, time.perf_counter())
        evals = [s.attrs["p99_evals"] for s in spans
                 if s.name == "ingress.close" and "n" in s.attrs]
        return acks, decisions, evals

    acks, decisions, evals = run(AdmissionConfig())
    acks_read, decisions_read, evals_read = run(
        AdmissionConfig(slo_p99_s=float("inf"))
    )
    assert len(evals) == 6 and evals == [0] * 6
    assert not any(read for _, read in decisions)
    assert sum(read for _, read in decisions_read) == 5 * D  # after tick 0
    assert evals_read == [0] + [1] * 5  # one p99 a tick observed
    assert [v for v, _ in decisions] == [v for v, _ in decisions_read]
    assert all(a.ok for a in acks)
    assert [(a.status, a.tick, a.score, a.drifted) for a in acks] == [
        (a.status, a.tick, a.score, a.drifted) for a in acks_read
    ]


def test_frontend_requires_telemetry():
    rng = _rng(0)
    x_init = rng.normal(size=(D, 2 * H, F)).astype(np.float32)
    fleet = init_fleet(
        jax.random.PRNGKey(0), D, F, H, x_init,
        activation="identity", ridge=RIDGE,
    )
    bare = FleetRuntime(fleet, RuntimeConfig(topology=ring(D, hops=1)))
    with pytest.raises(ValueError, match="telemetry"):
        ServeFrontend(bare, ServeConfig(batch=B))


# -------------------------------------------------- batcher edge cases


def test_batcher_head_blocked_close_raises_pre_mutation():
    """A head request larger than the window budget can never ride any
    window; close() must raise BEFORE popping anything so the depth
    invariant (Σ queue lengths == depth) survives the failed close."""
    wb = _builder()
    wb.add(_req(device=1, k=1, seed=1))
    wb.add(_req(device=4, k=2, seed=2))
    oversized = SampleRequest(
        device=4, x=_rng(3).normal(size=(B + 2, F)).astype(np.float32)
    )
    wb.pending[4].appendleft(oversized)  # bypasses add()'s burst cap
    wb.depth += 1
    before = [list(q) for q in wb.pending]
    with pytest.raises(ValueError, match="head-blocked"):
        wb.close(0)
    # nothing was dequeued: queues and depth are exactly pre-close
    assert [list(q) for q in wb.pending] == before
    assert wb.depth == sum(len(q) for q in wb.pending) == 3
    # unblocking the head lets the very next close drain normally
    assert wb.pending[4].popleft() is oversized
    wb.depth -= 1
    w = wb.close(0)
    assert w.n_requests == 2
    assert wb.depth == 0


def _check_window_partition(bursts, closes_between):
    """WindowBuilder invariants under an arbitrary admit/close script:
    depth always equals Σ queue lengths, and every admitted request
    lands in EXACTLY one window (no loss, no double-dispatch)."""
    wb = _builder()
    admitted: list[str] = []
    dispatched: list[str] = []
    seq = 0
    script = list(bursts)
    while script or wb.depth:
        for device, k in script[:closes_between]:
            r = _req(device=device, k=k, seed=len(admitted))
            wb.add(r)
            admitted.append(r.request_id)
            assert wb.depth == sum(len(q) for q in wb.pending)
        script = script[closes_between:]
        w = wb.close(seq, allow_merge=bool(seq % 2))
        seq += 1
        if w is not None:
            dispatched.extend(r.request_id for r in w.requests)
            assert w.served.sum() > 0
            assert w.n_samples <= D * B
        assert wb.depth == sum(len(q) for q in wb.pending)
        assert len(set(dispatched)) == len(dispatched), "double-dispatch"
    assert wb.close(seq) is None  # drained: empty tick, no window
    assert sorted(dispatched) == sorted(admitted), "lost or dropped request"


if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=50)
    @given(
        bursts=st.lists(
            st.tuples(st.integers(0, D - 1), st.integers(1, B)),
            max_size=24,
        ),
        closes_between=st.integers(1, 6),
    )
    def test_batcher_partition_property(bursts, closes_between):
        _check_window_partition(bursts, closes_between)
else:
    @pytest.mark.parametrize("seed,n,closes_between", [
        (0, 0, 1), (1, 7, 1), (2, 24, 2), (3, 24, 5), (4, 13, 3), (5, 24, 6),
    ])
    def test_batcher_partition_property(seed, n, closes_between):
        rng = _rng(seed)
        bursts = [
            (int(rng.integers(0, D)), int(rng.integers(1, B + 1)))
            for _ in range(n)
        ]
        _check_window_partition(bursts, closes_between)


def test_wal_warns_on_malformed_filename(tmp_path, caplog):
    wal = WriteAheadLog(tmp_path)
    wb = _builder()
    wb.add(_req(device=0, k=1, seed=0))
    wal.append(wb.close(3))
    (tmp_path / "wal_corrupted.npz").write_bytes(b"junk")
    import logging

    with caplog.at_level(logging.WARNING, logger="repro.serve.wal"):
        assert wal.entries() == [3]  # junk skipped, real entry kept
    assert any("wal_corrupted.npz" in rec.message for rec in caplog.records)
    # replay over the surviving entries still works end to end
    assert wal.replayable(3) == [3]
