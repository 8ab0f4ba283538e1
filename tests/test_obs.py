"""Telemetry subsystem tests: histogram bucket semantics, counter
monotonicity (including across snapshot/restore), registry exposition
and state round-trips, program spans (the bounded ring, parents across
threads, the JSONL export, phase histograms fed from spans), the
flight-recorder ring, and the runtime integration — compile-once with
the sink on, flight dumps on injected NaN payloads, and the bounded
detections log."""
import json
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.data import make_har_dataset
from repro.data.pipeline import anomaly_eval_arrays, train_test_split
from repro.data.synthetic import AnomalyDataset
from repro.fleet import DriftEvent, init_fleet, make_fleet_streams, ring
from repro.fleet.faults import FaultInjector, FaultSpec
from repro.fleet.robust import RobustConfig
from repro.obs import (
    Counter,
    FlightRecorder,
    Histogram,
    MetricsRegistry,
    SpanRing,
    TelemetryConfig,
    TelemetrySink,
    Tracer,
    load_dump,
    record,
    span,
    spans_between,
)
from repro.runtime import (
    DetectorConfig,
    FleetRuntime,
    GovernorConfig,
    RuntimeConfig,
    TickFeed,
)

RIDGE = 1e-3
H_RT = 16

# ------------------------------------------------------------------- metrics


def test_counter_monotone():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 3.5


def test_histogram_bucket_edges_are_inclusive_upper_bounds():
    h = Histogram(buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 4.0, 100.0):
        h.observe(v)
    # le semantics: a value equal to an edge lands in that edge's bucket
    assert h.counts == [2, 2, 1, 1]  # le=1, le=2, le=4, +Inf
    assert h.count == 6
    assert h.vmin == 0.5 and h.vmax == 100.0
    assert h.sum == pytest.approx(109.0)


def test_histogram_rejects_bad_edges():
    with pytest.raises(ValueError):
        Histogram(buckets=())
    with pytest.raises(ValueError):
        Histogram(buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        Histogram(buckets=(2.0, 1.0))


def test_histogram_observe_many_matches_sequential_observe():
    rng = np.random.default_rng(0)
    values = rng.gamma(1.0, 2.0, size=257)
    one = Histogram(buckets=(0.5, 1.0, 2.0, 8.0), sample_cap=100)
    many = Histogram(buckets=(0.5, 1.0, 2.0, 8.0), sample_cap=100)
    for v in values:
        one.observe(v)
    many.observe_many(values)
    assert one.counts == many.counts
    assert one.count == many.count
    assert one.sum == pytest.approx(many.sum)
    assert list(one.samples) == pytest.approx(list(many.samples))
    assert one.quantile(0.5) == pytest.approx(many.quantile(0.5))


def test_histogram_sample_window_is_bounded():
    h = Histogram(buckets=(1.0,), sample_cap=8)
    h.observe_many(np.arange(100, dtype=np.float64))
    assert len(h.samples) == 8
    assert list(h.samples) == list(range(92, 100))  # most recent retained
    assert h.count == 100  # aggregate stats still see everything


def test_histogram_quantile_kept_until_samples_change(monkeypatch):
    """quantile() equals np.percentile over the retained samples after
    observe, observe_many, eviction past sample_cap and load, and reads
    between changes compute no percentile."""
    calls = []
    percentile = np.percentile

    def counted(a, q, *args, **kw):
        calls.append(q)
        return percentile(a, q, *args, **kw)

    monkeypatch.setattr(np, "percentile", counted)

    def exact(h, q):
        return float(percentile(np.asarray(h.samples, np.float64), 100 * q))

    rng = np.random.default_rng(4)
    h = Histogram(buckets=(0.5, 1.0, 2.0), sample_cap=16)
    assert h.quantile(0.99) is None and not calls
    steps = [
        lambda: h.observe(rng.gamma(1.0)),
        lambda: h.observe_many(rng.gamma(1.0, size=7)),
        lambda: h.observe_many(rng.gamma(1.0, size=30)),  # evicts past the cap
        lambda: [h.observe(v) for v in rng.gamma(1.0, size=3)],  # evicts
    ]
    for change in steps:
        change()
        before = len(calls)
        for _ in range(5):
            assert h.quantile(0.99) == exact(h, 0.99)
            assert h.quantile(0.5) == exact(h, 0.5)
        assert len(calls) - before == 2  # once per q until the next change
    assert len(h.samples) == 16
    assert h.evals == len(calls) == 8

    other = Histogram(buckets=(0.5, 1.0, 2.0), sample_cap=16)
    other.observe_many([9.0, 10.0])
    assert other.quantile(0.99) == exact(other, 0.99)
    other.load(h.snapshot())
    assert list(other.samples) == list(h.samples)
    assert other.quantile(0.99) == exact(other, 0.99) == h.quantile(0.99)
    assert other.evals == 2


def test_histogram_quantile_never_outlives_an_observe_on_another_thread():
    """Readers on other threads compute quantiles across observes (the
    served path reads on the event loop while the worker observes); no
    value computed over the samples before an observe survives it."""
    qs = [i / 64 for i in range(65)]
    h = Histogram(buckets=(1.0,), sample_cap=16)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for round_ in range(20):
            stop = threading.Event()

            def read():
                i = 0
                while not stop.is_set():
                    h.quantile(qs[i % len(qs)])
                    i += 1

            readers = [threading.Thread(target=read) for _ in range(8)]
            for t in readers:
                t.start()
            for v in range(50):
                h.observe(float(round_ * 50 + v))
                time.sleep(0)
            stop.set()
            for t in readers:
                t.join(timeout=10)
            assert not any(t.is_alive() for t in readers)
            window = np.asarray(h.samples, np.float64)
            for q in qs:
                assert h.quantile(q) == float(np.percentile(window, 100 * q)), q
    finally:
        sys.setswitchinterval(switch)


def test_registry_labels_and_redeclare():
    r = MetricsRegistry()
    fam = r.counter("merge_bytes_total", labels=("precision",))
    fam.labels(precision="f32").inc(100)
    fam.labels(precision="int8").inc(25)
    assert fam.labels(precision="f32").value == 100
    # same (name, kind, labels) → the same object
    assert r.counter("merge_bytes_total", labels=("precision",)) is fam
    with pytest.raises(ValueError):
        r.gauge("merge_bytes_total")  # one name, one meaning
    with pytest.raises(ValueError):
        fam.labels(wrong="x")
    with pytest.raises(ValueError):
        r.counter("bad name!")


def test_registry_exposition_well_formed():
    r = MetricsRegistry()
    r.counter("ticks_total", "ticks").inc(5)
    r.gauge("quarantined_devices").set(2)
    h = r.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(10.0)
    text = r.exposition()
    assert "# TYPE ticks_total counter" in text
    assert "ticks_total 5" in text
    assert "# TYPE quarantined_devices gauge" in text
    assert "# TYPE lat_seconds histogram" in text
    # buckets are CUMULATIVE and +Inf equals the total count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text


def test_registry_state_roundtrip():
    r = MetricsRegistry()
    r.counter("ticks_total").inc(7)
    r.gauge("level").set(-1.5)
    fam = r.counter("bytes_total", labels=("precision",))
    fam.labels(precision="f32").inc(64)
    h = r.histogram("lat", buckets=(1.0, 2.0))
    h.observe_many([0.5, 1.5, 9.0])

    state = json.loads(json.dumps(r.state()))  # must survive JSON

    r2 = MetricsRegistry()
    r2.counter("ticks_total")
    r2.gauge("level")
    r2.counter("bytes_total", labels=("precision",))
    r2.histogram("lat", buckets=(1.0, 2.0))
    r2.load_state(state)
    assert r2.counter("ticks_total").value == 7
    assert r2.gauge("level").value == -1.5
    assert r2.counter(
        "bytes_total", labels=("precision",)
    ).labels(precision="f32").value == 64
    h2 = r2.histogram("lat", buckets=(1.0, 2.0))
    assert h2.counts == h.counts and h2.count == 3
    assert h2.quantile(0.5) == h.quantile(0.5)


def test_registry_load_rejects_bucket_mismatch():
    r = MetricsRegistry()
    r.histogram("lat", buckets=(1.0, 2.0)).observe(0.5)
    state = r.state()
    r2 = MetricsRegistry()
    r2.histogram("lat", buckets=(1.0, 4.0))
    with pytest.raises(ValueError):
        r2.load_state(state)


def test_phase_timer_fences_device_work():
    """A phase span fenced on its device work observes exactly the
    span's own duration into the phase histogram."""
    sink = TelemetrySink(TelemetryConfig())
    h = sink.phase_seconds.labels(phase="tick.ingest")
    with sink.phase("tick.ingest") as sp:
        x = jax.numpy.ones((256, 256)) @ jax.numpy.ones((256, 256))
        jax.block_until_ready(x)
    assert h.count == 1 and h.sum == sp.seconds > 0
    # an empty phase still observes
    with sink.phase("tick.ingest"):
        pass
    assert h.count == 2


# --------------------------------------------------------------------- trace


def test_tracer_writes_parseable_jsonl(tmp_path):
    ring = SpanRing(16)
    path = tmp_path / "trace.jsonl"
    tr = Tracer(path, ring=ring)
    with span("merge", seq=3, ring=ring, participants=5):
        pass
    record("ingress.queued", 1.0, 1.5, seq=3, ring=ring)
    tr.close()
    events = [json.loads(line) for line in path.read_text().splitlines()]
    assert [e["name"] for e in events] == ["merge", "ingress.queued"]
    assert events[0]["seq"] == 3 and events[0]["participants"] == 5
    assert events[0]["dur_s"] >= 0 and events[0]["parent"] == -1
    assert events[1]["dur_s"] == 0.5
    assert tr.events_written == 2


def test_tracer_disabled_is_noop(tmp_path):
    """Without a path the tracer writes nothing; the spans still record."""
    ring = SpanRing(16)
    tr = Tracer(None, ring=ring)
    with span("x", ring=ring):
        pass
    tr.close()
    assert tr.events_written == 0
    assert [s.name for s in ring.rows()] == ["x"]
    assert list(tmp_path.iterdir()) == []


def test_tracer_writes_only_at_flush(tmp_path):
    """Recording never touches the file; each flush appends the spans
    closed since the previous one."""
    ring = SpanRing(64)
    path = tmp_path / "trace.jsonl"
    tr = Tracer(path, ring=ring)
    for i in range(5):
        with span("tick", seq=i, ring=ring):
            pass
    assert path.read_text() == ""
    tr.flush()
    assert len(path.read_text().splitlines()) == 5
    with span("tick", seq=5, ring=ring):
        pass
    assert len(path.read_text().splitlines()) == 5
    tr.close()
    seqs = [json.loads(x)["seq"] for x in path.read_text().splitlines()]
    assert seqs == [0, 1, 2, 3, 4, 5]


def test_span_ring_bounded_overwrites_oldest():
    """A full ring overwrites its oldest slot: capacity bounds memory,
    the newest spans are kept, and the columns never grow."""
    ring = SpanRing(4)
    cols = ring._start
    for i in range(11):
        record("page.stage", float(i), i + 0.5, seq=i, ring=ring)
    assert ring.recorded == 11
    assert ring._start is cols and cols.shape == (4,)
    assert [s.seq for s in ring.rows()] == [7, 8, 9, 10]
    assert [s.seq for s in ring.between(8.0, 10.5)] == [8, 9, 10]
    # rows() from a position the ring has overwritten starts at the oldest kept
    assert [s.seq for s in ring.rows(2)] == [7, 8, 9, 10]
    with pytest.raises(ValueError):
        SpanRing(0)


def test_span_parents_and_seq_nest_per_thread():
    """Children name their parent and inherit its seq; a span opened on
    another thread starts its own tree."""
    ring = SpanRing(64)
    seen = {}

    def worker():
        with span("tick", seq=9, ring=ring) as root:
            with span("tick.ingest", ring=ring) as child:
                seen["w"] = (root, child)

    with span("ingress.close", seq=4, ring=ring) as outer:
        th = threading.Thread(target=worker)
        th.start()
        th.join(timeout=30)
        assert not th.is_alive()
        with span("ingress.complete", ring=ring) as inner:
            pass
    rows = {s.name: s for s in ring.rows()}
    assert rows["ingress.complete"].parent == rows["ingress.close"].id
    assert rows["ingress.complete"].seq == 4
    assert rows["ingress.close"].parent == -1
    # the worker's tree: its root has no parent (not the event loop's span)
    assert rows["tick"].parent == -1 and rows["tick"].seq == 9
    assert rows["tick.ingest"].parent == rows["tick"].id
    assert rows["tick.ingest"].seq == 9
    assert outer.start <= inner.start <= inner.end <= outer.end


def test_span_record_across_threads_and_perf_counter_clock():
    """A span whose ends were read on two threads is recorded after the
    fact, on the perf_counter clock spans use."""
    ring = SpanRing(16)
    t_close = time.perf_counter()
    got = []

    def worker():
        t_pick = time.perf_counter()
        record("ingress.queued", t_close, t_pick, seq=2, ring=ring)
        got.append(t_pick)

    th = threading.Thread(target=worker)
    th.start()
    th.join(timeout=30)
    assert not th.is_alive()
    lo = time.perf_counter()
    with span("tick", ring=ring):
        pass
    hi = time.perf_counter()
    queued, tick = ring.rows()
    assert (queued.name, queued.seq) == ("ingress.queued", 2)
    assert queued.start == t_close and queued.end == got[0]
    assert lo <= tick.start <= tick.end <= hi
    with pytest.raises(ValueError):
        record("x", 0.0, 1.0, ring=ring, a=1, b=2, c=3, d=4)
    assert ring.recorded == 2


def test_spans_between_filters_to_the_window():
    ring = SpanRing(16)
    for name, a, b in [("a", 1.0, 2.0), ("b", 2.5, 3.0), ("c", 2.9, 4.1), ("d", 5.0, 6.0)]:
        record(name, a, b, ring=ring)
    assert [s.name for s in spans_between(2.0, 4.5, ring=ring)] == ["b", "c"]
    assert spans_between(7.0, 8.0, ring=ring) == []


# -------------------------------------------------------------------- flight


def test_flight_ring_bounded():
    fr = FlightRecorder(capacity=4)
    for t in range(10):
        fr.record({"tick": t})
    assert len(fr) == 4
    assert fr.records_total == 10
    assert [r["tick"] for r in fr.records()] == [6, 7, 8, 9]


def test_flight_dump_roundtrip_and_rate_limit(tmp_path):
    fr = FlightRecorder(capacity=8, max_dumps=2)
    # records may carry numpy leaves; the dump must still serialize
    fr.record({"tick": 0, "losses": np.asarray([1.0, 2.0], np.float32),
               "n": np.int64(3)})
    inputs = np.arange(12, dtype=np.float32).reshape(2, 2, 3)
    path = fr.dump(tmp_path, 0, "nonfinite", inputs=inputs,
                   extra={"count": np.int32(2)})
    assert path is not None
    dump = load_dump(path)
    assert dump["reason"] == "nonfinite"
    assert dump["ring"][0]["losses"] == [1.0, 2.0]
    assert dump["extra"]["count"] == 2
    np.testing.assert_array_equal(dump["inputs"], inputs)
    assert dump["inputs"].dtype == np.float32

    assert fr.dump(tmp_path, 1, "nonfinite") is not None  # budget: 2
    assert fr.dump(tmp_path, 2, "nonfinite") is None      # over budget
    # a NEW reason always gets its first dump, even over budget
    assert fr.dump(tmp_path, 3, "slo") is not None
    assert len(fr.dumps) == 3


def test_flight_state_roundtrip():
    fr = FlightRecorder(capacity=4, max_dumps=1)
    for t in range(6):
        fr.record({"tick": t})
    state = json.loads(json.dumps(fr.state()))
    fr2 = FlightRecorder(capacity=4)
    fr2.load_state(state)
    assert fr2.records() == fr.records()
    assert fr2.records_total == 6


# ---------------------------------------------------------- sink + runtime


def _har3():
    ds = make_har_dataset(seed=0, samples_per_class=100)
    lo, hi = ds.x.min(0), ds.x.max(0)
    ds = ds._replace(x=((ds.x - lo) / (hi - lo + 1e-6)).astype(np.float32))
    train, test = train_test_split(ds, 0.8, seed=0)

    def sub(d):
        m = d.y < 3
        return AnomalyDataset(d.name, d.x[m], d.y[m], d.class_names[:3])

    return sub(train), sub(test)


@pytest.fixture(scope="module")
def obs_scenario():
    """8 devices, 60 ticks, 2 drifting mid-soak — small enough that the
    telemetry integration tests stay cheap."""
    train3, test3 = _har3()
    ticks, batch = 60, 2
    drift = tuple(
        DriftEvent(device=d, step=60 + 11 * i, new_pattern=2)
        for i, d in enumerate((2, 5))
    )
    fs = make_fleet_streams(
        train3, 8, ticks * batch, n_init=2 * H_RT, drift=drift, seed=0,
        n_assign=2,
    )
    x_eval, y_eval = anomaly_eval_arrays(test3, [0, 1], anomaly_ratio=0.3, seed=0)
    return train3, fs, batch


def _mk_runtime(fs, n_features, *, telemetry=None, **cfg_kw):
    fleet = init_fleet(
        jax.random.PRNGKey(0), fs.n_devices, n_features, H_RT, fs.x_init,
        activation="identity", ridge=RIDGE,
    )
    cfg_kw.setdefault("governor", GovernorConfig(merge_every=16))
    cfg = RuntimeConfig(
        topology=ring(fs.n_devices, hops=2), ridge=RIDGE,
        detector=DetectorConfig(),
        telemetry=telemetry, **cfg_kw,
    )
    return FleetRuntime(fleet, cfg)


def test_runtime_compile_once_with_telemetry(obs_scenario):
    """Enabling the sink must not add a single retrace."""
    train3, fs, batch = obs_scenario
    rt = _mk_runtime(
        fs, train3.n_features, telemetry=TelemetryConfig(band_sample_every=1)
    )
    rt.run(TickFeed(fs, batch))
    sizes = rt.assert_compile_once()
    assert all(v == 1 for v in sizes.values())
    summary = rt.finalize_telemetry()
    assert summary["ticks"] == 60
    assert summary["merge_rounds"] == rt.governor.state.merges
    assert summary["bytes_total"] == rt.governor.state.bytes_spent
    # band histograms sampled every tick here: calibrated devices observed
    assert summary["metrics"]["detector_band_width"]["series"][0]["count"] > 0
    # every phase that ran has latency stats
    assert {"tick.poison", "tick.put", "tick.ingest", "tick.govern",
            "tick.merge"} <= set(summary["phases"])


def test_runtime_tick_spans_are_the_report_and_phase_timings(obs_scenario):
    """Each tick is a root span ``tick`` (seq = tick number) over its
    phase spans; ``TickReport.ingest_seconds``/``merge_seconds`` are
    the spans' durations and the phase histogram holds the same
    numbers (one timing, three readers)."""
    train3, fs, batch = obs_scenario
    rt = _mk_runtime(fs, train3.n_features, telemetry=TelemetryConfig())
    feed = TickFeed(fs, batch)
    t0 = time.perf_counter()
    reports = [rt.tick(feed.tick_batch(t)) for t in range(17)]
    spans = spans_between(t0, time.perf_counter())
    roots = [s for s in spans if s.name == "tick"]
    assert [s.seq for s in roots] == list(range(17))
    by_id = {s.id: s for s in spans}
    for s in spans:
        if s.name != "tick":
            assert by_id[s.parent].name == "tick" and s.seq == by_id[s.parent].seq
    ingest = {s.seq: s for s in spans if s.name == "tick.ingest"}
    merge = {s.seq: s for s in spans if s.name == "tick.merge"}
    assert [r.ingest_seconds for r in reports] == [ingest[t].seconds for t in range(17)]
    merged = [r.tick for r in reports if r.merge_seconds is not None]
    assert merged == sorted(merge) == [15]
    assert reports[15].merge_seconds == merge[15].seconds
    h = rt.telemetry.phase_seconds.labels(phase="tick.ingest")
    assert h.count == 17
    assert h.sum == pytest.approx(sum(s.seconds for s in ingest.values()), rel=1e-12)
    # a put precedes each ingest, and the telemetry span closes each tick
    names = [s.name for s in spans if s.seq == 3 and s.name != "tick"]
    assert names == ["tick.poison", "tick.put", "tick.ingest", "tick.readback",
                     "tick.govern", "tick.telemetry"]


def test_runtime_records_spans_without_a_sink(obs_scenario):
    """The ring is always on: a runtime with no telemetry still records
    its tick spans (the fed benchmark cells run without a sink)."""
    train3, fs, batch = obs_scenario
    rt = _mk_runtime(fs, train3.n_features)
    assert rt.telemetry is None
    t0 = time.perf_counter()
    rep = rt.tick(TickFeed(fs, batch).tick_batch(0))
    spans = spans_between(t0, time.perf_counter())
    assert [s.name for s in spans if s.name.startswith("tick")] == [
        "tick.poison", "tick.put", "tick.ingest", "tick.readback", "tick.govern", "tick"]
    assert rep.ingest_seconds == next(s.seconds for s in spans if s.name == "tick.ingest")


def test_runtime_telemetry_counters_survive_restore(tmp_path, obs_scenario):
    """Kill/restore continuity: the restored sink resumes the counter
    trajectory (ticks, merges, bytes) instead of restarting from zero."""
    train3, fs, batch = obs_scenario

    def fresh():
        return _mk_runtime(
            fs, train3.n_features, telemetry=TelemetryConfig(),
            snapshot_every=20, snapshot_dir=tmp_path,
        )

    rt = fresh()
    feed = TickFeed(fs, batch)
    rt.run(feed, ticks=40)
    rt.snapshot()
    before = rt.telemetry.state()

    rt2 = fresh()
    assert rt2.restore() == 40
    assert int(rt2.telemetry.ticks.value) == 40
    assert rt2.telemetry.state()["registry"] == before["registry"]
    assert rt2.detections_total == rt.detections_total
    # counters keep climbing from the restored base, monotonically
    rt2.tick(feed.tick_batch(40))
    assert int(rt2.telemetry.ticks.value) == 41
    assert rt2.telemetry.tick_seconds.count == 41


def test_runtime_flight_dump_on_nan_payload(tmp_path, obs_scenario):
    """An injected NaN payload must trigger a ``flight_<tick>.json``
    whose captured inputs are the failing tick's post-poison batch."""
    train3, fs, batch = obs_scenario
    rt = _mk_runtime(
        fs, train3.n_features,
        telemetry=TelemetryConfig(dir=str(tmp_path / "tel")),
        governor=GovernorConfig(merge_every=8),
        robust=RobustConfig(trim=1),
        faults=FaultInjector(
            (FaultSpec(kind="nan", frac=0.2, start_tick=4, seed=3),),
            fs.n_devices, seed=0,
        ),
    )
    feed = TickFeed(fs, batch)
    reports = rt.run(feed)
    summary = rt.finalize_telemetry()
    assert summary["nonfinite_payloads_total"] > 0
    assert summary["flight"]["dumps"], "no flight dump written"
    dump = load_dump(summary["flight"]["dumps"][0])
    assert dump["reason"] == "nonfinite"
    t = dump["tick"]
    assert reports[t].nonfinite_payloads > 0
    np.testing.assert_array_equal(dump["inputs"], feed.tick_batch(t))
    # the ring's newest record is the failing tick itself
    assert dump["ring"][-1]["tick"] == t
    assert dump["ring"][-1]["losses"] == pytest.approx(
        np.asarray(reports[t].losses, np.float64), rel=1e-6
    )


def test_runtime_detections_log_is_bounded(obs_scenario):
    train3, fs, batch = obs_scenario
    rt = _mk_runtime(fs, train3.n_features, detections_cap=3)
    rt.run(TickFeed(fs, batch))
    assert len(rt.detections) <= 3
    assert rt.detections_total >= len(rt.detections)
    assert rt.detections_total > 0  # the drifted devices did flag


def test_sink_rejects_unknown_phase():
    sink = TelemetrySink(TelemetryConfig())
    with pytest.raises(ValueError):
        sink.phase("warp")
    with pytest.raises(ValueError):
        sink.phase("page_in")  # the paging phases are page.stage/wait/store
    with sink.phase("tick.ingest"):
        pass
    assert sink.phase_seconds.labels(phase="tick.ingest").count == 1


# ------------------------------------------------- ingress metrics (PR 9)


def test_sink_ingress_stats_in_summary():
    """The serving front-end books everything through the runtime sink —
    summary() carries an ingress block with admission outcomes, the
    degraded-ladder position and the watchdog's pressure by cause; no
    per-request latency histogram rides in it."""
    sink = TelemetrySink(TelemetryConfig(trace=False))
    sink.ingress_accepted.inc(5)
    sink.ingress_acked.inc(4)
    sink.ingress_retried.inc(2)
    sink.ingress_stale.inc()
    sink.ingress_shed.labels(reason="queue_full").inc(3)
    sink.ingress_deferred.labels(reason="backpressure").inc(2)
    sink.ingress_deferred.labels(reason="comm_budget").inc()
    sink.ingress_degraded_mode.set(2)
    sink.ingress_transitions.labels(mode="stale_scores").inc()
    sink.ingress_pressure_checks.labels(cause="stall").inc(3)

    ing = sink.summary()["ingress"]
    assert ing["accepted"] == 5 and ing["acked"] == 4
    assert ing["retried"] == 2 and ing["stale_served"] == 1
    assert ing["shed"] == {"queue_full": 3}
    assert ing["deferred"] == {"backpressure": 2, "comm_budget": 1}
    assert ing["degraded_mode"] == 2
    assert ing["degraded_transitions"] == {"stale_scores": 1}
    assert ing["pressure_checks"] == {"stall": 3}
    assert "request_latency" not in ing and "admission_latency" not in ing
    families = set(sink.registry.summary())
    assert "ingress_pressure_checks_total" in families
    assert not families & {"ingress_request_seconds", "ingress_admission_seconds"}


def test_sink_ingress_counters_survive_state_roundtrip():
    """Ingress counters ride the same snapshot blob the runtime
    persists, so a kill/restore keeps the serving counters continuous
    instead of resetting them to zero."""
    sink = TelemetrySink(TelemetryConfig(trace=False))
    sink.ingress_accepted.inc(7)
    sink.ingress_shed.labels(reason="degraded").inc(2)
    sink.ingress_replayed.inc(3)

    sink2 = TelemetrySink(TelemetryConfig(trace=False))
    sink2.load_state_bytes(sink.state_bytes())
    ing = sink2.ingress_stats()
    assert ing["accepted"] == 7
    assert ing["shed"] == {"degraded": 2}
    assert ing["replayed_ticks"] == 3
