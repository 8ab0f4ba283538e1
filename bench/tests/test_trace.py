"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5e (three har ticks at D=256 and a ring merge)."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def test_short_names():
    assert trace.short_name("%fleet_ingest_kernel.1 = (f32[4096]) custom-call(...)") == \
        "fleet_ingest_kernel"
    assert trace.short_name("%slice_select_fusion = f32[8] fusion(...)") == \
        "slice_select_fusion"
    assert trace.short_name(
        '%custom-call.9 = f32[4] custom-call(f32[4] %a), custom_call_target="Cholesky"'
    ) == "Cholesky"
    assert trace.short_name("copy-start.5") == "copy-start.5"


def test_reduce_by_hand():
    red = trace.Reduced(
        devices=[[("a", 10, 20), ("b", 15, 30), ("a", 50, 60), ("c", 90, 130)]],
        spans={"bench.window": [(0, 100)], "bench.tick": [(5, 40), (45, 70)],
               "bench.traffic": [(5, 12)]},
        host_window=(1.0, 1.0 + 100e-9),
    )
    # busy [10, 30] + [50, 60] + [90, 100] (clipped) = 40 ns of 100
    assert red.busy_s == pytest.approx(40e-9)
    assert red.window_s == pytest.approx(100e-9)
    assert dict(red.op_totals()) == pytest.approx({"a": 20e-9, "b": 15e-9, "c": 10e-9})
    # idle [0,10] [30,50] [60,90]: traffic [5,10]; tick [30,40] [45,50] [60,70]
    idle = dict(red.idle_by_span())
    assert idle["bench.traffic"] == pytest.approx(5e-9)
    assert idle["bench.tick"] == pytest.approx(25e-9)
    assert idle[trace.OUTSIDE] == pytest.approx(30e-9)
    assert red.to_ns(1.0 + 50e-9) == pytest.approx(50)
    assert red.busy_between(1.0, 1.0 + 20e-9) == pytest.approx(10e-9)
    assert red.op_seconds("^a$") == pytest.approx(20e-9)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.Reduced([[("a", 0, 1)]], {"bench.tick": [(0, 1)]}, (0.0, 1.0))


def _profile(planes):
    return SimpleNamespace(planes=[
        SimpleNamespace(name=p["name"], lines=[
            SimpleNamespace(name=ln["name"], events=[
                SimpleNamespace(name=n, start_ns=s, duration_ns=d)
                for n, s, d in ln["events"]])
            for ln in p["lines"]])
        for p in planes])


def test_recorded_v5e_trace():
    rec = json.loads((DATA / "trace_small.json").read_text())
    red = trace.reduce_profile(_profile(rec["planes"]), tuple(rec["host_window"]), [0])
    want = rec["expect"]
    assert red.busy_s == pytest.approx(want["busy_s"])
    assert red.window_s == pytest.approx(want["window_s"])
    assert red.op_seconds("^fleet_ingest_kernel$") == pytest.approx(want["kernel_s"])
    assert red.op_seconds("^fleet_ingest_kernel$") > 0
    assert 0 < red.busy_s < red.window_s
    got = red.breakdown()
    assert [n for n, _ in got["device_ops"]] == [n for n, _ in want["breakdown"]["device_ops"]]
    assert {n for n, _ in got["idle_gaps"]} <= {"bench.tick", "bench.traffic", trace.OUTSIDE}
    # busy and idle split the window: the idle gaps sum to the rest
    assert red.busy_s + sum(s for _, s in got["idle_gaps"]) == pytest.approx(red.window_s)


def test_only_chip_planes_count():
    """A /device: plane that is no chip (the v5e trace's empty
    ``/device:CUSTOM:Megascale Trace``) does not enter the average."""
    window = {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["bench.window", 0, 100]]}]}
    chip = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["%a.1 = f32[2] add(...)", 10, 40]]}]}
    for extra in ([], [{"name": "/device:CUSTOM:Megascale Trace", "lines": []}]):
        red = trace.reduce_profile(_profile([chip, window] + extra), (0.0, 100e-9), [0])
        assert red.busy_s == pytest.approx(40e-9)


def test_only_the_cells_chips_count():
    """Planes of chips the cell does not own change nothing; over the
    cell's chips busy time is averaged and idle gaps are the first chip's."""
    window = {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["bench.window", 0, 100], ["bench.tick", 0, 60]]}]}

    def chip(n, *events):
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": "XLA Ops", "events": [[f"%op{n}.1 = f32[2] add(...)", s, d]
                                           for s, d in events]}]}

    own = [chip(0, (10, 40))]
    others = [chip(1, (0, 100)), chip(2), chip(3, (50, 20))]
    alone = trace.reduce_profile(_profile(own + [window]), (0.0, 100e-9), [0])
    for planes in (others + own + [window], own + others + [window]):
        red = trace.reduce_profile(_profile(planes), (0.0, 100e-9), [0])
        assert red.busy_s == alone.busy_s == pytest.approx(40e-9)
        assert red.op_totals() == alone.op_totals()
        assert red.breakdown() == alone.breakdown()
    # four chips: chip 2 ran nothing and counts as idle
    red = trace.reduce_profile(_profile(others + own + [window]), (0.0, 100e-9),
                               [0, 1, 2, 3])
    assert red.busy_s == pytest.approx((40 + 100 + 0 + 20) / 4 * 1e-9)
    assert red.device_events == 3
    # first chip (0) idle [0,10] [50,100]: 10 + 10 ns under bench.tick
    assert dict(red.idle_by_span()) == pytest.approx(
        {"bench.tick": 20e-9, trace.OUTSIDE: 40e-9})
    # a cell on chips 1 and 3 only: chip 0's plane is left out
    red = trace.reduce_profile(_profile(others + own + [window]), (0.0, 100e-9), [1, 3])
    assert red.busy_s == pytest.approx(60e-9)
    assert [n for n, _ in red.op_totals()] == ["op1", "op3"]
