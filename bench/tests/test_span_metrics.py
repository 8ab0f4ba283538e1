"""The per-layer readers of program spans, on hand-made spans: only the
window's spans count, each reader groups by tick or window number as its
metric says, a traced run notes the device time under the spans, and a
program that records no spans reads None."""
import pytest

from bench import harness
from repro.obs import SpanRing, trace

WINDOW = (100.0, 200.0)


class FakeTrace:
    """Device busy for half of every host interval."""

    def busy_between(self, t0, t1):
        return (t1 - t0) / 2


@pytest.fixture
def ring(monkeypatch):
    r = SpanRing(256)
    monkeypatch.setattr(trace, "RING", r)
    return r


def _ctx(traced=False):
    cell = harness.Cell(name="c", config={}, traffic={}, chips=1,
                        end_to_end=[], per_layer=[])
    ctx = harness.Context(cell=cell, setup_s=1.0, log=harness.RunLog(window=WINDOW))
    if traced:
        ctx.trace = FakeTrace()
    return ctx


def _rec(name, start, dur, seq, **attrs):
    trace.record(name, start, start + dur, seq=seq, **attrs)


def test_window_put_is_the_mean_put_inside_the_window(ring):
    _rec("tick.put", 99.5, 0.02, 0)         # starts before the window
    _rec("tick.put", 110.0, 0.03, 1)
    _rec("tick.put", 120.0, 0.05, 2)
    _rec("tick.ingest", 120.1, 0.5, 2)      # another phase: not read
    _rec("tick.put", 199.99, 0.02, 3)       # ends after the window
    ctx = _ctx(traced=True)
    assert harness.reader("window_put_ms.feed")(ctx) == pytest.approx(40.0)
    assert len(ctx.notes) == 1 and "tick.put x2" in ctx.notes[0]
    assert "device busy 0.040000 s" in ctx.notes[0]


def test_page_copy_sums_stage_and_store_per_tick(ring):
    for seq, t in ((0, 110.0), (1, 120.0)):
        for k in range(2):
            _rec("page.stage", t + k, 0.1, seq, page=k)
            _rec("page.wait", t + k + 0.2, 5.0, seq, page=k)   # left out
            _rec("page.store", t + k + 0.3, 0.2 * (seq + 1), seq, page=k)
    _rec("page.stage", 250.0, 9.0, 2, page=0)                  # after the window
    ctx = _ctx(traced=True)
    # tick 0: 2 x (0.1 + 0.2); tick 1: 2 x (0.1 + 0.4)
    assert harness.reader("page_copy_ms.paged")(ctx) == pytest.approx((600 + 1000) / 2)
    assert [n.split(":")[1].split(" x")[0].strip() for n in ctx.notes] == [
        "page.stage", "page.store", "page.wait"]


def test_merge_fanout_sums_pages_per_round(ring):
    _rec("merge.gather", 110.0, 0.3, 3)
    _rec("merge.fanout", 110.5, 0.2, 3)                    # star: one fan-out
    for k in range(4):                                     # ring: one per page
        _rec("merge.fanout", 150.0 + k, 0.05, 7, page=k)
    _rec("merge.fanout", 90.0, 1.0, 0)                     # before the window
    ctx = _ctx()
    assert harness.reader("merge_fanout_ms.paged")(ctx) == pytest.approx(200.0)
    assert ctx.notes == []                                  # untraced: no notes


def test_ingress_host_adds_admission_close_and_completion(ring):
    _rec("ingress.close", 110.0, 0.01, 5)                 # cut nothing
    _rec("ingress.close", 110.5, 0.02, 5, n=8192, admit_s=0.3)
    _rec("ingress.queued", 110.52, 0.001, 5)               # not host time of the loop
    _rec("ingress.complete", 111.0, 0.05, 5, n=8192)
    _rec("ingress.close", 112.0, 0.02, 6, n=8192, admit_s=0.5)
    _rec("ingress.complete", 113.0, 0.03, 6, n=8192)
    _rec("ingress.close", 199.0, 0.02, 7, n=8192, admit_s=0.4)
    _rec("ingress.complete", 200.5, 0.05, 7, n=8192)       # acked after the window
    ctx = _ctx(traced=True)
    want = ((0.3 + 0.01 + 0.02 + 0.05) + (0.5 + 0.02 + 0.03)) / 2 * 1e3
    assert harness.reader("ingress_host_ms.closed")(ctx) == pytest.approx(want)
    assert any("ingress.queued" in n for n in ctx.notes)


@pytest.mark.parametrize("metric", [
    "window_put_ms.feed", "page_copy_ms.paged", "merge_fanout_ms.paged",
    "ingress_host_ms.closed",
])
def test_no_spans_reads_none(ring, monkeypatch, metric):
    """Nothing in the window, or a program whose trace module has no span
    ring (the parent of the change that added it), reads None."""
    _rec("tick.put", 10.0, 1.0, 0)
    _rec("page.stage", 10.0, 1.0, 0)
    assert harness.reader(metric)(_ctx(traced=True)) is None
    monkeypatch.delattr(trace, "spans_between")
    _rec("tick.put", 150.0, 1.0, 1)
    assert harness.reader(metric)(_ctx(traced=True)) is None
