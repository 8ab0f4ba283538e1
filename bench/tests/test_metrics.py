"""Metric readers on hand-made run logs."""
import numpy as np
import pytest

from bench import costs, harness


def _ctx(acks, window=(10.0, 20.0)):
    """A closed-loop log: one window-phase request acked ok at each time."""
    cell = harness.Cell(name="c", config={"n_devices": 1}, traffic={"batch": 2},
                        chips=1, end_to_end=[], per_layer=[])
    log = harness.RunLog(window=window)
    acks = np.asarray(acks, float)
    log.requests = {"phase": np.ones(acks.size, int), "ack": acks,
                    "status": np.full(acks.size, "ok")}
    return harness.Context(cell=cell, setup_s=1.0, log=log)


@pytest.mark.parametrize("acks,want", [
    ([], None),
    ([25.0], None),                         # acked after the window
    ([12.0, 12.0, 14.0], 3 / 4.0),          # over the time to the last ack
    ([12.0, 14.0, 21.0], 2 / 4.0),
])
def test_acked_samples_to_the_last_ack(acks, want):
    got = harness.reader("acked_samples_per_s")(_ctx(acks))
    assert got == (None if want is None else pytest.approx(want))


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
HAR = {"n_features": 561, "n_hidden": 128, "activation": "identity",
       "n_devices": 4096, "topology": "ring", "hops": 2}
PAGED = {"n_features": 225, "n_hidden": 16, "activation": "sigmoid",
         "n_devices": 2**17, "cohort_size": 2**14, "topology": "star", "hops": 0}


class KernelTrace:
    """The ingest kernel's device time, summed over the cell's chips."""

    def op_seconds(self, pattern):
        return 0.25


def _fed(config, chips, window=32):
    """Three fed ticks of every device in a 2 s window, the last a merge
    tick joined by 3/4 of the fleet."""
    d = config["n_devices"]
    mask = np.arange(d) % 4 != 0
    ticks = [harness.TickRec(tick=t, start=10.0 + t, end=10.5 + t, ingest_s=None,
                             merge_s=None, merge=t == 2, mask=mask if t == 2 else None,
                             served_rows=d) for t in range(3)]
    cell = harness.Cell(name="c", config=config, traffic={"window": window},
                        chips=chips, end_to_end=[], per_layer=[])
    log = harness.RunLog(ticks=ticks, window=(10.0, 12.0), window_ticks=(0, 3))
    return harness.Context(cell=cell, setup_s=1.0, log=log, peaks=PEAKS,
                           trace=KernelTrace())


@pytest.mark.parametrize("config", [HAR, PAGED], ids=["resident", "paged"])
@pytest.mark.parametrize("chips", [1, 4])
def test_tick_mfu_over_the_cells_chips(config, chips):
    n, h, d = config["n_features"], config["n_hidden"], config["n_devices"]
    flops = (3 * d * 32 * costs.sample_flops(n, h, config["activation"])
             + costs.merge_flops(n, h, d, 3 * d // 4, config["topology"], config["hops"]))
    want = flops / (2.0 * chips * PEAKS["bf16_flops_per_s"]) * 100.0
    assert harness.reader("tick_mfu.feed")(_fed(config, chips)) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("config", [HAR, PAGED], ids=["resident", "paged"])
@pytest.mark.parametrize("chips", [1, 4])
def test_ingest_kernel_roofline_reads_the_basis_once_a_call(config, chips):
    """A resident fleet makes one kernel call a tick on each chip, a paged
    one a call per cohort whatever the chips."""
    n, h, d = config["n_features"], config["n_hidden"], config["n_devices"]
    calls = d // config["cohort_size"] if "cohort_size" in config else chips
    flops = d * 32 * costs.sample_flops(n, h, config["activation"])
    nbytes = d * costs.tick_bytes(n, h, 32) + calls * costs.basis_bytes(n, h)
    least = 3 * max(flops / PEAKS["bf16_flops_per_s"], nbytes / PEAKS["hbm_bytes_per_s"])
    ctx = _fed(config, chips)
    got = harness.reader("ingest_kernel_roofline.feed")(ctx)
    assert got == pytest.approx(least / 0.25 * 100.0, rel=1e-12)
    assert "bound by bytes" in ctx.notes[0]
