"""Metric readers on hand-made run logs."""
import numpy as np
import pytest

from bench import harness


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
