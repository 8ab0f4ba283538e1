"""The four-chip cell ``har_sharded_w32``: rehearsed through ``run_cell``
on four CPU devices in a child process
(``--xla_force_host_platform_device_count=4``) at D=64, and its three
per-layer readers (``halo_exchange_ms``, ``ingest_skew_ms``,
``merge_kernel_roofline``) on hand-made four-chip traces."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import costs, harness, trace

ROOT = Path(__file__).resolve().parents[2]
CELL = "har_sharded_w32"
TINY = {"n_devices": 64}
CHIPS = 4


@pytest.fixture(scope="module")
def rehearsal():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={CHIPS}",
           "PYTHONPATH": os.pathsep.join([str(ROOT / "src"), str(ROOT)])}
    proc = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cell_is_the_fed_har_fleet_on_four_chips():
    cell = harness.load_cell(CELL)
    assert cell.chips == CHIPS and cell.traffic["kind"] == "feed"
    assert cell.config["n_devices"] == CHIPS * harness.load_cell("har_feed_w32").config[
        "n_devices"]
    assert {m["name"] for m in cell.end_to_end} == {"device_ticks_per_s", "setup_s"}
    for m in cell.per_layer:
        harness.reader(m["name"])  # every metric has a reader


@pytest.mark.parametrize("control", [False, True], ids=["sound", "control"])
def test_rehearsal_on_four_devices(rehearsal, control):
    got = rehearsal["control" if control else "sound"]
    assert got["correct"] is (not control), got
    assert got["count"] == CHIPS and got["platform"] == "cpu", got
    assert got["checked_merges"] >= 1 and got["merge_participants"] >= 0.5, got
    if not control:
        assert set(got["metrics"]) == {"device_ticks_per_s", "setup_s"}, got


# ------------------------------------------------------------ readers


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
HAR = {"n_features": 561, "n_hidden": 128, "activation": "identity",
       "n_devices": 16384, "topology": "ring", "hops": 2}


def _ctx(devices, *, chips=CHIPS, merge_s=0.4):
    """Three ticks of 1 s from host time 10.0, the last a merge whose
    phase is its last ``merge_s`` seconds, on a trace whose window
    starts at 0 ns at host time 10.0."""
    ticks = [harness.TickRec(tick=t, start=10.0 + t, end=11.0 + t, ingest_s=None,
                             merge_s=merge_s if t == 2 else None, merge=t == 2,
                             mask=None, served_rows=HAR["n_devices"]) for t in range(3)]
    cell = harness.Cell(name=CELL, config=HAR, traffic={"window": 32}, chips=chips,
                        end_to_end=[], per_layer=[])
    log = harness.RunLog(ticks=ticks, window=(10.0, 13.0), window_ticks=(0, 3))
    red = trace.Reduced(devices=devices, spans={"bench.window": [(0, 3e9)]},
                        host_window=(10.0, 13.0))
    return harness.Context(cell=cell, setup_s=1.0, log=log, peaks=PEAKS, trace=red)


def _ms(ns):
    return ns / 1e6


def test_halo_exchange_is_collective_time_in_the_merge_per_chip():
    """Collectives inside the merge phase [2.6 s, 3 s) count, clipped to
    it; the ingest's all-reduce outside it and other ops do not."""
    chip = [("collective-permute-start", 2.7e9, 2.71e9),
            ("collective-permute-done", 2.8e9, 2.83e9),
            ("all-reduce", 2.55e9, 2.65e9),       # half inside the phase
            ("all-reduce", 1.2e9, 1.3e9),         # the ingest tick's median
            ("banded_merge_solve", 2.7e9, 2.9e9)]
    got = harness.reader("halo_exchange_ms.sharded")(_ctx([chip, chip[:2], [], []]))
    assert got == pytest.approx(_ms((10e6 + 30e6 + 50e6) + (10e6 + 30e6)) / CHIPS)


@pytest.mark.parametrize("devices", [[[("banded_merge_solve", 2.7e9, 2.9e9)]] * CHIPS,
                                     [[("collective-permute", 2.7e9, 2.8e9)]]])
def test_halo_exchange_none_without_collectives_or_chips(devices):
    assert harness.reader("halo_exchange_ms.sharded")(
        _ctx(devices, chips=len(devices))) is None


def test_ingest_skew_is_the_straggler_chips_lead():
    """Per tick, the last kernel end of the latest chip minus the
    earliest chip's; a tick a chip did not ingest in is left out."""
    k = "fleet_ingest_kernel"
    devices = [
        [(k, 0.1e9, 0.2e9), (k, 0.3e9, 0.4e9), (k, 1.1e9, 1.5e9), (k, 2.1e9, 2.2e9)],
        [(k, 0.1e9, 0.25e9), (k, 1.1e9, 1.3e9), (k, 2.1e9, 2.3e9)],
        [(k, 0.1e9, 0.41e9), (k, 1.1e9, 1.35e9), ("copy", 2.1e9, 2.9e9)],
        [(k, 0.1e9, 0.38e9), (k, 1.1e9, 1.4e9), (k, 2.1e9, 2.25e9)],
    ]
    got = harness.reader("ingest_skew_ms.sharded")(_ctx(devices))
    assert got == pytest.approx(_ms(((0.41 - 0.25) + (1.5 - 1.3)) * 1e9 / 2))
    assert harness.reader("ingest_skew_ms.sharded")(_ctx(devices[:1], chips=1)) is None


@pytest.mark.parametrize("chips", [1, CHIPS])
def test_merge_kernel_roofline_is_its_work_over_its_time(chips):
    """One round of D devices: 2·hops payload sums and one solve each,
    2·hops + 1 payload reads and one (P, β) write, over the kernel's
    time summed over the chips."""
    n, h, d, hops = HAR["n_features"], HAR["n_hidden"], HAR["n_devices"], HAR["hops"]
    payload = h * (h + n)
    flops = d * (2 * hops * payload + h ** 3 / 3 + 2 * h ** 3 + 2 * h * h * n)
    nbytes = d * (2 * hops + 2) * 4 * payload
    least = max(flops / PEAKS["bf16_flops_per_s"], nbytes / PEAKS["hbm_bytes_per_s"])
    per_chip = 0.2e9 / chips
    devices = [[("banded_merge_solve", 2.7e9, 2.7e9 + per_chip)]] * chips
    got = harness.reader("merge_kernel_roofline.feed")(_ctx(devices, chips=chips))
    assert got == pytest.approx(least / 0.2 * 100.0, rel=1e-12)
    assert 0 < got < 100
    assert least == costs.least_seconds(flops, nbytes, PEAKS)[0]


def test_merge_device_ms_sharded_is_merge_busy_averaged_over_chips():
    """The four-chip cell's merge device time: busy time inside the merge
    phase [2.6 s, 3 s), overlaps counted once, clipped to the phase, the
    mean over the cell's chips (a chip that ran nothing there adds 0)."""
    chip = [("banded_merge_solve", 2.7e9, 2.9e9),
            ("collective-permute", 2.65e9, 2.75e9),   # overlaps the solve
            ("fleet_ingest_kernel", 2.5e9, 2.62e9),   # 20 ms inside
            ("fleet_ingest_kernel", 1.1e9, 1.5e9)]    # an ingest tick
    got = harness.reader("merge_device_ms.sharded")(_ctx([chip, chip[:1], [], chip]))
    assert got == pytest.approx(_ms(270e6 + 200e6 + 0 + 270e6) / CHIPS)


def test_merge_kernel_roofline_none_without_the_kernel():
    star = [[("_masked_segment_sum_mix_call", 2.7e9, 2.8e9)]] * CHIPS
    assert harness.reader("merge_kernel_roofline.feed")(_ctx(star)) is None


# ------------------------------------------------------------------ child


def _child():
    import jax

    assert len(jax.devices()) == CHIPS, jax.devices()
    cell = harness.scaled(harness.load_cell(CELL), TINY)
    out = {}
    for name, control in (("sound", False), ("control", True)):
        got = harness.run_cell(cell, seed=2**33 + 41, seconds=0.5, trace=False,
                               t_start=time.perf_counter(), peaks=None,
                               devices=jax.devices()[:CHIPS], control=control)
        out[name] = {"correct": got.correct, "metrics": sorted(got.metrics),
                     "count": got.device["count"], "platform": got.device["platform"],
                     **{k: v["value"] for k, v in got.check.items()}}
    print(json.dumps(out, default=float))


if __name__ == "__main__":
    _child()
