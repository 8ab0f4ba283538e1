"""Rehearsals off the chip: every cell's driver at a tiny fleet through the
same code as a chip run (the look for a chip skipped), the control, and
faults planted under the timed path, which must read ``correct`` false."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
CELLS = ["har_served_closed", "driving_paged_w2", "har_feed_w32"]
TINY = {"n_devices": 64, "cohort_size": 16, "close_at_requests": 128,
        "pool_per_device": 4}
DEVICE_SOURCES = {"device_trace"}


def _cell(name):
    return harness.scaled(harness.load_cell(name), TINY)


def _run(name, seed, driver=None, trace=False, control=False):
    import jax

    cell = _cell(name)
    # a served window must hold a whole merge cycle and the tick after it,
    # and on a CPU a ring merge tick alone takes seconds
    seconds = 8.0 if cell.traffic["kind"].startswith("served") else 0.5
    return harness.run_cell(
        cell, seed=seed, seconds=seconds, trace=trace,
        t_start=time.perf_counter(), peaks=None, devices=jax.devices()[:1],
        control=control, driver=driver,
    )


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name):
    """Correct on the CPU at a tiny fleet; its end-to-end metrics read;
    a traced run emits no device metric and no device busy time."""
    out = _run(name, seed=2**33 + 17)
    assert out.correct, out.check
    cell = _cell(name)
    assert set(out.metrics) == {m["name"] for m in cell.end_to_end}, out.notes
    assert all(v["value"] > 0 for v in out.metrics.values())
    assert out.device["platform"] == "cpu"
    traced = _run(name, seed=5, trace=True)
    assert traced.correct, traced.check
    device_metrics = {m["name"] for m in cell.per_layer if m["source"] in DEVICE_SOURCES}
    assert not device_metrics & set(traced.metrics)
    assert "busy_s" not in traced.device and traced.breakdown is None


def test_served_window_is_whole_merge_cycles():
    """The served window ends with the acks of a merge cycle's last tick:
    nothing is left queued, no tick runs after it, and every request sent
    in it is acked ok inside it."""
    kept = []

    class Kept(harness.Served):
        def built(self):
            kept.append(self)

    out = _run("har_served_closed", seed=2**32 + 11, driver=Kept)
    assert out.correct, out.check
    drv = kept[0]
    every = int(drv.tr["merge_every"])
    w0, w1 = drv.log.window
    req = drv.log.requests
    win = req["phase"] == 1
    assert (req["status"][win] == "ok").all() and (req["ack"][win] <= w1).all()
    last = int(req["tick"][win].max())
    assert last % every == every - 1 and drv.log.ticks[-1].tick == last
    assert drv.log.diag["depth_at_close"] == 0
    assert w1 - w0 >= 8.0


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, seed):
    """A run with the reference at bfloat16 operands put in the program's
    place reads ``correct`` false."""
    out = _run(name, seed=seed, control=True)
    assert not out.correct, out.check


# ------------------------------------------------------------------ faults


def _unchanged(rt):
    """The ingest returns the state it was given."""
    if hasattr(rt, "_ingest_detect"):
        orig = rt._ingest_detect
        rt._ingest_detect = lambda fleet, det, *a: (fleet,) + tuple(orig(fleet, det, *a)[1:])
    else:
        orig = rt._ingest
        rt._ingest = lambda p, b, w, s: (p, b, orig(p, b, w, s)[2])


def _half_batch(rt):
    """Half of each device's window left out; the loss is the mean over
    the rest."""
    if hasattr(rt, "_ingest_detect"):
        orig = rt._ingest_detect
        rt._ingest_detect = lambda fleet, det, batch, *a: orig(
            fleet, det, batch[:, :max(1, batch.shape[1] // 2)], *a)
    else:
        orig = rt._ingest
        rt._ingest = lambda p, b, w, s: orig(p, b, w[:, :max(1, w.shape[1] // 2)], s)


def _no_exchange(rt):
    """The merge's exchange left out: every device keeps its own model."""
    if hasattr(rt, "_merge_fresh"):
        rt._merge_fresh = lambda fleet, mask: fleet
    else:
        rt.merger.merge = lambda arena, mask: None


def _altered_answer(rt):
    """The losses (the acks' scores) altered where they are produced."""
    if hasattr(rt, "_ingest_detect"):
        orig = rt._ingest_detect

        def alter(*a):
            out = list(orig(*a))
            out[2] = out[2] * 1.001
            return tuple(out)

        rt._ingest_detect = alter
    else:
        orig = rt._ingest

        def alter(p, b, w, s):
            p2, b2, losses = orig(p, b, w, s)
            return p2, b2, losses * 1.001

        rt._ingest = alter


def _all_drifted(rt):
    """The detector marks every device drifted: no one joins a merge, and
    the merge changes nothing on either side of the comparison."""
    import jax.numpy as jnp

    if hasattr(rt, "_ingest_detect"):
        orig = rt._ingest_detect

        def flag(*a):
            out = list(orig(*a))
            out[3] = jnp.ones_like(out[3])
            return tuple(out)

        rt._ingest_detect = flag
    else:
        orig = rt._detect

        def flag(*a):
            det, drifted, fresh = orig(*a)
            return det, jnp.ones_like(drifted), fresh

        rt._detect = flag


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "no_exchange": _no_exchange, "altered_answer": _altered_answer,
          "all_drifted": _all_drifted}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault):
    base = harness.DRIVERS[_cell(name).traffic["kind"]]

    class Broken(base):
        def built(self):
            FAULTS[fault](self.runtime)

    out = _run(name, seed=7, driver=Broken)
    assert not out.correct, out.check


# ------------------------------------------------------------ command line


def test_no_tpu_exits_nonzero_without_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "har_served_closed",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and bench/ holds no system
    under test: the run fails and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "har_feed_w32",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_cells_match_benchmark_json():
    """Every cell's config, traffic and metric readers exist; every metric
    a cell reports has a reader."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], spec)
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.reader(m["name"]))
        assert set(harness.limits_for(cell)) >= {"beta_rel", "p_rel", "merge_participants"}
    moved = {m["name"] for m in spec["end_to_end"]}
    assert all(m["moves"] in moved for m in spec["per_layer"])
    assert np.all([c["file"].startswith("bench/") for c in spec["configs"]])
