"""``FleetRuntime`` on a fleet laid out by device range over four devices,
against the same runtime on one device and against the benchmark's plain
reference (``bench/reference.py``).

The four devices are the CPU backend's, forced in a child process
(``--xla_force_host_platform_device_count=4``): the child runs every
case once at a small fleet (D=64, 24 -> 8 widths, windows of 8) and
prints what it saw as one JSON line; the tests below read it.
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
CHIPS = 4
D, F, H, T, RIDGE = 64, 24, 8, 8, 1e-3
TOPOLOGIES = ("ring", "star")
KERNELS = ("off", "on")
UNSUPPORTED = ("staleness", "robust", "faults", "payload_precision", "snapshot_dir")
REL = 1e-6  # sharded against one device: the same f32 programs per device


@pytest.fixture(scope="module")
def child():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={CHIPS}",
           "PYTHONPATH": os.pathsep.join([str(ROOT / "src"), str(ROOT)])}
    proc = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kernels", KERNELS)
@pytest.mark.parametrize("topology", TOPOLOGIES)
def test_sharded_runtime_matches_one_device(child, topology, kernels):
    got = child["parity"][f"{topology}-{kernels}"]
    assert got["merges"] == 2 and got["masked_out"] > 0, got
    assert got["same_drifted"] and got["same_decisions"], got
    for k in ("p", "beta", "losses", "det"):
        assert got[k] <= REL, (k, got)
    # every output of the sharded tick stays on the four devices
    assert got["shards"] == [CHIPS, CHIPS], got
    assert got["halo_bytes"] == (
        2 * 2 * CHIPS * 4 * H * (H + F) if topology == "ring" else CHIPS * 4 * H * (H + F))


@pytest.mark.parametrize("topology", TOPOLOGIES)
def test_sharded_runtime_compiles_once(child, topology):
    for kernels in KERNELS:
        sizes = child["parity"][f"{topology}-{kernels}"]["cache_sizes"]
        assert sizes == {"ingest_detect": 1, "merge_fresh": 1}, sizes


def test_rebase_median_is_fleet_wide(child):
    """Shards whose loss ratios differ: the rebased baseline is the one
    device's, and the median of each shard alone would have moved it."""
    got = child["median"]
    assert got["by_fleet_median"] and got["det_mean"] <= REL, got
    assert got["per_shard_gap"] > 1e-2, got


@pytest.mark.parametrize("side", ["one_device", "sharded"])
def test_runtime_matches_reference(child, side):
    got, limits = child["reference"][side], child["limits"]
    assert got["merges"] >= 1, got
    for k in ("beta_rel", "p_rel", "loss_rel"):
        assert got[k] <= limits[k], (k, got, limits)


def test_hops_wider_than_a_shard_raises(child):
    assert "halo exchange needs hops=17" in child["raises"]["hops"]


@pytest.mark.parametrize("option", UNSUPPORTED)
def test_unsupported_option_raises(child, option):
    msg = child["raises"][option]
    assert "not supported there" in msg and option in msg, msg


@pytest.mark.parametrize("layout", ["replicated", "explicit_axis"])
def test_layout_other_than_by_device_range_raises(child, layout):
    assert "laid out by device range over one mesh axis" in child["raises"][layout]


# ------------------------------------------------------------------ child


def _rel(got, ref) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def _windows(n, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.standard_normal((D, T, F)).astype(np.float32) for _ in range(n)]


def _config(topology, kernels="off", **kw):
    from repro.fleet import ring, star
    from repro.runtime import DetectorConfig, GovernorConfig, RuntimeConfig

    on = kernels == "on"
    return RuntimeConfig(
        topology=ring(D, hops=2) if topology == "ring" else star(D), ridge=RIDGE,
        governor=GovernorConfig(merge_every=3), detector=DetectorConfig(warmup=2),
        use_ingest_kernel=on, use_merge_kernel=on,
        ingest_backend="pallas" if on else "auto", **kw)


def _policy(losses):
    """Devices 3, 10, 17, ... sit every merge out."""
    import numpy as np

    return np.arange(D) % 7 != 3


def _parity(fleet, fleet_s, topology, kernels):
    """Two merge cycles and a tick, the same windows on both layouts."""
    import numpy as np

    from repro.obs import trace
    from repro.runtime import FleetRuntime

    cfg = _config(topology, kernels)
    one = FleetRuntime(fleet, cfg, policies=(_policy,))
    four = FleetRuntime(fleet_s, cfg, policies=(_policy,))
    one.warmup(T)
    four.warmup(T)
    out = {"losses": 0.0, "same_drifted": True, "same_decisions": True, "merges": 0}
    t0 = time.perf_counter()
    for x in _windows(7):
        a, b = one.tick(x), four.tick(x)
        out["losses"] = max(out["losses"], _rel(b.losses, a.losses))
        out["same_drifted"] &= bool((a.drifted == b.drifted).all())
        out["same_decisions"] &= a.decision == b.decision
        if b.decision.merge:
            out["merges"] += 1
            out["masked_out"] = D - b.decision.participants
    out["p"] = _rel(four.states.p, one.states.p)
    out["beta"] = _rel(four.states.beta, one.states.beta)
    out["det"] = max(_rel(getattr(four.det, k), getattr(one.det, k))
                     for k in ("ewma", "mean", "var", "count"))
    out["shards"] = [len(four.states.beta.sharding.device_set),
                     len(four.det.mean.sharding.device_set)]
    merges = [s for s in trace.spans_between(t0, float("inf")) if s.name == "tick.merge"]
    out["halo_bytes"] = max(int(s.attrs["halo_bytes"]) for s in merges)
    out["cache_sizes"] = four.assert_compile_once()
    one.assert_compile_once()
    return out


def _median(fleet, fleet_s):
    """The rebase tick after a merge, with each shard's windows scaled
    apart: the factor every calibrated participant's EWMA was rescaled
    by, recovered from its update, is the whole fleet's median ratio."""
    import numpy as np

    from repro.runtime import FleetRuntime

    cfg = _config("ring")
    alpha = cfg.detector.alpha
    one, four = FleetRuntime(fleet, cfg), FleetRuntime(fleet_s, cfg)
    wins = _windows(4, seed=1)
    wins[3] *= np.repeat(np.array([1.0, 1.3, 1.7, 2.4], np.float32),
                         D // CHIPS)[:, None, None]
    for x in wins[:3]:
        one.tick(x)
        rep = four.tick(x)
    assert rep.decision.merge
    det = {k: np.asarray(getattr(four.det, k)) for k in ("ewma", "mean", "count")}
    valid = ~np.asarray(rep.drifted) & (det["count"] >= cfg.detector.warmup)
    one.tick(wins[3])
    losses = four.tick(wins[3]).losses
    ratio = losses / np.maximum(det["mean"], cfg.detector.min_sigma)
    fleet_median = float(np.median(ratio[valid]))
    per_shard = [float(np.median(r[v])) for r, v in
                 zip(np.split(ratio, CHIPS), np.split(valid, CHIPS))]
    used = ((np.asarray(four.det.ewma) - alpha * losses)
            / ((1 - alpha) * det["ewma"]))[valid]
    return {"by_fleet_median": bool(valid.sum() > D // 2
                            and np.allclose(used, fleet_median, rtol=1e-4)),
            "det_mean": max(_rel(four.det.mean, one.det.mean),
                            _rel(four.det.ewma, one.det.ewma)),
            "per_shard_gap": max(abs(m - fleet_median) / fleet_median for m in per_shard)}


def _reference(fleet, key, x0, topology="ring"):
    """The runtime's ticks replayed by the plain reference, merging with
    the program's masks, as the benchmark checks a cell."""
    import jax
    import numpy as np

    from bench import reference as ref
    from repro.runtime import FleetRuntime

    alpha, bias = ref.basis(key, F, H)
    dev = jax.devices()[0]
    with jax.default_device(dev):
        boot = ref.boot(alpha, bias, x0, activation="identity", ridge=RIDGE)
    want = ref.Fleet([boot], [dev], activation="identity", ridge=RIDGE)
    rt = FleetRuntime(fleet, _config(topology), policies=(_policy,))
    out = {"loss_rel": 0.0, "merges": 0}
    for x in _windows(7, seed=3):
        rep = rt.tick(x)
        losses = want.tick(alpha, bias, lambda lo, hi, x=x: x[lo:hi], np.ones(D, bool))
        out["loss_rel"] = max(out["loss_rel"], float(np.max(
            np.abs(rep.losses - losses) / np.maximum(np.abs(losses), 1e-12))))
        if rep.decision.merge:
            out["merges"] += 1
            want.merge(~np.asarray(rep.drifted) & _policy(None), topology, 2)
    p, beta = want.host_state()
    out["p_rel"] = _rel(rt.states.p, p)
    out["beta_rel"] = _rel(rt.states.beta, beta)
    return out


def _raises(fleet_s):
    import tempfile

    import jax
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.fleet import FaultInjector, StalenessSchedule, ring
    from repro.fleet.robust import RobustConfig
    from repro.runtime import FleetRuntime, RuntimeConfig

    options = {
        "staleness": {"staleness": StalenessSchedule(lags=np.zeros(D, np.int64))},
        "robust": {"robust": RobustConfig()},
        "faults": {"faults": FaultInjector((), D)},
        "payload_precision": {"payload_precision": "int8"},
        "snapshot_dir": {"snapshot_dir": tempfile.mkdtemp()},
        "hops": {},
    }
    out = {}
    mesh = fleet_s.beta.sharding.mesh
    explicit = jax.make_mesh((CHIPS,), ("fleet",), axis_types=(AxisType.Explicit,))
    for name, sharding in (("replicated", NamedSharding(mesh, P())),
                           ("explicit_axis", NamedSharding(explicit, P("fleet")))):
        try:
            FleetRuntime(jax.device_put(fleet_s, sharding), _config("ring"))
            out[name] = "did not raise"
        except ValueError as e:
            out[name] = str(e)
    for name, kw in options.items():
        cfg = _config("ring", **kw)
        if name == "hops":
            cfg = RuntimeConfig(topology=ring(D, hops=D // CHIPS + 1), ridge=RIDGE)
        try:
            FleetRuntime(fleet_s, cfg)
            out[name] = "did not raise"
        except ValueError as e:
            out[name] = str(e)
    return out


def _child():
    import jax

    from repro.fleet import init_fleet
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import shard_fleet

    assert len(jax.devices()) == CHIPS, jax.devices()
    key = jax.random.PRNGKey(7)
    x0 = jax.random.normal(jax.random.fold_in(key, 1), (D, 4 * H, F))
    fleet = init_fleet(key, D, F, H, x0, activation="identity", ridge=RIDGE)
    fleet_s = shard_fleet(fleet, make_mesh((CHIPS,), ("fleet",)), ("fleet",))
    out = {
        "parity": {f"{t}-{k}": _parity(fleet, fleet_s, t, k)
                   for t in TOPOLOGIES for k in KERNELS},
        "median": _median(fleet, fleet_s),
        "reference": {"one_device": _reference(fleet, key, x0),
                      "sharded": _reference(fleet_s, key, x0)},
        "limits": json.loads((ROOT / "bench" / "limits" / "har_feed_w32.json").read_text()),
        "raises": _raises(fleet_s),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    _child()
