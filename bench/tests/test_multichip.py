"""A cell on four chips, rehearsed on four CPU devices in a child process
(``--xla_force_host_platform_device_count=4``): the resident fleet is
booted in one block per chip and laid out by device range over them,
and the reference holds and merges the same blocks on the same chips."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
TINY = {"n_devices": 64}
CHIPS = 4


def _cell(chips=CHIPS, **scale):
    cell = harness.scaled(harness.load_cell("har_feed_w32"), {**TINY, **scale})
    return dataclasses.replace(cell, chips=chips)


def test_fleet_on_four_chips():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={CHIPS}",
           "PYTHONPATH": os.pathsep.join([str(ROOT / "src"), str(ROOT)])}
    proc = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    limits = harness.limits_for(_cell())
    assert got["beta_rel"] <= limits["beta_rel"] and got["p_rel"] <= limits["p_rel"], got
    assert got["ring_rel"] < 1e-6 and got["star_rel"] < 1e-6, got


def test_fleet_must_divide_over_the_chips():
    import jax

    drv = harness.Feed(_cell(chips=3), 1, [jax.devices()[0]] * 3)
    with pytest.raises(ValueError, match="'har_resident'.* 64 .* 3 chips"):
        drv._boot_blocks()


def _child():
    """Asserts the layout; prints the reference's gaps as one JSON line."""
    import jax
    import numpy as np

    from bench import reference as ref
    from repro.fleet import init_fleet

    devices = jax.devices()
    assert len(devices) == CHIPS, devices
    drv = harness.Feed(_cell(), 2**33 + 5, devices)
    blocks = drv._boot_blocks()
    per = drv.n_dev // CHIPS
    assert blocks == [(i * per, (i + 1) * per) for i in range(CHIPS)], blocks
    assert drv._block_devices() == devices
    fleet, fallback = drv._resident_fleet()

    def alone(lo, hi):
        x0 = drv._boot_x(lo, hi)
        return init_fleet(drv.key_basis, hi - lo, drv.n_feat, drv.n_hid, x0,
                          activation=drv.act, ridge=drv.ridge,
                          forget=float(drv.cfg["forget"])), x0[:, -1, :]

    want = []
    for (lo, hi), dev in zip(blocks, devices):
        with harness.float32_boot(), jax.default_device(dev):
            want.append(jax.jit(alone, static_argnums=(0, 1))(lo, hi))
    for k, (path, leaf) in enumerate(jax.tree_util.tree_leaves_with_path(fleet)):
        assert leaf.shape[0] == drv.n_dev, (path, leaf.shape)
        assert leaf.sharding.spec == jax.sharding.PartitionSpec("fleet"), path
        shards = sorted(leaf.addressable_shards, key=lambda s: s.index[0].start)
        assert [s.device for s in shards] == devices, path
        for s, (lo, hi), (block, _) in zip(shards, blocks, want):
            assert (s.index[0].start, s.index[0].stop) == (lo, hi), path
            np.testing.assert_array_equal(
                np.asarray(s.data), np.asarray(jax.tree_util.tree_leaves(block)[k]), str(path))
    for rows, (_, want_rows), dev in zip(fallback, want, devices):
        assert rows.devices() == {dev}
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(want_rows))

    alpha, bias = ref.basis(drv.key_basis, drv.n_feat, drv.n_hid)
    boots = []
    for (lo, hi), dev in zip(blocks, devices):
        with jax.default_device(dev):
            boots.append(ref.boot(alpha, bias, drv._boot_x(lo, hi), activation=drv.act,
                                  ridge=drv.ridge))
    out = {"beta_rel": harness._rel(np.asarray(fleet.beta), np.concatenate(
               [np.asarray(b) for _, b in boots])),
           "p_rel": harness._rel(np.asarray(fleet.p), np.concatenate(
               [np.asarray(p) for p, _ in boots]))}

    # the reference merges across chips as on one
    mask = np.arange(drv.n_dev) % 5 != 0
    for topology, hops in (("ring", 2), ("star", 0)):
        spread = ref.Fleet(list(boots), devices, activation=drv.act, ridge=drv.ridge)
        whole = ref.Fleet([tuple(np.concatenate([np.asarray(b[k]) for b in boots])
                                 for k in range(2))], devices[:1],
                          activation=drv.act, ridge=drv.ridge)
        for f in (spread, whole):
            f.merge(mask, topology, hops)
        assert [p.devices() for p, _ in spread.blocks] == [{d} for d in devices]
        (gp, gb), (wp, wb) = spread.host_state(), whole.host_state()
        out[f"{topology}_rel"] = max(harness._rel(gp, wp), harness._rel(gb, wb))
    print(json.dumps(out))


if __name__ == "__main__":
    _child()
