"""Beyond-paper: the cooperative model update as a mesh collective.

N host devices each train an OS-ELM autoencoder on a different HAR
pattern; ONE psum pair merges them (the paper's 2-device exchange,
generalized to N). Validates that the psum merge equals the sequential
pairwise merge and measures the jitted program latency.

Run under XLA_FLAGS=--xla_force_host_platform_device_count=8 for a
meaningful device count (benchmarks/run.py does this in-process only if
jax is not yet initialized).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import edge_config, normalized_dataset, timed
from repro.core import (
    cooperative_update,
    init_oselm,
    init_slfn,
    oselm_loss,
    to_uv,
)
from repro.data.pipeline import make_sharded_streams
from repro.federated import mesh_cooperative_update, mesh_federated_train
from repro.launch.mesh import make_mesh


def run(n_hidden: int = 64, steps: int = 200, seed: int = 0) -> dict:
    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("data",))
    ds = normalized_dataset("har", seed=seed)
    streams = make_sharded_streams(ds, n_dev, steps + 2 * n_hidden, seed=seed)
    ecfg = edge_config("har")

    params = init_slfn(jax.random.PRNGKey(seed), ds.n_features, n_hidden)
    states = []
    for s in range(n_dev):
        x0 = jnp.asarray(streams.xs[s, : 2 * n_hidden])
        states.append(
            init_oselm(params, x0, x0, activation="identity", ridge=ecfg.ridge)
        )
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    xs_rest = jnp.asarray(streams.xs[:, 2 * n_hidden:])

    merged = mesh_federated_train(stacked, xs_rest, mesh, ("data",), ridge=ecfg.ridge)

    # reference: sequential pairwise merge on device 0
    import repro.core as core
    host_states = [
        core.oselm_train_sequential(states[s], xs_rest[s], xs_rest[s])
        for s in range(n_dev)
    ]
    ref = cooperative_update(host_states[0], *[to_uv(s) for s in host_states[1:]])

    beta_mesh = np.asarray(merged.beta[0])
    diff = float(np.max(np.abs(beta_mesh - np.asarray(ref.beta))))

    # merged model covers every pattern
    losses = {}
    st0 = jax.tree.map(lambda l: l[0], merged)
    for pat in range(ds.n_classes):
        xp = jnp.asarray(ds.pattern(pat)[:32])
        losses[ds.class_names[pat]] = float(oselm_loss(st0, xp, xp).mean())

    merge_us = timed(
        lambda st: mesh_cooperative_update(st, mesh, ("data",), ridge=ecfg.ridge),
        merged, warmup=1, iters=5,
    )
    return {
        "n_devices": n_dev,
        "beta_diff_vs_pairwise": diff,
        "losses": losses,
        "psum_merge_us": merge_us,
    }


def main() -> list[str]:
    r = run()
    assert r["beta_diff_vs_pairwise"] < 0.05, r
    return [
        f"mesh_merge/har,{r['psum_merge_us']:.1f},"
        f"devices={r['n_devices']};beta_diff={r['beta_diff_vs_pairwise']:.2e};"
        f"max_pattern_loss={max(r['losses'].values()):.4f}"
    ]


if __name__ == "__main__":
    for l in main():
        print(l)
