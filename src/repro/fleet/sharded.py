"""Sharded fleet training and merges over mesh axes.

`repro.fleet` simulates the whole fleet as one stacked pytree;
`repro.federated.mesh_federation` runs one device per mesh shard. This
module combines them: the stacked device axis is laid out by device
range over a mesh axis (``fleet_layout``; ``repro.launch.sharding``),
and both halves of a federation round run shard-locally. These are the
per-shard programs ``FleetRuntime`` ticks a fleet laid out over several
chips with:

- ``sharded_ingest`` — per-tick ingest is embarrassingly parallel over
  devices, so the vmap+scan train loop (or the fused ``fleet_ingest``
  kernel family, ``kernel=True``) runs under ``shard_map`` with NO
  collectives at all: each shard trains only its resident devices and
  returns their pre-train scores.
- ``sharded_merge`` — the masked Eq. 8 merge. Cluster-wise-constant
  topologies (star, hierarchical, all-to-all, closed ring) segment-sum
  each shard's *local* payloads into per-cluster partials, and ONE
  ``jax.lax.psum`` of the (n_clusters, Ñ, Ñ+m) partials completes the
  sum — O(clusters) per shard, never O(devices). The open ring takes a
  **halo exchange**: each shard ``ppermute``s its ``hops`` edge payloads
  to the adjacent shards, then solves its devices over the extended
  block (``banded_merge_solve(halo=True)``).
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import UV, OSELMState, ae_score
from repro.federated.compat import revary, shard_map_compat as _shard_map
from repro.fleet.fleet import _bcast, _fleet_train, _solve_uv, fleet_from_uv, fleet_to_uv
from repro.fleet.topology import Topology


def _merge_cids(topology: Topology) -> tuple[np.ndarray, int, bool]:
    """(cluster_ids, n_clusters, cluster_isolated) for the topologies
    whose merged model is cluster-wise constant."""
    if topology.kind == "segment":
        return (
            np.asarray(topology.cluster_ids, np.int32),
            topology.n_clusters,
            not topology.head_exchange,
        )
    if topology.is_fully_connected:  # all_to_all / closed ring: one cluster
        return np.zeros(topology.n_devices, np.int32), 1, False
    raise NotImplementedError(
        f"sharded merge needs a cluster-wise-constant topology or an "
        f"open ring; {topology.name!r} (kind={topology.kind!r}) mixes "
        "per-device neighbor sets across shard boundaries"
    )


def _mesh_axis_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


# jitted cohort reductions keyed by their closure parameters — jax.jit's
# cache is keyed on the function OBJECT, so wrapping a fresh closure per
# call would re-trace/re-compile every merge round
_SHARDED_JIT_CACHE: dict[tuple, object] = {}


def _cached_sharded_jit(key: tuple, build):
    fn = _SHARDED_JIT_CACHE.get(key)
    if fn is None:
        fn = _SHARDED_JIT_CACHE[key] = jax.jit(build())
    return fn


def fleet_layout(states: OSELMState) -> tuple[Mesh, str] | None:
    """The mesh and axis a stacked fleet is laid out over by device
    range, or None for a fleet on one device. A fleet over several
    devices must be split along its device axis over exactly one mesh
    axis, of ``Auto`` type, that spans them all (what ``shard_fleet``
    over a 1-D ``repro.launch.mesh.make_mesh`` and the benchmark's
    ``lay_out`` give)."""
    sharding = getattr(states.beta, "sharding", None)
    if sharding is None or len(sharding.device_set) < 2:
        return None
    spec = sharding.spec if isinstance(sharding, NamedSharding) else ()
    axis = spec[0] if spec else None
    if isinstance(axis, tuple) and len(axis) == 1:
        axis = axis[0]
    mesh = getattr(sharding, "mesh", None)
    if (not isinstance(axis, str) or any(s is not None for s in spec[1:])
            or mesh.shape[axis] != len(sharding.device_set)
            or mesh.axis_types[mesh.axis_names.index(axis)] != AxisType.Auto):
        raise ValueError(
            "a fleet over several devices must be laid out by device range "
            f"over one mesh axis of Auto type (repro.launch.mesh.make_mesh); got {sharding}"
        )
    return mesh, axis


def ingest_local(
    states: OSELMState,
    window: jnp.ndarray,
    *,
    kernel: bool,
    backend: str = "auto",
    interpret: bool | None = None,
) -> tuple[OSELMState, jnp.ndarray]:
    """One tick's ingest of the devices at hand: (trained fleet, (D,)
    mean pre-train score of each device's window). ``kernel`` runs the
    fused ``fleet_ingest`` family; otherwise the score under the current
    model, then the k=1 sequential updates (``_fleet_train``)."""
    if kernel:
        from repro.kernels.fleet_ingest import fleet_ingest

        return fleet_ingest(states, window, backend=backend, interpret=interpret)
    losses = jax.vmap(lambda s, xb: jnp.mean(ae_score(s, xb)))(states, window)
    return _fleet_train(states, window), losses


def sharded_ingest(
    mesh: Mesh,
    axes: Sequence[str],
    *,
    kernel: bool,
    backend: str = "auto",
    interpret: bool | None = None,
):
    """``(fleet, window) -> (trained, losses)`` with every shard ingesting
    only its own devices (``ingest_local`` under ``shard_map``): no
    collectives, and each Pallas call sees one shard's block, so none is
    left to the automatic partitioner."""
    from repro.kernels.fleet_ingest import resolve_backend

    spec = P(tuple(axes))
    resolved = resolve_backend(backend)

    def body(st: OSELMState, xs: jnp.ndarray):
        return ingest_local(st, xs, kernel=kernel, backend=resolved, interpret=interpret)

    return _shard_map(
        body, mesh, in_specs=(spec, spec), out_specs=(spec, spec),
        # pallas_call has no shard_map replication rule; the body is
        # per-shard-local anyway (no collectives), so the check adds
        # nothing here
        check_rep=not (kernel and resolved == "pallas"),
    )


def halo_bytes(topology: Topology, n_shards: int, n_hidden: int, n_out: int) -> int:
    """Bytes one merge round ships between shards, from the shapes: an
    open ring's 2·hops edge payloads per shard, else each shard's
    cluster partials into the all-reduce; 0 on one shard. A payload is
    one device's [U | V], Ñ·(Ñ+m) float32."""
    if n_shards < 2:
        return 0
    payload = 4 * n_hidden * (n_hidden + n_out)
    if topology.kind == "banded" and not topology.band_closed:
        return 2 * topology.hops * n_shards * payload
    _, n_clusters, _ = _merge_cids(topology)
    return n_shards * n_clusters * payload


def _keep(st: OSELMState, p, beta, mask, axes) -> OSELMState:
    """Devices with mask 0 keep their own (P, β)."""
    keep = (mask > 0)[:, None, None]
    return st.replace(
        beta=jnp.where(keep, revary(beta.astype(st.beta.dtype), axes), st.beta),
        p=jnp.where(keep, revary(p.astype(st.p.dtype), axes), st.p),
    )


def _ring_merge_body(st, mask, *, axis, n_shards, hops, ridge, kernel, interpret):
    """One shard's masked open-ring merge: mask the payloads, ship the
    ``hops`` edge payloads to each neighbouring shard (``ppermute``; the
    ring closes from the last shard to the first), then solve the
    shard's devices over the extended block. Devices are laid out
    contiguously per shard (device d lives on shard d // L), so the
    global ring order is (shard, local) lexicographic."""
    uv = fleet_to_uv(st, ridge=ridge)
    n = uv.u.shape[1]
    w = jnp.concatenate([uv.u, uv.v], axis=2) * mask.astype(uv.u.dtype)[:, None, None]
    if hops > 0:  # w[-0:] would be the WHOLE block, a bogus full-shard halo
        left = jax.lax.ppermute(w[-hops:], axis, [(i, (i + 1) % n_shards)
                                                  for i in range(n_shards)])
        right = jax.lax.ppermute(w[:hops], axis, [(i, (i - 1) % n_shards)
                                                  for i in range(n_shards)])
        ext = jnp.concatenate([left, w, right], axis=0)  # (L + 2·hops, ...)
    else:
        ext = w
    n_local = w.shape[0]
    if kernel:
        from repro.kernels.topology_merge import banded_merge_solve

        p, beta = banded_merge_solve(ext, hops, ridge=ridge, halo=True,
                                     interpret=interpret)
    else:
        # the one-device ring's order of summation (Topology.mix)
        mixed = sum(ext[hops - o:hops - o + n_local] for o in range(-hops, hops + 1))
        merged = fleet_from_uv(st, UV(u=mixed[:, :, :n], v=mixed[:, :, n:]), ridge=ridge)
        p, beta = merged.p, merged.beta
    return _keep(st, p, beta, mask, (axis,))


def _segment_merge_body(st, mask, cids, *, axes, n_clusters, isolated, ridge,
                        kernel, interpret):
    """One shard's masked cluster merge: its devices' masked payloads
    summed per cluster, one ``psum`` of the O(clusters) partials, then
    the solve (once for a fleet-wide sum, per cluster otherwise)."""
    uv = fleet_to_uv(st, ridge=ridge)
    n = uv.u.shape[1]
    w = jnp.concatenate([uv.u, uv.v], axis=2)
    mf = mask.astype(w.dtype)
    if kernel:
        from repro.kernels.topology_merge import (
            _masked_segment_sum_mix_call,
            from_uv_solve,
        )

        sums = _masked_segment_sum_mix_call(w, cids, mf, n_clusters, interpret=interpret)
        # the kernel writes only the clusters this shard holds
        held = jax.ops.segment_sum(jnp.ones_like(mf), cids, num_segments=n_clusters) > 0
        sums = jnp.where(held[:, None, None], sums, 0.0)
        solve = lambda s: from_uv_solve(  # noqa: E731
            s[:, :, :n], s[:, :, n:], ridge=ridge, interpret=interpret)
    else:
        sums = jax.ops.segment_sum(w * mf[:, None, None], cids, num_segments=n_clusters)
        solve = lambda s: jax.vmap(lambda u, v: _solve_uv(u, v, ridge))(  # noqa: E731
            s[:, :, :n], s[:, :, n:])
    sums = jax.lax.psum(sums, axes)  # O(clusters) per-shard collective
    if isolated:
        pc, betac = solve(sums)
        p, beta = pc[cids], betac[cids]
    else:
        p1, beta1 = solve(sums.sum(0, keepdims=True))
        p, beta = _bcast(p1[0], cids.shape[0]), _bcast(beta1[0], cids.shape[0])
    return _keep(st, p, beta, mask, axes)


def sharded_merge(
    topology: Topology,
    mesh: Mesh,
    axes: Sequence[str],
    ridge: float,
    *,
    kernel: bool,
    interpret: bool | None = None,
) -> tuple:
    """The masked Eq. 8 merge of a fleet laid out over ``axes``, each
    shard solving only its own devices: ``(fn, operands)`` with
    ``fn(fleet, mask, *operands)`` under ``shard_map``. An open ring
    exchanges halos (``_ring_merge_body``); cluster-wise-constant
    topologies psum their partials (``_segment_merge_body``). ``kernel``
    runs the topology-merge kernels per shard."""
    axes = tuple(axes)
    n_shards = _mesh_axis_size(mesh, axes)
    if topology.n_devices % n_shards:
        raise ValueError(
            f"n_devices={topology.n_devices} not divisible by {n_shards} shards"
        )
    spec = P(axes)
    if topology.kind == "banded" and not topology.band_closed:
        if len(axes) != 1:
            raise NotImplementedError(
                "open-ring halo exchange shards over exactly one mesh axis"
            )
        n_local = topology.n_devices // n_shards
        if topology.hops > n_local:
            raise ValueError(
                f"halo exchange needs hops={topology.hops} <= devices per "
                f"shard ({n_local}): a wider band straddles non-adjacent "
                "shards — use fewer shards or the single-process merge"
            )
        body = partial(_ring_merge_body, axis=axes[0], n_shards=n_shards,
                       hops=topology.hops, ridge=ridge, kernel=kernel,
                       interpret=interpret)
        in_specs, operands = (spec, spec), ()
    else:
        cids, n_clusters, isolated = _merge_cids(topology)
        body = partial(_segment_merge_body, axes=axes, n_clusters=n_clusters,
                       isolated=isolated, ridge=ridge, kernel=kernel,
                       interpret=interpret)
        in_specs = (spec, spec, spec)
        operands = (np.asarray(cids, np.int32),)
    # pallas_call has no shard_map replication rule
    fn = _shard_map(body, mesh, in_specs=in_specs, out_specs=spec, check_rep=not kernel)
    return fn, operands


# ------------------------------------------- inter-cohort tier-2 reduction


def _tree_fold(stack: jnp.ndarray) -> jnp.ndarray:
    """Pairwise binary-tree sum over the leading axis — the reduction
    shape the cohort-head overlay actually ships (⌈log₂ n⌉ rounds of
    pairwise exchanges), and the summation order the paged merge's
    ≤1e-5 agreement with flat ``fleet_merge`` is stated against."""
    while stack.shape[0] > 1:
        n = stack.shape[0]
        even = stack[0 : n - (n % 2) : 2] + stack[1::2]
        if n % 2:
            even = jnp.concatenate([even, stack[n - 1 :]], axis=0)
        stack = even
    return stack[0]


def cohort_tree_reduce(
    partials: jnp.ndarray,
    mesh: Mesh | None = None,
    axes: Sequence[str] = ("data",),
) -> jnp.ndarray:
    """Tier-2 of the two-tier cohort merge: reduce the stacked
    per-cohort partial (U, V) sums ``(n_cohorts, R, C) → (R, C)``.

    Eq. 8 is a sum, so the inter-cohort tier is pure reduction — on a
    single device an explicit pairwise binary tree (``_tree_fold``), on
    a mesh the cohort axis is sharded over ``axes``, each shard folds
    its resident cohorts locally, and ONE ``psum`` of the O(Ñ(Ñ+m))
    partial completes the tree — the collective never scales with the
    number of cohorts, let alone devices."""
    partials = jnp.asarray(partials)
    if mesh is None:
        fn = _cached_sharded_jit(("cohort_tree", partials.shape), lambda: _tree_fold)
        return fn(partials)
    n_shards = _mesh_axis_size(mesh, axes)
    if partials.shape[0] % n_shards:
        raise ValueError(
            f"n_cohorts={partials.shape[0]} not divisible by "
            f"{n_shards} mesh shards"
        )

    def body(local: jnp.ndarray) -> jnp.ndarray:
        return jax.lax.psum(_tree_fold(local), tuple(axes))

    fn = _cached_sharded_jit(
        ("cohort_psum", mesh, tuple(axes), partials.shape),
        lambda: _shard_map(
            body, mesh, in_specs=(P(tuple(axes)),), out_specs=P()
        ),
    )
    return fn(partials)
