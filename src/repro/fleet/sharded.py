"""Sharded fleet training and merges over mesh axes.

`repro.fleet` simulates the whole fleet as one stacked pytree;
`repro.federated.mesh_federation` runs one device per mesh shard. This
module combines them: the stacked device axis is sharded across mesh
devices (``repro.launch.sharding.fleet_shardings``), and both halves
of a federation round run shard-locally:

- ``fleet_train_sharded`` — per-tick ingest is embarrassingly parallel
  over devices, so the vmap+scan train loop (or the fused
  ``fleet_ingest`` kernel family, ``kernel=True``) runs under
  ``shard_map`` with NO collectives at all: each shard trains only its
  resident devices, which is the multi-host deployment where ticks
  arrive per-shard (the ROADMAP's multi-host-ingest item).
- ``fleet_merge_sharded`` — each shard segment-sums its *local*
  devices' (U, V) into per-cluster partials, and ONE ``jax.lax.psum``
  of the (n_clusters, Ñ, Ñ+m) partials completes Eq. 8 globally — the
  per-shard collective is O(clusters), never O(devices), which is what
  lets a 10k-device fleet merge over a handful of TPU shards without
  all-gathering 10k payloads. Cluster-wise-constant topologies (star,
  hierarchical, all-to-all, closed ring) take that psum path; the open
  ring takes a **halo exchange**: each shard ``ppermute``s its ``hops``
  edge (U, V) payload blocks to the adjacent shards (O(hops) payloads
  per shard, never the fleet), then forms its devices' banded neighbor
  sums from the extended local block — so banded merges compose with
  sharded fleets end-to-end.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import OSELMState
from repro.federated.compat import revary, shard_map_compat as _shard_map
from repro.fleet.fleet import _bcast, _fleet_train, _solve_uv, fleet_to_uv
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


# jitted shard_map callables keyed by their closure parameters —
# jax.jit's cache is keyed on the function OBJECT, so wrapping a fresh
# closure per call would re-trace/re-compile every tick of the serve
# loop these functions are the hot path of
_SHARDED_JIT_CACHE: dict[tuple, object] = {}


def _cached_sharded_jit(key: tuple, build):
    fn = _SHARDED_JIT_CACHE.get(key)
    if fn is None:
        fn = _SHARDED_JIT_CACHE[key] = jax.jit(build())
    return fn


def fleet_train_sharded(
    states: OSELMState,
    streams: jnp.ndarray,
    mesh: Mesh,
    axes: Sequence[str] = ("data",),
    *,
    kernel: bool = False,
    backend: str = "auto",
    interpret: bool | None = None,
) -> OSELMState:
    """Per-shard tick ingest of a mesh-sharded stacked fleet.

    ``states`` leaves and ``streams`` (D, T, n) carry a leading device
    axis sharded over ``axes``; every shard trains only its local
    devices (k=1 sequential updates) — zero collectives, so ingest
    scales linearly with shard count. ``kernel=True`` runs each shard's
    local ingest through the fused ``fleet_ingest`` family, same
    dispatch as ``fleet_train(kernel=True)``. Returns the trained fleet
    with the same sharding.
    """
    n_shards = _mesh_axis_size(mesh, axes)
    n_dev = states.beta.shape[0]
    if n_dev % n_shards:
        raise ValueError(f"n_devices={n_dev} not divisible by {n_shards} shards")
    spec = P(tuple(axes))
    from repro.kernels.fleet_ingest import resolve_backend, validate_shared_basis

    if kernel:
        validate_shared_basis(states)  # concrete here, pre-shard_map
    resolved = resolve_backend(backend)

    def build():
        def body(st: OSELMState, xs: jnp.ndarray) -> OSELMState:
            if kernel:
                from repro.kernels.fleet_ingest import fleet_ingest

                st, _ = fleet_ingest(st, xs, backend=resolved, interpret=interpret)
            else:
                st = _fleet_train(st, xs)
            return st

        return _shard_map(
            body, mesh, in_specs=(spec, spec), out_specs=spec,
            # pallas_call has no shard_map replication rule; the body is
            # per-shard-local anyway (no collectives), so the check adds
            # nothing here
            check_rep=not (kernel and resolved == "pallas"),
        )

    fn = _cached_sharded_jit(
        ("train", mesh, tuple(axes), kernel, resolved, interpret), build
    )
    return fn(states, jnp.asarray(streams))


def _halo_ring_merge_body(
    st: OSELMState,
    axis: str,
    n_shards: int,
    hops: int,
    ridge: float,
) -> OSELMState:
    """Open-ring merge of one shard's local devices with a halo
    exchange: ``ppermute`` ships the ``hops`` edge payload blocks to
    each neighboring shard (the only cross-shard traffic — O(hops)
    payloads per shard), after which every local device's ≤2·hops+1
    banded neighbor sum is shard-local. Devices are laid out
    contiguously per shard (device d lives on shard d // L), so the
    global ring order is (shard, local) lexicographic."""
    uv = fleet_to_uv(st, ridge=ridge)
    w = jnp.concatenate([uv.u, uv.v], axis=2)  # (L, Ñ, Ñ+m) local payloads
    if hops > 0:
        fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        bwd = [(i, (i - 1) % n_shards) for i in range(n_shards)]
        # halo from the LEFT neighbor shard: its last `hops` devices
        left = jax.lax.ppermute(w[-hops:], axis, fwd)
        # halo from the RIGHT neighbor shard: its first `hops` devices
        right = jax.lax.ppermute(w[:hops], axis, bwd)
        ext = jnp.concatenate([left, w, right], axis=0)  # (L + 2·hops, ...)
    else:  # hops=0 band: each device merges only itself (w[-0:] would
        ext = w  # be the WHOLE block, shipping a bogus full-shard halo)
    n_local = w.shape[0]
    mixed = ext[:n_local]
    for off in range(1, 2 * hops + 1):
        mixed = mixed + ext[off : off + n_local]
    n = uv.u.shape[1]
    p, beta = jax.vmap(lambda u, v: _solve_uv(u, v, ridge))(
        mixed[:, :, :n], mixed[:, :, n:]
    )
    return st.replace(
        beta=revary(beta.astype(st.beta.dtype), (axis,)),
        p=revary(p.astype(st.p.dtype), (axis,)),
    )


def fleet_merge_sharded(
    states: OSELMState,
    topology: Topology,
    mesh: Mesh,
    axes: Sequence[str] = ("data",),
    *,
    ridge: float = 0.0,
) -> OSELMState:
    """Cooperative update of a mesh-sharded stacked fleet.

    ``states`` leaves carry a leading device axis sharded over ``axes``
    (shard it with ``repro.launch.sharding.shard_fleet``). Each shard
    computes local per-cluster (U, V) partial sums, one psum of the
    O(clusters)-sized partials completes the Eq. 8 sum, and each shard
    solves + broadcasts locally. Open-ring (banded) topologies instead
    take the halo-exchange path: ``ppermute`` of the ``hops`` edge
    payload blocks between adjacent shards, then shard-local banded
    sums + per-device solves. Returns the merged fleet with the same
    sharding.
    """
    fn, operands = _sharded_merge_program(topology, mesh, tuple(axes), ridge)
    return fn(states, *operands)


def _sharded_merge_program(
    topology: Topology, mesh: Mesh, axes: tuple[str, ...], ridge: float
) -> tuple:
    """The jitted shard_map program of one sharded merge, and the
    operands it takes after the fleet."""
    n_shards = _mesh_axis_size(mesh, axes)
    if topology.n_devices % n_shards:
        raise ValueError(
            f"n_devices={topology.n_devices} not divisible by {n_shards} shards"
        )
    spec = P(tuple(axes))

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
        fn = _cached_sharded_jit(
            ("halo", mesh, axes, topology, ridge),
            lambda: _shard_map(
                lambda st: _halo_ring_merge_body(
                    st, axes[0], n_shards, topology.hops, ridge
                ),
                mesh, in_specs=(spec,), out_specs=spec,
            ),
        )
        return fn, ()

    cids, n_clusters, isolated = _merge_cids(topology)

    def body(st: OSELMState, cids_local: jnp.ndarray) -> OSELMState:
        n_local = cids_local.shape[0]
        uv = fleet_to_uv(st, ridge=ridge)  # this shard's devices only
        su = jax.ops.segment_sum(uv.u, cids_local, num_segments=n_clusters)
        sv = jax.ops.segment_sum(uv.v, cids_local, num_segments=n_clusters)
        su = jax.lax.psum(su, axes)  # O(clusters) per-shard collective
        sv = jax.lax.psum(sv, axes)
        if isolated:
            pc, betac = jax.vmap(lambda u, v: _solve_uv(u, v, ridge))(su, sv)
            p, beta = pc[cids_local], betac[cids_local]
        else:
            p1, beta1 = _solve_uv(su.sum(0), sv.sum(0), ridge)
            p, beta = _bcast(p1, n_local), _bcast(beta1, n_local)
        return st.replace(
            beta=revary(beta.astype(st.beta.dtype), axes),
            p=revary(p.astype(st.p.dtype), axes),
        )

    fn = _cached_sharded_jit(
        ("merge", mesh, axes, topology, ridge),
        lambda: _shard_map(body, mesh, in_specs=(spec, spec), out_specs=spec),
    )
    return fn, (jnp.asarray(cids),)


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
