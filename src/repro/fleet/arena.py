"""Host-side fleet arena, cohort schedule, and the two-tier cohort merge.

Every layer since PR 1 assumed the whole stacked fleet is ONE resident
device array, which caps D at device memory (benchmarks topped out at
D=4096). This module removes that assumption for D ≫ 10⁵:

- ``FleetArena`` — the per-device OS-ELM state (P, β) lives in host
  numpy, (D, Ñ, Ñ) + (D, Ñ, m); the random SLFN basis (α, b) is stored
  ONCE (Eq. 8 requires all devices to share it, so replicating it per
  device — the stacked-fleet layout — is pure waste at arena scale).
  At Ñ=4, m=8 one million devices is ~192 MB of arena — host memory,
  not HBM. ``page()`` views a cohort's slice as an ``OSELMState`` whose
  2-D shared basis streams through the fused ingest kernel family
  unchanged (``fleet_ingest`` reads the basis via ``_shared_basis``,
  which passes an unstacked (n, Ñ) basis through without broadcast).
- ``CohortSchedule`` — which contiguous device block is resident when:
  D must divide into equal cohorts so every page has the same shape and
  the jitted per-page closures compile once.
- ``CohortMerger`` — Eq. 8 as a two-tier tree. Tier 1 (intra-cohort)
  masked segment sums of the resident page's (U, V) payloads — the
  Pallas ``masked_segment_sum_mix`` kernel or its XLA twin. Tier 2
  (inter-cohort) reduces the O(clusters)-sized partials: a pairwise
  binary tree / mesh psum (``repro.fleet.sharded.cohort_tree_reduce``)
  for cluster-wise-constant topologies, a boundary-halo exchange for
  the open ring. Because the cooperative update is a SUM, the tree
  reorders but never changes the result (≤1e-5 vs flat
  ``fleet_merge``, asserted in tests/test_cohort.py).
- ``cohort_round_cost`` — per-tier payload/byte accounting: tier 1
  stays inside a cohort (cheap, local links), tier 2 is what crosses
  the cohort-head overlay (the traffic that matters at fleet scale).

The hierarchical/location-clustered structure mirrors Jung et al.
(Sensors 2024): devices cluster to a head, heads exchange aggregates —
here cohorts are the residency unit and clusters the topology unit,
and the merge handles clusters nesting inside, spanning, or straddling
cohort boundaries identically (partial sums just add up).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import OSELMState, init_oselm, init_slfn
from repro.core.e2lm import uv_of
from repro.core.elm import SLFNParams
from repro.fleet.comm import payload_nbytes
from repro.fleet.fleet import _solve_uv
from repro.fleet.sharded import cohort_tree_reduce
from repro.fleet.topology import Topology

__all__ = [
    "FleetArena",
    "CohortSchedule",
    "CohortMerger",
    "TierCost",
    "cohort_round_cost",
    "init_arena",
]


# ------------------------------------------------------------------ arena


@dataclasses.dataclass
class FleetArena:
    """Host-resident fleet state: shared basis once, (P, β) per device."""

    alpha: np.ndarray        # (n_features, Ñ) shared random basis
    bias: np.ndarray         # (Ñ,)
    p: np.ndarray            # (D, Ñ, Ñ) float32
    beta: np.ndarray         # (D, Ñ, m) float32
    activation: str = "sigmoid"
    forget: float = 1.0

    def __post_init__(self) -> None:
        if self.p.ndim != 3 or self.beta.ndim != 3:
            raise ValueError(
                f"arena (P, β) must be (D, Ñ, ·): got {self.p.shape}, "
                f"{self.beta.shape}"
            )
        if self.p.shape[0] != self.beta.shape[0]:
            raise ValueError(
                f"P and β disagree on D: {self.p.shape[0]} vs "
                f"{self.beta.shape[0]}"
            )

    @property
    def n_devices(self) -> int:
        return self.p.shape[0]

    @property
    def n_hidden(self) -> int:
        return self.p.shape[1]

    @property
    def n_out(self) -> int:
        return self.beta.shape[2]

    @property
    def n_features(self) -> int:
        return self.alpha.shape[0]

    @property
    def nbytes(self) -> int:
        return int(
            self.alpha.nbytes + self.bias.nbytes
            + self.p.nbytes + self.beta.nbytes
        )

    @classmethod
    def from_fleet(cls, states: OSELMState) -> "FleetArena":
        """Adopt a stacked resident fleet (basis must be shared — it is
        checked, because Eq. 8 is meaningless otherwise)."""
        alpha = np.asarray(states.params.alpha)
        bias = np.asarray(states.params.bias)
        if alpha.ndim == 3:
            if not (np.all(alpha == alpha[:1]) and np.all(bias == bias[:1])):
                raise ValueError(
                    "stacked fleet does not share its SLFN basis — the "
                    "arena stores the basis once, and Eq. 8 merges are "
                    "only meaningful over a shared basis"
                )
            alpha, bias = alpha[0], bias[0]
        return cls(
            alpha=alpha.copy(),
            bias=bias.copy(),
            p=np.asarray(states.p, np.float32).copy(),
            beta=np.asarray(states.beta, np.float32).copy(),
            activation=states.activation,
            forget=states.forget,
        )

    def page(self, lo: int, hi: int) -> OSELMState:
        """The cohort's slice as an ``OSELMState`` with the UNSTACKED
        shared basis — numpy views, zero copies; the fused ingest
        lowerings consume this shape directly (no per-device basis
        broadcast is ever materialized)."""
        return OSELMState(
            params=SLFNParams(alpha=self.alpha, bias=self.bias),
            beta=self.beta[lo:hi],
            p=self.p[lo:hi],
            activation=self.activation,
            forget=self.forget,
        )

    def write_page(
        self,
        lo: int,
        hi: int,
        p,
        beta,
        where: np.ndarray | None = None,
    ) -> None:
        """Scatter a computed page back (``where`` row-masks the write —
        unserved / non-receiving devices keep their arena state)."""
        p = np.asarray(p, np.float32)
        beta = np.asarray(beta, np.float32)
        if where is None:
            self.p[lo:hi] = p
            self.beta[lo:hi] = beta
        else:
            w = np.asarray(where, bool)
            self.p[lo:hi][w] = p[w]
            self.beta[lo:hi][w] = beta[w]

    def to_fleet(self) -> OSELMState:
        """Materialize the full stacked fleet (basis broadcast per
        device) — for evaluation and differential tests at small D;
        at arena scale this is exactly the layout the arena exists to
        avoid."""
        d = self.n_devices
        return OSELMState(
            params=SLFNParams(
                alpha=jnp.broadcast_to(self.alpha, (d,) + self.alpha.shape),
                bias=jnp.broadcast_to(self.bias, (d,) + self.bias.shape),
            ),
            beta=jnp.asarray(self.beta),
            p=jnp.asarray(self.p),
            activation=self.activation,
            forget=self.forget,
        )


def init_arena(
    key: jax.Array,
    n_devices: int,
    n_features: int,
    n_hidden: int,
    x_init_fn,
    *,
    cohort_size: int,
    activation: str = "sigmoid",
    ridge: float = 0.0,
    forget: float = 1.0,
) -> FleetArena:
    """Paged ``init_fleet``: one shared ``init_slfn`` basis, then Eq. 13
    per-cohort — ``x_init_fn(lo, hi) -> (hi-lo, n_init, n_features)``
    supplies each cohort's boot chunks, so the full (D, n_init, n)
    array never exists. One jitted init per page shape."""
    if n_hidden >= n_features:
        raise ValueError(
            f"autoencoder needs a bottleneck: Ñ={n_hidden} >= n={n_features}"
        )
    schedule = CohortSchedule(n_devices, cohort_size)
    params = init_slfn(key, n_features, n_hidden)

    @jax.jit
    def _init(x0):
        def one(x):
            return init_oselm(
                params, x, x,
                activation=activation, ridge=ridge, forget=forget,
            )

        st = jax.vmap(one)(x0)
        return st.p, st.beta

    p = beta = None
    for lo, hi in schedule.bounds():
        pc, bc = _init(jnp.asarray(x_init_fn(lo, hi), jnp.float32))
        if p is None:
            p = np.empty((n_devices,) + pc.shape[1:], np.float32)
            beta = np.empty((n_devices,) + bc.shape[1:], np.float32)
        p[lo:hi] = np.asarray(pc)
        beta[lo:hi] = np.asarray(bc)
    return FleetArena(
        alpha=np.asarray(params.alpha),
        bias=np.asarray(params.bias),
        p=p,
        beta=beta,
        activation=activation,
        forget=forget,
    )


# --------------------------------------------------------------- schedule


@dataclasses.dataclass(frozen=True)
class CohortSchedule:
    """Which contiguous device block is device-resident when.

    Equal cohorts (D divisible by ``cohort_size``) keep every page the
    same shape, so the per-page jits compile exactly once.
    ``active_per_tick=None`` serves every cohort every tick; an integer
    round-robins that many cohorts per tick (the remaining devices'
    state stays untouched in the arena — they still contribute to
    merge rounds, they just are not serving new samples)."""

    n_devices: int
    cohort_size: int
    active_per_tick: int | None = None

    def __post_init__(self) -> None:
        if not 1 <= self.cohort_size <= self.n_devices:
            raise ValueError(
                f"need 1 <= cohort_size <= D: {self.cohort_size} vs "
                f"D={self.n_devices}"
            )
        if self.n_devices % self.cohort_size:
            raise ValueError(
                f"D={self.n_devices} not divisible by cohort_size="
                f"{self.cohort_size}: ragged pages would retrace the "
                "per-page jits"
            )
        if self.active_per_tick is not None and not (
            1 <= self.active_per_tick <= self.n_cohorts
        ):
            raise ValueError(
                f"active_per_tick={self.active_per_tick} outside "
                f"[1, {self.n_cohorts}]"
            )

    @property
    def n_cohorts(self) -> int:
        return self.n_devices // self.cohort_size

    def bounds(self, k: int | None = None):
        """(lo, hi) of cohort ``k``, or all cohorts' bounds in order."""
        if k is not None:
            return k * self.cohort_size, (k + 1) * self.cohort_size
        return [
            (i * self.cohort_size, (i + 1) * self.cohort_size)
            for i in range(self.n_cohorts)
        ]

    def active(self, tick: int) -> list[int]:
        """Cohorts served on ``tick`` (round-robin window)."""
        n = self.n_cohorts
        a = self.active_per_tick
        if a is None or a >= n:
            return list(range(n))
        start = (tick * a) % n
        return [(start + i) % n for i in range(a)]


# ----------------------------------------------------------- tier costs


@dataclasses.dataclass(frozen=True)
class TierCost:
    """One two-tier merge round's traffic, split by tier. Tier 1 is the
    device↔cohort-aggregator traffic that never leaves a cohort; tier 2
    is what crosses the cohort-head overlay (tree / halo) — the number
    that must stay O(cohorts·clusters), never O(devices)."""

    topology: str
    n_devices: int
    n_cohorts: int
    tier1_payloads: int
    tier2_payloads: int
    payload_bytes: int

    @property
    def bytes_tier1(self) -> int:
        return self.tier1_payloads * self.payload_bytes

    @property
    def bytes_tier2(self) -> int:
        return self.tier2_payloads * self.payload_bytes

    @property
    def bytes_total(self) -> int:
        return self.bytes_tier1 + self.bytes_tier2


def cohort_round_cost(
    topology: Topology,
    schedule: CohortSchedule,
    n_hidden: int,
    n_out: int,
    *,
    itemsize: int = 4,
    precision: str = "f32",
) -> TierCost:
    """Per-tier payload counts of ONE two-tier cooperative update.

    - Cluster-wise-constant topologies (star / all-to-all / closed ring
      / head-exchange hierarchical): every non-aggregator device ships
      up + downloads down inside its cohort (tier 1 = 2(D − n_cohorts));
      cohort heads run a pairwise reduction tree and broadcast back
      (tier 2 = 2(n_cohorts − 1)).
    - Isolated hierarchical clusters: members exchange with their
      cluster head (tier 1 = 2(D − n_clusters)); tier 2 is only the
      straddle traffic — a cluster spanning c > 1 cohorts ships c − 1
      partial sums (and downloads) across the overlay; clusters nested
      inside one cohort cost tier 2 nothing.
    - Open ring: the band is local, so tier 1 is the in-cohort share of
      the flat ring traffic and tier 2 the 2·hops payload halo each
      cohort boundary exchanges per direction.
    """
    if topology.n_devices != schedule.n_devices:
        raise ValueError(
            f"topology D={topology.n_devices} vs schedule "
            f"D={schedule.n_devices}"
        )
    nb = payload_nbytes(
        n_hidden, n_out, itemsize,
        precision=None if precision == "f32" else precision,
    )
    d, nc = schedule.n_devices, schedule.n_cohorts
    if topology.kind == "segment" and not topology.head_exchange:
        cids = np.asarray(topology.cluster_ids)
        incidences = sum(
            len(np.unique(cids[lo:hi])) for lo, hi in schedule.bounds()
        )
        tier1 = 2 * (d - topology.n_clusters)
        tier2 = 2 * (incidences - topology.n_clusters)
    elif topology.kind == "banded" and not topology.band_closed:
        tier2 = 2 * topology.hops * nc
        tier1 = max(topology.payloads_per_round - tier2, 0)
    elif topology.is_fully_connected or topology.kind == "segment":
        tier1 = 2 * (d - nc)
        tier2 = 2 * (nc - 1)
    else:
        raise NotImplementedError(
            f"no two-tier decomposition for topology {topology.name!r} "
            f"(kind={topology.kind!r})"
        )
    return TierCost(
        topology=topology.name,
        n_devices=d,
        n_cohorts=nc,
        tier1_payloads=int(tier1),
        tier2_payloads=int(tier2),
        payload_bytes=int(nb),
    )


# ------------------------------------------------------- two-tier merge


class CohortMerger:
    """Eq. 8 over a paged arena, one cohort page resident at a time.

    Modes, chosen from the topology:

    - ``global`` (star / all-to-all / closed ring / head-exchange
      hierarchical — any merged model that is fleet-wide constant):
      tier 1 reduces each page to ONE (Ñ, Ñ+m) masked partial sum
      (Pallas ``masked_segment_sum_mix`` with a single segment, or the
      XLA sum), tier 2 folds the (n_cohorts, Ñ, Ñ+m) stack through
      ``cohort_tree_reduce`` (pairwise tree, or psum over a mesh), and
      one §4.2 solve serves every participant.
    - ``clusters`` (isolated hierarchical): tier 1 segment-sums each
      page over its LOCAL cluster ids; tier 2 scatter-adds the per-page
      partials into the global (n_clusters, Ñ, Ñ+m) accumulator —
      clusters that straddle a cohort boundary just contribute from
      both pages (a sum is a sum); per-cluster solves, then each page
      gathers its devices' cluster solutions back.
    - ``ring`` (open banded): each page extends itself with ``hops``
      pre-merge halo rows from both neighbors (snapshotted before any
      page writes back, so in-place scatters never leak merged state
      into a later page's halo), forms the banded window sums over the
      extended block, and solves per device — the paged twin of the
      sharded ``ppermute`` halo exchange.

    ``kernel="auto"`` follows the repo's dispatch convention: Pallas on
    TPU, XLA elsewhere (the Pallas interpreter on CPU is a correctness
    tool, not a fast path). All per-page callables are jitted once per
    page shape (and, for ``clusters``, per unique local-cluster-id
    pattern); participation masks are traced operands, so governor
    gating never retraces — same contract as the resident merge.
    """

    def __init__(
        self,
        topology: Topology,
        schedule: CohortSchedule,
        *,
        ridge: float = 0.0,
        kernel: bool | str = "auto",
        interpret: bool | None = None,
        mesh=None,
        mesh_axes=("data",),
    ) -> None:
        if topology.n_devices != schedule.n_devices:
            raise ValueError(
                f"topology D={topology.n_devices} vs schedule "
                f"D={schedule.n_devices}"
            )
        self.topology = topology
        self.schedule = schedule
        self.ridge = float(ridge)
        if kernel == "auto":
            from repro.kernels.fleet_ingest import native_kernels

            kernel = native_kernels()
        self.kernel = bool(kernel)
        self.interpret = interpret  # None: decided by platform
        self.mesh = mesh
        self.mesh_axes = tuple(mesh_axes)
        self._jits: dict = {}

        if topology.kind == "segment" and not topology.head_exchange:
            self.mode = "clusters"
            cids = np.asarray(topology.cluster_ids, np.int64)
            if cids.shape[0] != topology.n_devices or np.any(np.diff(cids) < 0):
                raise ValueError(
                    "cluster_ids must be sorted/contiguous (as built by "
                    "fleet.topology.hierarchical) — the paged segment "
                    "sums assume each page's clusters are a contiguous "
                    "id range"
                )
            self._cids = cids
            # per cohort: local ids (offset to 0) + the global offset;
            # k_max pads every page's partial to one static shape so a
            # single trace serves all pages sharing a local-id pattern
            self._locals = []
            k_max = 1
            for lo, hi in schedule.bounds():
                sl = cids[lo:hi]
                off = int(sl[0])
                local = (sl - off).astype(np.int32)
                k_max = max(k_max, int(local[-1]) + 1)
                self._locals.append((off, local))
            self._k_max = k_max
        elif topology.kind == "banded" and not topology.band_closed:
            self.mode = "ring"
            if 2 * topology.hops >= topology.n_devices:
                raise ValueError("open band wider than the fleet")
        elif topology.is_fully_connected or topology.kind == "segment":
            self.mode = "global"
        else:
            raise NotImplementedError(
                f"two-tier merge needs a cluster-wise-constant topology "
                f"or an open ring; {topology.name!r} "
                f"(kind={topology.kind!r}) mixes per-device neighbor "
                "sets that do not decompose over cohorts"
            )

    # -- payload math shared by every mode: the resident fleet_to_uv,
    # minus the per-device basis (a page's basis is the one shared copy)
    def _w_of(self, p, beta):
        uv = jax.vmap(lambda pp, bb: uv_of(pp, bb, ridge=self.ridge))(p, beta)
        return jnp.concatenate([uv.u, uv.v], axis=-1)

    def _page_partial_fn(self, local_cids: np.ndarray, n_segments: int):
        """Jitted tier-1 partial: (page p, β, mask) → (n_segments, Ñ,
        Ñ+m) masked segment sums. Cached per local-id pattern — evenly
        nested clusters share one pattern across all pages."""
        key = ("partial", local_cids.tobytes(), n_segments)
        fn = self._jits.get(key)
        if fn is not None:
            return fn
        use_kernel, interpret = self.kernel, self.interpret

        def partial(p, beta, mask):
            w = self._w_of(p, beta)
            if use_kernel:
                from repro.kernels.topology_merge import masked_segment_sum_mix

                return masked_segment_sum_mix(
                    w, local_cids, mask, n_segments, interpret=interpret
                )
            wm = w * mask.astype(w.dtype)[:, None, None]
            return jax.ops.segment_sum(
                wm, jnp.asarray(local_cids), num_segments=n_segments
            )

        fn = self._jits[key] = jax.jit(partial)
        return fn

    def _solve_fn(self, batched: bool):
        key = ("solve", batched)
        fn = self._jits.get(key)
        if fn is not None:
            return fn
        ridge, use_kernel, interpret = self.ridge, self.kernel, self.interpret

        def solve(u, v):
            if use_kernel:
                from repro.kernels.topology_merge import from_uv_solve

                if not batched:
                    pc, bc = from_uv_solve(
                        u[None], v[None], ridge=ridge, interpret=interpret
                    )
                    return pc[0], bc[0]
                return from_uv_solve(u, v, ridge=ridge, interpret=interpret)
            if not batched:
                return _solve_uv(u, v, ridge)
            return jax.vmap(lambda uu, vv: _solve_uv(uu, vv, ridge))(u, v)

        fn = self._jits[key] = jax.jit(solve)
        return fn

    def jit_cache_sizes(self) -> dict[str, int]:
        return {
            "_".join(str(k) for k in key if isinstance(key, tuple)): (
                fn._cache_size() if hasattr(fn, "_cache_size") else -1
            )
            for key, fn in self._jits.items()
        }

    # ------------------------------------------------------------- merge

    def merge(self, arena: FleetArena, mask: np.ndarray) -> TierCost:
        """One participation-masked two-tier cooperative update, in
        place on the arena. Devices with mask 0 neither contribute nor
        receive (their arena rows are untouched) — identical semantics
        to the resident ``fleet_merge_masked``. Returns the round's
        per-tier cost."""
        mask = np.asarray(mask, bool)
        if mask.shape != (arena.n_devices,):
            raise ValueError(
                f"mask shape {mask.shape} != (D={arena.n_devices},)"
            )
        if self.mode == "ring":
            self._merge_ring(arena, mask)
        elif self.mode == "clusters":
            self._merge_clusters(arena, mask)
        else:
            self._merge_global(arena, mask)
        return cohort_round_cost(
            self.topology, self.schedule, arena.n_hidden, arena.n_out
        )

    def _merge_global(self, arena: FleetArena, mask: np.ndarray) -> None:
        zeros = np.zeros(self.schedule.cohort_size, np.int32)
        partial_fn = self._page_partial_fn(zeros, 1)
        parts = []
        for lo, hi in self.schedule.bounds():
            parts.append(partial_fn(
                jnp.asarray(arena.p[lo:hi]),
                jnp.asarray(arena.beta[lo:hi]),
                jnp.asarray(mask[lo:hi], jnp.float32),
            )[0])
        total = cohort_tree_reduce(
            jnp.stack(parts), self.mesh, self.mesh_axes
        )
        nh = arena.n_hidden
        p1, b1 = self._solve_fn(batched=False)(total[:, :nh], total[:, nh:])
        p1, b1 = np.asarray(p1), np.asarray(b1)
        for lo, hi in self.schedule.bounds():
            m = mask[lo:hi]
            arena.p[lo:hi][m] = p1
            arena.beta[lo:hi][m] = b1

    def _merge_clusters(self, arena: FleetArena, mask: np.ndarray) -> None:
        nh, m_out = arena.n_hidden, arena.n_out
        acc = np.zeros(
            (self.topology.n_clusters, nh, nh + m_out), np.float32
        )
        for (lo, hi), (off, local) in zip(
            self.schedule.bounds(), self._locals
        ):
            part = self._page_partial_fn(local, self._k_max)(
                jnp.asarray(arena.p[lo:hi]),
                jnp.asarray(arena.beta[lo:hi]),
                jnp.asarray(mask[lo:hi], jnp.float32),
            )
            k_here = int(local[-1]) + 1
            acc[off : off + k_here] += np.asarray(part)[:k_here]
        pc, bc = self._solve_fn(batched=True)(
            jnp.asarray(acc[:, :, :nh]), jnp.asarray(acc[:, :, nh:])
        )
        pc, bc = np.asarray(pc), np.asarray(bc)
        for lo, hi in self.schedule.bounds():
            m = mask[lo:hi]
            gcids = self._cids[lo:hi]
            arena.p[lo:hi][m] = pc[gcids[m]]
            arena.beta[lo:hi][m] = bc[gcids[m]]

    def _ring_page_fn(self):
        key = ("ring_page",)
        fn = self._jits.get(key)
        if fn is not None:
            return fn
        hops = self.topology.hops
        c = self.schedule.cohort_size
        solve = self._solve_fn(batched=True)

        def page(p_ext, beta_ext, mask_ext):
            w = self._w_of(p_ext, beta_ext)
            w = w * mask_ext.astype(w.dtype)[:, None, None]
            # offsets descending to match Topology.mix's roll order
            mixed = w[2 * hops : 2 * hops + c]
            for o in range(2 * hops - 1, -1, -1):
                mixed = mixed + w[o : o + c]
            nh = p_ext.shape[-1]
            return solve(mixed[:, :, :nh], mixed[:, :, nh:])

        fn = self._jits[key] = jax.jit(page)
        return fn

    def _merge_ring(self, arena: FleetArena, mask: np.ndarray) -> None:
        d = arena.n_devices
        hops = self.topology.hops
        page_fn = self._ring_page_fn()
        # pre-merge halo snapshot: each page's window sums must read its
        # neighbors' PRE-merge payloads even after those pages already
        # scattered their merged state back into the arena
        halos = []
        for lo, hi in self.schedule.bounds():
            ids = np.concatenate(
                [np.arange(lo - hops, lo), np.arange(hi, hi + hops)]
            ) % d
            halos.append((
                arena.p[ids].copy(), arena.beta[ids].copy(), mask[ids].copy()
            ))
        for (lo, hi), (hp, hb, hm) in zip(self.schedule.bounds(), halos):
            p_ext = np.concatenate([hp[:hops], arena.p[lo:hi], hp[hops:]])
            b_ext = np.concatenate([hb[:hops], arena.beta[lo:hi], hb[hops:]])
            m_ext = np.concatenate([hm[:hops], mask[lo:hi], hm[hops:]])
            pc, bc = page_fn(
                jnp.asarray(p_ext), jnp.asarray(b_ext),
                jnp.asarray(m_ext, jnp.float32),
            )
            arena.write_page(lo, hi, pc, bc, where=mask[lo:hi])
