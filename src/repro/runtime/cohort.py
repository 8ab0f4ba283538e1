"""Cohort-paged fleet runtime — million-device serving on one host.

``FleetRuntime`` keeps the whole stacked fleet device-resident, which
caps D at accelerator memory. This runtime removes that cap for
D ≫ 10⁵ by splitting the state by its scaling law:

- the O(D·(Ñ² + Ñm)) model state — every device's (P, β) — lives in a
  host-side ``FleetArena`` and only the ACTIVE cohort's page is ever
  device-resident. Pages stream through the fused ingest family
  (``fleet_ingest_paged``) double-buffered: cohort k+1's page is
  staged host→device while cohort k's ingest computes, and k's
  trained page scatters back while k+1 runs.
- the O(D) scalar state — the drift-detector bank, participation
  masks, per-tick losses — stays resident (24 bytes/device: one
  million devices is ~24 MB), so detection runs as ONE full-fleet
  ``detector_update`` per tick, exactly the resident trace.
- merges run as a two-tier tree (``repro.fleet.arena.CohortMerger``):
  intra-cohort masked segment sums on the resident page (tier 1),
  an O(cohorts)-sized inter-cohort reduction (tier 2). Eq. 8 is a sum,
  so the tree reorders but never changes the result — the paged
  runtime's TickReport stream matches the resident runtime's
  tick-by-tick (tests/test_cohort.py's differential test).

One resident-path divergence, by design: the resident detect computes
the post-merge common-mode median in-trace every tick (XLA cannot skip
it — ``rebase`` is traced). Here the host KNOWS whether this tick
rebases, so the O(D log D) median (``common_mode_ratio``) runs only on
actual post-merge ticks and its scalar feeds ``detector_update`` via
``common=`` — same f32 arithmetic on rebase ticks, no sort at all on
the ~(merge_every−1)/merge_every that do not rebase.

Governor, telemetry, and report schema are shared with the resident
runtime. Each page runs three program spans (``repro.obs.trace``):
``page.stage`` (window slice and page puts), ``page.wait`` (its ingest
on the device) and ``page.store`` (page back into the arena), inside the
tick's ``tick.ingest``; the arena/cohort gauges track residency.
"""
from __future__ import annotations

import logging
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.fleet.arena import (
    CohortMerger,
    CohortSchedule,
    FleetArena,
    TierCost,
)
from repro.kernels.fleet_ingest import fleet_ingest_paged
from repro.obs import TelemetrySink, trace
from repro.runtime.detector import (
    common_mode_ratio,
    detector_update,
    init_detector,
)
from repro.runtime.feed import TickFeed
from repro.runtime.governor import MergeDecision, MergeGovernor
from repro.runtime.runtime import (
    RuntimeConfig,
    TickReport,
    _where_served,
)

__all__ = ["CohortFleetRuntime"]

logger = logging.getLogger(__name__)

_UNSUPPORTED = (
    ("staleness", "the stale published-version ring stores full stacked "
     "payload histories — O(D·lag) device memory, the exact layout the "
     "arena exists to avoid"),
    ("robust", "robust merges score every device's payload jointly; a "
     "paged robust boundary needs its own two-tier scoring pass"),
    ("faults", "the fault injector's payload boundary operates on the "
     "full stacked (U, V) stack"),
)


class CohortFleetRuntime:
    """A paged fleet: host arena + resident detector bank + governor."""

    def __init__(
        self,
        arena: FleetArena,
        config: RuntimeConfig,
        *,
        cohort_size: int | None = None,
        schedule: CohortSchedule | None = None,
        active_per_tick: int | None = None,
        policies: tuple = (),
    ) -> None:
        d = arena.n_devices
        if config.topology.n_devices != d:
            raise ValueError(
                f"topology is for {config.topology.n_devices} devices, "
                f"arena has {d}"
            )
        for attr, why in _UNSUPPORTED:
            if getattr(config, attr) is not None:
                raise ValueError(
                    f"cohort-paged runtime does not support {attr}: {why}"
                )
        if config.payload_precision != "f32":
            raise ValueError(
                "cohort-paged runtime requires payload_precision='f32' "
                "(the quantized codec's error-feedback accumulator is a "
                "second full-fleet stack; page it before enabling this)"
            )
        if config.snapshot_dir is not None or config.snapshot_every:
            raise ValueError(
                "cohort-paged runtime has no snapshot path yet — the "
                "checkpoint store serializes stacked fleets; persist the "
                "arena's numpy leaves directly instead"
            )
        if schedule is None:
            if cohort_size is None:
                raise ValueError("need cohort_size= (or a full schedule=)")
            schedule = CohortSchedule(d, cohort_size, active_per_tick)
        elif schedule.n_devices != d:
            raise ValueError(
                f"schedule D={schedule.n_devices} vs arena D={d}"
            )

        self.arena = arena
        self.schedule = schedule
        self.config = config
        self.det = init_detector(d)
        self.governor = MergeGovernor(
            config.topology, arena.n_hidden, arena.n_out, config.governor,
            policies=policies, payload_precision=config.payload_precision,
        )
        self.merger = CohortMerger(
            config.topology, schedule, ridge=config.ridge,
            kernel=True if config.use_merge_kernel else "auto",
        )
        self.tick_no = 0
        self.merge_round = 0
        self.detections: deque[tuple[int, int]] = deque(
            maxlen=config.detections_cap
        )
        self.detections_total = 0
        self.telemetry = (
            TelemetrySink(config.telemetry)
            if config.telemetry is not None else None
        )
        self._post_merge = False
        self._merge_mask = np.ones(d, bool)
        self._all_served = np.ones(d, bool)

        det_cfg = config.detector
        backend = config.ingest_backend
        alpha_j = jnp.asarray(arena.alpha)
        bias_j = jnp.asarray(arena.bias)
        activation, forget = arena.activation, arena.forget

        def ingest(p, beta, window, served):
            # the fused one-pass ingest on one page; un-served devices
            # keep their page rows bit-for-bit (same served contract as
            # the resident tick — a traced operand, never a retrace)
            p2, b2, losses = fleet_ingest_paged(
                p, beta, alpha_j, bias_j, window,
                activation=activation, forget=forget, backend=backend,
            )
            sel = served.astype(bool)[:, None, None]
            return jnp.where(sel, p2, p), jnp.where(sel, b2, beta), losses

        self._ingest = jax.jit(ingest)

        def detect(det, losses, rebase, participants, served, common):
            det_new, _, fresh = detector_update(
                det, losses, det_cfg, rebase=rebase,
                participants=participants, common=common,
            )
            keep = served.astype(bool)
            det = _where_served(keep, det_new, det)
            return det, det.drifted, fresh & keep

        self._detect = jax.jit(detect)
        self._common = jax.jit(
            lambda det, losses, participants: common_mode_ratio(
                det, losses, det_cfg, participants=participants
            )
        )

    @property
    def n_devices(self) -> int:
        return self.arena.n_devices

    # ------------------------------------------------------------- tick loop

    def _phase(self, name: str, **attrs):
        if self.telemetry is None:
            return trace.span(name, **attrs)
        return self.telemetry.phase(name, **attrs)

    def _resolve_batch(self, batch):
        """Normalize the tick's data source to ``fn(lo, hi) -> (C, B, F)``.

        A full (D, B, F) array works at small D (the differential-test
        surface); at arena scale the full array would be the second
        thing that does not fit, so a callable deals each active
        cohort's slice on demand and the full batch never exists."""
        if callable(batch):
            return batch
        arr = np.asarray(batch)
        d = self.n_devices
        if arr.ndim != 3 or arr.shape[0] != d:
            raise ValueError(
                f"tick batch must be (n_devices={d}, B, features) or a "
                f"callable (lo, hi) -> (cohort, B, features); got shape "
                f"{getattr(arr, 'shape', None)}"
            )
        if arr.shape[1] < 1:
            raise ValueError(
                "tick batch has zero samples per device (B=0) — an "
                "all-shed tick window carries no data to ingest"
            )
        return lambda lo, hi: arr[lo:hi]

    def tick(
        self,
        batch,
        *,
        served: np.ndarray | None = None,
        allow_merge: bool = True,
    ) -> TickReport:
        """One paged serving tick: stream the active cohorts' pages
        through ingest (double-buffered), one full-fleet detect, then
        govern and (maybe) run the two-tier merge on the arena.

        Same surface as the resident ``FleetRuntime.tick`` — ``batch``
        may additionally be a callable ``(lo, hi) -> (cohort, B, F)``
        so the full (D, B, F) window never has to exist at arena scale.
        Devices in cohorts OUTSIDE this tick's active window report
        NaN losses (they served nothing) and keep model + detector
        state untouched."""
        with trace.span("tick", seq=self.tick_no) as root:
            return self._tick(root, batch, served, allow_merge)

    def _tick(self, root, batch, served, allow_merge) -> TickReport:
        t = self.tick_no
        d = self.n_devices
        sched = self.schedule
        c = sched.cohort_size
        batch_fn = self._resolve_batch(batch)
        if served is None:
            served_np = self._all_served
        else:
            served_np = np.asarray(served).astype(bool)
            if served_np.shape != (d,):
                raise ValueError(
                    f"served mask must be ({d},); got {served_np.shape}"
                )
        active = sched.active(t)
        tel = self.telemetry

        # devices actually serving this tick: served ∧ active-cohort
        if len(active) == sched.n_cohorts:
            served_eff = served_np
        else:
            served_eff = np.zeros(d, bool)
            for k in active:
                lo, hi = sched.bounds(k)
                served_eff[lo:hi] = served_np[lo:hi]

        # ---- paged ingest, double-buffered: stage page k+1 while page
        # k's compute is in flight, scatter k back as it lands
        def stage(k: int):
            lo, hi = sched.bounds(k)
            with self._phase("page.stage", page=k):
                win = np.asarray(batch_fn(lo, hi), np.float32)
                if win.shape[0] != c or win.ndim != 3 or win.shape[1] < 1:
                    raise ValueError(
                        f"cohort batch for [{lo}, {hi}) must be "
                        f"({c}, B>=1, features); got {win.shape}"
                    )
                return (
                    lo, hi,
                    jax.device_put(self.arena.p[lo:hi]),
                    jax.device_put(self.arena.beta[lo:hi]),
                    jax.device_put(win),
                    jax.device_put(served_np[lo:hi]),
                )

        with self._phase("tick.ingest") as ingest:
            losses_np = np.full(d, np.nan, np.float32)
            cur = stage(active[0])
            for i in range(len(active)):
                k = active[i]
                lo, hi, pj, bj, wj, sj = cur
                out = self._ingest(pj, bj, wj, sj)      # async dispatch
                cur = stage(active[i + 1]) if i + 1 < len(active) else None
                # the wait is its own span: the page's ingest on the
                # device, which the store's first copy would block on
                with self._phase("page.wait", page=k):
                    jax.block_until_ready(out)
                with self._phase("page.store", page=k):
                    p2, b2, lo_j = out
                    self.arena.p[lo:hi] = np.asarray(p2)
                    self.arena.beta[lo:hi] = np.asarray(b2)
                    losses_np[lo:hi] = np.asarray(lo_j)
                if tel is not None:
                    tel.cohort_pages.inc()

            # ---- full-fleet detect (O(D) scalars stay resident). The
            # common-mode median is fleet-wide state the pages cannot
            # see — computed here from the PRE-update bank, only on
            # rebase ticks.
            with self._phase("tick.detect"):
                losses_j = jnp.asarray(losses_np)
                merge_mask_j = jnp.asarray(self._merge_mask)
                if self._post_merge:
                    common = self._common(self.det, losses_j, merge_mask_j)
                else:
                    common = jnp.float32(1.0)  # unused: no device rebases
                self.det, drifted, fresh = self._detect(
                    self.det, losses_j, jnp.asarray(self._post_merge),
                    merge_mask_j, jnp.asarray(served_eff), common,
                )
                jax.block_until_ready((self.det, drifted, fresh))

        with self._phase("tick.readback"):
            drifted_np = np.asarray(drifted)
            fresh_np = np.asarray(fresh)
            n_fresh = int(fresh_np.sum())
            self.detections_total += n_fresh
            for dev in np.flatnonzero(fresh_np):
                self.detections.append((t, int(dev)))

        with self._phase("tick.govern"):
            if self.config.gate_merges:
                mask = self.governor.participation(drifted_np, losses_np)
            else:
                mask = np.ones(d, bool)
            decision = self.governor.decide(t, mask, None, allow=allow_merge)

        merge_seconds = None
        tier_cost: TierCost | None = None
        if decision.merge:
            with self._phase("tick.merge") as merge:
                tier_cost = self.merger.merge(self.arena, mask)
            merge_seconds = merge.seconds
            self.merge_round += 1

        tick_seconds = time.perf_counter() - root.start
        if tel is not None:
            with self._phase("tick.telemetry"):
                self._record_telemetry(
                    t, losses_np, drifted_np, fresh_np, n_fresh, decision,
                    tier_cost, ingest.seconds, merge_seconds, tick_seconds,
                    served_eff, len(active),
                )

        self._post_merge = decision.merge
        if decision.merge:
            self._merge_mask = mask.copy()
        self.tick_no = t + 1
        full = served is None and len(active) == sched.n_cohorts
        return TickReport(
            tick=t, losses=losses_np, drifted=drifted_np,
            fresh_detections=fresh_np, decision=decision,
            merge_seconds=merge_seconds, ingest_seconds=ingest.seconds,
            served=None if full else served_eff,
        )

    # ---------------------------------------------------------- telemetry

    def _record_telemetry(
        self, t: int, losses: np.ndarray, drifted: np.ndarray,
        fresh: np.ndarray, n_fresh: int, decision: MergeDecision,
        tier_cost: TierCost | None, ingest_seconds: float,
        merge_seconds: float | None, tick_seconds: float,
        served: np.ndarray, n_active: int,
    ) -> None:
        tel = self.telemetry
        tel.ticks.inc()
        tel.tick_seconds.observe(tick_seconds)
        if n_fresh:
            tel.detections.inc(n_fresh)
        tel.quarantined.set(int(drifted.sum()))
        tel.arena_bytes.set(self.arena.nbytes)
        # residency = the streaming window: active cohorts' devices
        tel.arena_resident_devices.set(n_active * self.schedule.cohort_size)
        if decision.merge:
            tel.merge_rounds.inc()
            split = self.governor.round_bytes_by_precision(
                decision.participants, decision.fp_participants
            )
            for precision, nbytes in split.items():
                tel.merge_bytes.labels(precision=precision).inc(nbytes)
            if tier_cost is not None:
                tel.merge_tier_bytes.labels(tier="intra").inc(
                    tier_cost.bytes_tier1
                )
                tel.merge_tier_bytes.labels(tier="inter").inc(
                    tier_cost.bytes_tier2
                )
        live = losses[served] if not served.all() else losses
        if live.size == 0:
            live = losses
        rec = {
            "tick": t,
            "loss_mean": float(np.nanmean(live)) if live.size else float("nan"),
            "loss_max": float(np.nanmax(live)) if live.size else float("nan"),
            "quarantined": int(drifted.sum()),
            "fresh": np.flatnonzero(fresh).tolist() if n_fresh else [],
            "decision": {
                "merge": decision.merge, "reason": decision.reason,
                "participants": decision.participants,
                "round_bytes": decision.round_bytes,
            },
            "active_cohorts": n_active,
            "ingest_seconds": ingest_seconds,
            "merge_seconds": merge_seconds,
            "tick_seconds": tick_seconds,
        }
        if tier_cost is not None:
            rec["tier_bytes"] = {
                "intra": tier_cost.bytes_tier1,
                "inter": tier_cost.bytes_tier2,
            }
        tel.flight.record(rec)
        slo = tel.config.slo_tick_seconds
        if slo is not None and tick_seconds > slo:
            tel.slo_breaches.inc()
            tel.maybe_dump(
                t, "slo",
                extra={"tick_seconds": tick_seconds, "slo_seconds": slo},
            )

    def finalize_telemetry(self) -> dict | None:
        if self.telemetry is None:
            return None
        self.telemetry.close()
        return self.telemetry.summary()

    # ------------------------------------------------------------- driving

    def run(self, feed: TickFeed, *, ticks: int | None = None) -> list[TickReport]:
        """Drive the runtime over a feed (all of it by default)."""
        if ticks is not None and ticks > feed.n_ticks:
            logger.warning(
                "run(ticks=%d) exceeds the feed's %d ticks; truncating",
                ticks, feed.n_ticks,
            )
        n = feed.n_ticks if ticks is None else min(ticks, feed.n_ticks)
        return [self.tick(feed.tick_batch(t)) for t in range(n)]

    def assert_compile_once(self) -> None:
        """Hard check of the compile-once contract: every jit owned by
        the runtime (and its merger) has traced at most once. The soak
        benchmark calls this after the run — a second trace of the page
        ingest at 1M devices is a multi-second stall per COHORT."""
        sizes = {
            "ingest": self._ingest._cache_size(),
            "detect": self._detect._cache_size(),
            "common": self._common._cache_size(),
        }
        sizes.update(self.merger.jit_cache_sizes())
        bad = {k: v for k, v in sizes.items() if v > 1}
        if bad:
            raise AssertionError(f"jits traced more than once: {bad}")
