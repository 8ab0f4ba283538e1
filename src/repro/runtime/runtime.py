"""Resident streaming fleet runtime — the paper's deployment loop.

Converts the offline ``fleet_train_rounds`` batch simulator into an
event-driven serving system that keeps the whole fleet resident and
processes a stream of ticks:

1. **ingest** — every device scores its incoming tick batch under its
   CURRENT model (the drift signal: prediction loss on new data) and
   then trains on it with the paper's k=1 sequential updates, as one
   vmapped-scan jitted alongside step 2;
2. **detect** — the vectorized sequential drift detector
   (``repro.runtime.detector``) updates per-device EWMA/baseline state
   in the same compiled tick function;
3. **govern + merge** — between ticks, the merge governor
   (``repro.runtime.governor``) builds a participation mask (quarantine
   drifted devices, re-admit after re-convergence) and admits
   cooperative updates under the topology's comm-budget SLO; admitted
   merges run through the compile-once masked merge
   (``fleet_merge_masked`` / ``fleet_merge_masked_kernel``), optionally
   against STALE neighbor payloads from a published-version ring
   (``StalenessSchedule``), the async model the ROADMAP's serve-loop
   item called for;
4. **snapshot** — the resident fleet (model + detector + ledger, plus
   the payload ring when staleness is on) persists through
   ``CheckpointManager`` so a restart resumes mid-stream.

Every jitted function is owned by the runtime instance and is traced
exactly once for a given (fleet shape, batch, topology) — masks, tick
indices, and payload versions are all runtime operands.
``assert_compile_once()`` turns that property into a hard check the
soak benchmark enforces.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from collections import deque
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import CheckpointManager
from repro.core import UV, OSELMState
from repro.federated.selection import FleetMaskFn
from repro.fleet.faults import FaultInjector
from repro.fleet.fleet import (
    _masked_kernel_merge_from_w,
    _masked_merge_body,
    _quantized_merge_body,
    fleet_from_uv,
    fleet_merge_masked_kernel,
    fleet_to_uv,
)
from repro.fleet.quantize import init_residual, validate_precision
from repro.fleet.robust import (
    RobustConfig,
    finite_payload_mask,
    robust_merge_from_w,
)
from repro.fleet.sharded import (
    fleet_layout,
    halo_bytes,
    ingest_local,
    sharded_ingest,
    sharded_merge,
)
from repro.fleet.staleness import StalenessSchedule, _lagged_gather
from repro.fleet.topology import Topology
from repro.kernels.fleet_ingest import native_kernels
from repro.obs import TelemetryConfig, TelemetrySink, trace
from repro.runtime.detector import (
    DetectorConfig,
    detector_update,
    init_detector,
    quarantine_risk,
)
from repro.runtime.feed import TickFeed
from repro.runtime.governor import GovernorConfig, MergeDecision, MergeGovernor

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Static configuration of one resident fleet runtime.

    ``use_merge_kernel`` and ``use_ingest_kernel`` are off-chip test
    switches: on a TPU both kernel families always run (Mosaic), so the
    fields only choose, elsewhere, between plain XLA and the Pallas
    interpreter."""

    topology: Topology
    ridge: float = 1e-3
    detector: DetectorConfig = dataclasses.field(default_factory=DetectorConfig)
    governor: GovernorConfig = dataclasses.field(default_factory=GovernorConfig)
    gate_merges: bool = True          # False: no-quarantine baseline (everyone merges)
    staleness: StalenessSchedule | None = None
    use_merge_kernel: bool = False    # route merges through the Pallas family
                                      # (always on a TPU)
    payload_precision: str = "f32"    # merge wire format ("f32" | "f16" | "int8");
                                      # non-f32 runs the error-feedback codec with
                                      # the detector-gated precision policy:
                                      # quarantine-risk devices ship f32 payloads,
                                      # stable devices the quantized format
                                      # (repro.fleet.quantize / detector.quarantine_risk)
    use_ingest_kernel: bool = False   # fused tick ingest (repro.kernels.fleet_ingest;
                                      # always on a TPU)
    ingest_backend: str = "auto"      # "pallas" | "xla" | "auto" (TPU→pallas)
    snapshot_every: int | None = None
    snapshot_dir: str | Path | None = None
    snapshot_keep: int = 3
    robust: RobustConfig | None = None   # Byzantine-robust merge (clip/trim/score
                                         # + governor quarantine escalation); None
                                         # keeps the exact paper merge bit-for-bit
    faults: FaultInjector | None = None  # deterministic fault injection at the
                                         # payload boundary (repro.fleet.faults)
    telemetry: TelemetryConfig | None = None  # structured metrics + tracing +
                                              # crash flight recorder (repro.obs);
                                              # None = zero instrumentation
    detections_cap: int = 4096  # detection-event ring length — the full ledger
                                # of a months-long soak lives in the telemetry
                                # counters/flight ring, not an unbounded list


@dataclasses.dataclass(frozen=True)
class TickReport:
    """What one tick did — the runtime's observable event record."""

    tick: int
    losses: np.ndarray          # (D,) mean ae_score of the incoming batch
    drifted: np.ndarray         # (D,) quarantine flags after detection
    fresh_detections: np.ndarray  # (D,) flags that rose this tick
    decision: MergeDecision
    merge_seconds: float | None  # the admitted merge's tick.merge span (full
                                 # output pytree fenced), else None
    robust_scores: np.ndarray | None = None  # (D,) contribution-outlier scores
                                             # of an admitted robust merge round
    nonfinite_payloads: int = 0  # payloads rejected by the finite guard this tick
    ingest_seconds: float | None = None  # the tick.ingest span: fenced ingest
                                         # + detect (paged: every page too)
    served: np.ndarray | None = None  # (D,) devices whose batch rows carried
                                      # real (non-padding) samples this tick;
                                      # None = every row (the default path)


def _where_served(keep: jnp.ndarray, new, old):
    """Per-device select over a (D,)-leading pytree: devices with
    ``keep`` take the freshly-computed leaves, the rest keep their old
    state bit-for-bit (an un-served device must not train, and its
    detector must not observe, a padded batch row)."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(
            keep.reshape(keep.shape + (1,) * (n.ndim - 1)), n, o
        ),
        new, old,
    )


def ingest_detect_program(ingest, det_cfg: DetectorConfig):
    """The tick's ingest + detect: ``ingest(fleet, batch) -> (trained,
    losses)`` (per shard on a sharded fleet), then the detector over the
    whole fleet's losses, so the post-merge rebase median is fleet-wide
    on any layout."""

    def ingest_detect(fleet, det, batch, rebase, participants, served):
        trained, losses = ingest(fleet, batch)
        det_new, drifted, fresh = detector_update(
            det, losses, det_cfg, rebase=rebase, participants=participants
        )
        # un-served devices (padded rows of a partially-filled serving
        # window) keep model AND detector state — the served mask is a
        # traced operand, so partial windows never retrace; all-ones
        # served is bit-for-bit the unmasked path
        keep = served.astype(bool)
        fleet = _where_served(keep, trained, fleet)
        det = _where_served(keep, det_new, det)
        return fleet, det, losses, det.drifted, fresh & keep

    return ingest_detect


class FleetRuntime:
    """A live fleet: stacked OS-ELM states + detector bank + governor.

    A fleet laid out by device range over several chips (its leaves
    carry a ``NamedSharding`` over one mesh axis; ``fleet_layout``) runs
    the same tick per shard: each chip gets only its slice of the window,
    ingests its own devices (``sharded_ingest``) and merges them after a
    halo exchange or a psum (``sharded_merge``); the detector runs over
    the whole fleet."""

    def __init__(
        self,
        states: OSELMState,
        config: RuntimeConfig,
        *,
        policies: tuple[FleetMaskFn, ...] = (),
    ) -> None:
        n_devices = states.beta.shape[0]
        if config.topology.n_devices != n_devices:
            raise ValueError(
                f"topology is for {config.topology.n_devices} devices, "
                f"fleet has {n_devices}"
            )
        if config.staleness is not None and len(config.staleness.lags) != n_devices:
            raise ValueError("staleness schedule device count mismatch")
        validate_precision(config.payload_precision)
        if config.payload_precision != "f32" and config.staleness is not None:
            raise ValueError(
                "quantized payloads are not supported with the stale "
                "published-version ring yet (the ring stores exact payloads)"
            )
        hardened = config.robust is not None or config.faults is not None
        if hardened and config.staleness is not None:
            raise ValueError(
                "robust/fault-injected merges are not supported with the "
                "stale published-version ring (the ring replays un-guarded "
                "historical payloads)"
            )
        if hardened and config.payload_precision != "f32":
            raise ValueError(
                "robust/fault-injected merges require payload_precision='f32' "
                "(the quantized codec path has its own publish boundary)"
            )
        if config.faults is not None and config.faults.n_devices != n_devices:
            raise ValueError(
                f"fault injector is for {config.faults.n_devices} devices, "
                f"fleet has {n_devices}"
            )
        # a fleet laid out by device range over several chips runs its
        # tick per shard (sharded ingest, halo or psum merge)
        layout = fleet_layout(states)
        if layout is not None:
            unsupported = [name for name, on in (
                ("staleness", config.staleness is not None),
                ("robust", config.robust is not None),
                ("faults", config.faults is not None),
                ("payload_precision", config.payload_precision != "f32"),
                ("snapshot_dir", config.snapshot_dir is not None),
            ) if on]
            if unsupported:
                raise ValueError(
                    f"a fleet laid out over {layout[0].shape[layout[1]]} devices "
                    f"runs exact f32 merges of fresh payloads without snapshots; "
                    f"not supported there: {', '.join(unsupported)}"
                )
        self.states = states
        self.config = config
        self._by_device = self._replicated = None
        self.shards = 1
        if layout is not None:
            mesh, axis = layout
            self._by_device = NamedSharding(mesh, PartitionSpec(axis))
            self._replicated = NamedSharding(mesh, PartitionSpec())
            self.shards = mesh.shape[axis]
        self.det = self._put(init_detector(n_devices))
        # NB: on the stacked fleet pytree beta is (D, Ñ, m), so the
        # scalar-state n_hidden/n_out properties would read (D, Ñ)
        n_hidden, n_out = states.beta.shape[1], states.beta.shape[2]
        self.governor = MergeGovernor(
            config.topology, n_hidden, n_out, config.governor,
            policies=policies, payload_precision=config.payload_precision,
            robust=config.robust,
        )
        self.tick_no = 0
        self.merge_round = 0
        # bounded detection-event ring: recent (tick, device) flags for
        # delay accounting; detections_total keeps the lifetime count a
        # long soak would otherwise grow an unbounded list for
        self.detections: deque[tuple[int, int]] = deque(
            maxlen=config.detections_cap
        )
        self.detections_total = 0
        self.telemetry = (
            TelemetrySink(config.telemetry)
            if config.telemetry is not None else None
        )
        self._merge_operands = ()  # the sharded merge's cluster ids
        self._tick_inputs: np.ndarray | None = None  # last post-poison batch,
                                                     # carried for flight dumps
        self.ckpt = (
            CheckpointManager(config.snapshot_dir, keep=config.snapshot_keep)
            if config.snapshot_dir is not None else None
        )

        det_cfg = config.detector
        topology, ridge = config.topology, config.ridge
        # on a TPU the tick always runs the fused ingest and the
        # topology-merge kernels (Mosaic); elsewhere the flags choose
        native = native_kernels()
        use_merge_kernel = config.use_merge_kernel or native

        use_ingest_kernel = config.use_ingest_kernel or native
        if use_ingest_kernel:
            from repro.kernels.fleet_ingest import validate_shared_basis

            # the tick is jitted (tracers inside), so the kernel-ingest
            # shared-basis precondition is checked once, here, while the
            # fleet is concrete
            validate_shared_basis(states)
        if layout is not None:
            ingest = sharded_ingest(mesh, (axis,), kernel=use_ingest_kernel,
                                    backend=config.ingest_backend)
        else:
            # the fused ingest family computes the pre-train drift signal
            # and the k=1 window updates in ONE pass over the batch ((P, β)
            # resident across the window); without it the loss of the
            # incoming data under the current model is scored BEFORE
            # training (§3.4 / 2203.01077) — the same losses either way
            def ingest(fleet, batch):
                return ingest_local(fleet, batch, kernel=use_ingest_kernel,
                                    backend=config.ingest_backend)

        self._ingest_detect = self._jit(ingest_detect_program(ingest, det_cfg))
        # first tick after a merge: participants' bands rebase common-mode
        self._post_merge = False
        self._merge_mask = np.ones(n_devices, bool)
        self._all_served = np.ones(n_devices, bool)

        # error-feedback accumulator of the quantized merge path (None on
        # the exact-f32 path); advanced only on admitted merge rounds
        self._residual = (
            init_residual(states) if config.payload_precision != "f32" else None
        )
        if config.payload_precision != "f32":
            precision = config.payload_precision

            def merge_fresh(fleet, mask, fp_mask, residual):
                # stateful lossy merge: fp_mask (quarantine-risk) devices
                # publish exact f32, the rest the quantized wire format
                # with error feedback — all three masks/accumulators are
                # traced operands, so precision gating never retraces
                return _quantized_merge_body(
                    fleet, topology, residual, precision, ridge,
                    mask, fp_mask, use_merge_kernel, None,
                )
        elif layout is not None:
            merge_fresh, operands = sharded_merge(
                topology, mesh, (axis,), ridge, kernel=use_merge_kernel)
            self._merge_operands = tuple(self._put(o) for o in operands)
        elif use_merge_kernel:
            def merge_fresh(fleet, mask):
                return fleet_merge_masked_kernel(fleet, topology, mask, ridge=ridge)
        else:
            def merge_fresh(fleet, mask):
                return _masked_merge_body(fleet, topology, mask, ridge)

        self._merge_fresh = self._jit(merge_fresh)
        self._halo_bytes = halo_bytes(topology, self.shards, n_hidden, n_out)

        # ---- hardened merge boundary: faults in, robustness out ----
        # One compile-once closure owns the whole payload boundary of an
        # admitted round: extract w=[U|V], apply the tick's fault operands
        # (mult/noise/nonfin — identity when no fault is active, so clean
        # and attacked rounds share ONE trace), reject non-finite payloads
        # (the device publishes its last finite (U, V) instead), then merge
        # robustly (clip/trim/score) or naively (the degradation arm the
        # benchmark measures).
        self._merge_boundary = None
        self._last_good = None
        if hardened:
            robust_cfg = config.robust
            use_kernel = use_merge_kernel

            def merge_boundary(fleet, mask, receive, mult, noise, nonfin, last_good):
                uv = fleet_to_uv(fleet, ridge=ridge)
                n = uv.u.shape[1]
                w = jnp.concatenate([uv.u, uv.v], axis=2)
                w = w * mult[:, None, None] + noise
                w = jnp.where((nonfin == 1)[:, None, None], jnp.nan, w)
                w = jnp.where((nonfin == 2)[:, None, None], jnp.inf, w)
                finite = finite_payload_mask(w)
                if robust_cfg is None:
                    # naive arm: whatever the faults produced flows straight
                    # into the plain masked Eq. 8 sum — the baseline the
                    # robust arm is proven against
                    if use_kernel:
                        merged = _masked_kernel_merge_from_w(
                            fleet, topology, mask, w, ridge, None
                        )
                    else:
                        merged = _masked_merge_body(
                            fleet, topology, mask, ridge,
                            uv=UV(u=w[:, :, :n], v=w[:, :, n:]),
                        )
                    scores = jnp.zeros(mask.shape[0], jnp.float32)
                    return merged, last_good, scores, finite
                # finite-payload guard: a non-finite contribution is replaced
                # by that device's last published finite payload, so one
                # overflowing device never NaN-poisons the neighborhood sum
                w_pub = jnp.where(finite[:, None, None], w, last_good)
                new_last = jnp.where(finite[:, None, None], w, last_good)
                merged, scores = robust_merge_from_w(
                    fleet, topology, mask, w_pub, robust_cfg, ridge,
                    kernel=use_kernel, receive=receive,
                )
                return merged, new_last, scores, finite

            self._merge_boundary = jax.jit(merge_boundary)
            uv0 = jax.jit(lambda s: fleet_to_uv(s, ridge=ridge))(states)
            self._last_good = jnp.concatenate([uv0.u, uv0.v], axis=2)

        # ---- staleness-aware merge: published-payload version ring ----
        self._hist_u = self._hist_v = None
        if config.staleness is not None:
            lags = jnp.asarray(config.staleness.lags)
            n_hist = config.staleness.max_lag + 1
            m_off = jnp.asarray(topology.dense_matrix()) - jnp.eye(
                n_devices, dtype=jnp.float32
            )

            # NB: lagged merges mix via the dense m_off einsum (same
            # convention as fleet_train_async — each device needs a
            # DIFFERENT version of each neighbor's payload, which the
            # sparse Topology.mix paths cannot express). O(D²) per
            # merge round; prefer staleness=None at large D until a
            # banded lagged-gather kernel exists.
            def merge_stale(fleet, hist_u, hist_v, mask, r):
                fresh = fleet_to_uv(fleet, ridge=ridge)
                mf = mask.astype(fresh.u.dtype)
                # publish this round's payload (quarantined devices
                # publish too — peers just will not mix them in)
                hist_u = hist_u.at[r % n_hist].set(fresh.u)
                hist_v = hist_v.at[r % n_hist].set(fresh.v)
                stale_u = _lagged_gather(hist_u, lags, r) * mf[:, None, None]
                stale_v = _lagged_gather(hist_v, lags, r) * mf[:, None, None]
                merged = UV(
                    u=fresh.u + jnp.einsum("ij,j...->i...", m_off, stale_u),
                    v=fresh.v + jnp.einsum("ij,j...->i...", m_off, stale_v),
                )
                out = fleet_from_uv(fleet, merged, ridge=ridge)
                keep = (mf > 0)[:, None, None]
                out = fleet.replace(
                    beta=jnp.where(keep, out.beta, fleet.beta),
                    p=jnp.where(keep, out.p, fleet.p),
                )
                return out, hist_u, hist_v

            self._merge_stale = jax.jit(merge_stale)
            # version-0 backfill: until a device has published, peers see
            # its initial payload (same convention as fleet_train_async)
            uv0 = jax.jit(lambda s: fleet_to_uv(s, ridge=ridge))(states)
            self._hist_u = jnp.broadcast_to(uv0.u[None], (n_hist,) + uv0.u.shape)
            self._hist_v = jnp.broadcast_to(uv0.v[None], (n_hist,) + uv0.v.shape)

    @property
    def n_devices(self) -> int:
        return self.det.n_devices

    def _put(self, x, *, replicated: bool = False):
        """A host value on the device: on a sharded fleet each chip gets
        only its slice of a (D, ...) leading array (or, ``replicated``,
        all of a scalar)."""
        if self._by_device is None:
            return jax.tree_util.tree_map(jnp.asarray, x)
        return jax.device_put(x, self._replicated if replicated else self._by_device)

    def _jit(self, fn):
        """A tick program; on a sharded fleet every output stays laid out
        by device range, so the next tick's inputs hit the same trace."""
        if self._by_device is None:
            return jax.jit(fn)
        return jax.jit(fn, out_shardings=self._by_device)

    # ------------------------------------------------------------- tick loop

    def _phase(self, name: str, **attrs):
        """The span of one tick phase (``repro.obs.TICK_PHASES``); with
        telemetry on, its duration also lands in the phase histogram."""
        if self.telemetry is None:
            return trace.span(name, **attrs)
        return self.telemetry.phase(name, **attrs)

    def tick(
        self,
        batch: np.ndarray,
        *,
        served: np.ndarray | None = None,
        allow_merge: bool = True,
    ) -> TickReport:
        """Process one serving tick: ingest + detect, then govern and
        (maybe) merge between ticks, then (maybe) snapshot.

        ``served`` is the serving front-end's (D,) admission outcome:
        devices marked False carry padding in their batch row and keep
        their model/detector state untouched (all-ones — the default —
        is bit-for-bit the unmasked tick). ``allow_merge=False`` vetoes
        any merge this tick (the skip-merge degraded mode) while the
        governor's ledger keeps advancing. Both are per-tick operands
        of the compile-once tick function — never a retrace.

        The tick and its phases are program spans (``repro.obs.trace``)
        under the root span ``tick``, whose ``seq`` is the tick number.
        With telemetry configured an escaping exception dumps the
        flight ring (plus this tick's input batch) before propagating."""
        try:
            with trace.span("tick", seq=self.tick_no) as root:
                return self._tick(root, batch, served, allow_merge)
        except Exception:
            tel = self.telemetry
            if tel is not None:
                tel.maybe_dump(
                    self.tick_no, "exception", inputs=self._tick_inputs
                )
                tel.write_outputs()
            raise

    def _tick(
        self,
        root,
        batch: np.ndarray,
        served: np.ndarray | None = None,
        allow_merge: bool = True,
    ) -> TickReport:
        t = self.tick_no
        injector = self.config.faults
        batch = np.asarray(batch)
        d = self.n_devices
        if batch.ndim != 3 or batch.shape[0] != d:
            raise ValueError(
                f"tick batch must be (n_devices={d}, B, features); got "
                f"shape {batch.shape}"
            )
        if batch.shape[1] < 1:
            raise ValueError(
                "tick batch has zero samples per device (B=0) — an "
                "all-shed tick window carries no data to ingest; skip "
                "dispatching the tick, or pad the window and mark the "
                "padded devices via served=..."
            )
        if served is None:
            served_np = self._all_served
        else:
            served_np = np.asarray(served).astype(bool)
            if served_np.shape != (d,):
                raise ValueError(
                    f"served mask must be ({d},); got {served_np.shape}"
                )
        with self._phase("tick.poison"):
            if injector is not None:
                # data poisoning attacks through training itself, upstream
                # of the payload boundary (host-side, before jitted ingest)
                batch = injector.poison_batch(np.asarray(batch), t)
        # the post-poison batch is what reaches the model — the thing a
        # flight dump must carry for the failing tick to be replayable
        self._tick_inputs = batch

        # the window (and the tick's small operands) to the device,
        # fenced: nothing else is in flight at this point
        with self._phase("tick.put", shards=self.shards):
            operands = (
                self._put(batch), self._put(self._post_merge, replicated=True),
                self._put(self._merge_mask), self._put(served_np),
            )
            jax.block_until_ready(operands)

        with self._phase("tick.ingest", shards=self.shards) as ingest:
            self.states, self.det, losses, drifted, fresh = self._ingest_detect(
                self.states, self.det, *operands
            )
            jax.block_until_ready((self.states, self.det, losses))

        with self._phase("tick.readback"):
            losses_np = np.asarray(losses)
            drifted_np = np.asarray(drifted)
            fresh_np = np.asarray(fresh)
            n_fresh = int(fresh_np.sum())
            self.detections_total += n_fresh
            for dev in np.flatnonzero(fresh_np):
                self.detections.append((t, int(dev)))

        with self._phase("tick.govern"):
            # detector-gated precision policy: on candidate rounds of a
            # quantized runtime, quarantine-risk devices are priced (and
            # shipped) at f32 — computed host-side from the post-update
            # detector state, like the participation mask
            fp_mask = None
            if (
                self._residual is not None
                and (t + 1) % self.config.governor.merge_every == 0
            ):
                fp_mask = np.asarray(
                    quarantine_risk(self.det, self.config.detector)
                )
            if self.config.gate_merges:
                mask = self.governor.participation(drifted_np, losses_np)
            else:
                mask = np.ones(self.n_devices, bool)
            if injector is not None:
                # crashed devices are down for the window: no publish, no
                # download — regardless of gating mode
                mask = mask & ~injector.crash_mask(t)
            decision = self.governor.decide(t, mask, fp_mask, allow=allow_merge)

        merge_seconds = None
        robust_scores = None
        nonfinite = 0
        if decision.merge:
            with self._phase("tick.merge", halo_bytes=self._halo_bytes) as merge:
                mask_j = self._put(mask.astype(np.float32))
                if self._merge_boundary is not None:
                    shape = tuple(self._last_good.shape)
                    if injector is not None:
                        mult, noise, nonfin = injector.payload_ops(t, shape)
                    else:
                        mult = np.ones(shape[0], np.float32)
                        noise = np.zeros(shape, np.float32)
                        nonfin = np.zeros(shape[0], np.int32)
                    # robust-quarantined devices still DOWNLOAD the merged
                    # model (their payload is distrusted, they are not cut
                    # off) — unless drift-flagged or crashed this tick
                    receive = mask.astype(bool)
                    if self.config.robust is not None:
                        rq = self.governor.robust_quarantined & ~drifted_np.astype(bool)
                        if injector is not None:
                            rq = rq & ~injector.crash_mask(t)
                        receive = receive | rq
                    (self.states, self._last_good, scores_j, finite_j,
                     ) = self._merge_boundary(
                        self.states, mask_j, jnp.asarray(receive, jnp.float32),
                        jnp.asarray(mult), jnp.asarray(noise),
                        jnp.asarray(nonfin), self._last_good,
                    )
                    fence = (self.states, self._last_good, scores_j, finite_j)
                elif self.config.staleness is not None:
                    self.states, self._hist_u, self._hist_v = self._merge_stale(
                        self.states, self._hist_u, self._hist_v, mask_j,
                        jnp.int32(self.merge_round),
                    )
                    fence = (self.states, self._hist_u, self._hist_v)
                elif self._residual is not None:
                    self.states, self._residual = self._merge_fresh(
                        self.states, mask_j, jnp.asarray(fp_mask), self._residual
                    )
                    fence = (self.states, self._residual)
                else:
                    self.states = self._merge_fresh(
                        self.states, mask_j, *self._merge_operands)
                    fence = self.states
                # fence the FULL output pytree, not just states.beta — async
                # dispatch would otherwise bill unfinished ring/residual/score
                # work to whichever later phase synchronizes first
                jax.block_until_ready(fence)
            merge_seconds = merge.seconds
            if self._merge_boundary is not None:
                robust_scores = np.asarray(scores_j)
                nonfinite = int((~np.asarray(finite_j)).sum())
                if self.config.robust is not None:
                    self.governor.observe_robust(robust_scores)
            self.merge_round += 1

        # serving latency of THIS tick: ingest through merge; snapshots
        # amortize across the snapshot_every window and are timed as
        # their own phase below rather than folded into tick_seconds
        tick_seconds = time.perf_counter() - root.start
        if self.telemetry is not None:
            with self._phase("tick.telemetry"):
                self._record_telemetry(
                    t, batch, losses_np, drifted_np, fresh_np, n_fresh,
                    decision, ingest.seconds, merge_seconds, tick_seconds,
                    robust_scores, nonfinite, served_np,
                )

        self._post_merge = decision.merge
        if decision.merge:
            self._merge_mask = mask.copy()
        self.tick_no = t + 1
        if (
            self.ckpt is not None
            and self.config.snapshot_every
            and self.tick_no % self.config.snapshot_every == 0
        ):
            with self._phase("tick.snapshot"):
                self.snapshot()
        return TickReport(
            tick=t, losses=losses_np, drifted=drifted_np,
            fresh_detections=fresh_np, decision=decision,
            merge_seconds=merge_seconds, robust_scores=robust_scores,
            nonfinite_payloads=nonfinite, ingest_seconds=ingest.seconds,
            served=None if served is None else served_np,
        )

    def _record_telemetry(
        self, t: int, batch, losses: np.ndarray, drifted: np.ndarray,
        fresh: np.ndarray, n_fresh: int, decision: MergeDecision,
        ingest_seconds: float, merge_seconds: float | None,
        tick_seconds: float, robust_scores: np.ndarray | None, nonfinite: int,
        served: np.ndarray | None = None,
    ) -> None:
        """Fold one tick into the sink: counters/gauges/histograms, the
        flight-ring record, and the nonfinite/SLO dump triggers."""
        tel = self.telemetry
        cfg = self.config
        tel.ticks.inc()
        tel.tick_seconds.observe(tick_seconds)
        if n_fresh:
            tel.detections.inc(n_fresh)
        injector = cfg.faults
        faults = injector.active_faults(t) if injector is not None else []
        for kind, n in faults:
            tel.fault_events.labels(kind=kind).inc(n)
        n_quarantined = int(drifted.sum())
        tel.quarantined.set(n_quarantined)
        if cfg.robust is not None:
            tel.robust_quarantined.set(
                int(self.governor.robust_quarantined.sum())
            )

        # detector band dynamics over calibrated devices, in host numpy
        # (mirrors detector._sigma — the band the flags fire against);
        # sampled every band_sample_every ticks: the three detector-state
        # device reads per observation are the costliest line in the
        # telemetry path and the band moves slowly
        det_cfg = cfg.detector
        if t % tel.config.band_sample_every == 0:
            calibrated = np.asarray(self.det.count) >= det_cfg.warmup
            if calibrated.any():
                mean = np.asarray(self.det.mean)
                sigma = np.maximum(
                    np.sqrt(np.maximum(np.asarray(self.det.var), 0.0))
                    + det_cfg.min_sigma,
                    det_cfg.rel_sigma * mean,
                )
                tel.band_width.observe_many(det_cfg.k_sigma * sigma[calibrated])
                tel.loss_ratio.observe_many(
                    losses[calibrated]
                    / np.maximum(mean[calibrated], det_cfg.min_sigma)
                )

        if decision.merge:
            tel.merge_rounds.inc()
            tel.merge_halo_bytes.inc(self._halo_bytes)
            split = self.governor.round_bytes_by_precision(
                decision.participants, decision.fp_participants
            )
            for precision, nbytes in split.items():
                tel.merge_bytes.labels(precision=precision).inc(nbytes)
            if self._residual is not None:
                tel.ef_residual_norm.set(float(jnp.sqrt(sum(
                    jnp.sum(jnp.square(leaf))
                    for leaf in jax.tree_util.tree_leaves(self._residual)
                ))))
        if nonfinite:
            tel.nonfinite.inc(nonfinite)

        # partially-served windows: padded rows scored padding data, so
        # loss stats aggregate over served devices only
        live = losses if served is None or served.all() else losses[served]
        if live.size == 0:
            live = losses
        rec = {
            "tick": t,
            "loss_mean": float(live.mean()),
            "loss_max": float(live.max()),
            "quarantined": n_quarantined,
            "fresh": np.flatnonzero(fresh).tolist() if n_fresh else [],
            "decision": {
                "merge": decision.merge, "reason": decision.reason,
                "participants": decision.participants,
                "round_bytes": decision.round_bytes,
                "fp_participants": decision.fp_participants,
            },
            "ingest_seconds": ingest_seconds,
            "merge_seconds": merge_seconds,
            "tick_seconds": tick_seconds,
            "nonfinite_payloads": nonfinite,
        }
        if served is not None and not served.all():
            rec["n_served"] = int(served.sum())
        if losses.shape[0] <= 512:
            # small fleets: full loss vector + quarantine set, the replay
            # probe's comparison surface; large fleets keep the ring lean
            # (tolist() already widens f32 to exact Python floats)
            rec["losses"] = losses.tolist()
            rec["drifted"] = (
                np.flatnonzero(drifted).tolist() if n_quarantined else []
            )
        if faults:
            rec["faults"] = faults
        if robust_scores is not None and robust_scores.size:
            top = np.argsort(robust_scores)[::-1][:5]
            rec["robust_outliers"] = [
                (int(d), float(robust_scores[d])) for d in top
            ]
        tel.flight.record(rec)

        if nonfinite:
            tel.maybe_dump(
                t, "nonfinite", inputs=batch,
                extra={"nonfinite_payloads": nonfinite},
            )
        slo = tel.config.slo_tick_seconds
        if slo is not None and tick_seconds > slo:
            tel.slo_breaches.inc()
            tel.maybe_dump(
                t, "slo", inputs=batch,
                extra={"tick_seconds": tick_seconds, "slo_seconds": slo},
            )

    def finalize_telemetry(self) -> dict | None:
        """Flush the sink's outputs (trace + exposition, dir mode) and
        return the end-of-run summary; None when telemetry is off."""
        if self.telemetry is None:
            return None
        self.telemetry.close()
        return self.telemetry.summary()

    def run(self, feed: TickFeed, *, ticks: int | None = None) -> list[TickReport]:
        """Drive the runtime over a feed (all of it by default). Asking
        for more ticks than the feed holds is a truncation, not an
        error: the runtime processes what exists and says so."""
        if ticks is not None and ticks > feed.n_ticks:
            logger.warning(
                "run(ticks=%d) exceeds the feed's %d ticks; truncating",
                ticks, feed.n_ticks,
            )
        n = feed.n_ticks if ticks is None else min(ticks, feed.n_ticks)
        return [self.tick(feed.tick_batch(t)) for t in range(n)]

    def warmup(self, batch_size: int) -> None:
        """Compile the tick-loop jits before live traffic arrives.

        Dispatches the ingest and merge traces on all-zero operands
        with ``served`` all-False and a zero participation mask, then
        DISCARDS every output — no model, detector, governor, or
        telemetry state changes. Without this, the first real tick
        pays multi-second XLA compilation, which a serving watchdog
        cannot tell apart from a stalled runtime. Uses the same shapes
        as real ticks, so compile-once still holds afterwards."""
        d = self.n_devices
        f = int(self.states.params.alpha.shape[1])
        batch = self._put(np.zeros((d, batch_size, f), np.float32))
        none_served = self._put(np.zeros(d, bool))
        out = self._ingest_detect(
            self.states, self.det, batch, self._put(False, replicated=True),
            self._put(np.ones(d, bool)), none_served,
        )
        jax.block_until_ready(out)
        mask = self._put(np.zeros(d, np.float32))
        if self._merge_boundary is not None:
            shape = tuple(self._last_good.shape)
            out = self._merge_boundary(
                self.states, mask, mask,
                jnp.ones(shape[0], jnp.float32),
                jnp.zeros(shape, jnp.float32),
                jnp.zeros(shape[0], jnp.int32),
                self._last_good,
            )
        elif self.config.staleness is not None:
            out = self._merge_stale(
                self.states, self._hist_u, self._hist_v, mask, jnp.int32(0)
            )
        elif self._residual is not None:
            out = self._merge_fresh(
                self.states, mask, jnp.zeros(d, bool), self._residual
            )
        else:
            out = self._merge_fresh(self.states, mask, *self._merge_operands)
        jax.block_until_ready(out)

    # ------------------------------------------------------------ durability

    def _snapshot_tree(self):
        tree = {
            "states": self.states,
            "det": self.det,
            # host-side counters stay numpy (int64-exact through npz)
            "tick": np.asarray(self.tick_no, np.int64),
            "merge_round": np.asarray(self.merge_round, np.int64),
            "gov": np.asarray(
                [self.governor.state.ticks, self.governor.state.merges,
                 self.governor.state.bytes_spent,
                 self.governor.state.deferred_budget,
                 self.governor.state.deferred_participants,
                 self.governor.state.deferred_degraded], np.int64,
            ),
            # (N, 2) detection-event ring; restored whole (shape may
            # differ from the template's — the numpy path allows that)
            "detections": np.asarray(self.detections, np.int64).reshape(-1, 2),
            "detections_total": np.asarray(self.detections_total, np.int64),
            "post_merge": np.asarray(self._post_merge, np.int32),
            "merge_mask": np.asarray(self._merge_mask, np.int32),
        }
        if self.telemetry is not None:
            # registry counters + flight ring as a JSON blob in a uint8
            # leaf: npz round-trips bytes exactly, and the variable
            # length rides the same shape-free numpy restore path the
            # detection ledger uses — so a kill/restore resumes with
            # CONTINUOUS metrics instead of a zeroed registry
            tree["telemetry"] = np.frombuffer(
                self.telemetry.state_bytes(), np.uint8
            )
        if self._hist_u is not None:
            tree["hist_u"] = self._hist_u
            tree["hist_v"] = self._hist_v
        if self._residual is not None:
            tree["residual"] = self._residual
        if self._last_good is not None:
            tree["last_good"] = self._last_good
            tree["robust_gov"] = np.stack([
                self.governor.robust_strikes,
                self.governor.robust_calm,
                self.governor.robust_quarantined.astype(np.int64),
            ])
        return tree

    def snapshot(self) -> Path:
        if self.ckpt is None:
            raise RuntimeError("runtime has no snapshot_dir configured")
        return self.ckpt.save(self.tick_no, self._snapshot_tree())

    def restore(self, step: int | None = None) -> int:
        """Load the latest (or a specific) snapshot into the live
        runtime; returns the restored tick number."""
        if self.ckpt is None:
            raise RuntimeError("runtime has no snapshot_dir configured")
        tree, _ = self.ckpt.restore(self._snapshot_tree(), step)
        self.states = tree["states"]
        self.det = tree["det"]
        self.tick_no = int(tree["tick"])
        self.merge_round = int(tree["merge_round"])
        gov = np.asarray(tree["gov"])
        self.governor.state.ticks = int(gov[0])
        self.governor.state.merges = int(gov[1])
        self.governor.state.bytes_spent = int(gov[2])
        self.governor.state.deferred_budget = int(gov[3])
        self.governor.state.deferred_participants = int(gov[4])
        # PR-8-era snapshots carry a 5-element gov ledger (no
        # deferred_degraded); restoring one resets only that counter
        self.governor.state.deferred_degraded = (
            int(gov[5]) if gov.shape[0] > 5 else 0
        )
        self.detections = deque(
            ((int(t), int(d)) for t, d in np.asarray(tree["detections"])),
            maxlen=self.config.detections_cap,
        )
        self.detections_total = int(tree["detections_total"])
        if self.telemetry is not None:
            self.telemetry.load_state_bytes(
                np.asarray(tree["telemetry"], np.uint8).tobytes()
            )
        self._post_merge = bool(int(tree["post_merge"]))
        self._merge_mask = np.asarray(tree["merge_mask"]).astype(bool)
        if self._hist_u is not None:
            self._hist_u = tree["hist_u"]
            self._hist_v = tree["hist_v"]
        if self._residual is not None:
            self._residual = tree["residual"]
        if self._last_good is not None:
            self._last_good = tree["last_good"]
            rg = np.asarray(tree["robust_gov"])
            self.governor.robust_strikes = rg[0].astype(np.int64)
            self.governor.robust_calm = rg[1].astype(np.int64)
            self.governor.robust_quarantined = rg[2].astype(bool)
        return self.tick_no

    # ---------------------------------------------------------- compile-once

    def jit_cache_sizes(self) -> dict[str, int]:
        sizes = {"ingest_detect": self._ingest_detect._cache_size()}
        if self._merge_boundary is not None:
            # the hardened boundary owns all merges; _merge_fresh is never
            # dispatched (its 0-entry cache would read as a false miss)
            sizes["merge_boundary"] = self._merge_boundary._cache_size()
        else:
            sizes["merge_fresh"] = self._merge_fresh._cache_size()
        if self.config.staleness is not None:
            sizes["merge_stale"] = self._merge_stale._cache_size()
        return sizes

    def assert_compile_once(self) -> dict[str, int]:
        """The tick loop must be a compile-once path: every runtime-owned
        jitted function has at most one trace. Raises on retracing."""
        sizes = self.jit_cache_sizes()
        bad = {k: v for k, v in sizes.items() if v > 1}
        if bad:
            raise AssertionError(f"per-tick retracing detected: {bad}")
        return sizes
