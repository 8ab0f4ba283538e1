"""Byzantine-robust cooperative merges — bounded-influence Eq. 8.

The paper's cooperative update sums raw (U, V) sufficient statistics,
so a single hostile or broken device corrupts every participant's model
in one round. This module makes the merge path survive such devices
with three composable defenses, all operating on the stacked published
payload ``w = [U | V]`` at the same boundary the wire codec uses:

- **norm clipping** (``payload_clip``) — each device's payload is
  scaled by ``min(1, clip_norm / ‖w‖_F)``, bounding the magnitude any
  one contribution can inject;
- **coordinate-wise trimmed reduction** (``RobustConfig.trim``) — each
  neighborhood sum drops the ``trim`` smallest and largest
  participating values per coordinate and rescales the mean of the
  rest back to sum units (``trim=0`` IS the plain masked merge,
  bit-for-bit). With ≤ ``trim`` adversaries per neighborhood the
  merged coordinate stays within the honest participants' range;
- **contribution-outlier scores** (``payload_outlier_scores``) — the
  Frobenius distance of each device's clipped payload from the
  participant coordinate-wise median, normalized by the participant
  median distance. Honest devices score ≈1; Byzantine payloads score
  orders of magnitude higher. The runtime feeds these to the governor
  next to the drift detector for quarantine escalation with hysteresis
  re-admission (``MergeGovernor.observe_robust``).

Topology dispatch mirrors ``_masked_merge_body``: segment topologies
(star / hierarchical, plus every fully-connected equivalence class)
trim per cluster via the Pallas ``robust_segment_sum_mix`` kernel or
its XLA oracle, the open ring trims per ±hops neighborhood via an
explicit gather, and hierarchical head exchange sums the per-cluster
robust estimates. Custom dense masks with ``trim > 0`` are rejected
(no neighborhood structure to trim within) — clip + scores still work
there through the ``trim=0`` path.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import UV, OSELMState
from repro.fleet.fleet import (
    _bcast,
    _masked_kernel_merge_from_w,
    _masked_merge_body,
    _solve_uv,
    fleet_from_uv,
    fleet_to_uv,
)
from repro.fleet.topology import Topology

__all__ = [
    "RobustConfig",
    "finite_payload_mask",
    "fleet_merge_robust",
    "payload_clip",
    "payload_outlier_scores",
    "robust_merge_from_w",
]

_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class RobustConfig:
    """Robust-merge knobs (frozen/hashable — a static jit argument).

    ``trim``/``clip_norm`` shape the aggregation itself;
    ``score_threshold``..``readmit_after`` drive the governor's
    robust-score quarantine escalation (strike/calm hysteresis — see
    ``MergeGovernor.observe_robust``)."""

    clip_norm: float | None = None  # Frobenius clip of w=[U|V]; None = off
    trim: int = 1                   # values trimmed per side per coordinate
    score_threshold: float = 4.0    # outlier score that counts a strike
    score_readmit: float = 2.0      # score below which calm ticks accrue
    escalate_after: int = 2         # consecutive hot rounds → quarantine
    readmit_after: int = 3          # consecutive calm rounds → re-admission

    def __post_init__(self) -> None:
        if self.trim < 0:
            raise ValueError(f"need trim >= 0, got {self.trim}")
        if self.clip_norm is not None and self.clip_norm <= 0:
            raise ValueError(f"need clip_norm > 0, got {self.clip_norm}")
        if self.score_readmit > self.score_threshold:
            raise ValueError(
                "hysteresis needs score_readmit <= score_threshold "
                f"({self.score_readmit} > {self.score_threshold})"
            )
        if self.escalate_after < 1 or self.readmit_after < 1:
            raise ValueError("escalate_after and readmit_after must be >= 1")


def payload_clip(
    w: jnp.ndarray, clip_norm: float | None
) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """Per-device Frobenius norm clip of the stacked payload (D, R, C).

    Returns ``(clipped, scale)``; ``scale`` is the (D,) multiplier fed
    to the fused kernel path, or None when clipping is off (the
    payload passes through untouched — bit-for-bit, no ×1.0)."""
    if clip_norm is None:
        return w, None
    norms = jnp.sqrt(jnp.sum(w * w, axis=(1, 2)))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norms, _EPS))
    return w * scale[:, None, None], scale


def finite_payload_mask(w: jnp.ndarray) -> jnp.ndarray:
    """(D,) bool — devices whose whole published payload is finite."""
    return jnp.isfinite(w).all(axis=(1, 2))


def payload_outlier_scores(w: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Contribution-outlier score per device (computed for ALL devices,
    so a quarantined device's return to normalcy is still observable).

    ``dist_d = ‖w_d − median_participants(w)‖_F`` (coordinate-wise
    median over participating devices), normalized by the participant
    median distance: honest payloads score ≈1, Byzantine ones ≫1. The
    score is what the governor escalates on — it identifies WHO is
    hostile, while clip/trim bound WHAT a hostile payload can do in
    the meantime."""
    mf = jnp.asarray(mask) > 0
    sentinel = jnp.where(mf[:, None, None], w, jnp.nan)
    med = jnp.nanmedian(sentinel, axis=0)                      # (R, C)
    dist = jnp.sqrt(jnp.nansum((w - med[None]) ** 2, axis=(1, 2)))
    ref = jnp.nanmedian(jnp.where(mf, dist, jnp.nan))
    scores = dist / (jnp.maximum(ref, 0.0) + _EPS)
    return jnp.where(jnp.isfinite(scores), scores, 0.0)


def _segment_counts(mask: jnp.ndarray, cids: jnp.ndarray, n_clusters: int):
    return jax.ops.segment_sum(mask, cids, num_segments=n_clusters)


def _repair_u(est: jnp.ndarray, n: int, eps: float = 1e-4) -> jnp.ndarray:
    """PSD-repair the U half of trimmed estimates ``est`` (..., R, n+m).

    A coordinate-wise trimmed mean of PSD Gram matrices is not itself
    guaranteed PSD — with few participants per neighborhood (open ring
    ±1 hop: three values, trim=1 keeps the coordinate median) the
    estimate can go indefinite and blow up the (U+εI)⁻¹ solve.
    Symmetrize and clamp the spectrum to a small positive floor; for
    honest, well-populated neighborhoods the eigenvalues are already
    comfortably positive and this is an f32-rounding no-op. Only the
    trim > 0 paths pay this (trim=0 stays bit-for-bit exact)."""
    u = est[..., :, :n]
    u = 0.5 * (u + jnp.swapaxes(u, -1, -2))
    evals, evecs = jnp.linalg.eigh(u)
    floor = eps * jnp.maximum(jnp.abs(evals).max(axis=-1, keepdims=True), 1.0)
    evals = jnp.maximum(evals, floor)
    u = jnp.einsum("...ij,...j,...kj->...ik", evecs, evals, evecs)
    return jnp.concatenate([u, est[..., :, n:]], axis=-1)


def _robust_segments(
    w: jnp.ndarray,
    scale: jnp.ndarray | None,
    cluster_ids,
    mask: jnp.ndarray,
    n_clusters: int,
    trim: int,
    kernel: bool,
    interpret: bool,
) -> jnp.ndarray:
    """Per-cluster robust sum estimates (n_clusters, R, C)."""
    from repro.kernels.robust_merge import (
        robust_segment_combine,
        robust_segment_sum_mix,
        robust_segment_sum_xla,
    )

    d = w.shape[0]
    sc = jnp.ones(d, jnp.float32) if scale is None else scale
    if kernel:
        tot, lo, hi = robust_segment_sum_mix(
            w, cluster_ids, mask, sc, n_clusters, trim, interpret=interpret
        )
    else:
        tot, lo, hi = robust_segment_sum_xla(w, cluster_ids, mask, sc, n_clusters, trim)
    counts = _segment_counts(mask, jnp.asarray(cluster_ids, jnp.int32), n_clusters)
    return robust_segment_combine(tot, lo, hi, counts, trim)


def _robust_banded(
    w: jnp.ndarray, mask: jnp.ndarray, hops: int, trim: int
) -> jnp.ndarray:
    """Per-device robust neighborhood estimates on the open ring: an
    explicit (D, 2·hops+1) neighbor gather, trimmed over the offset
    axis. A ±hops band with ≤ 2·trim participants cannot be trimmed
    and falls back to its plain masked sum (same combine guard as the
    segment path)."""
    d = w.shape[0]
    idx = (jnp.arange(d)[:, None] + jnp.arange(-hops, hops + 1)[None, :]) % d
    vals = w[idx]                                   # (D, n_off, R, C)
    mm = mask[idx]                                  # (D, n_off)
    live = (mm > 0)[:, :, None, None]
    tot = jnp.sum(jnp.where(live, vals, 0.0), axis=1)
    counts = mm.sum(1)
    n_off = 2 * hops + 1
    k = min(trim, n_off)
    lo = jnp.sort(jnp.where(live, vals, jnp.inf), axis=1)[:, :k]
    hi = jnp.sort(jnp.where(live, vals, -jnp.inf), axis=1)[:, n_off - k:]
    lo = jnp.where(jnp.isfinite(lo), lo, 0.0).sum(1)
    hi = jnp.where(jnp.isfinite(hi), hi, 0.0).sum(1)
    live_n = (counts - 2.0 * trim)[:, None, None]
    trimmed = (tot - lo - hi) / jnp.maximum(live_n, 1.0) * counts[:, None, None]
    return jnp.where(live_n >= 1.0, trimmed, tot)


def robust_merge_from_w(
    states: OSELMState,
    topology: Topology,
    mask: jnp.ndarray,
    w: jnp.ndarray,
    cfg: RobustConfig,
    ridge: float,
    *,
    kernel: bool = False,
    interpret: bool | None = None,
    receive: jnp.ndarray | None = None,
) -> tuple[OSELMState, jnp.ndarray]:
    """Robust participation-masked merge of published payloads ``w``
    (finite — the runtime's finite guard runs upstream). Returns
    ``(merged_states, outlier_scores)``; non-participants keep their
    own (P, β) exactly like ``_masked_merge_body``, unless ``receive``
    widens the download set (a robust-quarantined device's payload is
    distrusted, but it still receives the fleet model — that is what
    lets its published payload re-converge and earn re-admission)."""
    n = states.p.shape[-1]
    n_dev = topology.n_devices
    mf = jnp.asarray(mask).astype(w.dtype)
    w_clip, scale = payload_clip(w, cfg.clip_norm)
    scores = payload_outlier_scores(w_clip, mf)

    if cfg.trim == 0:
        # no trimming: the clipped payload goes through the EXACT masked
        # merge paths (with clipping off this is bit-for-bit
        # fleet_merge_masked — same arrays, same summation order)
        if kernel:
            return _masked_kernel_merge_from_w(
                states, topology, mf, w_clip, ridge, interpret, receive=receive
            ), scores
        uv = UV(u=w_clip[:, :, :n], v=w_clip[:, :, n:])
        return _masked_merge_body(
            states, topology, mf, ridge, uv=uv, receive=receive
        ), scores

    if topology.kind == "segment":
        # per-cluster trim; the kernel path feeds raw payload + clip
        # scale so clipping happens inside the streaming segment-sum
        est = _robust_segments(
            w if kernel else w_clip, scale if kernel else None,
            topology.cluster_ids, mf, topology.n_clusters, cfg.trim,
            kernel, interpret,
        )
        est = _repair_u(est, n)
        if topology.head_exchange:
            # heads exchange their cluster-level ROBUST estimates — the
            # attacker is trimmed inside its own cluster before the
            # global sum ever sees its contribution
            total = est.sum(0)
            p, beta = _solve_uv(total[:, :n], total[:, n:], ridge)
            merged = states.replace(beta=_bcast(beta, n_dev), p=_bcast(p, n_dev))
        else:
            cids = jnp.asarray(topology.cluster_ids)
            pc, betac = jax.vmap(partial(_solve_uv, ridge=ridge))(
                est[:, :, :n], est[:, :, n:]
            )
            merged = states.replace(beta=betac[cids], p=pc[cids])
    elif topology.is_fully_connected:
        # closed ring / all-ones dense mask: one global segment
        est = _robust_segments(
            w if kernel else w_clip, scale if kernel else None,
            np.zeros(n_dev, np.int32), mf, 1, cfg.trim, kernel, interpret,
        )[0]
        est = _repair_u(est, n)
        p, beta = _solve_uv(est[:, :n], est[:, n:], ridge)
        merged = states.replace(beta=_bcast(beta, n_dev), p=_bcast(p, n_dev))
    elif topology.kind == "banded":
        est = _repair_u(_robust_banded(w_clip, mf, topology.hops, cfg.trim), n)
        merged = fleet_from_uv(
            states, UV(u=est[:, :, :n], v=est[:, :, n:]), ridge=ridge
        )
    else:
        raise NotImplementedError(
            "trimmed robust merges need neighborhood structure (segment/"
            "banded/fully-connected); a custom dense mask has none — use "
            f"trim=0 with clipping + outlier scores instead (topology "
            f"{topology.name!r}, trim={cfg.trim})"
        )

    kf = mf if receive is None else jnp.asarray(receive).astype(mf.dtype)
    keep = (kf > 0)[:, None, None]
    return states.replace(
        beta=jnp.where(keep, merged.beta, states.beta),
        p=jnp.where(keep, merged.p, states.p),
    ), scores


@partial(
    jax.jit, static_argnames=("topology", "config", "ridge", "kernel", "interpret")
)
def fleet_merge_robust(
    states: OSELMState,
    topology: Topology,
    *,
    config: RobustConfig,
    mask: jnp.ndarray | None = None,
    ridge: float = 0.0,
    kernel: bool = False,
    interpret: bool | None = None,
) -> tuple[OSELMState, jnp.ndarray]:
    """``fleet_merge_masked`` with bounded Byzantine influence: clip,
    trim, score. Returns ``(merged_states, outlier_scores)``.

    ``config=RobustConfig(trim=0, clip_norm=None)`` reproduces
    ``fleet_merge_masked`` bit-for-bit (the property the robustness
    tests lock). The runtime composes the same body with its fault
    boundary and finite-payload guard (``FleetRuntime``)."""
    uv = fleet_to_uv(states, ridge=ridge)
    w = jnp.concatenate([uv.u, uv.v], axis=2)
    if mask is None:
        mask = jnp.ones(topology.n_devices, jnp.float32)
    return robust_merge_from_w(
        states, topology, jnp.asarray(mask), w, config, ridge,
        kernel=kernel, interpret=interpret,
    )
