"""Fleet-scale OS-ELM federation as one stacked pytree.

Hundreds-to-thousands of virtual edge devices are simulated in a single
process: the whole fleet is ONE ``OSELMState`` whose leaves carry a
leading device axis (like ``DetectorBank``), trained with
``vmap``-over-devices of ``scan``-over-stream, and merged with
topology-aware neighbor sums over the stacked (U, V) axis
(``repro.fleet.topology``).

This is exact simulation, not approximation: each virtual device runs
the paper's k=1 sequential update on its own non-IID stream, and the
cooperative update applies Eq. 8 restricted to the topology's neighbor
set. An all-to-all topology reproduces `cooperative_update` /
`mesh_cooperative_update` bit-for-bit (up to f32 summation order).

The merge is structure-aware end to end: sparse topologies never form
the D×D mixing matrix (``Topology.mix``), and the §4.2 step-5 solve
runs once per *equivalence class* of merged models — one solve for a
fully-connected merge, one per cluster for isolated hierarchical
clusters, per device only when the neighbor sets genuinely differ
(ring). ``fleet_merge_kernel`` runs the same dispatch through the
Pallas kernel family in ``repro.kernels.topology_merge``, including the
fully fused banded mix+solve. ``fleet_train_rounds`` is a single
compile-once ``lax.scan`` over round chunks (buffers donated on
accelerator backends), not a retracing Python loop.

API sketch::

    fleet = init_fleet(key, n_devices=256, n_features=225, n_hidden=32,
                       x_init=init_chunks, ridge=1e-3)
    fleet = fleet_train(fleet, streams)              # (D, T, n) streams
    fleet = fleet_merge(fleet, ring(256, hops=2), ridge=1e-3)
    scores = fleet_score(fleet, x_eval)              # (D, k) anomaly scores
"""
from __future__ import annotations

import logging
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    UV,
    OSELMState,
    ae_score,
    from_uv,
    init_oselm,
    init_slfn,
    oselm_step_k1,
    to_uv,
)
from repro.core.elm import invert_u, solve_beta
from repro.fleet.quantize import apply_codec, quantize_roundtrip
from repro.fleet.topology import Topology

log = logging.getLogger(__name__)


def init_fleet(
    key: jax.Array,
    n_devices: int,
    n_features: int,
    n_hidden: int,
    x_init: jnp.ndarray,
    *,
    activation: str = "sigmoid",
    ridge: float = 0.0,
    forget: float = 1.0,
) -> OSELMState:
    """Initialize ``n_devices`` OS-ELM autoencoders as one stacked state.

    Every device gets the SAME random SLFN basis (α, b) — Eq. 8 only
    sums meaningfully when all devices map inputs through an identical
    hidden layer (the paper's devices share the random basis; see the
    shared ``init_slfn`` in ``benchmarks.mesh_merge`` and the shared key
    in ``examples/federated_fleet.py``). Per-device state differs only
    through the Eq. 13 init chunks: ``x_init`` is (D, n_init,
    n_features), each device's own non-IID boot data.
    """
    if n_hidden >= n_features:
        raise ValueError(f"autoencoder needs a bottleneck: Ñ={n_hidden} >= n={n_features}")
    params = init_slfn(key, n_features, n_hidden)

    def one(x0: jnp.ndarray) -> OSELMState:
        return init_oselm(
            params, x0, x0, activation=activation, ridge=ridge, forget=forget
        )

    return jax.vmap(one)(jnp.asarray(x_init))


def _fleet_train(states: OSELMState, streams: jnp.ndarray) -> OSELMState:
    def train_one(st: OSELMState, xs: jnp.ndarray) -> OSELMState:
        def step(s, x):
            return oselm_step_k1(s, x, x), None

        out, _ = jax.lax.scan(step, st, xs)
        return out

    return jax.vmap(train_one)(states, streams)


def _warn_ingest_padding(steps: int, backend: str, caller: str) -> None:
    """Log (at trace time — once per compiled shape) when the fused
    ingest lowering pads the sample window. Padded slots are masked to
    exact identity steps (they never update P/β), so results are
    unchanged; the warning only surfaces the wasted slots."""
    from repro.kernels.fleet_ingest import ingest_padding, resolve_backend

    backend = resolve_backend(backend)
    pallas_pad, xla_pad = ingest_padding(steps)
    pad = xla_pad if backend == "xla" else pallas_pad
    if pad:
        log.warning(
            "%s: kernel ingest pads the %d-sample window with %d masked "
            "identity slots (tile/block alignment) — results are exact, "
            "but %d slots per device per window are wasted work",
            caller, steps, pad, pad,
        )


@partial(jax.jit, static_argnames=("kernel", "backend", "interpret"))
def fleet_train(
    states: OSELMState,
    streams: jnp.ndarray,
    *,
    kernel: bool = False,
    backend: str = "auto",
    interpret: bool | None = None,
) -> OSELMState:
    """Every device sequentially trains (k=1 autoencoder steps) on its
    own stream. ``streams``: (D, T, n_features).

    ``kernel=True`` routes the whole window through the fused ingest
    family (``repro.kernels.fleet_ingest``) — the Pallas VMEM-resident
    kernel on TPU, its fused-XLA lowering elsewhere (``backend=`` to
    force one) — mirroring ``fleet_merge_kernel``'s dispatch. The
    kernel path requires the fleet-shared SLFN basis ``init_fleet``
    provides; this function is itself jitted, so the shared-basis
    precondition is validated at the concrete entry points
    (``fleet_ingest`` called directly, ``fleet_train_rounds``,
    ``FleetRuntime``) rather than here."""
    streams = jnp.asarray(streams)
    if kernel:
        from repro.kernels.fleet_ingest import fleet_ingest

        _warn_ingest_padding(streams.shape[1], backend, "fleet_train")
        states, _ = fleet_ingest(
            states, streams, backend=backend, interpret=interpret
        )
        return states
    return _fleet_train(states, streams)


def fleet_to_uv(states: OSELMState, *, ridge: float = 0.0) -> UV:
    """Eq. 15 per device: stacked UV with u (D, Ñ, Ñ), v (D, Ñ, m)."""
    return jax.vmap(partial(to_uv, ridge=ridge))(states)


def fleet_from_uv(
    states: OSELMState, uv: UV, *, ridge: float = 0.0, nonfinite: str = "error"
) -> OSELMState:
    """§4.2 step 5 per device: recover (P, β) from each device's merged
    (U, V).

    A non-finite (U, V) — one NaN payload in an Eq. 8 sum — would
    silently poison the recovered (P, β) of every device it merged
    into. ``nonfinite="error"`` (default) raises a ValueError naming
    the bad devices when the payloads are concrete (inside a jit trace
    the check is skipped — guard at the boundary instead, as
    ``FleetRuntime`` does); ``"repair"`` replaces a bad device's (U, V)
    with (I, 0), resetting it to an untrained-but-solvable state."""
    if nonfinite not in ("error", "repair"):
        raise ValueError(f"nonfinite must be 'error' or 'repair', got {nonfinite!r}")
    ok = jnp.isfinite(uv.u).all(axis=(1, 2)) & jnp.isfinite(uv.v).all(axis=(1, 2))
    if nonfinite == "repair":
        eye = jnp.eye(uv.u.shape[-1], dtype=uv.u.dtype)
        uv = UV(
            u=jnp.where(ok[:, None, None], uv.u, eye[None]),
            v=jnp.where(ok[:, None, None], uv.v, jnp.zeros_like(uv.v)),
        )
    else:
        # traced (inside a jit): the eager check is not possible here
        ok_np = None if isinstance(ok, jax.core.Tracer) else np.asarray(ok)
        if ok_np is not None and not ok_np.all():
            raise ValueError(
                "non-finite merged (U, V) for devices "
                f"{np.flatnonzero(~ok_np).tolist()} — a corrupt payload "
                "reached the §4.2 solve; reject it upstream "
                "(repro.fleet.robust.finite_payload_mask) or pass "
                "nonfinite='repair' to reset those devices to (I, 0)"
            )
    return jax.vmap(partial(from_uv, ridge=ridge))(states, uv)


def _solve_uv(u: jnp.ndarray, v: jnp.ndarray, ridge: float, nonfinite: str = "error"):
    """One §4.2 step-5 solve: P = (U+εI)⁻¹, β = (U+εI)⁻¹V.

    Same non-finite guard as ``fleet_from_uv``, for the single-matrix
    solves of the fully-connected/cluster merge paths (eager calls
    fail loudly; traced/vmapped calls skip the check)."""
    ok = jnp.isfinite(u).all() & jnp.isfinite(v).all()
    if nonfinite == "repair":
        u = jnp.where(ok, u, jnp.eye(u.shape[-1], dtype=u.dtype))
        v = jnp.where(ok, v, jnp.zeros_like(v))
    else:
        if not isinstance(ok, jax.core.Tracer) and not bool(ok):
            raise ValueError(
                "non-finite (U, V) reached the §4.2 solve — reject the "
                "corrupt payload upstream or pass nonfinite='repair'"
            )
    return invert_u(u, ridge=ridge), solve_beta(u, v, ridge=ridge)


def _bcast(x: jnp.ndarray, n_devices: int) -> jnp.ndarray:
    return jnp.broadcast_to(x[None], (n_devices,) + x.shape)


def _codec_uv(states: OSELMState, precision: str, ridge: float) -> UV:
    """The lossy wire view of a fleet's (U, V) payloads: pack, one-shot
    quantize→dequantize round-trip at ``precision`` (no feedback state —
    the stateful path is ``fleet_merge_quantized``), unpack."""
    uv = fleet_to_uv(states, ridge=ridge)
    n = uv.u.shape[1]
    w = quantize_roundtrip(jnp.concatenate([uv.u, uv.v], axis=2), precision)
    return UV(u=w[:, :, :n], v=w[:, :, n:])


def _merge_body(
    states: OSELMState, topology: Topology, ridge: float, uv: UV | None = None
) -> OSELMState:
    """Structure-aware Eq. 8 merge: mix sparsely, then solve once per
    equivalence class of merged (U, V) — fully-connected merges produce
    one global model (1 solve, broadcast), isolated clusters one model
    per cluster (C solves, gather), and only genuinely per-device
    neighbor sets (open ring, custom dense masks) pay D solves.

    ``uv`` optionally injects pre-codec'd payloads (the quantized wire
    view) in place of the exact ``fleet_to_uv`` extraction."""
    if uv is None:
        uv = fleet_to_uv(states, ridge=ridge)
    n_dev = topology.n_devices

    if topology.kind == "segment":
        cids = jnp.asarray(topology.cluster_ids)
        su = jax.ops.segment_sum(uv.u, cids, num_segments=topology.n_clusters)
        sv = jax.ops.segment_sum(uv.v, cids, num_segments=topology.n_clusters)
        if topology.head_exchange:
            p, beta = _solve_uv(su.sum(0), sv.sum(0), ridge)
            return states.replace(beta=_bcast(beta, n_dev), p=_bcast(p, n_dev))
        pc, betac = jax.vmap(partial(_solve_uv, ridge=ridge))(su, sv)
        return states.replace(beta=betac[cids], p=pc[cids])

    if topology.is_fully_connected:  # closed ring / all-ones dense mask
        p, beta = _solve_uv(uv.u.sum(0), uv.v.sum(0), ridge)
        return states.replace(beta=_bcast(beta, n_dev), p=_bcast(p, n_dev))

    mixed = UV(u=topology.mix(uv.u), v=topology.mix(uv.v))
    return fleet_from_uv(states, mixed, ridge=ridge)


@partial(jax.jit, static_argnames=("topology", "ridge", "payload_precision"))
def fleet_merge(
    states: OSELMState,
    topology: Topology,
    *,
    ridge: float = 0.0,
    payload_precision: str = "f32",
) -> OSELMState:
    """Topology-aware cooperative update: each device's merged (U, V) is
    the Eq. 8 sum over its neighbor set (self included).

    ``payload_precision`` selects the wire format of the exchanged
    payloads ("f32" exact, "f16"/"int8" block-quantized one-shot —
    see ``repro.fleet.quantize``; the error-feedback stateful variant
    is ``fleet_merge_quantized``)."""
    uv = (None if payload_precision == "f32"
          else _codec_uv(states, payload_precision, ridge))
    return _merge_body(states, topology, ridge, uv=uv)


def _kernel_merge_from_w(
    states: OSELMState,
    topology: Topology,
    w: jnp.ndarray,
    ridge: float,
    interpret: bool,
) -> OSELMState:
    """Kernel-family merge of a pre-packed stacked payload ``w = [U | V]``
    (possibly codec'd): the dispatch half of ``fleet_merge_kernel``."""
    from repro.kernels.topology_merge import (
        banded_merge_solve,
        dense_mix,
        from_uv_solve,
        segment_sum_mix,
    )

    n = states.p.shape[-1]
    n_dev = topology.n_devices

    if topology.kind == "banded" and not topology.band_closed:
        p, beta = banded_merge_solve(w, topology.hops, ridge=ridge, interpret=interpret)
        return states.replace(beta=beta, p=p)

    if topology.kind == "segment":
        sums = segment_sum_mix(
            w, topology.cluster_ids, topology.n_clusters, interpret=interpret
        )
        if topology.head_exchange:
            total = sums.sum(0, keepdims=True)
            p, beta = from_uv_solve(
                total[:, :, :n], total[:, :, n:], ridge=ridge, interpret=interpret
            )
            return states.replace(
                beta=_bcast(beta[0], n_dev), p=_bcast(p[0], n_dev)
            )
        cids = jnp.asarray(topology.cluster_ids)
        pc, betac = from_uv_solve(
            sums[:, :, :n], sums[:, :, n:], ridge=ridge, interpret=interpret
        )
        return states.replace(beta=betac[cids], p=pc[cids])

    if topology.is_fully_connected:  # closed ring / all-ones dense mask
        total = w.sum(0, keepdims=True)
        p, beta = from_uv_solve(
            total[:, :, :n], total[:, :, n:], ridge=ridge, interpret=interpret
        )
        return states.replace(beta=_bcast(beta[0], n_dev), p=_bcast(p[0], n_dev))

    mixed = dense_mix(w, topology.dense_matrix(), interpret=interpret)
    p, beta = from_uv_solve(
        mixed[:, :, :n], mixed[:, :, n:], ridge=ridge, interpret=interpret
    )
    return states.replace(beta=beta, p=p)


@partial(jax.jit, static_argnames=("topology", "ridge", "interpret", "payload_precision"))
def fleet_merge_kernel(
    states: OSELMState,
    topology: Topology,
    *,
    ridge: float = 0.0,
    interpret: bool | None = None,
    payload_precision: str = "f32",
) -> OSELMState:
    """``fleet_merge`` on the Pallas merge-kernel family: the stacked
    [U | V] payload is mixed by the sparsity-aware kernels and solved by
    the fused Gauss-Jordan kernel (``repro.kernels.topology_merge``);
    on the open ring the mix and solve are ONE kernel, so merged (U, V)
    never round-trips through HBM. The platform picks the lowering
    (Mosaic on TPU, the interpreter elsewhere). ``payload_precision`` applies
    the one-shot wire codec (f16 cast or the fused Pallas
    ``quantize_pack`` for int8) before the mix."""
    uv = fleet_to_uv(states, ridge=ridge)
    w = jnp.concatenate([uv.u, uv.v], axis=2)  # stacked [U | V] payloads
    if payload_precision == "int8":
        from repro.fleet.quantize import dequantize_tiles
        from repro.kernels.quantize_pack import quantize_pack

        codes, scales, _ = quantize_pack(uv.u, uv.v, interpret=interpret)
        w = dequantize_tiles(codes, scales)
    elif payload_precision != "f32":
        w = quantize_roundtrip(w, payload_precision)
    return _kernel_merge_from_w(states, topology, w, ridge, interpret)


def _masked_merge_body(
    states: OSELMState,
    topology: Topology,
    mask: jnp.ndarray,
    ridge: float,
    uv: UV | None = None,
    receive: jnp.ndarray | None = None,
) -> OSELMState:
    """Participation-masked Eq. 8 merge. ``mask`` is a traced (D,) 0/1
    vector: devices with mask 0 neither contribute their (U, V) to any
    neighbor's sum nor receive the merged model (they keep their own
    (P, β) untouched). Because the mask is a runtime operand, gating a
    device in or out between rounds never retraces the merge. ``uv``
    optionally injects pre-codec'd payloads. ``receive`` optionally
    widens the set of devices that DOWNLOAD the merged model beyond the
    contributors (robust quarantine distrusts a device's payload while
    still serving it the fleet model); None keeps the symmetric
    contribute-and-receive semantics."""
    if uv is None:
        uv = fleet_to_uv(states, ridge=ridge)
    mf = mask.astype(uv.u.dtype)
    wu = uv.u * mf[:, None, None]
    wv = uv.v * mf[:, None, None]
    n_dev = topology.n_devices

    if topology.kind == "segment":
        cids = jnp.asarray(topology.cluster_ids)
        su = jax.ops.segment_sum(wu, cids, num_segments=topology.n_clusters)
        sv = jax.ops.segment_sum(wv, cids, num_segments=topology.n_clusters)
        if topology.head_exchange:
            p, beta = _solve_uv(su.sum(0), sv.sum(0), ridge)
            merged = states.replace(beta=_bcast(beta, n_dev), p=_bcast(p, n_dev))
        else:
            pc, betac = jax.vmap(partial(_solve_uv, ridge=ridge))(su, sv)
            merged = states.replace(beta=betac[cids], p=pc[cids])
    elif topology.is_fully_connected:
        p, beta = _solve_uv(wu.sum(0), wv.sum(0), ridge)
        merged = states.replace(beta=_bcast(beta, n_dev), p=_bcast(p, n_dev))
    else:
        mixed = UV(u=topology.mix(wu), v=topology.mix(wv))
        merged = fleet_from_uv(states, mixed, ridge=ridge)

    kf = mf if receive is None else receive.astype(mf.dtype)
    keep = (kf > 0)[:, None, None]
    return states.replace(
        beta=jnp.where(keep, merged.beta, states.beta),
        p=jnp.where(keep, merged.p, states.p),
    )


@partial(jax.jit, static_argnames=("topology", "ridge", "payload_precision"))
def fleet_merge_masked(
    states: OSELMState,
    topology: Topology,
    mask: jnp.ndarray,
    *,
    ridge: float = 0.0,
    payload_precision: str = "f32",
) -> OSELMState:
    """``fleet_merge`` with a runtime participation mask — the merge
    governor's quarantine primitive (drifted devices are masked out of
    the topology without recompiling). An all-ones mask reproduces
    ``fleet_merge`` exactly. Use ``ridge > 0`` so a cluster whose
    members are all quarantined still solves a well-posed (discarded)
    system. ``payload_precision`` applies the one-shot wire codec to
    the participating payloads."""
    uv = (None if payload_precision == "f32"
          else _codec_uv(states, payload_precision, ridge))
    return _masked_merge_body(states, topology, jnp.asarray(mask), ridge, uv=uv)


def _masked_kernel_merge_from_w(
    states: OSELMState,
    topology: Topology,
    mask: jnp.ndarray,
    w: jnp.ndarray,
    ridge: float,
    interpret: bool,
    receive: jnp.ndarray | None = None,
) -> OSELMState:
    """Kernel-family masked merge of a pre-packed (possibly codec'd)
    stacked payload ``w``: the dispatch half of
    ``fleet_merge_masked_kernel``. ``receive`` widens the download set
    exactly as in ``_masked_merge_body``."""
    from repro.kernels.topology_merge import (
        banded_merge_solve,
        dense_mix,
        from_uv_solve,
        masked_segment_sum_mix,
    )

    n = states.p.shape[-1]
    n_dev = topology.n_devices
    mf = mask.astype(w.dtype)

    if topology.kind == "segment":
        sums = masked_segment_sum_mix(
            w, topology.cluster_ids, mf, topology.n_clusters, interpret=interpret
        )
        if topology.head_exchange:
            total = sums.sum(0, keepdims=True)
            p, beta = from_uv_solve(
                total[:, :, :n], total[:, :, n:], ridge=ridge, interpret=interpret
            )
            merged = states.replace(
                beta=_bcast(beta[0], n_dev), p=_bcast(p[0], n_dev)
            )
        else:
            cids = jnp.asarray(topology.cluster_ids)
            pc, betac = from_uv_solve(
                sums[:, :, :n], sums[:, :, n:], ridge=ridge, interpret=interpret
            )
            merged = states.replace(beta=betac[cids], p=pc[cids])
    else:
        wm = w * mf[:, None, None]
        if topology.kind == "banded" and not topology.band_closed:
            p, beta = banded_merge_solve(
                wm, topology.hops, ridge=ridge, interpret=interpret
            )
            merged = states.replace(beta=beta, p=p)
        elif topology.is_fully_connected:
            total = wm.sum(0, keepdims=True)
            p, beta = from_uv_solve(
                total[:, :, :n], total[:, :, n:], ridge=ridge, interpret=interpret
            )
            merged = states.replace(beta=_bcast(beta[0], n_dev), p=_bcast(p[0], n_dev))
        else:
            mixed = dense_mix(wm, topology.dense_matrix(), interpret=interpret)
            p, beta = from_uv_solve(
                mixed[:, :, :n], mixed[:, :, n:], ridge=ridge, interpret=interpret
            )
            merged = states.replace(beta=beta, p=p)

    kf = mf if receive is None else receive.astype(mf.dtype)
    keep = (kf > 0)[:, None, None]
    return states.replace(
        beta=jnp.where(keep, merged.beta, states.beta),
        p=jnp.where(keep, merged.p, states.p),
    )


@partial(jax.jit, static_argnames=("topology", "ridge", "interpret", "payload_precision"))
def fleet_merge_masked_kernel(
    states: OSELMState,
    topology: Topology,
    mask: jnp.ndarray,
    *,
    ridge: float = 0.0,
    interpret: bool | None = None,
    payload_precision: str = "f32",
) -> OSELMState:
    """``fleet_merge_masked`` through the Pallas merge-kernel family:
    segment topologies gate participation *inside* the segment-sum
    kernel (``masked_segment_sum_mix``, scalar-prefetched mask — the
    masked payload stack never exists in HBM); banded/dense paths fold
    the mask into the payload before the existing kernels.
    ``payload_precision`` applies the one-shot wire codec (fused Pallas
    ``quantize_pack`` for int8) before the mix."""
    uv = fleet_to_uv(states, ridge=ridge)
    w = jnp.concatenate([uv.u, uv.v], axis=2)  # stacked [U | V] payloads
    if payload_precision == "int8":
        from repro.fleet.quantize import dequantize_tiles
        from repro.kernels.quantize_pack import quantize_pack

        codes, scales, _ = quantize_pack(uv.u, uv.v, interpret=interpret)
        w = dequantize_tiles(codes, scales)
    elif payload_precision != "f32":
        w = quantize_roundtrip(w, payload_precision)
    return _masked_kernel_merge_from_w(
        states, topology, jnp.asarray(mask), w, ridge, interpret
    )


def _quantized_merge_body(
    states: OSELMState,
    topology: Topology,
    residual: jnp.ndarray | None,
    payload_precision: str,
    ridge: float,
    mask: jnp.ndarray | None,
    fp_mask: jnp.ndarray | None,
    kernel: bool,
    interpret: bool,
) -> tuple[OSELMState, jnp.ndarray | None]:
    """Unjitted body of ``fleet_merge_quantized`` (the runtime embeds it
    in its own compile-once tick closures)."""
    uv = fleet_to_uv(states, ridge=ridge)
    n = uv.u.shape[1]
    w = jnp.concatenate([uv.u, uv.v], axis=2)
    roundtrip = None
    if kernel and payload_precision == "int8":
        from repro.fleet.quantize import dequantize_tiles
        from repro.kernels.quantize_pack import quantize_pack

        codes, scales, _ = quantize_pack(uv.u, uv.v, residual, interpret=interpret)
        roundtrip = dequantize_tiles(codes, scales)
    w_pub, new_resid = apply_codec(
        w, payload_precision, residual=residual, fp_mask=fp_mask,
        participate=mask, roundtrip=roundtrip,
    )
    uv_pub = UV(u=w_pub[:, :, :n], v=w_pub[:, :, n:])
    if mask is None:
        merged = (
            _kernel_merge_from_w(states, topology, w_pub, ridge, interpret)
            if kernel else _merge_body(states, topology, ridge, uv=uv_pub)
        )
    else:
        mask = jnp.asarray(mask)
        merged = (
            _masked_kernel_merge_from_w(states, topology, mask, w_pub, ridge, interpret)
            if kernel else _masked_merge_body(states, topology, mask, ridge, uv=uv_pub)
        )
    return merged, new_resid


@partial(
    jax.jit,
    static_argnames=("topology", "payload_precision", "ridge", "kernel", "interpret"),
)
def fleet_merge_quantized(
    states: OSELMState,
    topology: Topology,
    *,
    residual: jnp.ndarray | None,
    payload_precision: str = "int8",
    ridge: float = 0.0,
    mask: jnp.ndarray | None = None,
    fp_mask: jnp.ndarray | None = None,
    kernel: bool = False,
    interpret: bool | None = None,
) -> tuple[OSELMState, jnp.ndarray | None]:
    """The stateful lossy merge round: every participating device
    publishes its error-feedback-compensated quantized payload, the
    topology mixes the published payloads (self-contribution included —
    all members of a fully-connected merge still receive the identical
    model, preserving the solve-per-equivalence-class structure), and
    the per-device residual accumulators advance. Returns
    ``(merged_states, residual')``.

    - ``residual`` — (D, Ñ, Ñ+m) error-feedback backlog from
      ``repro.fleet.quantize.init_residual`` (None = zero backlog,
      one-shot semantics).
    - ``mask`` — optional participation gate, exactly
      ``fleet_merge_masked``; non-participants' residuals are untouched.
    - ``fp_mask`` — optional per-device full-precision override
      (quarantine-risk devices ship exact f32; their residual clears).
    - ``kernel=True`` — publish through the fused Pallas
      ``quantize_pack`` (int8) and merge through the kernel family.
    """
    return _quantized_merge_body(
        states, topology, residual, payload_precision, ridge, mask, fp_mask,
        kernel, interpret,
    )


@jax.jit
def fleet_score(states: OSELMState, x: jnp.ndarray) -> jnp.ndarray:
    """Per-device anomaly scores on shared eval data: (D, k)."""
    return jax.vmap(lambda s: ae_score(s, x))(states)


def _rounds_body(
    states: OSELMState,
    chunks: jnp.ndarray,
    topology: Topology,
    ridge: float,
    kernel: bool,
    backend: str,
    interpret: bool | None,
) -> OSELMState:
    """Compile-once train→merge loop: one ``lax.scan`` over the round
    axis (chunks: (rounds, D, per, feat)) instead of a Python loop
    re-dispatching two jits per round. ``kernel=True`` ingests each
    round's chunk through the fused ``fleet_ingest`` family."""

    def body(st, chunk):
        if kernel:
            from repro.kernels.fleet_ingest import fleet_ingest

            st, _ = fleet_ingest(st, chunk, backend=backend, interpret=interpret)
        else:
            st = _fleet_train(st, chunk)
        return _merge_body(st, topology, ridge), None

    out, _ = jax.lax.scan(body, states, chunks)
    return out


_ROUNDS_STATIC = ("topology", "ridge", "kernel", "backend", "interpret")
_ROUNDS_SCAN = {
    # donate=True lets XLA reuse the input fleet buffers for the scan
    # carry (the CPU backend ignores donation, with a warning)
    True: partial(
        jax.jit, static_argnames=_ROUNDS_STATIC, donate_argnums=(0,)
    )(_rounds_body),
    False: partial(jax.jit, static_argnames=_ROUNDS_STATIC)(_rounds_body),
}


def fleet_train_rounds(
    states: OSELMState,
    streams: jnp.ndarray,
    topology: Topology,
    *,
    rounds: int,
    ridge: float = 0.0,
    donate: bool = False,
    kernel: bool = False,
    backend: str = "auto",
    interpret: bool | None = None,
) -> OSELMState:
    """The paper's "repeatedly applied to synchronize" mode at fleet
    scale: chunk each stream into ``rounds`` pieces, train a chunk,
    merge over the topology, repeat. Synchronous (no staleness) —
    see ``repro.fleet.staleness.fleet_train_async`` for the lagged
    variant.

    The whole loop is a single jitted ``lax.scan`` (compiled once per
    (shape, topology)). Pass ``donate=True`` on accelerator backends to
    donate the input state buffers to the scan — halves peak state
    memory, but invalidates the caller's ``states`` pytree.

    .. note:: When ``steps % rounds != 0`` the tail ``steps % rounds``
       samples of every stream are **dropped** (each round trains on
       exactly ``steps // rounds`` samples); a warning is logged when
       that truncation is nonzero. With ``kernel=True`` a second
       warning fires when the fused lowering pads each round's
       ``steps // rounds``-sample window up to its tile/block size —
       padded slots are masked identity steps (they never update P/β),
       so that padding is wasted work, never a result change.
    """
    streams = jnp.asarray(streams)
    n_dev, steps, feat = streams.shape
    if not 1 <= rounds <= steps:
        raise ValueError(f"need 1 <= rounds={rounds} <= steps={steps}")
    per = steps // rounds
    tail = steps - rounds * per
    if tail:
        log.warning(
            "fleet_train_rounds: steps=%d not divisible by rounds=%d — "
            "dropping the tail %d samples of every device stream",
            steps, rounds, tail,
        )
    if kernel:
        from repro.kernels.fleet_ingest import validate_shared_basis

        validate_shared_basis(states)  # concrete here, pre-jit
        _warn_ingest_padding(per, backend, "fleet_train_rounds")
    chunks = (
        streams[:, : rounds * per]
        .reshape(n_dev, rounds, per, feat)
        .transpose(1, 0, 2, 3)
    )
    return _ROUNDS_SCAN[donate](
        states, chunks, topology, ridge, kernel, backend, interpret
    )


def device_state(states: OSELMState, idx: int) -> OSELMState:
    """Slice one device's state out of the stacked fleet."""
    return jax.tree.map(lambda l: l[idx], states)
