"""Pallas kernel family for the fleet topology merge (Eq. 8 at scale).

A fleet merge round is

    mix:   w'ᵢ = Σⱼ Mᵢⱼ wⱼ        w = [U | V]   (D, Ñ, Ñ+m) stacked
    solve: Pᵢ = (U'ᵢ + εI)⁻¹,  βᵢ = (U'ᵢ + εI)⁻¹ V'ᵢ

Materializing the dense D×D mask M costs O(D²·Ñ·(Ñ+m)) FLOPs and HBM
traffic even when the topology touches ≤2·hops neighbors. This module
exploits the adjacency structure directly:

- ``banded_mix`` — ring gossip: grid over (device, col-tile, offset);
  the BlockSpec index map gathers only the ``(d+o) mod D`` neighbor
  blocks (≤ 2·hops+1 of them) per device tile, accumulating in f32
  VMEM. M is never formed.
- ``segment_sum_mix`` / ``segment_broadcast`` — star/hierarchical:
  a scalar-prefetched cluster-id array drives the output (resp. input)
  BlockSpec index map, so member payloads accumulate straight into
  their cluster's aggregate block (contiguous cluster ids → the output
  block is revisited consecutively, the supported TPU accumulation
  pattern) and the merged aggregate is gathered back without a D×D
  product.
- ``dense_mix`` — tiled M @ flatten(w) fallback for arbitrary masks
  (the all-to-all baseline), f32 VMEM accumulation over device tiles.
- ``from_uv_solve`` — the batched §4.2 step-5 solve: one fused
  Gauss-Jordan sweep per device over the augmented system
  [U+εI | I | V] → [I | P | β] held entirely in VMEM/registers, giving
  P and β in a single kernel (no separate Cholesky factor + two
  triangular solves round-tripping through HBM). U+εI is SPD, so no
  pivoting is needed; the sweep runs on the unit-diagonal scaled
  system, without which f32 loses 3-4 digits (``_gj_solve``).
- ``banded_merge_solve`` — the fully fused hot path: neighbor-sum AND
  solve in ONE kernel invocation per device, so the merged (U, V)
  never exists in HBM at all.

All paths lower via Mosaic on TPU and run in the Pallas interpreter
elsewhere (``resolve_interpret``), same pattern as ``kernels/ops.py``.
``fleet_merge_kernel`` dispatches a whole stacked ``OSELMState`` merge;
cluster-level solving (one solve per cluster instead of per device when
the merged models are provably identical) comes from
``repro.fleet.fleet.fleet_merge`` which shares the same dispatch logic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.fleet.topology import Topology
from repro.kernels.fleet_ingest import resolve_interpret

__all__ = [
    "banded_mix",
    "segment_sum_mix",
    "masked_segment_sum_mix",
    "segment_broadcast",
    "dense_mix",
    "topology_mix",
    "from_uv_solve",
    "banded_merge_solve",
]

_LANE = 128
_SUBLANE = 8


def _pad_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _pad_stacked(x: jnp.ndarray) -> tuple[jnp.ndarray, int, int]:
    """Pad a stacked (D, R, C) array to f32 tile boundaries (R→8k, C→128k)."""
    d, r, c = x.shape
    rp, cp = _pad_up(r, _SUBLANE), _pad_up(c, _LANE)
    return jnp.pad(x, ((0, 0), (0, rp - r), (0, cp - c))), rp, cp


# --------------------------------------------------------------- banded (ring)


def _banded_kernel(x_ref, o_ref, acc_ref, *, n_off: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += x_ref[...].astype(jnp.float32)

    @pl.when(pl.program_id(2) == n_off - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("hops", "interpret"))
def banded_mix(x: jnp.ndarray, hops: int, *, interpret: bool | None = None) -> jnp.ndarray:
    """Circular banded neighbor-sum out[d] = Σ_{o=-hops..hops} x[(d+o)%D].

    Requires 2·hops+1 ≤ D (a wider band double-counts; that regime is
    all-to-all and is a plain sum + broadcast)."""
    d, r, c = x.shape
    if 2 * hops + 1 > d:
        raise ValueError(f"band 2*{hops}+1 exceeds n_devices={d}; use a full-sum path")
    xp, rp, cp = _pad_stacked(x)
    n_off = 2 * hops + 1
    out = pl.pallas_call(
        functools.partial(_banded_kernel, n_off=n_off),
        grid=(d, cp // _LANE, n_off),
        in_specs=[
            pl.BlockSpec((1, rp, _LANE), lambda i, j, o: ((i + o - hops) % d, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, rp, _LANE), lambda i, j, o: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((d, rp, cp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, rp, _LANE), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xp)
    return out[:, :r, :c]


# ------------------------------------------------------- segment (star / hier)


def _segsum_kernel(cids_ref, x_ref, o_ref, acc_ref):
    d = pl.program_id(1)
    first = jnp.logical_or(
        d == 0, cids_ref[d] != cids_ref[jnp.maximum(d - 1, 0)]
    )

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += x_ref[...].astype(jnp.float32)
    # the out block tracks this device's segment: the last write of a
    # contiguous cluster run is the completed aggregate
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def segment_sum_mix(
    x: jnp.ndarray, cluster_ids, n_clusters: int, *, interpret: bool | None = None
) -> jnp.ndarray:
    """Cluster aggregates (C, R, Cc) = segment_sum(x, cluster_ids).

    ``cluster_ids`` must be sorted (contiguous clusters, as built by
    ``fleet.topology.hierarchical``) so each output block is revisited
    consecutively — the accumulator resets on every id change, so
    unsorted ids would silently drop earlier partials. Validated here
    on the host array."""
    cids = np.asarray(cluster_ids)
    if not np.all(np.diff(cids) >= 0):
        raise ValueError(
            "segment_sum_mix needs sorted (contiguous-cluster) cluster_ids; "
            "sort the device axis by cluster first"
        )
    return _segment_sum_mix_call(x, jnp.asarray(cids, jnp.int32), n_clusters,
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_clusters", "interpret"))
def _segment_sum_mix_call(
    x: jnp.ndarray, cluster_ids: jnp.ndarray, n_clusters: int, *, interpret: bool | None = None
) -> jnp.ndarray:
    d, r, c = x.shape
    xp, rp, cp = _pad_stacked(x)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(cp // _LANE, d),
        in_specs=[pl.BlockSpec((1, rp, _LANE), lambda j, i, cids: (i, 0, j))],
        out_specs=pl.BlockSpec((1, rp, _LANE), lambda j, i, cids: (cids[i], 0, j)),
        scratch_shapes=[pltpu.VMEM((1, rp, _LANE), jnp.float32)],
    )
    out = pl.pallas_call(
        _segsum_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_clusters, rp, cp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(cluster_ids, jnp.int32), xp)
    return out[:, :r, :c]


def _masked_segsum_kernel(cids_ref, mask_ref, x_ref, o_ref, acc_ref):
    d = pl.program_id(1)
    first = jnp.logical_or(
        d == 0, cids_ref[d] != cids_ref[jnp.maximum(d - 1, 0)]
    )

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the participation gate is applied in VMEM as the block streams in:
    # a quarantined device's payload is read but contributes 0, so the
    # masked stack is never materialized in HBM and the mask can change
    # every merge round without retracing (it is a traced operand)
    acc_ref[...] += x_ref[...].astype(jnp.float32) * mask_ref[d].astype(jnp.float32)
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def masked_segment_sum_mix(
    x: jnp.ndarray,
    cluster_ids,
    mask: jnp.ndarray,
    n_clusters: int,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Participation-masked cluster aggregates: out[c] = Σ_{d: cid[d]=c}
    mask[d]·x[d]. Same contiguous-cluster requirement as
    ``segment_sum_mix``; ``mask`` is a traced (D,) 0/1 vector prefetched
    next to the cluster ids, so gating devices in and out of a merge
    round never recompiles the kernel."""
    cids = np.asarray(cluster_ids)
    if not np.all(np.diff(cids) >= 0):
        raise ValueError(
            "masked_segment_sum_mix needs sorted (contiguous-cluster) cluster_ids; "
            "sort the device axis by cluster first"
        )
    return _masked_segment_sum_mix_call(
        x, jnp.asarray(cids, jnp.int32), jnp.asarray(mask, jnp.float32),
        n_clusters, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("n_clusters", "interpret"))
def _masked_segment_sum_mix_call(
    x: jnp.ndarray,
    cluster_ids: jnp.ndarray,
    mask: jnp.ndarray,
    n_clusters: int,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    d, r, c = x.shape
    xp, rp, cp = _pad_stacked(x)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(cp // _LANE, d),
        in_specs=[pl.BlockSpec((1, rp, _LANE), lambda j, i, cids, mask: (i, 0, j))],
        out_specs=pl.BlockSpec((1, rp, _LANE), lambda j, i, cids, mask: (cids[i], 0, j)),
        scratch_shapes=[pltpu.VMEM((1, rp, _LANE), jnp.float32)],
    )
    out = pl.pallas_call(
        _masked_segsum_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_clusters, rp, cp), jnp.float32),
        interpret=resolve_interpret(interpret),
        name="_masked_segment_sum_mix_call",  # the op name chip traces show
    )(cluster_ids, mask, xp)
    return out[:, :r, :c]


def _gather_kernel(cids_ref, s_ref, o_ref):
    o_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def segment_broadcast(
    cluster_sums: jnp.ndarray, cluster_ids: jnp.ndarray, *, interpret: bool | None = None
) -> jnp.ndarray:
    """Gather each device's cluster aggregate back: out[d] = sums[cid[d]]."""
    d = cluster_ids.shape[0]
    _, r, c = cluster_sums.shape
    sp, rp, cp = _pad_stacked(cluster_sums)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(cp // _LANE, d),
        in_specs=[pl.BlockSpec((1, rp, _LANE), lambda j, i, cids: (cids[i], 0, j))],
        out_specs=pl.BlockSpec((1, rp, _LANE), lambda j, i, cids: (i, 0, j)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((d, rp, cp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(cluster_ids, jnp.int32), sp)
    return out[:, :r, :c]


# -------------------------------------------------------------- dense fallback


def _dense_kernel(m_ref, x_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        m_ref[...], x_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bi", "bj", "bk", "interpret"))
def dense_mix(
    x: jnp.ndarray,
    matrix: jnp.ndarray,
    *,
    bi: int = 128,
    bj: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Tiled out = M @ flatten(x) for an arbitrary (D, D) mask — the
    dense baseline the sparse paths are measured against."""
    d, r, c = x.shape
    f = r * c
    xf = x.reshape(d, f)
    dp_i, dp_k, fp = _pad_up(d, bi), _pad_up(d, bk), _pad_up(f, bj)
    mp = jnp.pad(jnp.asarray(matrix, jnp.float32), ((0, dp_i - d), (0, dp_k - d)))
    xfp = jnp.pad(xf, ((0, dp_k - d), (0, fp - f)))
    nk = dp_k // bk
    out = pl.pallas_call(
        functools.partial(_dense_kernel, nk=nk),
        grid=(dp_i // bi, fp // bj, nk),
        in_specs=[
            pl.BlockSpec((bi, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bj), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((dp_i, fp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(mp, xfp)
    return out[:d, :f].reshape(d, r, c)


# ------------------------------------------------------------------- dispatch


def topology_mix(
    x: jnp.ndarray, topology: Topology, *, interpret: bool | None = None
) -> jnp.ndarray:
    """Kernel equivalent of ``Topology.mix`` on a stacked (D, R, C)
    array — same dispatch, Pallas execution."""
    if topology.kind == "segment":
        sums = segment_sum_mix(
            x, topology.cluster_ids, topology.n_clusters, interpret=interpret
        )
        if topology.head_exchange:
            total = jnp.sum(sums, axis=0)  # O(clusters) head exchange
            return jnp.broadcast_to(total[None], x.shape)
        return segment_broadcast(sums, topology.cluster_ids, interpret=interpret)
    if topology.kind == "banded":
        if topology.band_closed:
            total = jnp.sum(x, axis=0)
            return jnp.broadcast_to(total[None], x.shape)
        return banded_mix(x, topology.hops, interpret=interpret)
    return dense_mix(x, topology.dense_matrix(), interpret=interpret)


# ----------------------------------------------- fused Gauss-Jordan (U,V) solve


def _gj_sweep(w: jnp.ndarray, n: int, rows: jnp.ndarray, cols: jnp.ndarray):
    """n in-place elimination steps on the augmented [A | I | V] block;
    afterwards cols n_p:n_p+n hold A⁻¹ and the V block holds A⁻¹V."""

    def body(k, w):
        row_k = jnp.sum(jnp.where(rows == k, w, 0.0), axis=0, keepdims=True)
        pivot = jnp.sum(jnp.where(cols == k, row_k, 0.0))
        row_k = row_k / pivot
        col_k = jnp.sum(jnp.where(cols == k, w, 0.0), axis=1, keepdims=True)
        e_k = jnp.where(rows == k, 1.0, 0.0)
        return w - (col_k - e_k) * row_k

    return jax.lax.fori_loop(0, n, body, w)


def _gj_solve(w: jnp.ndarray, n: int, rows: jnp.ndarray, cols: jnp.ndarray):
    """[A | I | V] → [I | A⁻¹ | A⁻¹V], eliminating on the Jacobi-scaled
    system S·A·S (S = diag(A)^-½, unit diagonal) and scaling back.

    Unscaled, f32 elimination without pivoting loses 3-4 digits on the
    merged Grams Eq. 8 produces (a dominant all-ones direction from
    sigmoid features, diagonals ~10⁵): β off by 1e-2 relative at
    κ(A) = 25, where the Cholesky reference holds 1e-6. Scaled, the
    sweep matches it. Row-scaling the augmented block turns the I
    block into S, so the sweep leaves (SAS)⁻¹S and (SAS)⁻¹SV; one more
    row scaling gives A⁻¹ and A⁻¹V."""
    diag = jnp.where(rows == cols, w, 0.0)
    s_row = jnp.where(rows < n, jax.lax.rsqrt(jnp.sum(diag, axis=1, keepdims=True)), 1.0)
    s_col = jnp.where(cols < n, jax.lax.rsqrt(jnp.sum(diag, axis=0, keepdims=True)), 1.0)
    return _gj_sweep(w * s_row * s_col, n, rows, cols) * s_row


def _solve_kernel(w_ref, o_ref, *, n: int, n_p: int, w_p: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_p, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, w_p), 1)
    o_ref[0] = _gj_solve(w_ref[0], n, rows, cols)


def _augment(u: jnp.ndarray, v: jnp.ndarray, ridge: float, n_p: int, w_p: int):
    """[U+εI | I | V] per device, padded so rows n..n_p are the identity
    (inert under elimination since the sweep only pivots k < n)."""
    dn, n, _ = u.shape
    m = v.shape[-1]
    diag = jnp.concatenate(
        [jnp.full(n, ridge, u.dtype), jnp.ones(n_p - n, u.dtype)]
    )
    a = jnp.pad(u, ((0, 0), (0, n_p - n), (0, n_p - n))) + jnp.diag(diag)[None]
    eye = jnp.broadcast_to(
        jnp.pad(jnp.eye(n, dtype=u.dtype), ((0, n_p - n), (0, 0))), (dn, n_p, n)
    )
    vp = jnp.pad(v, ((0, 0), (0, n_p - n), (0, 0)))
    w = jnp.concatenate([a, eye, vp], axis=2)
    return jnp.pad(w, ((0, 0), (0, 0), (0, w_p - (n_p + n + m))))


@functools.partial(jax.jit, static_argnames=("ridge", "interpret"))
def from_uv_solve(
    u: jnp.ndarray,
    v: jnp.ndarray,
    *,
    ridge: float = 0.0,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched §4.2 step 5 over the leading device axis: ridge-add +
    solve fused into one Gauss-Jordan kernel per device, returning
    P = (U+εI)⁻¹ and β = (U+εI)⁻¹V without an intermediate Cholesky
    factor in HBM. Drop-in for vmap(from_uv)."""
    dn, n, _ = u.shape
    m = v.shape[-1]
    n_p = _pad_up(n, _SUBLANE)
    w_p = _pad_up(n_p + n + m, _LANE)
    w = _augment(u, v, ridge, n_p, w_p)
    out = pl.pallas_call(
        functools.partial(_solve_kernel, n=n, n_p=n_p, w_p=w_p),
        grid=(dn,),
        in_specs=[pl.BlockSpec((1, n_p, w_p), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, n_p, w_p), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((dn, n_p, w_p), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(w)
    return out[:, :n, n_p : n_p + n], out[:, :n, n_p + n : n_p + n + m]


# ------------------------------------------- fully fused banded merge + solve


def _banded_solve_kernel(*refs, n: int, n_p: int, w_p: int, n_off: int, ridge: float):
    """refs = (x_ref × n_off, p_ref, beta_ref): sum the neighbor blocks
    in VMEM, build the augmented system in registers, eliminate, write
    (P, β) — merged (U, V) never touches HBM.

    The payload blocks are laid out [U (n_p cols, zero-padded) | V (m)].
    """
    x_refs, p_ref, b_ref = refs[:n_off], refs[n_off], refs[n_off + 1]
    wsum = x_refs[0][0].astype(jnp.float32)
    for r in x_refs[1:]:
        wsum = wsum + r[0].astype(jnp.float32)

    rows = jax.lax.broadcasted_iota(jnp.int32, (n_p, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, w_p), 1)
    a_cols = jax.lax.broadcasted_iota(jnp.int32, (1, n_p), 1)
    # augmented [U+εI | I | V] assembled from the summed [U | V] block:
    # ridge on the live diagonal, 1 on the inert padded rows
    reg = jnp.where(
        (rows == a_cols) & (rows < n), ridge, jnp.where(rows == a_cols, 1.0, 0.0)
    )
    a = wsum[:, :n_p] + reg
    eye_blk = jnp.where(
        (rows == jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)) & (rows < n), 1.0, 0.0
    )
    v_blk = wsum[:, n_p:]
    w = jnp.concatenate([a, eye_blk, v_blk], axis=1)
    w = jnp.pad(w, ((0, 0), (0, w_p - w.shape[1])))
    w = _gj_solve(w, n, rows, cols)
    m = v_blk.shape[1]
    p_ref[0] = jnp.pad(w[:, n_p : n_p + n], ((0, 0), (0, p_ref.shape[-1] - n)))
    b_ref[0] = jnp.pad(w[:, n_p + n : n_p + n + m], ((0, 0), (0, b_ref.shape[-1] - m)))


@functools.partial(jax.jit, static_argnames=("hops", "ridge", "interpret"))
def banded_merge_solve(
    w: jnp.ndarray,
    hops: int,
    *,
    ridge: float = 0.0,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The fused ring hot path: ``w`` is the stacked [U | V] payload
    (D, Ñ, Ñ+m) — Ñ is read off the row dimension; one kernel
    invocation per device gathers its ≤2·hops+1 neighbor blocks, sums
    them in VMEM, and solves for (P, β) in place.
    """
    d, n, nm = w.shape
    m = nm - n
    if 2 * hops + 1 > d:
        raise ValueError(f"band 2*{hops}+1 exceeds n_devices={d}; use a full-sum path")
    n_off = 2 * hops + 1
    n_p = _pad_up(n, _SUBLANE)
    w_p = _pad_up(n_p + n + m, _LANE)
    # re-lay the payload as [U (zero-padded to n_p cols) | V] so the
    # in-kernel column split lands on the sublane-aligned n_p boundary
    wp = jnp.concatenate(
        [jnp.pad(w[:, :, :n], ((0, 0), (0, n_p - n), (0, n_p - n))),
         jnp.pad(w[:, :, n:], ((0, 0), (0, n_p - n), (0, 0)))],
        axis=2,
    )  # (D, n_p, n_p + m)
    p_cols = _pad_up(n, _LANE)
    b_cols = _pad_up(m, _LANE)
    specs = [
        pl.BlockSpec((1, n_p, n_p + m), lambda i, o=o: ((i + o - hops) % d, 0, 0))
        for o in range(n_off)
    ]
    p_out, b_out = pl.pallas_call(
        functools.partial(
            _banded_solve_kernel, n=n, n_p=n_p, w_p=w_p, n_off=n_off, ridge=ridge
        ),
        grid=(d,),
        in_specs=specs,
        out_specs=[
            pl.BlockSpec((1, n_p, p_cols), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n_p, b_cols), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, n_p, p_cols), jnp.float32),
            jax.ShapeDtypeStruct((d, n_p, b_cols), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
        name="banded_merge_solve",  # the op name chip traces are read by
    )(*([wp] * n_off))
    return p_out[:, :n, :n], b_out[:, :n, :m]
