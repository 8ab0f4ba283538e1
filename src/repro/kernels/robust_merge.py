"""Byzantine-robust segment-sum kernel — the clipped/trimmed variant of
``masked_segment_sum_mix``.

The plain Eq. 8 merge sums raw (U, V) payloads, so one device shipping
a scaled or poisoned contribution moves every neighbor's merged model
by an unbounded amount. The robust reduction bounds that influence in
two composable ways, both evaluated INSIDE the streaming segment-sum:

- **clipping** — each device's payload is scaled by a prefetched
  per-device factor (``min(1, clip_norm / ‖w‖_F)``, computed by
  ``repro.fleet.robust.payload_clip``), so no single contribution can
  dominate the sum by magnitude;
- **trimming** — alongside the masked running total, the kernel keeps
  the ``trim`` smallest and ``trim`` largest participating values PER
  COORDINATE in VMEM register chains (classic online k-extrema
  insertion: the chains stay sorted, one min/max swap per register per
  device). The caller combines the three outputs into the
  coordinate-wise trimmed-mean estimate of the segment sum
  (``robust_segment_combine``): with ≤ ``trim`` adversarial devices
  per segment, every surviving coordinate lies within the honest
  participants' range.

Grid/BlockSpec structure is identical to ``_masked_segsum_kernel``
(``repro.kernels.topology_merge``): contiguous sorted cluster ids drive
the output index map, the accumulator and extrema registers reset on
every id change, and the last write of a cluster's contiguous run wins.
``cids``/``mask``/``scale`` are scalar-prefetched so participation and
clipping change every merge round without retracing. ``trim`` is static
(it sizes the register chains).

``robust_segment_sum_xla`` is the sort-based XLA oracle the parity
tests hold the kernel to (≤1e-5); both sanitize the ±inf sentinels of
under-filled registers to 0, so outputs are finite even for segments
with fewer than ``trim`` participants (the combine falls back to the
plain sum there anyway).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fleet_ingest import resolve_interpret
from repro.kernels.topology_merge import _LANE, _pad_stacked

__all__ = [
    "robust_segment_combine",
    "robust_segment_sum_mix",
    "robust_segment_sum_xla",
]


def _robust_segsum_kernel(
    cids_ref, mask_ref, scale_ref, x_ref, tot_ref, lo_ref, hi_ref,
    acc_ref, *extrema_refs, trim: int,
):
    d = pl.program_id(1)
    first = jnp.logical_or(
        d == 0, cids_ref[d] != cids_ref[jnp.maximum(d - 1, 0)]
    )
    mins, maxs = extrema_refs[:trim], extrema_refs[trim:]

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for mr in mins:
            mr[...] = jnp.full_like(mr, jnp.inf)
        for xr in maxs:
            xr[...] = jnp.full_like(xr, -jnp.inf)

    # clipping fuses into the stream: the payload block is scaled as it
    # is read, so the clipped stack never exists in HBM
    m = mask_ref[d].astype(jnp.float32)
    v = x_ref[...].astype(jnp.float32) * scale_ref[d]
    acc_ref[...] += v * m

    # online k-extrema insertion chains: each register chain is kept
    # sorted; masked devices insert ±inf sentinels, which never displace
    # a participating value
    lo_v = jnp.where(m > 0, v, jnp.inf)
    for mr in mins:
        cur = mr[...]
        mr[...] = jnp.minimum(cur, lo_v)
        lo_v = jnp.maximum(cur, lo_v)
    hi_v = jnp.where(m > 0, v, -jnp.inf)
    for xr in maxs:
        cur = xr[...]
        xr[...] = jnp.maximum(cur, hi_v)
        hi_v = jnp.minimum(cur, hi_v)

    # the out blocks track this device's segment: the last write of a
    # contiguous cluster run is the completed aggregate. Under-filled
    # registers still hold ±inf — sanitized to 0 so the outputs stay
    # finite (the combine discards lo/hi for such segments anyway).
    tot_ref[...] = acc_ref[...]
    lo_sum = jnp.zeros_like(acc_ref[...])
    for mr in mins:
        cur = mr[...]
        lo_sum = lo_sum + jnp.where(jnp.isfinite(cur), cur, 0.0)
    lo_ref[...] = lo_sum
    hi_sum = jnp.zeros_like(acc_ref[...])
    for xr in maxs:
        cur = xr[...]
        hi_sum = hi_sum + jnp.where(jnp.isfinite(cur), cur, 0.0)
    hi_ref[...] = hi_sum


def robust_segment_sum_mix(
    x: jnp.ndarray,
    cluster_ids,
    mask: jnp.ndarray,
    scale: jnp.ndarray,
    n_clusters: int,
    trim: int,
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Clipped/trimmed masked cluster aggregates.

    Returns ``(total, lo, hi)``, each (n_clusters, R, C):
    ``total[c] = Σ_{d: cid[d]=c} mask[d]·scale[d]·x[d]`` and ``lo``/``hi``
    the coordinate-wise sums of the ``trim`` smallest/largest
    participating scaled values per cluster. Same contiguous-sorted
    cluster-id requirement as ``segment_sum_mix``; ``mask`` and
    ``scale`` are traced (D,) operands, so gating and re-clipping never
    recompile. ``trim=0`` degenerates to ``masked_segment_sum_mix``
    outputs (with zero lo/hi)."""
    cids = np.asarray(cluster_ids)
    if not np.all(np.diff(cids) >= 0):
        raise ValueError(
            "robust_segment_sum_mix needs sorted (contiguous-cluster) "
            "cluster_ids; sort the device axis by cluster first"
        )
    if trim < 0:
        raise ValueError(f"need trim >= 0, got {trim}")
    return _robust_segment_sum_mix_call(
        x, jnp.asarray(cids, jnp.int32), jnp.asarray(mask, jnp.float32),
        jnp.asarray(scale, jnp.float32), n_clusters, trim,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("n_clusters", "trim", "interpret"))
def _robust_segment_sum_mix_call(
    x: jnp.ndarray,
    cluster_ids: jnp.ndarray,
    mask: jnp.ndarray,
    scale: jnp.ndarray,
    n_clusters: int,
    trim: int,
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    d, r, c = x.shape
    xp, rp, cp = _pad_stacked(x)
    out_spec = pl.BlockSpec((1, rp, _LANE), lambda j, i, cids, mask, scale: (cids[i], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(cp // _LANE, d),
        in_specs=[
            pl.BlockSpec((1, rp, _LANE), lambda j, i, cids, mask, scale: (i, 0, j))
        ],
        out_specs=[out_spec, out_spec, out_spec],
        scratch_shapes=[pltpu.VMEM((1, rp, _LANE), jnp.float32)] * (1 + 2 * trim),
    )
    shape = jax.ShapeDtypeStruct((n_clusters, rp, cp), jnp.float32)
    tot, lo, hi = pl.pallas_call(
        functools.partial(_robust_segsum_kernel, trim=trim),
        grid_spec=grid_spec,
        out_shape=[shape, shape, shape],
        interpret=resolve_interpret(interpret),
    )(cluster_ids, mask, scale, xp)
    return tot[:, :r, :c], lo[:, :r, :c], hi[:, :r, :c]


def robust_segment_sum_xla(
    x: jnp.ndarray,
    cluster_ids,
    mask: jnp.ndarray,
    scale: jnp.ndarray,
    n_clusters: int,
    trim: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort-based XLA oracle of ``robust_segment_sum_mix`` — identical
    semantics, including the ±inf→0 sanitization of segments with fewer
    than ``trim`` participants. Cluster membership is static (host
    cluster ids), so the per-cluster loop unrolls at trace time."""
    cids = np.asarray(cluster_ids)
    mf = jnp.asarray(mask, jnp.float32)
    v = jnp.asarray(x, jnp.float32) * jnp.asarray(scale, jnp.float32)[:, None, None]
    tot = jax.ops.segment_sum(
        v * mf[:, None, None], jnp.asarray(cids, jnp.int32),
        num_segments=n_clusters,
    )
    if trim == 0:
        z = jnp.zeros_like(tot)
        return tot, z, z
    los, his = [], []
    for cluster in range(n_clusters):
        sel = np.flatnonzero(cids == cluster)
        vc = v[sel]
        live = (mf[sel] > 0)[:, None, None]
        k = min(trim, len(sel))
        lo_k = jnp.sort(jnp.where(live, vc, jnp.inf), axis=0)[:k]
        hi_k = jnp.sort(jnp.where(live, vc, -jnp.inf), axis=0)[len(sel) - k:]
        los.append(jnp.where(jnp.isfinite(lo_k), lo_k, 0.0).sum(0))
        his.append(jnp.where(jnp.isfinite(hi_k), hi_k, 0.0).sum(0))
    return tot, jnp.stack(los), jnp.stack(his)


def robust_segment_combine(
    tot: jnp.ndarray,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    counts: jnp.ndarray,
    trim: int,
) -> jnp.ndarray:
    """Coordinate-wise trimmed-mean estimate of each segment SUM:
    ``(tot − lo − hi) / (count − 2·trim) · count``. Scaling the trimmed
    mean back by the participant count keeps the estimate in Eq. 8's
    sum units, so ``trim=0`` is exactly the plain masked sum and the
    downstream (U+εI)⁻¹ solve is unchanged. Segments with ≤ 2·trim
    participants cannot be trimmed and fall back to their plain sum."""
    if trim == 0:
        return tot
    counts = jnp.asarray(counts, jnp.float32).reshape(-1, 1, 1)
    live = counts - 2.0 * trim
    trimmed = (tot - lo - hi) / jnp.maximum(live, 1.0) * counts
    return jnp.where(live >= 1.0, trimmed, tot)
