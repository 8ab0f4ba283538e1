"""Fused Pallas quantize-on-pack kernel for the quantized merge path.

The lossy merge publish step is pack → error-feedback add → per-tile
int8 quantize (``repro.fleet.quantize``). Running it as separate XLA
ops materializes the packed f32 payload ``w = [U | V]`` AND the
feedback sum ``w + r`` in HBM before the codes are ever produced. This
kernel fuses the whole publish into one VMEM-resident pass per device:

    grid (D,): one program per device payload (≤ ~350 KB of VMEM for
    the largest preset, far under budget)
      1. pack   — concat the logical columns of U (Ñ) and V (m)
      2. EF add — x = [U | V] + residual
      3. per-tile quantize — for each 128-column slab: amax → scale
         (1.0 on an all-zero slab) → round/clip int8 codes, and the
         fresh residual x − dq(codes) in the same pass

    outputs: int8 codes (the wire payload), one f32 scale per
    (device, tile) packed as a lane row, and the next error-feedback
    accumulator — the f32 packed payload never exists in HBM.

int8 outputs use the (32, 128) Mosaic minimum tile (f32 uses (8, 128)),
so rows are padded to 32. The in-kernel concat splits at the unaligned
column Ñ; that relayout is free in the interpreter (CPU CI) and
acceptable on Mosaic because the whole payload is register/VMEM
resident. ``quantize_pack_xla`` is the bit-identical XLA reference the
CPU parity tests pin against (same reduction/round/clip semantics as
``repro.fleet.quantize.quantize_tiles``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.fleet.quantize import (
    INT8_MAX,
    TILE_COLS,
    dequantize_tiles,
    n_col_tiles,
    quantize_tiles,
)
from repro.kernels.fleet_ingest import resolve_interpret

__all__ = ["quantize_pack", "quantize_pack_xla"]

_LANE = 128
_SUBLANE_I8 = 32  # int8 minimum sublane tile (f32 is 8)


def _pad_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _qpack_kernel(
    u_ref, v_ref, r_ref, codes_ref, scales_ref, resid_ref, *, n: int, m: int, nt: int
):
    u = u_ref[0]
    v = v_ref[0]
    cp = nt * TILE_COLS
    x = jnp.concatenate([u[:, :n], v[:, :m]], axis=1)      # pack [U | V]
    x = jnp.pad(x, ((0, 0), (0, cp - (n + m))))
    x = x + r_ref[0]                                       # error feedback
    codes, resids, scales = [], [], []
    for t in range(nt):                                    # static unroll, nt ≤ 8
        tile = x[:, t * TILE_COLS : (t + 1) * TILE_COLS]
        amax = jnp.max(jnp.abs(tile))
        scale = jnp.where(amax > 0, amax / INT8_MAX, 1.0)
        q = jnp.clip(jnp.round(tile / scale), -INT8_MAX, INT8_MAX)
        codes.append(q.astype(jnp.int8))
        resids.append(tile - q * scale)
        scales.append(scale.reshape(1, 1))
    codes_ref[0] = jnp.concatenate(codes, axis=1)
    resid_ref[0] = jnp.concatenate(resids, axis=1)
    # the ≤ 8 per-tile scales ship as one padded lane row per device
    scales_ref[0] = jnp.concatenate(
        scales + [jnp.zeros((1, _LANE - nt), jnp.float32)], axis=1
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_pack(
    u: jnp.ndarray,
    v: jnp.ndarray,
    residual: jnp.ndarray | None = None,
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused publish step for a stacked fleet: u (D, Ñ, Ñ), v (D, Ñ, m),
    residual (D, Ñ, Ñ+m) or None → ``(codes int8 (D, Ñ, Ñ+m),
    scales f32 (D, nt), residual' f32 (D, Ñ, Ñ+m))``. The network ships
    codes + scales; ``repro.fleet.quantize.dequantize_tiles`` recovers
    the payload on the receive side."""
    d, n, _ = u.shape
    m = v.shape[-1]
    nt = n_col_tiles(n + m)
    if nt > _LANE:
        raise ValueError(f"payload needs {nt} scale tiles > one {_LANE}-lane row")
    cp = nt * TILE_COLS
    if residual is None:
        residual = jnp.zeros((d, n, n + m), jnp.float32)
    rp = _pad_up(n, _SUBLANE_I8)
    up = jnp.pad(u, ((0, 0), (0, rp - n), (0, _pad_up(n, _LANE) - n)))
    vp = jnp.pad(v, ((0, 0), (0, rp - n), (0, _pad_up(m, _LANE) - m)))
    rs = jnp.pad(residual, ((0, 0), (0, rp - n), (0, cp - (n + m))))
    codes, scales, resid = pl.pallas_call(
        functools.partial(_qpack_kernel, n=n, m=m, nt=nt),
        grid=(d,),
        in_specs=[
            pl.BlockSpec((1, rp, up.shape[-1]), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, rp, vp.shape[-1]), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, rp, cp), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, rp, cp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, _LANE), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, rp, cp), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, rp, cp), jnp.int8),
            jax.ShapeDtypeStruct((d, 1, _LANE), jnp.float32),
            jax.ShapeDtypeStruct((d, rp, cp), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(up, vp, rs)
    return (
        codes[:, :n, : n + m],
        scales[:, 0, :nt],
        resid[:, :n, : n + m],
    )


@jax.jit
def quantize_pack_xla(
    u: jnp.ndarray,
    v: jnp.ndarray,
    residual: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """XLA reference for ``quantize_pack`` — identical semantics (pack,
    error-feedback add, per-tile quantize) through the
    ``repro.fleet.quantize`` codec; the CPU parity baseline."""
    w = jnp.concatenate([u, v], axis=2)
    x = w if residual is None else w + residual
    codes, scales = quantize_tiles(x)
    resid = x - dequantize_tiles(codes, scales)
    return codes, scales, resid
