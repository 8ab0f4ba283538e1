"""Pallas TPU kernel: blocked rank-1 update O = X + scale · u ⊗ v.

The OS-ELM k=1 sequential step (Eq. 12 with scalar reciprocal) is two
rank-1 updates:

    P' = P − (Ph)(Ph)ᵀ / denom         (scale = −1/denom, u = v = Ph)
    β' = β + (Ph)(t − hᵀβ)ᵀ / denom    (scale = +1/denom, u = Ph, v = err)

Each (bi × bj) output tile touches only bi + bj vector elements — the
kernel is memory-bound (arithmetic intensity ≈ 1 FLOP/byte on X), so the
tiles are sized to stream X through VMEM at full HBM bandwidth. ``u`` is
delivered as a (1, N) row and transposed in-register to a column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fleet_ingest import resolve_interpret


def _rank1_kernel(x_ref, u_ref, v_ref, s_ref, o_ref):
    scale = s_ref[0, 0]
    u_col = u_ref[...].T  # (bi, 1) in-register transpose
    o_ref[...] = (
        x_ref[...].astype(jnp.float32)
        + scale * u_col.astype(jnp.float32) * v_ref[...].astype(jnp.float32)
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bi", "bj", "interpret"))
def rank1_add(
    x: jnp.ndarray,
    u: jnp.ndarray,
    v: jnp.ndarray,
    scale: jnp.ndarray | float,
    *,
    bi: int = 256,
    bj: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """O = X + scale·u vᵀ for X:(N1,N2), u:(N1,), v:(N2,) → f32."""
    n1, n2 = x.shape
    assert u.shape == (n1,) and v.shape == (n2,)
    n1p = -(-n1 // bi) * bi
    n2p = -(-n2 // bj) * bj
    xp = jnp.pad(x, ((0, n1p - n1), (0, n2p - n2)))
    up = jnp.pad(u, (0, n1p - n1))[None, :]  # (1, N1)
    vp = jnp.pad(v, (0, n2p - n2))[None, :]  # (1, N2)
    s = jnp.asarray(scale, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        _rank1_kernel,
        grid=(n1p // bi, n2p // bj),
        in_specs=[
            pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
            pl.BlockSpec((1, bi), lambda i, j: (0, i)),
            pl.BlockSpec((1, bj), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n1p, n2p), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(xp, up, vp, s)
    return out[:n1, :n2]
