"""Pallas TPU kernel: AᵀB accumulation — the E²LM sufficient statistics.

U = HᵀH and V = Hᵀt (Eq. 6 / Eq. 15) are both AᵀB with the *sample* axis
contracted. The kernel reads A twice under two BlockSpecs (row-block and
column-block views) so Aᵀ is never materialized in HBM; partial products
accumulate in an f32 VMEM scratch over the innermost sample-tile axis.

This contraction has arithmetic intensity ~Ñ on the MXU and is the
compute term of the merge path's roofline (benchmarks/roofline of the
detector path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fleet_ingest import resolve_interpret


def _atb_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a_ref block: (bk, bi) sample-major; contract the sample axis.
    acc_ref[...] += jnp.dot(
        a_ref[...].T, b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bi", "bj", "bk", "interpret"))
def matmul_atb(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bi: int = 128,
    bj: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """AᵀB for A:(K,N1), B:(K,N2) → (N1,N2) f32 (K = samples)."""
    k, n1 = a.shape
    k2, n2 = b.shape
    assert k == k2
    kp = -(-k // bk) * bk
    n1p = -(-n1 // bi) * bi
    n2p = -(-n2 // bj) * bj
    ap = jnp.pad(a, ((0, kp - k), (0, n1p - n1)))
    bp = jnp.pad(b, ((0, kp - k), (0, n2p - n2)))
    nk = kp // bk

    out = pl.pallas_call(
        functools.partial(_atb_kernel, nk=nk),
        grid=(n1p // bi, n2p // bj, nk),
        in_specs=[
            pl.BlockSpec((bk, bi), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bk, bj), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n1p, n2p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(ap, bp)
    return out[:n1, :n2]


def uv_accum(
    h: jnp.ndarray, t: jnp.ndarray, *, interpret: bool | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """U = HᵀH, V = Hᵀt in one pass each (paper Eq. 6 intermediates)."""
    u = matmul_atb(h, h, interpret=interpret)
    v = matmul_atb(h, t, interpret=interpret)
    return u, v
