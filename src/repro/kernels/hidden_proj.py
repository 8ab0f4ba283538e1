"""Pallas TPU kernel: fused hidden-layer projection H = G(x·α + b).

The ELM/OS-ELM forward hot spot (Eq. 1). Tiled (bm × bn) output blocks
with a bk contraction loop on the innermost grid axis; partial products
accumulate in an f32 VMEM scratch and the bias + activation are applied
once on the final k-step (fused epilogue — H never round-trips to HBM
in anything but its final form).

Tile sizes default to MXU-aligned multiples of 128 lanes / 8 sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.activations import get_activation
from repro.kernels.fleet_ingest import resolve_interpret


def _hidden_kernel(x_ref, a_ref, b_ref, o_ref, acc_ref, *, activation: str, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], a_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        g = get_activation(activation)
        o_ref[...] = g(acc_ref[...] + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("activation", "bm", "bn", "bk", "interpret")
)
def hidden_proj(
    x: jnp.ndarray,
    alpha: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    activation: str = "sigmoid",
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """H = G(x·α + b) for x:(M,K), α:(K,N), b:(N,) → (M,N) f32.

    Shapes are padded up to tile multiples; zero-padded K contributes
    zero to the accumulator so results are exact after slicing.
    """
    m, k = x.shape
    k2, n = alpha.shape
    assert k == k2 and bias.shape == (n,)
    mp, kp, np_ = (-(-m // bm) * bm, -(-k // bk) * bk, -(-n // bn) * bn)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    ap = jnp.pad(alpha, ((0, kp - k), (0, np_ - n)))
    bp = jnp.pad(bias, (0, np_ - n))[None, :]  # (1, Np) for lane layout
    nk = kp // bk

    out = pl.pallas_call(
        functools.partial(_hidden_kernel, activation=activation, nk=nk),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xp, ap, bp)
    return out[:m, :n]
