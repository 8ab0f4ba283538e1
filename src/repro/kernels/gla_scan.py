"""Pallas TPU kernel: chunked gated-linear-attention forward.

The sequence-mixing hot spot of the SSM/hybrid architectures
(mamba heads, mLSTM — `models/ssm.py`). One grid step processes one
(batch·head, chunk) pair with the recurrent state carried in VMEM
scratch across the chunk axis:

    h_t = a_t · h_{t-1} + k_t v_tᵀ        y_t = q_tᵀ h_t

Per chunk (C = chunk length, all MXU matmuls):
    y_intra = (q kᵀ ⊙ decay_mask) v
    y_inter = (q ⊙ e^{cum}) · S
    S ← e^{tot} · S + (k ⊙ e^{tot−cum})ᵀ v

Matches `models.ssm.chunked_linear_attention` (the jnp oracle) exactly;
decays arrive as per-token log-decay and are cumulated in-kernel in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fleet_ingest import resolve_interpret


def _gla_kernel(q_ref, k_ref, v_ref, la_ref, o_ref, s_scr, *, nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    q = q_ref[0].astype(jnp.float32)        # (C, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)        # (C, dv)
    la = la_ref[0].astype(jnp.float32)      # (C,) log decay, ≤ 0 (0 on padding)

    cum = jnp.cumsum(la)                    # (C,) inclusive
    tot = cum[-1]

    # inter-chunk: y += (q ⊙ e^{cum}) S_prev
    y = jax.lax.dot_general(
        q * jnp.exp(cum)[:, None], s_scr[...],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    # intra-chunk: scores[t,τ] = (q_t·k_τ)·e^{cum_t − cum_τ}, τ ≤ t
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    c = q.shape[0]
    rel = cum[:, None] - cum[None, :]
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        <= jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    )
    gate = jnp.where(tri, jnp.exp(rel), 0.0)
    y = y + jax.lax.dot_general(
        scores * gate, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = y.astype(o_ref.dtype)

    # state update: S ← e^{tot} S + Σ_τ e^{tot − cum_τ} k_τ v_τᵀ
    w = jnp.exp(tot - cum)[:, None]
    s_scr[...] = s_scr[...] * jnp.exp(tot) + jax.lax.dot_general(
        k * w, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gla_forward(
    q: jnp.ndarray,        # (B, S, H, dk)
    k: jnp.ndarray,
    v: jnp.ndarray,        # (B, S, H, dv)
    log_a: jnp.ndarray,    # (B, S, H)
    *,
    chunk: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused chunked GLA forward. Padding tokens get log_a = 0 and
    zeroed k/v so the carried state is unaffected."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    nc = -(-s // c)
    pad = nc * c - s

    def prep(x, zero_pad):
        xp = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        if x.ndim == 4:
            return xp.transpose(0, 2, 1, 3).reshape(b * h, nc * c, x.shape[-1])
        return xp.transpose(0, 2, 1).reshape(b * h, nc * c)

    qb = prep(q, False)
    kb = prep(k, True)
    vb = prep(v, True)
    lab = prep(log_a, False)
    if pad:
        valid = (jnp.arange(nc * c) < s)[None, :]
        kb = kb * valid[..., None]
        vb = vb * valid[..., None]
        lab = lab * valid  # log a = 0 → a = 1 on padding

    out = pl.pallas_call(
        functools.partial(_gla_kernel, nc=nc),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, c, dk), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, c, dk), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, c, dv), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, c), lambda bh, ci: (bh, ci)),
        ],
        out_specs=pl.BlockSpec((1, c, dv), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, nc * c, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(qb, kb, vb, lab)
    return out.reshape(b, h, nc * c, dv).transpose(0, 2, 1, 3)[:, :s]
