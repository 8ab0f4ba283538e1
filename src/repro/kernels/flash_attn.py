"""Pallas TPU kernel: fused flash attention (forward).

§Perf iteration 1b (EXPERIMENTS.md): the pure-jnp blockwise attention
keeps score chunks *logically* small, but every (cq × ck) f32 chunk
round-trips HBM between XLA fusions — the dominant memory-roofline term
for the attention-heavy architectures. This kernel keeps the online-
softmax state (m, l, acc) and the score chunk in VMEM for the whole KV
sweep; HBM traffic collapses to q/k/v reads + one output write (the
``t_memory_fused_attn`` roofline term).

Layout: grid = (B·H, nq, nk), nk innermost so the VMEM scratch carries
across KV steps of one query block. Blocks are MXU-aligned (cq, ck
multiples of 128 on the lane dim; hd is the contraction).

Causal masking is positional (block offsets), matching
``models.layers.blockwise_attention`` exactly; the jnp oracle for tests
is that function.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fleet_ingest import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, nk: int, cq: int, ck: int, sk: int,
):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                           # (cq, hd)
    k = k_ref[0]                           # (ck, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                              # (cq, ck)

    kpos = kk * ck + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 1)
    valid = kpos < sk
    if causal:
        qi = pl.program_id(1)
        qpos = qi * cq + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 0)
        valid = valid & (kpos <= qpos)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new

    @pl.when(kk == nk - 1)
    def _store():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "cq", "ck", "interpret")
)
def flash_attention(
    q: jnp.ndarray,   # (B, S, H, hd)
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    cq: int = 128,
    ck: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused flash attention forward (Pallas, VMEM-resident softmax)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = hd ** -0.5
    cq = min(cq, max(sq, 8))
    ck = min(ck, max(sk, 8))
    nq = -(-sq // cq)
    nk = -(-sk // ck)

    def to_bh(x, s, c, n):
        xp = jnp.pad(x, ((0, 0), (0, n * c - s), (0, 0), (0, 0)))
        return xp.transpose(0, 2, 1, 3).reshape(b * h, n * c, hd)

    qb = to_bh(q, sq, cq, nq)
    kb = to_bh(k, sk, ck, nk)
    vb = to_bh(v, sk, ck, nk)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal,
            nk=nk, cq=cq, ck=ck, sk=sk,
        ),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, cq, hd), lambda bh, qi, kk: (bh, qi, 0)),
            pl.BlockSpec((1, ck, hd), lambda bh, qi, kk: (bh, kk, 0)),
            pl.BlockSpec((1, ck, hd), lambda bh, qi, kk: (bh, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, cq, hd), lambda bh, qi, kk: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, nq * cq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((cq,), jnp.float32),
            pltpu.VMEM((cq,), jnp.float32),
            pltpu.VMEM((cq, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qb, kb, vb)
    return out.reshape(b, h, nq * cq, hd).transpose(0, 2, 1, 3)[:, :sq]
