"""jit'd public wrappers over the Pallas kernels.

``interpret=None`` lets the platform decide (``resolve_interpret``:
Mosaic on TPU, the Pallas interpreter elsewhere). The fused
k=1 OS-ELM step composes three kernels:

    1. hidden_proj   h  = G(x·α + b)                 (MXU matmul + epilogue)
    2. rowvec matvec ph = P h  (via matmul_atb on a symmetric P)
    3. rank1_add ×2  P' = P − phphᵀ/denom, β' = β + ph errᵀ/denom

with the two scalars (denom) and the m-vector (err) computed inline —
they are O(Ñ + m) work, not worth a kernel launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.oselm import OSELMState
from repro.kernels.hidden_proj import hidden_proj
from repro.kernels.matmul_atb import matmul_atb, uv_accum
from repro.kernels.rank1_add import rank1_add

__all__ = [
    "hidden_proj",
    "matmul_atb",
    "uv_accum",
    "rank1_add",
    "oselm_step_k1_kernel",
    "uv_from_state_kernel",
]


@functools.partial(jax.jit, static_argnames=("interpret",))
def oselm_step_k1_kernel(
    state: OSELMState, x: jnp.ndarray, t: jnp.ndarray, *, interpret: bool | None = None
) -> OSELMState:
    """Kernelized Eq. 12 k=1 step — drop-in for `core.oselm.oselm_step_k1`."""
    h = hidden_proj(
        x[None, :], state.params.alpha, state.params.bias,
        activation=state.activation, interpret=interpret,
    )[0]                                            # (Ñ,)
    p = state.p / state.forget
    # ph = P h: P is symmetric, so hᵀP = (Ph)ᵀ → AᵀB with A=h column.
    ph = matmul_atb(h[:, None], p, interpret=interpret)[0]  # (Ñ,)
    denom = 1.0 + h @ ph
    err = t - h @ state.beta                        # (m,)
    p_new = rank1_add(p, ph, ph, -1.0 / denom, interpret=interpret)
    beta_new = rank1_add(state.beta, ph, err, 1.0 / denom, interpret=interpret)
    return state.replace(beta=beta_new, p=p_new)


@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def uv_from_batch_kernel(
    params_alpha: jnp.ndarray,
    params_bias: jnp.ndarray,
    x: jnp.ndarray,
    t: jnp.ndarray,
    *,
    activation: str = "sigmoid",
    interpret: bool | None = None,
):
    """Batched E²LM statistics straight from raw data:
    H = G(xα+b); U = HᵀH; V = Hᵀt — the ELM/E²LM training front half."""
    h = hidden_proj(x, params_alpha, params_bias, activation=activation, interpret=interpret)
    return uv_accum(h, t, interpret=interpret)


def uv_from_state_kernel(state: OSELMState, x: jnp.ndarray, *, interpret: bool | None = None):
    """Autoencoder variant (t = x)."""
    return uv_from_batch_kernel(
        state.params.alpha, state.params.bias, x, x,
        activation=state.activation, interpret=interpret,
    )
