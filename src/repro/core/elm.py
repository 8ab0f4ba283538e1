"""ELM — batch Extreme Learning Machine (paper §3.1, Eqs. 1–3).

Single hidden-layer feedforward network (SLFN):

    y = G(x·α + b) · β

The input weight ``α`` and bias ``b`` are random and frozen; only the
output weight ``β`` is trained, analytically and in one shot:

    β̂ = H† t,   H = G(x·α + b)

With ``rank H = Ñ`` the pseudo-inverse decomposes (Eq. 4) as
``H† = (HᵀH)⁻¹ Hᵀ`` — the form E²LM (``e2lm.py``) builds on. ``HᵀH`` is
symmetric positive (semi-)definite so we solve via Cholesky; a ridge
``εI`` is available (default 0.0 — faithful to the paper, which assumes
nonsingularity).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.activations import get_activation


class SLFNParams(NamedTuple):
    """Frozen random projection of the SLFN (shared by ELM / OS-ELM).

    The paper assumes ``α`` and ``b`` are identical across federated
    devices (Section 4.2) — achieved by seeding with the same key.
    """

    alpha: jnp.ndarray  # (n, n_hidden) input weights — random, frozen
    bias: jnp.ndarray   # (n_hidden,) hidden bias — random, frozen

    @property
    def n_in(self) -> int:
        return self.alpha.shape[0]

    @property
    def n_hidden(self) -> int:
        return self.alpha.shape[1]


def init_slfn(
    key: jax.Array,
    n_in: int,
    n_hidden: int,
    *,
    dist: str = "uniform",
    dtype=jnp.float32,
) -> SLFNParams:
    """Random frozen projection; ``dist`` matches the paper's p(x)=Uniform."""
    ka, kb = jax.random.split(key)
    if dist == "uniform":
        alpha = jax.random.uniform(ka, (n_in, n_hidden), dtype, -1.0, 1.0)
        bias = jax.random.uniform(kb, (n_hidden,), dtype, -1.0, 1.0)
    elif dist == "normal":
        alpha = jax.random.normal(ka, (n_in, n_hidden), dtype)
        bias = jax.random.normal(kb, (n_hidden,), dtype)
    else:
        raise ValueError(f"unknown init dist {dist!r}")
    return SLFNParams(alpha=alpha, bias=bias)


def hidden(params: SLFNParams, x: jnp.ndarray, activation: str = "sigmoid") -> jnp.ndarray:
    """H = G(x·α + b) for a chunk x of shape (k, n)."""
    g = get_activation(activation)
    return g(x @ params.alpha + params.bias)


class ELMModel(NamedTuple):
    params: SLFNParams
    beta: jnp.ndarray  # (n_hidden, m)
    activation: str = "sigmoid"


def train_elm(
    params: SLFNParams,
    x: jnp.ndarray,
    t: jnp.ndarray,
    *,
    activation: str = "sigmoid",
    ridge: float = 0.0,
) -> ELMModel:
    """One-shot batch solve β̂ = (HᵀH + εI)⁻¹ Hᵀ t (Eqs. 4–5)."""
    h = hidden(params, x, activation)
    u = h.T @ h
    v = h.T @ t
    beta = solve_beta(u, v, ridge=ridge)
    return ELMModel(params=params, beta=beta, activation=activation)


def cho_factor(a: jnp.ndarray):
    """The lower Cholesky factor, as the factorization returns it. The
    upper form transposes it, and in a multi-device TPU program the
    compiler folds that transpose into the batched factor's output
    layout, where the factor came out wrong by 2-4% (4-chip v5e, jax
    0.9.0); one-device programs and the lower form are exact."""
    return jax.scipy.linalg.cho_factor(a, lower=True)


def solve_beta(u: jnp.ndarray, v: jnp.ndarray, *, ridge: float = 0.0) -> jnp.ndarray:
    """β = U⁻¹V via Cholesky (U is SPD up to rank deficiency).

    Falls back to the paper-faithful plain solve semantics: with
    ridge=0 this is numerically the same system the paper inverts.
    """
    n = u.shape[0]
    u_reg = u + ridge * jnp.eye(n, dtype=u.dtype)
    return jax.scipy.linalg.cho_solve(cho_factor(u_reg), v)


def invert_u(u: jnp.ndarray, *, ridge: float = 0.0) -> jnp.ndarray:
    """P = U⁻¹ via Cholesky; used when re-entering sequential training."""
    n = u.shape[0]
    u_reg = u + ridge * jnp.eye(n, dtype=u.dtype)
    return jax.scipy.linalg.cho_solve(cho_factor(u_reg), jnp.eye(n, dtype=u.dtype))


def predict_elm(model: ELMModel, x: jnp.ndarray) -> jnp.ndarray:
    h = hidden(model.params, x, model.activation)
    return h @ model.beta
