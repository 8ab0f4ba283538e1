"""OS-ELM — Online Sequential ELM (paper §3.3, Eqs. 9–13).

Sequential recursive-least-squares update of the output weight β with
state P = K⁻¹ where K accumulates Σ HᵀH:

    P_i = P_{i-1} − P_{i-1} Hᵢᵀ (I + Hᵢ P_{i-1} Hᵢᵀ)⁻¹ Hᵢ P_{i-1}
    β_i = β_{i-1} + P_i Hᵢᵀ (tᵢ − Hᵢ β_{i-1})

The paper fixes batch size k=1 so the k×k inverse becomes a scalar
reciprocal (§3.3 last paragraph) — `oselm_step_k1` is that fast path
and the shape targeted by the Pallas kernel (`repro.kernels.oselm_step`).

A low-cost exponential forgetting factor λ (ref [2]) is supported:
K_i = λ K_{i-1} + HᵀH  ⇔  P pre-scaled by 1/λ. λ=1 (paper default)
disables it.

``OSELMState`` is a registered pytree whose ``activation``/``forget``
fields are static aux data, so states scan/vmap/psum cleanly while the
activation name stays a Python string.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.elm import SLFNParams, hidden, invert_u, solve_beta


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class OSELMState:
    """Sequential training state. Arrays are pytree leaves; the
    activation name and forgetting factor are static metadata."""

    params: SLFNParams
    beta: jnp.ndarray   # (n_hidden, m)
    p: jnp.ndarray      # (n_hidden, n_hidden) = K⁻¹
    activation: str = dataclasses.field(default="sigmoid", metadata=dict(static=True))
    forget: float = dataclasses.field(default=1.0, metadata=dict(static=True))

    @property
    def n_hidden(self) -> int:
        return self.beta.shape[0]

    @property
    def n_out(self) -> int:
        return self.beta.shape[1]

    def replace(self, **kw) -> "OSELMState":
        return dataclasses.replace(self, **kw)


def init_oselm(
    params: SLFNParams,
    x0: jnp.ndarray,
    t0: jnp.ndarray,
    *,
    activation: str = "sigmoid",
    ridge: float = 0.0,
    forget: float = 1.0,
) -> OSELMState:
    """Eq. 13: P₀ = (H₀ᵀH₀)⁻¹, β₀ = P₀H₀ᵀt₀.

    The paper requires the initial chunk to have at least Ñ rows so that
    H₀ᵀH₀ is nonsingular; ``ridge`` relaxes that when needed.
    """
    h0 = hidden(params, x0, activation)
    u0 = h0.T @ h0
    p0 = invert_u(u0, ridge=ridge)
    beta0 = solve_beta(u0, h0.T @ t0, ridge=ridge)
    return OSELMState(params=params, beta=beta0, p=p0, activation=activation, forget=forget)


def oselm_step(state: OSELMState, x: jnp.ndarray, t: jnp.ndarray) -> OSELMState:
    """Eq. 12 for an arbitrary batch k (k×k solve)."""
    h = hidden(state.params, x, state.activation)  # (k, Ñ)
    p = state.p / state.forget
    k = h.shape[0]
    ph = p @ h.T                                     # (Ñ, k)
    s = jnp.eye(k, dtype=p.dtype) + h @ ph           # (k, k)
    gain = ph @ jnp.linalg.inv(s)                    # (Ñ, k)  — Kalman gain
    p_new = p - gain @ ph.T
    beta_new = state.beta + p_new @ h.T @ (t - h @ state.beta)
    return state.replace(beta=beta_new, p=p_new)


def oselm_step_k1(
    state: OSELMState,
    x: jnp.ndarray,
    t: jnp.ndarray,
    *,
    kernel: bool = False,
    interpret: bool | None = None,
) -> OSELMState:
    """k=1 fast path (paper's deployed configuration).

    The (I + hPhᵀ) inverse is a scalar reciprocal — no SVD/QRD. ``x`` and
    ``t`` are single samples shaped (n,) and (m,). ``kernel=True`` runs
    the step through the fused Pallas kernels
    (``repro.kernels.ops.oselm_step_k1_kernel``: hidden_proj +
    matmul_atb + rank1_add; Mosaic on TPU, the interpreter elsewhere) — same dispatch
    convention as ``fleet_train(kernel=True)``.
    """
    if kernel:
        # lazy import: repro.kernels.ops itself imports this module
        from repro.kernels.ops import oselm_step_k1_kernel

        return oselm_step_k1_kernel(state, x, t, interpret=interpret)
    h = hidden(state.params, x[None, :], state.activation)[0]  # (Ñ,)
    p = state.p / state.forget
    ph = p @ h                                   # (Ñ,)
    denom = 1.0 + h @ ph                         # scalar
    p_new = p - jnp.outer(ph, ph) / denom
    err = t - h @ state.beta                     # (m,)
    beta_new = state.beta + jnp.outer(p_new @ h, err)
    return state.replace(beta=beta_new, p=p_new)


def oselm_predict(state: OSELMState, x: jnp.ndarray) -> jnp.ndarray:
    h = hidden(state.params, x, state.activation)
    return h @ state.beta


def oselm_loss(state: OSELMState, x: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """Per-sample MSE loss L(x,y) = 1/n Σ (xᵢ−yᵢ)² (paper's L)."""
    y = oselm_predict(state, x)
    return jnp.mean((t - y) ** 2, axis=-1)


@jax.jit
def _scan_train(state: OSELMState, xs: jnp.ndarray, ts: jnp.ndarray) -> OSELMState:
    def body(s, xt):
        x, t = xt
        return oselm_step_k1(s, x, t), None

    out, _ = jax.lax.scan(body, state, (xs, ts))
    return out


def oselm_train_sequential(
    state: OSELMState,
    xs: jnp.ndarray,
    ts: jnp.ndarray,
    *,
    kernel: bool = False,
    backend: str = "auto",
    interpret: bool | None = None,
) -> OSELMState:
    """Stream samples one at a time (k=1), jitted scan over the stream.

    ``kernel=True`` fuses the whole stream into one ingest-kernel call
    (``repro.kernels.fleet_ingest`` with a singleton device axis): the
    hidden projections batch into one matmul and (P, β) stay resident
    across the stream instead of round-tripping HBM per sample."""
    if kernel:
        from repro.kernels.fleet_ingest import fleet_ingest

        stacked = jax.tree.map(lambda leaf: leaf[None], state)
        out, _ = fleet_ingest(
            stacked, jnp.asarray(xs)[None], jnp.asarray(ts)[None],
            backend=backend, interpret=interpret,
        )
        return jax.tree.map(lambda leaf: leaf[0], out)
    return _scan_train(state, xs, ts)
