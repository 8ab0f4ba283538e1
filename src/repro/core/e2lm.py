"""E²LM intermediate form + the paper's cooperative model update (§3.2, §4).

E²LM expresses the ELM solution through additive sufficient statistics

    U = HᵀH,   V = Hᵀt,   β̂ = U⁻¹V                     (Eq. 6)

which combine across datasets by plain addition (Eq. 8):

    U' = U + ΔU,   V' = V + ΔV

The paper's §4.1 modification extracts (U, V) from a *sequentially*
trained OS-ELM without storing past data (Eq. 15):

    Uᵢ = Kᵢ = Pᵢ⁻¹,   Vᵢ = Uᵢ βᵢ

and §4.2 defines the cooperative model update: devices exchange (U, V),
add them, and recover P ← U'⁻¹, β ← U'⁻¹V'.

Because Eq. 8 is associative and commutative, the N-device merge is an
all-reduce — `merge_mesh` in `repro.federated.mesh_federation` runs it
as one `jax.lax.psum`. Here we implement the algebra itself, including
the subtraction/replacement operations the paper notes E²LM supports.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.elm import invert_u, solve_beta
from repro.core.oselm import OSELMState


class UV(NamedTuple):
    """The exchanged intermediate results — the *only* payload devices
    share (never raw data; the paper's privacy argument)."""

    u: jnp.ndarray  # (Ñ, Ñ)  = Σ HᵀH
    v: jnp.ndarray  # (Ñ, m)  = Σ Hᵀt

    @property
    def nbytes(self) -> int:
        return int(self.u.size * self.u.dtype.itemsize + self.v.size * self.v.dtype.itemsize)


def uv_of(p: jnp.ndarray, beta: jnp.ndarray, *, ridge: float = 0.0) -> UV:
    """Eq. 15 on a bare (P, β) pair: U = P⁻¹, V = Uβ."""
    u = invert_u(p, ridge=ridge)
    u = 0.5 * (u + u.T)  # P is SPD in exact arithmetic; re-symmetrize f32 noise
    # full f32: at DEFAULT precision a TPU runs this as one bf16 pass,
    # and the merge solve amplifies that error by κ(U)
    v = jnp.matmul(u, beta, precision=jax.lax.Precision.HIGHEST)
    return UV(u=u, v=v)


def to_uv(state: OSELMState, *, ridge: float = 0.0) -> UV:
    """Eq. 15: U = P⁻¹, V = Uβ.

    Only computed when results are shipped (the paper notes there is no
    need to maintain U,V per input chunk).
    """
    return uv_of(state.p, state.beta, ridge=ridge)


def uv_add(a: UV, b: UV) -> UV:
    """Eq. 8 — dataset union."""
    return UV(u=a.u + b.u, v=a.v + b.v)


def uv_sub(a: UV, b: UV) -> UV:
    """Dataset removal (supported by E²LM per §3.2 last paragraph)."""
    return UV(u=a.u - b.u, v=a.v - b.v)


def uv_replace(a: UV, old: UV, new: UV) -> UV:
    """Dataset replacement = subtraction followed by addition."""
    return uv_add(uv_sub(a, old), new)


def uv_sum(parts: Sequence[UV]) -> UV:
    """N-way merge (tree-sum; order-independent up to f32 rounding)."""
    u = jnp.sum(jnp.stack([p.u for p in parts]), axis=0)
    v = jnp.sum(jnp.stack([p.v for p in parts]), axis=0)
    return UV(u=u, v=v)


def from_uv(state: OSELMState, uv: UV, *, ridge: float = 0.0) -> OSELMState:
    """§4.2 step 5: P ← U⁻¹, β ← U⁻¹V — re-enter sequential training
    with the merged model."""
    p = invert_u(uv.u, ridge=ridge)
    beta = solve_beta(uv.u, uv.v, ridge=ridge)
    return state.replace(beta=beta, p=p)


@jax.jit
def cooperative_update(state: OSELMState, *remote: UV) -> OSELMState:
    """The full one-shot cooperative model update (§4.2 steps 2–5) as a
    single jitted call: local (U,V) + Σ remote (U,V) → merged state."""
    local = to_uv(state)
    merged = local
    for r in remote:
        merged = uv_add(merged, r)
    return from_uv(state, merged)
