"""Fused fleet-ingest kernel family — the per-tick training hot path.

One serve tick ingests a window of samples on every device: score the
incoming window under the CURRENT model (the pre-train ``ae_score``
drift signal, §3.4 / arXiv:2203.01077), then run the paper's k=1
sequential OS-ELM updates (Eqs. 9–13, scalar-reciprocal fast path,
forgetting factor λ) over the window. The reference implementation —
a score pass plus ``vmap``-of-``lax.scan`` over single-sample RLS
steps — round-trips each device's P (Ñ×Ñ) and β (Ñ×m) through HBM
once **per sample** and walks the window twice.

This module fuses the whole tick into one pass with two lowerings:

- ``fleet_ingest_kernel`` — ONE ``pallas_call`` whose grid tiles the
  device axis in blocks of ``block_d`` devices. Each program keeps its
  devices' (P, β) resident in VMEM for the entire window: the hidden
  projections H = G(xα+b) for the whole window are one MXU matmul, the
  pre-train reconstruction errors (the drift signal) fall out of the
  same H against the tick-start β, and an in-kernel ``fori_loop`` then
  applies the k=1 rank-1 RLS updates sample by sample. Per-device
  state touches HBM once per tick instead of once per sample. Sample
  slots padded up to the sublane tile are masked to exact identity
  (they never update P/β and contribute nothing to the score).
  Mosaic on TPU, the interpreter elsewhere (``resolve_interpret``, the
  one decision every kernel in this package defers to).

- ``fleet_ingest_xla`` — the same one-pass dataflow lowered through
  XLA for backends without Pallas execution (this container's CPU):
  batched H + pre-train errors, then the window's k=1 chain applied
  one *block* of ``block_t`` samples at a time in its exact batched
  Woodbury form.  c sequential rank-1 RLS steps are algebraically one
  rank-c update — with forgetting they solve
  min_β Σ_t λ^{c-t} ‖h_tβ − t_t‖² + λ^c ‖β − β₀‖²_{K₀} — so

      P' = P/λ^c − (P/λ^c) H̃ᵀ (I + H̃ (P/λ^c) H̃ᵀ)⁻¹ H̃ (P/λ^c)
      β' = β + P' Hᵀ W E₀,      H̃ = W^{1/2} H,  W = diag(λ^{c-t})

  where E₀ = T − Hβ is exactly the pre-train error the drift score
  already computed (the update re-uses it; the window is never walked
  twice). Equality with the sequential chain is exact in real
  arithmetic; in f32 the c×c Cholesky reorders the accumulation, so
  the bit-level drift vs the sequential oracle is a little wider than
  the Pallas kernel's (tests bound both). Padded sample slots carry
  weight 0, which is an exact identity step.

Both lowerings accept an optional supervised ``targets`` window; the
default (``None``) is the paper's autoencoder tick (targets = inputs,
the x block is not duplicated). ``fleet_ingest`` dispatches between
the two (``backend="auto"`` picks Pallas on TPU, the fused XLA form
elsewhere) and is what ``fleet_train(kernel=True)``,
``oselm_train_sequential(kernel=True)`` and the runtime's kernel
ingest ride on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.activations import get_activation
from repro.core.elm import cho_factor
from repro.core.oselm import OSELMState

__all__ = [
    "fleet_ingest",
    "fleet_ingest_kernel",
    "fleet_ingest_paged",
    "fleet_ingest_xla",
    "ingest_padding",
    "native_kernels",
    "resolve_backend",
    "resolve_interpret",
    "validate_shared_basis",
]

_LANE = 128
_SUBLANE = 8


def _pad_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def native_kernels() -> bool:
    """Whether the Pallas kernels lower natively here (TPU). Where they
    do, the runtimes always run the fused ingest and the topology-merge
    kernels; elsewhere the XLA forms are the fast path."""
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str) -> str:
    """The ONE place the ``"auto"`` ingest dispatch is decided: Pallas
    only where it compiles natively (TPU), the fused XLA form elsewhere.
    Shared by the dispatcher, the padding warning and the sharded
    ingest's check_rep decision so they can never disagree."""
    if backend == "auto":
        return "pallas" if native_kernels() else "xla"
    if backend not in ("pallas", "xla"):
        raise ValueError(f"unknown ingest backend {backend!r}")
    return backend


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The ONE place the Pallas lowering is decided: Mosaic on TPU, the
    interpreter elsewhere. Every kernel entry point defers here, so a
    kernel that Mosaic refuses fails the run instead of quietly falling
    back. An explicit ``False`` is honoured off the chip (compiling for
    a described TPU from a CPU host); the interpreter is refused on a
    TPU."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "the Pallas interpreter was requested on a TPU; pass "
            "interpret=None so the kernel lowers through Mosaic"
        )
    return bool(interpret)


def ingest_padding(n_samples: int, block_t: int = 32) -> tuple[int, int]:
    """(pallas_pad, xla_pad): sample slots each lowering pads the window
    with. Padded slots are masked to exact identity steps; callers warn
    when nonzero (see ``fleet_train_rounds``)."""
    bt = min(block_t, n_samples)
    return (
        _pad_up(n_samples, _SUBLANE) - n_samples,
        _pad_up(n_samples, bt) - n_samples,
    )


def validate_shared_basis(states: OSELMState) -> None:
    """Raise if a stacked fleet does NOT carry the fleet-shared SLFN
    basis the fused ingest assumes (``init_fleet`` broadcasts ONE
    (α, b); Eq. 8 merging requires it — see PR 1 note). A fleet stacked
    from per-device random bases would otherwise be silently projected
    through device 0's basis. Spot-checks first vs last device; a no-op
    under tracing (the jitted lowerings can't inspect values), so the
    non-jitted entry points — the ``fleet_ingest`` dispatcher, the
    rounds/sharded wrappers and ``FleetRuntime.__init__`` — call it
    where the arrays are still concrete."""
    alpha = states.params.alpha
    if alpha.ndim != 3 or isinstance(alpha, jax.core.Tracer):
        return
    import numpy as np

    if not np.array_equal(np.asarray(alpha[0]), np.asarray(alpha[-1])):
        raise ValueError(
            "fused ingest requires the fleet-shared SLFN basis "
            "(init_fleet broadcasts one (α, b)); this stack carries "
            "per-device bases, which the kernel cannot honor"
        )


def _shared_basis(states: OSELMState) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The fleet's (α, b): device 0's copy of the shared basis (see
    ``validate_shared_basis``; inside the jitted lowerings the leaves
    are tracers, so the invariant is checked at the concrete entry
    points, not here). Single-device states pass through unchanged."""
    alpha, bias = states.params.alpha, states.params.bias
    if alpha.ndim == 3:  # stacked fleet: (D, n, Ñ) identical copies
        alpha, bias = alpha[0], bias[0]
    return alpha, bias


# ------------------------------------------------------------- pallas kernel


def _ingest_kernel(*refs, tied: bool, t_real: int, m_real: int, nh_real: int,
                   activation: str, forget: float):
    """One grid step = ``block_d`` devices' whole tick, VMEM-resident.

    Layouts (B = block_d, TP sublane-padded, NL/ML/NHL lane-padded, NHR
    sublane-padded): x (B, TP, NL), targets (B, TP, ML) — the x block
    itself when ``tied`` — α (NL, NHL), bias (1, NHL), P (B, NHR, NHL),
    β (B, NHR, ML), the hidden-projection scratch h (B, TP, NHL). P/β
    rows ≥ Ñ and lanes ≥ Ñ (resp. m) are zero and stay zero.

    Only the window's hidden projection runs on the MXU (one 2-D
    matmul at HIGHEST precision). Each rank-1 RLS step is a handful of
    f32 VPU products and lane/sublane reductions over one device's 2-D
    (P, β) — no batched matvec and no value slicing, which Mosaic does
    not lower. A vector moves between its lane and sublane layouts by
    a reduction against the identity, which is exact (×1, +0).
    """
    if tied:
        x_ref, a_ref, b_ref, p_ref, be_ref, po_ref, bo_ref, l_ref, h_ref = refs
        tt_ref = x_ref
    else:
        (x_ref, tt_ref, a_ref, b_ref, p_ref, be_ref,
         po_ref, bo_ref, l_ref, h_ref) = refs
    bd, tp, nl = x_ref.shape
    nhr, nhl = p_ref.shape[1:]
    g = get_activation(activation)
    # hidden projection for all B windows: ONE MXU matmul + epilogue.
    # Lanes ≥ Ñ are masked off — G(0·α + 0) need not be 0 (sigmoid!).
    h = g(
        jax.lax.dot_general(
            x_ref[...].reshape(bd * tp, nl), a_ref[...],
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        + b_ref[...]
    )
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, nhl), 1)
    h_ref[...] = jnp.where(lane < nh_real, h, 0.0).reshape(bd, tp, nhl)
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (nhr, nhl), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (nhr, nhl), 1)
    ).astype(jnp.float32)

    def device(b, carry):
        be0 = be_ref[b]                                          # (NHR, ML)

        def step(t, c):
            p, be, sq = c
            h_row = h_ref[b, pl.ds(t, 1), :]                     # (1, NHL)
            t_row = tt_ref[b, pl.ds(t, 1), :]                    # (1, ML)
            h_col = jnp.sum(h_row * eye, axis=1, keepdims=True)  # (NHR, 1)
            # pre-train drift signal: error under the tick-start β
            e0 = t_row - jnp.sum(h_col * be0, axis=0, keepdims=True)
            sq = sq + jnp.sum(e0 * e0, keepdims=True)
            pf = p / forget
            ph_col = jnp.sum(pf * h_row, axis=1, keepdims=True)  # P h
            ph_row = jnp.sum(ph_col * eye, axis=0, keepdims=True)
            denom = 1.0 + jnp.sum(h_col * ph_col, keepdims=True)
            p_new = pf - ph_col * ph_row / denom
            err = t_row - jnp.sum(h_col * be, axis=0, keepdims=True)
            gain = jnp.sum(p_new * h_row, axis=1, keepdims=True)  # P' h
            return p_new, be + gain * err, sq

        # padded sample slots are never visited: the loop stops at T
        p, be, sq = jax.lax.fori_loop(
            0, t_real, step,
            (p_ref[b], be0, jnp.zeros((1, 1), jnp.float32)),
        )
        po_ref[b] = p
        bo_ref[b] = be
        l_ref[b] = jnp.broadcast_to(sq / (t_real * m_real), (1, _LANE))
        return carry

    jax.lax.fori_loop(0, bd, device, 0)


# Of v5e's 16 MiB default scoped VMEM, what one ingest program may
# plan for; the rest is headroom for Mosaic's own spills.
_VMEM_BUDGET = 12 * 1024 * 1024
_MAX_BLOCK_D = 64


def _ingest_block_d(d: int, tp: int, nl: int, ml: int, nhr: int, nhl: int,
                   tied: bool = True) -> int:
    """Devices per ingest grid program, derived from the VMEM budget:
    the largest power of two whose double-buffered x/targets, (P, β)
    in and out, loss rows and hidden scratch fit next to the shared
    basis and one device's live temporaries."""
    f32 = 4
    shared = 2 * f32 * (nl * nhl + nhl)                   # α, bias
    work = f32 * (6 * nhr * nhl + 5 * nhr * ml)           # one device's step
    per_dev = f32 * (
        2 * tp * nl + (0 if tied else 2 * tp * ml)        # x (+ targets)
        + 4 * nhr * (nhl + ml)                            # P, β in + out
        + 2 * _LANE + tp * nhl                            # losses, h scratch
    )
    fit = (_VMEM_BUDGET - shared - work) // per_dev
    if fit < 1:
        raise ValueError(
            f"one device's ingest state (Ñ rows {nhr}, lanes {nhl}, "
            f"m lanes {ml}, window {tp}) exceeds the {_VMEM_BUDGET} B "
            "VMEM budget"
        )
    return int(min(1 << (int(fit).bit_length() - 1), _MAX_BLOCK_D, d))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fleet_ingest_kernel(
    states: OSELMState,
    window: jnp.ndarray,
    targets: jnp.ndarray | None = None,
    *,
    block_d: int | None = None,
    interpret: bool | None = None,
) -> tuple[OSELMState, jnp.ndarray]:
    """Fused Pallas tick ingest over a stacked fleet.

    ``window`` is (D, T, n), ``targets`` (D, T, m) or None for the
    autoencoder tick (targets = window); returns (trained fleet, (D,)
    mean pre-train prediction loss of each device's window — the drift
    signal). Each grid program holds ``block_d`` devices' (P, β) in
    VMEM across the whole window: HBM sees the state once per tick,
    not once per sample. ``block_d=None`` derives it from the VMEM
    budget (``_ingest_block_d``).
    """
    window = jnp.asarray(window)
    d, t, n = window.shape
    nh, m = states.beta.shape[1], states.beta.shape[2]
    tied = targets is None
    if tied:
        assert m == n, "autoencoder ingest needs m == n"
    else:
        targets = jnp.asarray(targets)
        assert targets.shape == (d, t, m), (targets.shape, (d, t, m))

    tp = _pad_up(t, _SUBLANE)
    nl = _pad_up(n, _LANE)
    ml = _pad_up(m, _LANE)
    nhl = _pad_up(nh, _LANE)
    nhr = _pad_up(nh, _SUBLANE)
    bd = (_ingest_block_d(d, tp, nl, ml, nhr, nhl, tied) if block_d is None
          else min(block_d, d))
    dp = _pad_up(d, bd)

    alpha, bias = _shared_basis(states)
    xw = jnp.pad(window, ((0, dp - d), (0, tp - t), (0, nl - n)))
    ap = jnp.pad(alpha, ((0, nl - n), (0, nhl - nh)))
    bp = jnp.pad(bias, (0, nhl - nh))[None, :]
    pp = jnp.pad(states.p, ((0, dp - d), (0, nhr - nh), (0, nhl - nh)))
    bep = jnp.pad(states.beta, ((0, dp - d), (0, nhr - nh), (0, ml - m)))

    operands = [xw]
    in_specs = [pl.BlockSpec((bd, tp, nl), lambda i: (i, 0, 0))]
    if not tied:
        operands.append(jnp.pad(targets, ((0, dp - d), (0, tp - t), (0, ml - m))))
        in_specs.append(pl.BlockSpec((bd, tp, ml), lambda i: (i, 0, 0)))
    operands += [ap, bp, pp, bep]
    in_specs += [
        pl.BlockSpec((nl, nhl), lambda i: (0, 0)),
        pl.BlockSpec((1, nhl), lambda i: (0, 0)),
        pl.BlockSpec((bd, nhr, nhl), lambda i: (i, 0, 0)),
        pl.BlockSpec((bd, nhr, ml), lambda i: (i, 0, 0)),
    ]

    kern = functools.partial(
        _ingest_kernel, tied=tied, t_real=t, m_real=m, nh_real=nh,
        activation=states.activation, forget=states.forget,
    )
    p_out, b_out, l_out = pl.pallas_call(
        kern,
        grid=(dp // bd,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bd, nhr, nhl), lambda i: (i, 0, 0)),
            pl.BlockSpec((bd, nhr, ml), lambda i: (i, 0, 0)),
            pl.BlockSpec((bd, 1, _LANE), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((dp, nhr, nhl), jnp.float32),
            jax.ShapeDtypeStruct((dp, nhr, ml), jnp.float32),
            jax.ShapeDtypeStruct((dp, 1, _LANE), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, tp, nhl), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(*operands)
    new_states = states.replace(
        p=p_out[:d, :nh, :nh].astype(states.p.dtype),
        beta=b_out[:d, :nh, :m].astype(states.beta.dtype),
    )
    return new_states, l_out[:d, 0, 0]


# --------------------------------------------------------- fused XLA lowering


@functools.partial(jax.jit, static_argnames=("block_t",))
def fleet_ingest_xla(
    states: OSELMState,
    window: jnp.ndarray,
    targets: jnp.ndarray | None = None,
    *,
    block_t: int = 32,
) -> tuple[OSELMState, jnp.ndarray]:
    """``fleet_ingest_kernel``'s dataflow lowered through plain XLA —
    the hot path on backends where Pallas only interprets (CPU).

    One pass over the window: batched hidden projections, the pre-train
    drift score, and the k=1 chain applied ``block_t`` samples at a time
    in its exact batched Woodbury form (module docstring).
    """
    window = jnp.asarray(window)
    d, t, n = window.shape
    nh, m = states.beta.shape[1], states.beta.shape[2]
    if targets is None:
        assert m == n, "autoencoder ingest needs m == n"
        targets = window
    else:
        targets = jnp.asarray(targets)
        assert targets.shape == (d, t, m), (targets.shape, (d, t, m))
    alpha, bias = _shared_basis(states)
    g = get_activation(states.activation)
    h_all = g(jnp.einsum("dtn,nh->dth", window, alpha) + bias)  # (D, T, Ñ)

    # pre-train drift signal under the tick-start β
    e0_all = targets - jnp.einsum("dth,dhm->dtm", h_all, states.beta)
    losses = jnp.mean(e0_all * e0_all, axis=(1, 2))

    bt = min(block_t, t)
    n_blocks = -(-t // bt)
    tp = n_blocks * bt
    if tp != t:  # ragged tail block: zero-weight (exact identity) slots
        h_all = jnp.pad(h_all, ((0, 0), (0, tp - t), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, tp - t), (0, 0)))
    h_blk = h_all.reshape(d, n_blocks, bt, nh).transpose(1, 0, 2, 3)
    t_blk = targets.reshape(d, n_blocks, bt, m).transpose(1, 0, 2, 3)
    forget = states.forget

    def block_update(p, beta, hb, e0, c):
        """One block's exact rank-c Woodbury update; ``e0`` are the
        PRE-BLOCK errors (targets − hβ under the block-entry β)."""
        # weights λ^{c-1-t} for live slots, 0 for padded ones
        idx = jnp.arange(bt)
        w = jnp.where(idx < c, forget ** (c - 1 - idx).astype(p.dtype), 0.0)
        lam_c = jnp.asarray(forget, p.dtype) ** c
        sw = jnp.sqrt(w)
        hw = hb * sw[None, :, None]                     # W^1/2 H
        pl_ = p / lam_c
        php = jnp.einsum("dtn,dnm->dtm", hw, pl_)       # H̃ P/λ^c
        s = jnp.einsum("dtn,dun->dtu", php, hw)
        s = s + jnp.eye(bt, dtype=s.dtype)
        cho = cho_factor(s)
        gain = jax.scipy.linalg.cho_solve(cho, php)     # S⁻¹ H̃ P/λ^c
        p_new = pl_ - jnp.einsum("dtn,dtm->dnm", php, gain)
        # β' = β + P' Hᵀ W E₀
        hwe = jnp.einsum("dtn,dtm->dnm", hw, e0 * sw[None, :, None])
        beta_new = beta + jnp.einsum("dnm,dmk->dnk", p_new, hwe)
        return p_new, beta_new

    # block 0's pre-block β IS the tick-start β, so its errors are the
    # drift-score errors already computed — re-used, not recomputed
    # (block 0 is always fully live: tail padding only reaches the last
    # block, and a padded window implies n_blocks >= 2)
    p, beta = block_update(
        states.p, states.beta, h_blk[0], e0_all[:, :bt], jnp.int32(bt)
    )
    if n_blocks > 1:
        c_real = jnp.minimum(
            jnp.full(n_blocks - 1, bt, jnp.int32),
            t - bt * jnp.arange(1, n_blocks, dtype=jnp.int32),
        )

        def body(carry, blk):
            p, beta = carry
            hb, tb, c = blk
            e0 = tb - jnp.einsum("dtn,dnm->dtm", hb, beta)  # pre-BLOCK errors
            return block_update(p, beta, hb, e0, c), None

        (p, beta), _ = jax.lax.scan(
            body, (p, beta), (h_blk[1:], t_blk[1:], c_real)
        )
    return states.replace(p=p, beta=beta), losses


# ------------------------------------------------------------------ dispatch


def fleet_ingest_paged(
    p: jnp.ndarray,
    beta: jnp.ndarray,
    alpha: jnp.ndarray,
    bias: jnp.ndarray,
    window: jnp.ndarray,
    *,
    activation: str = "sigmoid",
    forget: float = 1.0,
    backend: str = "auto",
    block_t: int = 32,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paged entry of the fused ingest family: one arena page's raw
    leaves instead of a stacked ``OSELMState``.

    The cohort-paged runtime streams (C, Ñ, Ñ) + (C, Ñ, m) pages of a
    host arena through the device while the (n, Ñ) shared SLFN basis
    stays put — so the caller holds no pytree, just the four leaves.
    This wrapper rebuilds the page as an ``OSELMState`` carrying the
    UNSTACKED basis (``_shared_basis`` passes a 2-D (α, b) straight
    through both lowerings; no per-device broadcast is materialized)
    and returns raw leaves again: ``(P', β', losses)`` with the same
    per-device pre-train drift scores as ``fleet_ingest``.
    """
    from repro.core.elm import SLFNParams

    states = OSELMState(
        params=SLFNParams(alpha=alpha, bias=bias),
        beta=beta,
        p=p,
        activation=activation,
        forget=forget,
    )
    trained, losses = fleet_ingest(
        states, window, backend=backend,
        block_t=block_t, interpret=interpret,
    )
    return trained.p, trained.beta, losses


def fleet_ingest(
    states: OSELMState,
    window: jnp.ndarray,
    targets: jnp.ndarray | None = None,
    *,
    backend: str = "auto",
    block_t: int = 32,
    interpret: bool | None = None,
) -> tuple[OSELMState, jnp.ndarray]:
    """Fused tick ingest: (trained fleet, per-device pre-train score).

    ``backend="pallas"`` runs the VMEM-resident kernel, ``"xla"`` the
    fused Woodbury lowering, ``"auto"`` picks Pallas only where it
    compiles natively (TPU) and the XLA form elsewhere — both are the
    same dataflow and match the sequential reference (tests bound
    both). ``interpret=None`` resolves per platform
    (``resolve_interpret``): Mosaic on TPU, the Pallas interpreter on
    CPU.
    """
    validate_shared_basis(states)  # no-op when already under a trace
    backend = resolve_backend(backend)
    if backend == "pallas":
        return fleet_ingest_kernel(
            states, window, targets, interpret=interpret,
        )
    return fleet_ingest_xla(states, window, targets, block_t=block_t)
