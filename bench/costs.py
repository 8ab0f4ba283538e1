"""Operations and bytes the OS-ELM fleet's work needs, from unpadded widths.

The counts follow the algorithm, not a lowering: the sequential k=1
chain per sample and the Eq. 8 merge per round, at the configuration's
own widths (n features, H hidden, m = n outputs for the autoencoder).
Padding, layouts and block-Woodbury rewrites do not change them, so a
later change to the kernels is measured against the same work.

Conventions: a multiply-add is 2 operations, every other elementwise
operation 1; a sigmoid is 4 (negate, exp, add, divide), identity 0.
A Cholesky factor of an H x H matrix is H^3 / 3, a triangular solve
with k right-hand sides H^2 k, so an inverse from its factor (two
solves against I) is 2 H^3 and a solve for k columns 2 H^2 k.
Bytes are float32 state and data that must cross HBM once per call.
"""
from __future__ import annotations

import json
from pathlib import Path

F32 = 4
ACTIVATION_OPS = {"identity": 0, "sigmoid": 4}
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def load_peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (peaks.json); a
    kind that is not in the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {PEAKS_FILE.name} "
            f"(have {sorted(table)})"
        )
    return table[device_kind]


def sample_flops(n: int, h: int, activation: str) -> int:
    """One device, one sample: the pre-train score plus one k=1 step.

    score: hidden 2nH + H + aH, reconstruction 2Hm, error/square/sum 3m;
    step: P h 2H^2, h.Ph 2H + 1, P - (Ph)(Ph)'/d 2H^2 + H,
    error x - h beta 2Hm + m, P'h 2H^2, beta + (P'h) e' 2Hm."""
    m = n
    a = ACTIVATION_OPS[activation]
    score = 2 * n * h + h + a * h + 2 * h * m + 3 * m
    step = 6 * h * h + 4 * h * m + 3 * h + m + 1
    return score + step


def steps_per_row(traffic: dict) -> int:
    """k=1 steps a served device row takes per tick: the feed's window, or
    the served batch (a row with fewer requests cycles its own samples)."""
    return int(traffic["window"] if "window" in traffic else traffic["batch"])


def tick_bytes(n: int, h: int, window: int) -> int:
    """One device, one tick: read P, beta and the window; write P, beta
    and the loss."""
    m = n
    state = F32 * (h * h + h * m)
    return 2 * state + F32 * window * n + F32


def basis_bytes(n: int, h: int) -> int:
    """The shared basis (alpha, b), read once per call."""
    return F32 * (n * h + h)


def _inverse_flops(h: int) -> float:
    return h ** 3 / 3 + 2 * h ** 3


def merge_flops(n: int, h: int, devices: int, participants: int,
                topology: str, hops: int = 0) -> float:
    """One masked Eq. 8 round.

    Each participant forms U = (P + eps I)^-1 (inverse, symmetrize H^2)
    and V = U beta (2 H^2 m). A ring sums 2 hops + 1 payloads per
    participant and solves per participant; a star sums all of them
    once and solves once (inverse plus 2 H^2 m for beta)."""
    m = n
    payload = participants * (_inverse_flops(h) + h * h + 2 * h * h * m)
    solve = _inverse_flops(h) + 2 * h * h * m
    if topology == "ring":
        mix = participants * 2 * hops * (h * h + h * m)
        return payload + mix + participants * solve
    if topology == "star":
        mix = max(participants - 1, 0) * (h * h + h * m)
        return payload + mix + solve
    raise ValueError(f"unknown topology {topology!r}")


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    t_ops = flops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "flops") if t_ops >= t_mem else (t_mem, "bytes")
