"""Plain reference of the OS-ELM fleet, independent of the program.

The same semantics as the system under test, written out directly in
jax.numpy and importing nothing from it:

- Eq. 13 boot: P0 = (H0'H0 + eps I)^-1, beta0 = P0 H0' X0, H0 = G(X0 a + b);
- a tick: each served device scores its window under its current model
  (mean squared reconstruction error, the ack's score), then runs the
  sequential k=1 OS-ELM updates over the window, sample by sample;
- Eq. 8 merge: U = (P + eps I)^-1, V = U beta; every participating
  device sums the participants' (U, V) over its neighbour set (a
  circular band of +-hops, or the whole fleet for a star) and solves
  P = (sum U + eps I)^-1, beta = P sum V; non-participants keep theirs.

``precision="highest"`` runs every matrix product at full float32
(``Precision.HIGHEST``), and so do the factorizations and triangular
solves (traced under ``jax.default_matmul_precision("highest")``: on a
TPU their blocked matrix products would otherwise take one bfloat16
pass). ``precision="bf16"`` is the control: the same
products with bfloat16 operands and float32 accumulation, the step a
faster lowering would be tempted to take. Both keep the state in
float32 and factor in float32.
"""
from __future__ import annotations

from functools import partial, wraps

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16")

_ACT = {
    "identity": lambda z: z,
    "sigmoid": lambda z: 1.0 / (1.0 + jnp.exp(-z)),
}


def _float32(fn):
    """Trace ``fn`` with every matrix product inside it at float32."""
    @wraps(fn)
    def run(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return run


def _dot(a, b, precision: str):
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def basis(key, n_features: int, n_hidden: int):
    """The shared random SLFN basis: alpha, b ~ Uniform(-1, 1) (Table 3)."""
    ka, kb = jax.random.split(key)
    alpha = jax.random.uniform(ka, (n_features, n_hidden), jnp.float32, -1.0, 1.0)
    bias = jax.random.uniform(kb, (n_hidden,), jnp.float32, -1.0, 1.0)
    return alpha, bias


def _spd_inverse_and_solve(u, rhs, ridge: float):
    n = u.shape[-1]
    eye = jnp.eye(n, dtype=u.dtype)
    c = jax.scipy.linalg.cho_factor(u + ridge * eye, lower=True)
    return (jax.scipy.linalg.cho_solve(c, eye),
            jax.scipy.linalg.cho_solve(c, rhs))


@partial(jax.jit, static_argnames=("activation", "ridge", "precision"))
@_float32
def boot(alpha, bias, x0, *, activation: str, ridge: float,
         precision: str = "highest"):
    """Eq. 13 for a block of devices: x0 (D, n0, F) -> (P0, beta0)."""
    act = _ACT[activation]

    def one(x):
        h = act(_dot(x, alpha, precision) + bias)
        u = _dot(h.T, h, precision)
        return _spd_inverse_and_solve(u, _dot(h.T, x, precision), ridge)

    return jax.vmap(one)(x0)


@partial(jax.jit, static_argnames=("activation", "precision"))
@_float32
def ingest(p, beta, alpha, bias, window, served, *, activation: str,
           precision: str = "highest"):
    """One tick for a block of devices: window (D, T, F), served (D,).

    Returns (P', beta', losses); unserved devices keep (P, beta)."""
    act = _ACT[activation]

    def one(p_i, b_i, xs):
        hs = act(_dot(xs, alpha, precision) + bias)          # (T, H)
        err = xs - _dot(hs, b_i, precision)
        loss = jnp.mean(jnp.mean(err * err, axis=-1))

        def step(carry, hx):
            pp, bb = carry
            h, x = hx
            ph = _dot(pp, h, precision)
            pp = pp - jnp.outer(ph, ph) / (1.0 + _dot(h, ph, precision))
            e = x - _dot(h, bb, precision)
            bb = bb + jnp.outer(_dot(pp, h, precision), e)
            return (pp, bb), None

        (p2, b2), _ = jax.lax.scan(step, (p_i, b_i), (hs, xs))
        return p2, b2, loss

    p2, b2, losses = jax.vmap(one)(p, beta, window)
    sel = served.astype(bool)[:, None, None]
    return jnp.where(sel, p2, p), jnp.where(sel, b2, beta), losses


@partial(jax.jit, static_argnames=("ridge", "precision"))
@_float32
def payload(p, beta, mask, *, ridge: float, precision: str = "highest"):
    """Masked Eq. 15 payloads of a block: U = (P + eps I)^-1, V = U beta."""
    n = p.shape[-1]
    eye = jnp.eye(n, dtype=p.dtype)

    def one(p_i, b_i):
        c = jax.scipy.linalg.cho_factor(p_i + ridge * eye, lower=True)
        u = jax.scipy.linalg.cho_solve(c, eye)
        u = 0.5 * (u + u.T)
        return u, _dot(u, b_i, precision)

    u, v = jax.vmap(one)(p, beta)
    m = mask.astype(u.dtype)[:, None, None]
    return u * m, v * m


@partial(jax.jit, static_argnames=("ridge",))
@_float32
def solve(u, v, *, ridge: float):
    """Eq. 8's last step for a batch of merged sums: (P, beta)."""
    return jax.vmap(lambda uu, vv: _spd_inverse_and_solve(uu, vv, ridge))(u, v)


@partial(jax.jit, static_argnames=("hops",))
def band_sum(x, *, hops: int):
    """Circular +-hops neighbour sum over the device axis (self included)."""
    return sum(jnp.roll(x, o, axis=0) for o in range(-hops, hops + 1))


@partial(jax.jit, static_argnames=("hops",))
def band_sum_halo(prev, x, nxt, *, hops: int):
    """``band_sum`` of one block of a ring split into blocks: ``prev`` is
    the last ``hops`` rows of the block before it, ``nxt`` the first
    ``hops`` rows of the block after it. The terms are added in
    ``band_sum``'s order."""
    ext = jnp.concatenate([prev, x, nxt])
    n = x.shape[0]
    return sum(ext[hops - o:hops - o + n] for o in range(-hops, hops + 1))


def band_sums(xs, hops: int, devices):
    """``band_sum`` over the concatenation of the blocks ``xs``, computed
    block by block on ``devices[i]``: each block takes ``hops`` rows from
    each neighbouring block, and the last block's neighbour is block 0."""
    if min(int(x.shape[0]) for x in xs) < hops:
        raise ValueError(f"a ring block holds fewer than hops={hops} devices "
                         f"(blocks of {[int(x.shape[0]) for x in xs]})")
    if len(xs) == 1:
        return [band_sum(xs[0], hops=hops)]
    out = []
    for i, (x, dev) in enumerate(zip(xs, devices)):
        before, after = xs[i - 1], xs[(i + 1) % len(xs)]
        halo = jax.device_put((before[before.shape[0] - hops:], after[:hops]), dev)
        out.append(band_sum_halo(halo[0], x, halo[1], hops=hops))
    return out


@jax.jit
def keep_where(mask, new_p, new_b, p, beta):
    sel = mask.astype(bool)[:, None, None]
    return jnp.where(sel, new_p, p), jnp.where(sel, new_b, beta)


class Fleet:
    """Reference fleet state held on the device in blocks of devices:
    block i (a contiguous device range) lives on ``devices[i]``, and its
    work runs there."""

    def __init__(self, blocks, devices, *, activation: str, ridge: float,
                 precision: str = "highest"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        if len(devices) != len(blocks):
            raise ValueError(f"{len(blocks)} blocks on {len(devices)} devices")
        self.devices = list(devices)
        self.blocks = [jax.device_put(b, d) for b, d in zip(blocks, self.devices)]
        self.activation = activation
        self.ridge = ridge
        self.precision = precision

    @property
    def n_devices(self) -> int:
        return sum(int(p.shape[0]) for p, _ in self.blocks)

    def _bounds(self):
        bounds, lo = [], 0
        for p, _ in self.blocks:
            bounds.append((lo, lo + int(p.shape[0])))
            lo = bounds[-1][1]
        return bounds

    def tick(self, alpha, bias, window_fn, served):
        """window_fn(lo, hi) -> (hi-lo, T, F) host array; served (D,) bool.
        Returns the (D,) losses as a host array."""
        import numpy as np

        losses = np.empty(self.n_devices, np.float32)
        for i, (lo, hi) in enumerate(self._bounds()):
            p, b = self.blocks[i]
            window, srv = jax.device_put((window_fn(lo, hi), served[lo:hi]),
                                         self.devices[i])
            p, b, lj = ingest(p, b, alpha, bias, window, srv,
                              activation=self.activation, precision=self.precision)
            self.blocks[i] = (p, b)
            losses[lo:hi] = np.asarray(lj)
        return losses

    def merge(self, mask, topology: str, hops: int = 0):
        """One masked Eq. 8 round; topology "star" or "ring"."""
        bounds = self._bounds()
        masks = [jax.device_put(mask[lo:hi], d) for (lo, hi), d in zip(bounds, self.devices)]
        parts = [
            payload(p, b, m, ridge=self.ridge, precision=self.precision)
            for (p, b), m in zip(self.blocks, masks)
        ]
        if topology == "star":
            home = self.devices[0]
            su = sum(jax.device_put(u.sum(0), home) for u, _ in parts)
            sv = sum(jax.device_put(v.sum(0), home) for _, v in parts)
            merged = solve(su[None], sv[None], ridge=self.ridge)
            for i, ((p, b), (lo, hi)) in enumerate(zip(self.blocks, bounds)):
                d = hi - lo
                pm, bm = jax.device_put(merged, self.devices[i])
                self.blocks[i] = keep_where(
                    masks[i],
                    jnp.broadcast_to(pm, (d,) + pm.shape[1:]),
                    jnp.broadcast_to(bm, (d,) + bm.shape[1:]), p, b,
                )
        elif topology == "ring":
            su = band_sums([u for u, _ in parts], hops, self.devices)
            sv = band_sums([v for _, v in parts], hops, self.devices)
            del parts
            for i, (p, b) in enumerate(self.blocks):
                pm, bm = solve(su[i], sv[i], ridge=self.ridge)
                self.blocks[i] = keep_where(masks[i], pm, bm, p, b)
        else:
            raise ValueError(f"unknown topology {topology!r}")

    def host_state(self):
        import numpy as np

        p = np.concatenate([np.asarray(p) for p, _ in self.blocks])
        b = np.concatenate([np.asarray(b) for _, b in self.blocks])
        return p, b
