"""The paper's client/server cooperative-update protocol (§4.2, Fig. 4/5).

Edge devices sequentially train OS-ELM autoencoders; when a cooperative
update is requested they (1) compute (U, V) by Eq. 15, (2) upload to the
server, (3) download the peers' intermediate results they demand,
(4) add them (Eq. 8), and (5) recover (P, β) (Eq. 6). The server is a
dumb exchange — merging can equally run on-device (§2 note in paper).

Communication cost is accounted per payload: Ñ(Ñ+m) floats per upload,
independent of how much data was trained — this is the paper's
communication-cost claim vs. R-round FedAvg.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    OSELMState,
    UV,
    ae_score,
    ae_train_stream,
    from_uv,
    init_autoencoder,
    to_uv,
    uv_add,
)


@dataclasses.dataclass
class Payload:
    """Serialized (U, V) — what actually crosses the network."""

    device_id: str
    u: np.ndarray
    v: np.ndarray
    version: int = 0

    @property
    def nbytes(self) -> int:
        return self.u.nbytes + self.v.nbytes

    def to_uv(self) -> UV:
        return UV(u=jnp.asarray(self.u), v=jnp.asarray(self.v))

    @staticmethod
    def from_uv(device_id: str, uv: UV, version: int = 0) -> "Payload":
        return Payload(device_id, np.asarray(uv.u), np.asarray(uv.v), version)


@dataclasses.dataclass
class CommLog:
    uploads: int = 0
    downloads: int = 0
    bytes_up: int = 0
    bytes_down: int = 0

    def up(self, payload: Payload) -> None:
        self.uploads += 1
        self.bytes_up += payload.nbytes

    def down(self, payload: Payload) -> None:
        self.downloads += 1
        self.bytes_down += payload.nbytes


class FederationServer:
    """Holds the latest intermediate results per device (Fig. 4)."""

    def __init__(self) -> None:
        self.store: dict[str, Payload] = {}
        self.log = CommLog()

    def upload(self, payload: Payload) -> None:
        self.log.up(payload)
        self.store[payload.device_id] = payload

    def download(self, device_id: str, exclude: str | None = None) -> Payload:
        p = self.store[device_id]
        self.log.down(p)
        return p

    def peers_of(self, device_id: str) -> list[str]:
        return [d for d in self.store if d != device_id]


class EdgeDevice:
    """One edge device: OS-ELM autoencoder + the cooperative protocol."""

    def __init__(
        self,
        device_id: str,
        key: jax.Array,
        n_features: int,
        n_hidden: int,
        x_init: np.ndarray,
        *,
        activation: str = "sigmoid",
        ridge: float = 0.0,
    ) -> None:
        self.device_id = device_id
        self.state: OSELMState = init_autoencoder(
            key, n_features, n_hidden, jnp.asarray(x_init), activation=activation, ridge=ridge
        )
        self.version = 0

    # --- local life-cycle -------------------------------------------------
    def train(self, xs: np.ndarray) -> None:
        """Sequential k=1 training on the device's own stream."""
        self.state = ae_train_stream(self.state, jnp.asarray(xs))

    def score(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(ae_score(self.state, jnp.asarray(x)))

    # --- cooperative update (§4.2) -----------------------------------------
    def share(self, server: FederationServer) -> None:
        """Steps 2–3: compute (U,V) by Eq. 15 and upload."""
        uv = to_uv(self.state)
        self.version += 1
        server.upload(Payload.from_uv(self.device_id, uv, self.version))

    def merge_from(self, server: FederationServer, peer_ids: Iterable[str]) -> None:
        """Steps 3–5: download demanded peers, add (Eq. 8), recover (Eq. 6).

        The payloads are added in device-id order, so every device of a
        round solves the same f32 sum: another order per device leaves
        their merged models apart by up to κ(ΣU)·ε."""
        parts = {self.device_id: to_uv(self.state)}
        for pid in peer_ids:
            parts[pid] = server.download(pid, exclude=self.device_id).to_uv()
        ids = sorted(parts)
        merged = parts[ids[0]]
        for pid in ids[1:]:
            merged = uv_add(merged, parts[pid])
        self.state = from_uv(self.state, merged)


def cooperative_round(
    devices: list[EdgeDevice], server: FederationServer, *, select=None
) -> None:
    """One one-shot cooperative model update across a device set.

    ``select(device_ids) -> ids`` is the pluggable client-selection
    strategy hook (refs [19][20]); default merges everyone.
    """
    for d in devices:
        d.share(server)
    ids = [d.device_id for d in devices]
    chosen = list(select(ids)) if select is not None else ids
    for d in devices:
        if d.device_id in chosen:
            peers = [i for i in chosen if i != d.device_id]
            d.merge_from(server, peers)
