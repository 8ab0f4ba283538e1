"""shard_map helpers shared by every manual-axes user in the repo
(``federated.mesh_federation``, ``fleet.sharded``).

jax tracks which manual mesh axes a value varies over: psum outputs are
device-invariant and must be re-varied (``jax.lax.pcast(...,
to="varying")``) before flowing out through a sharded out_spec or back
into a device-varying scan carry.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def shard_map_compat(f, mesh, in_specs, out_specs, check_rep=True):
    """``jax.shard_map`` over ``mesh``. ``check_rep=False`` turns off the
    varying-axes check (``check_vma``), which bodies containing ops with
    no replication rule (e.g. ``pallas_call``) need."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_rep,
    )


def revary(x: jnp.ndarray, axes: Sequence[str]) -> jnp.ndarray:
    """Re-vary a device-invariant value (e.g. a psum output) over
    ``axes``."""
    missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x
