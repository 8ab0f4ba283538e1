"""Divisibility-aware sharding rules (DESIGN.md §6).

2-D weight sharding (FSDP × TP): the contraction-free ("output") dim of
each matmul weight goes on "model", the d_model dim on "data" — so the
405B/480B archs fit 256×16 GB. A dim is sharded only when divisible by
the mesh axis size; otherwise it stays replicated (e.g. hymba's 32001
vocab, granite-moe's 40 experts). The "pod" axis never carries weights
(pure DP across pods).

Rules are keyed by leaf parameter name; a rule applies only when the
leaf's trailing ndim matches the rule length (stacked layer leaves have
a leading layer axis mapped to None).
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# leaf-name → per-dim preferred axes (innermost dims; layer axis prepended)
RULES: dict[str, tuple] = {
    "embed": ("model", "data"),
    # attention
    "wq": ("data", "model"),
    "wk": ("data", "model"),
    "wv": ("data", "model"),
    "wo": ("model", "data"),
    # dense FFN
    "gate": ("data", "model"),
    "up": ("data", "model"),
    "down": ("model", "data"),
    # MoE
    "router": ("data", None),
    "w_gate": ("model", "data", None),
    "w_up": ("model", "data", None),
    "w_down": ("model", None, "data"),
    # mamba
    "in_proj": ("data", "model"),
    "out_proj": ("model", "data"),
    "b_proj": ("data", None),
    "c_proj": ("data", None),
    "dt_proj": ("data", None),
    "d_skip": (None,),
    "dt_bias": (None,),
    "a_log": (None,),
    # mLSTM / sLSTM
    "w_gates": ("data", "model"),
    "r_gates": (None, None, None),
    "wf": ("data", None),
    "wi": ("data", None),
    "wo_gate": ("data", None),
    "bf": (None,), "bi": (None,), "bo": (None,),
    # frontend stub projection
    "frontend_proj": (None, "data"),
}


def _axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for_leaf(name: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    sizes = _axis_sizes(mesh)
    rule = RULES.get(name)
    if rule is None or len(shape) < len(rule):
        return P()
    # MoE expert weights: if the expert dim can't shard over "model"
    # (granite-moe's 40 % 16 != 0), shard the per-expert FFN dim there
    # instead — otherwise every model-axis device recomputes identical
    # expert work (§Perf iteration 3: 16x redundant FLOPs).
    if name in ("w_gate", "w_up", "w_down") and len(shape) >= 3:
        e_dim = shape[-3]
        if "model" in sizes and e_dim % sizes["model"] != 0:
            rule = (None, "data", "model") if name != "w_down" else (None, "model", "data")
    # leading (layer-stack) dims → None
    lead = len(shape) - len(rule)
    dims: list = [None] * lead
    for dim_size, axis in zip(shape[lead:], rule):
        if axis is not None and axis in sizes and dim_size % sizes[axis] == 0:
            dims.append(axis)
        else:
            dims.append(None)
    return P(*dims)


def param_specs(params: PyTree, mesh: Mesh) -> PyTree:
    """PartitionSpec tree matching ``params`` (works on ShapeDtypeStructs)."""

    def visit(path, leaf):
        name = None
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                name = str(entry.key)
                break
        return spec_for_leaf(name or "", leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(visit, params)


def param_shardings(params: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs(params, mesh)
    )


def fleet_stack_spec(axes: tuple[str, ...] = ("data",)) -> P:
    """PartitionSpec sharding a stacked fleet's leading device axis over
    the federation mesh axes (all trailing dims replicated)."""
    return P(tuple(axes))


def fleet_shardings(states: PyTree, mesh: Mesh, axes: tuple[str, ...] = ("data",)) -> PyTree:
    """NamedShardings for a stacked ``OSELMState`` fleet: every leaf's
    leading device axis lands on ``axes``; ``FleetRuntime`` ticks a
    fleet laid out so per shard (``repro.fleet.sharded``), its topology
    merge a psum of O(clusters) segment sums or a ring halo exchange."""
    sharding = NamedSharding(mesh, fleet_stack_spec(axes))
    return jax.tree.map(lambda _: sharding, states)


def shard_fleet(states: PyTree, mesh: Mesh, axes: tuple[str, ...] = ("data",)) -> PyTree:
    """Place a stacked fleet on the mesh, device axis sharded over ``axes``."""
    return jax.device_put(states, fleet_shardings(states, mesh, axes))


def opt_state_specs(opt_state: PyTree, params_specs_tree: PyTree) -> PyTree:
    """Adam moments follow their parameter's sharding; step is replicated."""
    from repro.optim.optimizers import OptState

    mu = opt_state.mu and params_specs_tree
    nu = opt_state.nu and params_specs_tree
    return OptState(step=P(), mu=mu, nu=nu)


def batch_specs(batch_kind: str, dp_axes: tuple[str, ...], mesh: Mesh, cfg=None) -> dict:
    """Input shardings per shape kind. Batch dim on the data(+pod) axes."""
    if batch_kind == "train":
        specs = {"tokens": P(dp_axes, None), "labels": P(dp_axes, None)}
        if cfg is not None and cfg.frontend is not None:
            specs["frontend"] = P(dp_axes, None, None)
        return specs
    if batch_kind == "prefill":
        specs = {"tokens": P(dp_axes, None)}
        if cfg is not None and cfg.frontend is not None:
            specs["frontend"] = P(dp_axes, None, None)
        return specs
    raise ValueError(batch_kind)


def cache_specs_sharding(
    caches: PyTree, mesh: Mesh, dp_axes: tuple[str, ...], *, shard_seq: bool = False
) -> PyTree:
    """Decode caches: (L, B, S, KV, hd) attention caches and recurrent
    states. Batch on data axes; for long-context batch=1 decodes,
    ``shard_seq`` puts the cache sequence dim on "data" instead (context
    parallelism — DESIGN.md §6)."""
    sizes = _axis_sizes(mesh)

    def visit(leaf):
        shp = leaf.shape
        if len(shp) == 5:  # (L, B, S, KV, hd) attention cache
            l, b, s, kv, hd = shp
            if shard_seq and s % int(np.prod([sizes[a] for a in dp_axes])) == 0:
                return P(None, None, dp_axes, None, None)
            bspec = dp_axes if b % int(np.prod([sizes[a] for a in dp_axes])) == 0 else None
            return P(None, bspec, None, None, None)
        if len(shp) >= 2:  # recurrent states (L, B, ...)
            l, b = shp[0], shp[1]
            bspec = dp_axes if b % int(np.prod([sizes[a] for a in dp_axes])) == 0 else None
            return P(None, bspec, *([None] * (len(shp) - 2)))
        return P()

    return jax.tree.map(visit, caches)
