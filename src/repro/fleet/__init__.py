"""repro.fleet — fleet-scale federation simulator.

Simulates hundreds-to-thousands of virtual OS-ELM edge devices in one
process as a single stacked pytree (``vmap`` over devices, ``scan``
over streams), with topology-aware cooperative updates (all-to-all /
star / ring gossip / hierarchical clusters), an async-staleness model
for repeated synchronization under realistic payload lag, a non-IID
stream partitioner with drift injection, and per-round communication
accounting.

The ROADMAP's scaling line is built in: sparse topology mixing that
never forms the D×D mask (``topology``), the Pallas banded/segment
merge-kernel path fused with the Eq. 8 solve (``fleet_merge_kernel`` /
``repro.kernels.topology_merge``), and mesh-sharded merges that lower
to a psum of O(clusters) segment sums per shard (``sharded``).
Serve-loop integration still builds on the stacked-(U, V) layout
defined here.
"""
from repro.fleet.arena import (
    CohortMerger,
    CohortSchedule,
    FleetArena,
    TierCost,
    cohort_round_cost,
    init_arena,
)
from repro.fleet.faults import FAULT_KINDS, FaultInjector, FaultSpec
from repro.fleet.robust import (
    RobustConfig,
    finite_payload_mask,
    fleet_merge_robust,
    payload_clip,
    payload_outlier_scores,
    robust_merge_from_w,
)
from repro.fleet.comm import (
    RoundCost,
    fedavg_total_cost,
    model_nbytes,
    payload_nbytes,
    topology_round_cost,
)
from repro.fleet.fleet import (
    device_state,
    fleet_from_uv,
    fleet_merge,
    fleet_merge_kernel,
    fleet_merge_masked,
    fleet_merge_masked_kernel,
    fleet_merge_quantized,
    fleet_score,
    fleet_to_uv,
    fleet_train,
    fleet_train_rounds,
    init_fleet,
)
from repro.fleet.quantize import (
    apply_codec,
    dequantize_tiles,
    init_residual,
    payload_precision_nbytes,
    quantize_roundtrip,
    quantize_tiles,
)
from repro.fleet.sharded import (
    cohort_tree_reduce,
    sharded_ingest,
    sharded_merge,
)
from repro.fleet.partition import (
    DriftEvent,
    FleetStreams,
    make_fleet_streams,
    random_drift_schedule,
)
from repro.fleet.staleness import StalenessSchedule, fleet_train_async
from repro.fleet.topology import (
    TOPOLOGIES,
    Topology,
    all_to_all,
    hierarchical,
    make_topology,
    ring,
    star,
)

__all__ = [
    "CohortMerger", "CohortSchedule", "FleetArena", "TierCost",
    "cohort_round_cost", "cohort_tree_reduce", "init_arena",
    "FAULT_KINDS", "FaultInjector", "FaultSpec",
    "RobustConfig", "finite_payload_mask", "fleet_merge_robust",
    "payload_clip", "payload_outlier_scores", "robust_merge_from_w",
    "RoundCost", "fedavg_total_cost", "model_nbytes", "payload_nbytes",
    "topology_round_cost",
    "device_state", "fleet_from_uv", "fleet_merge", "fleet_merge_kernel",
    "fleet_merge_masked", "fleet_merge_masked_kernel", "fleet_merge_quantized",
    "fleet_to_uv", "fleet_score", "fleet_train", "fleet_train_rounds",
    "init_fleet", "sharded_ingest", "sharded_merge",
    "apply_codec", "dequantize_tiles", "init_residual",
    "payload_precision_nbytes", "quantize_roundtrip", "quantize_tiles",
    "DriftEvent", "FleetStreams", "make_fleet_streams", "random_drift_schedule",
    "StalenessSchedule", "fleet_train_async",
    "TOPOLOGIES", "Topology", "all_to_all", "hierarchical", "make_topology",
    "ring", "star",
]
