"""Pallas TPU kernels for the paper's compute hot spots.

Layout per kernel: ``<name>.py`` holds the pallas_call + BlockSpec,
``ops.py`` the jit'd wrappers, ``ref.py`` the pure-jnp oracles the
tests assert against. Each kernel lowers through Mosaic on a TPU and
runs in the Pallas interpreter elsewhere (``resolve_interpret``).
"""
from repro.kernels.flash_attn import flash_attention
from repro.kernels.fleet_ingest import (
    fleet_ingest,
    fleet_ingest_kernel,
    fleet_ingest_xla,
    ingest_padding,
)
from repro.kernels.gla_scan import gla_forward
from repro.kernels.ops import (
    hidden_proj,
    matmul_atb,
    oselm_step_k1_kernel,
    rank1_add,
    uv_accum,
    uv_from_state_kernel,
)
from repro.kernels.quantize_pack import quantize_pack, quantize_pack_xla
from repro.kernels.robust_merge import (
    robust_segment_combine,
    robust_segment_sum_mix,
    robust_segment_sum_xla,
)
from repro.kernels.topology_merge import (
    banded_merge_solve,
    banded_mix,
    dense_mix,
    from_uv_solve,
    segment_broadcast,
    segment_sum_mix,
    topology_mix,
)

__all__ = [
    "flash_attention",
    "fleet_ingest",
    "fleet_ingest_kernel",
    "fleet_ingest_xla",
    "ingest_padding",
    "gla_forward",
    "quantize_pack",
    "quantize_pack_xla",
    "robust_segment_combine",
    "robust_segment_sum_mix",
    "robust_segment_sum_xla",
    "hidden_proj",
    "matmul_atb",
    "oselm_step_k1_kernel",
    "rank1_add",
    "uv_accum",
    "uv_from_state_kernel",
    "banded_merge_solve",
    "banded_mix",
    "dense_mix",
    "from_uv_solve",
    "segment_broadcast",
    "segment_sum_mix",
    "topology_mix",
]
