"""The main-path kernels compile for a TPU v5e, at the paper's widths.

Each test lowers one Pallas kernel with ``interpret=False`` for one chip
of a described (not attached) ``v5e:2x2`` topology and compiles it with
the TPU compiler — what Mosaic refuses here (unsupported primitives,
unaligned slices, more VMEM than a kernel may use) would fail a chip run
the same way. Widths are Table 3's (driving 225→16, mnist_like 784→64,
har 561→128) at the fleet sizes the chip smoke runs; the ingest kernel
also at the serve window (T=2) and a 32-sample window. Nothing runs.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import OSELMState
from repro.core.elm import SLFNParams
from repro.kernels import (
    banded_merge_solve,
    fleet_ingest_kernel,
    from_uv_solve,
    quantize_pack,
    robust_segment_sum_mix,
)
from repro.kernels.topology_merge import masked_segment_sum_mix

# name: (features, Ñ, activation, fleet size)
WIDTHS = {
    "driving": (225, 16, "sigmoid", 16384),
    "mnist_like": (784, 64, "identity", 4096),
    "har": (561, 128, "identity", 4096),
}


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _native(compiled, kernel: str | None = None) -> None:
    """The program holds a Mosaic kernel; ``kernel`` names the one whose
    op name a chip trace must show (the benchmark's readers match it)."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if kernel is not None:
        assert re.search(
            rf"%{kernel}\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"", text
        ), kernel


@pytest.mark.parametrize("window", [2, 32])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_fleet_ingest_compiles(one_chip, width, window):
    n, nh, act, d = WIDTHS[width]
    s = lambda *shape: _shape(one_chip, shape)  # noqa: E731
    states = OSELMState(
        params=SLFNParams(alpha=s(d, n, nh), bias=s(d, nh)),
        beta=s(d, nh, n), p=s(d, nh, nh), activation=act,
    )
    _native(fleet_ingest_kernel.lower(
        states, s(d, window, n), interpret=False
    ).compile(), "fleet_ingest_kernel")


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_banded_merge_solve_compiles(one_chip, width):
    n, nh, _, d = WIDTHS[width]
    w = _shape(one_chip, (d, nh, nh + n))
    _native(banded_merge_solve.lower(
        w, 2, ridge=1e-3, interpret=False
    ).compile(), "banded_merge_solve")


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_from_uv_solve_compiles(one_chip, width):
    n, nh, _, _ = WIDTHS[width]
    _native(from_uv_solve.lower(
        _shape(one_chip, (8, nh, nh)), _shape(one_chip, (8, nh, n)),
        ridge=1e-3, interpret=False,
    ).compile())


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_masked_segment_sum_mix_compiles(one_chip, width):
    n, nh, _, d = WIDTHS[width]
    cids = np.repeat(np.arange(4, dtype=np.int32), d // 4)
    fn = jax.jit(lambda w, mask: masked_segment_sum_mix(
        w, cids, mask, 4, interpret=False
    ))
    _native(fn.lower(
        _shape(one_chip, (d, nh, nh + n)), _shape(one_chip, (d,))
    ).compile(), "_masked_segment_sum_mix_call")


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_quantize_pack_compiles(one_chip, width):
    n, nh, _, d = WIDTHS[width]
    _native(quantize_pack.lower(
        _shape(one_chip, (d, nh, nh)), _shape(one_chip, (d, nh, n)),
        _shape(one_chip, (d, nh, nh + n)), interpret=False,
    ).compile())


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_robust_segment_sum_mix_compiles(one_chip, width):
    n, nh, _, d = WIDTHS[width]
    cids = np.repeat(np.arange(4, dtype=np.int32), d // 4)
    fn = jax.jit(lambda w, mask, scale: robust_segment_sum_mix(
        w, cids, mask, scale, 4, 1, interpret=False
    ))
    _native(fn.lower(
        _shape(one_chip, (d, nh, nh + n)), _shape(one_chip, (d,)),
        _shape(one_chip, (d,)),
    ).compile())


@pytest.mark.parametrize("topology", ["star", "ring"])
def test_sharded_merge_factors_untransposed(v5e_2x2, topology):
    """The 4-chip sharded merge at har widths (D=4096) compiles, and no
    Cholesky factor in it comes out in a transposed layout: in a
    multi-device program the compiler folds the upper factor's transpose
    into the batched factorization's output layout, and on four v5e
    chips that factor was wrong by 2-4% (core.elm.cho_factor)."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec

    from repro.fleet import ring, star
    from repro.fleet.sharded import sharded_merge
    from repro.launch.mesh import make_mesh

    n, nh, act, d = WIDTHS["har"]
    mesh = make_mesh((4,), ("data",), devices=v5e_2x2.devices[:4])
    sh = NamedSharding(mesh, PartitionSpec("data"))
    s = lambda *shape: _shape(sh, shape)  # noqa: E731
    states = OSELMState(
        params=SLFNParams(alpha=s(d, n, nh), bias=s(d, nh)),
        beta=s(d, nh, n), p=s(d, nh, nh), activation=act,
    )
    topo = {"star": star, "ring": lambda d: ring(d, hops=2)}[topology](d)
    fn, operands = sharded_merge(topo, mesh, ("data",), 1e-3, kernel=False)
    operands = [_shape(sh, o.shape, o.dtype) for o in operands]
    text = jax.jit(fn).lower(states, _shape(sh, (d,)), *operands).compile().as_text()
    layouts = re.findall(
        r"\{([0-9,]+):[^}]*\} custom-call\([^)]*\), "
        r'custom_call_target="Cholesky"', text,
    )
    assert layouts
    for lay in layouts:
        dims = [int(x) for x in lay.split(",")]
        assert dims[:2] == [len(dims) - 1, len(dims) - 2], layouts


@pytest.mark.parametrize("program", ["ingest", "ring", "star"])
def test_sharded_tick_compiles(v5e_2x2, program):
    """The tick programs of a har fleet laid out over the four chips
    compile with every Pallas call inside ``shard_map`` (none is left to
    the automatic partitioner, which refuses Mosaic kernels), and the
    merges cross chips by a halo ``collective-permute`` (ring) or an
    ``all-reduce`` of the cluster partials (star)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.fleet import ring, star
    from repro.fleet.sharded import sharded_ingest, sharded_merge
    from repro.launch.mesh import make_mesh
    from repro.runtime.detector import DetectorConfig, init_detector
    from repro.runtime.runtime import ingest_detect_program

    n, nh, act, _ = WIDTHS["har"]
    d = 4 * 256
    mesh = make_mesh((4,), ("fleet",), devices=v5e_2x2.devices[:4])
    sh = NamedSharding(mesh, PartitionSpec("fleet"))
    s = lambda *shape, dtype=jnp.float32: _shape(sh, shape, dtype)  # noqa: E731
    states = OSELMState(
        params=SLFNParams(alpha=s(d, n, nh), bias=s(d, nh)),
        beta=s(d, nh, n), p=s(d, nh, nh), activation=act,
    )
    if program == "ingest":
        fn = ingest_detect_program(sharded_ingest(
            mesh, ("fleet",), kernel=True, backend="pallas", interpret=False,
        ), DetectorConfig())
        det = jax.tree.map(lambda x: s(*x.shape, dtype=x.dtype), init_detector(d))
        args = (states, det, s(d, 32, n),
                _shape(NamedSharding(mesh, PartitionSpec()), (), bool),
                s(d, dtype=bool), s(d, dtype=bool))
        kernel, collective = "fleet_ingest_kernel", None
    else:
        topo = ring(d, hops=2) if program == "ring" else star(d)
        fn, operands = sharded_merge(topo, mesh, ("fleet",), 1e-3, kernel=True,
                                     interpret=False)
        args = (states, s(d), *[s(*o.shape, dtype=o.dtype) for o in operands])
        kernel, collective = {"ring": ("banded_merge_solve", "collective-permute"),
                              "star": ("_masked_segment_sum_mix_call", "all-reduce")}[program]
    compiled = jax.jit(fn, out_shardings=sh).lower(*args).compile()
    _native(compiled, kernel)
    if collective is not None:
        assert collective in compiled.as_text()
