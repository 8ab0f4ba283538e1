#!/usr/bin/env python3
"""Chip benchmark of the OS-ELM fleet runtime.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process on the cell's chips: it builds the cell's fleet on
the device from ``--seed``, warms the shapes the cell's traffic uses,
measures for ``--seconds``, replays the plain reference over everything
the run served, and prints one JSON line last on standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics read from a profiler
trace of the window), ``device``, with ``--trace 1`` ``breakdown``, and
last ``check``: each number compared with its limit. The same numbers
end standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result: there is no CPU fallback. ``--control 1`` puts the
reference at bfloat16 operands in the program's place for the check
(the window still runs the program): ``correct`` must then read false.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _fail(msg: str, code: int) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return _fail("the system under test (src/repro) is not in this checkout", 2)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    # the compile cache lives at one fixed path inside the checkout, so
    # only a cell's first run there compiles and two checkouts share none
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench import costs, harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"no TPU (JAX platform {devices[0].platform!r}); "
                     "this benchmark runs only on the chip", 3)
    if len(devices) < cell.chips:
        return _fail(f"{cell.name} needs {cell.chips} chips, JAX sees {len(devices)}", 3)
    peaks = costs.load_peaks(devices[0].device_kind)

    out = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, peaks=peaks, devices=devices[:cell.chips],
        control=bool(args.control),
    )
    for note in out.notes:
        print(f"bench: {note}", file=sys.stderr)
    result = {
        "correct": out.correct, "attempted": out.attempted, "failed": out.failed,
        "metrics": out.metrics, "device": out.device,
    }
    if out.breakdown is not None:
        result["breakdown"] = out.breakdown
    result["check"] = {k: {"value": v["value"], "limit": v["limit"]}
                       for k, v in out.check.items()}
    for k, v in out.check.items():
        op = ">=" if v.get("at_least") else "<="
        print(f"check {k} {v['value']!r} {op} {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
