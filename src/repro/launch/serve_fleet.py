"""Async fleet-ingress driver: concurrent clients → ServeFrontend →
resident FleetRuntime.

The runnable face of the serving-under-load stack (README "Serving
under load"): N synthetic clients stream per-device sample bursts
through the deadline batcher, the admission controller applies
backpressure, and the run exits with the ingress summary — accepted /
acked / shed / deferred, admission and request latency percentiles —
from the runtime's own telemetry sink. The exit code is non-zero when
any request was acked ``failed``.

    PYTHONPATH=src python -m repro.launch.serve_fleet \
        --devices 64 --batch 2 --requests 2000 --clients 8

    # the paper's Table 3 edge config (features, Ñ, activation, ridge)
    PYTHONPATH=src python -m repro.launch.serve_fleet --config har \
        --devices 4096 --merge-every 2

    # durable mode: snapshots + write-ahead log, resumable after a kill
    PYTHONPATH=src python -m repro.launch.serve_fleet \
        --devices 64 --snapshot-dir /tmp/fleet-snap --wal-dir /tmp/fleet-wal
"""
from __future__ import annotations

import argparse
import asyncio
import json

import jax
import numpy as np

from repro.configs.oselm_edge import EDGE_CONFIGS
from repro.fleet import init_fleet, ring
from repro.obs import TelemetryConfig
from repro.runtime import FleetRuntime, GovernorConfig, RuntimeConfig
from repro.serve import (
    AdmissionConfig,
    SampleRequest,
    ServeConfig,
    ServeFrontend,
)


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", choices=sorted(EDGE_CONFIGS), default=None,
                    help="Table 3 edge config; overrides --features/--hidden "
                         "and sets its activation and ridge")
    ap.add_argument("--devices", type=int, default=64)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2,
                    help="per-device samples per tick window")
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--merge-every", type=int, default=16)
    ap.add_argument("--window-requests", type=int, default=None,
                    help="close a tick window at this many queued requests "
                         "(default: one per device)")
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="tick p99 SLO driving admission backpressure")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--snapshot-dir", default=None)
    ap.add_argument("--snapshot-every", type=int, default=64)
    ap.add_argument("--wal-dir", default=None)
    ap.add_argument("--recover", action="store_true",
                    help="restore newest snapshot + replay WAL before serving")
    ap.add_argument("--telemetry-dir", default=None)
    args = ap.parse_args(argv)
    for name in ("devices", "batch", "requests", "clients", "merge_every"):
        if getattr(args, name) < 1:
            ap.error(f"--{name.replace('_', '-')} must be >= 1 "
                     f"(got {getattr(args, name)})")
    if args.recover and not args.snapshot_dir:
        ap.error("--recover requires --snapshot-dir")
    return args


def build_frontend(args) -> tuple[FleetRuntime, ServeFrontend]:
    d = args.devices
    if args.config is not None:
        edge = EDGE_CONFIGS[args.config]
        f, h, activation, ridge = (
            edge.n_features, edge.n_hidden, edge.activation, edge.ridge
        )
    else:
        f, h, activation, ridge = args.features, args.hidden, "identity", 1e-3
    # boot chunks are drawn on the device: at paper widths the (D, 2Ñ, F)
    # block is gigabytes, which a host generator would take seconds over
    key_basis, key_init = jax.random.split(jax.random.PRNGKey(args.seed))
    x_init = jax.random.normal(key_init, (d, 2 * h, f))
    fleet = init_fleet(
        key_basis, d, f, h, x_init, activation=activation, ridge=ridge,
    )
    runtime = FleetRuntime(fleet, RuntimeConfig(
        topology=ring(d, hops=2),
        ridge=ridge,
        governor=GovernorConfig(merge_every=args.merge_every),
        snapshot_every=args.snapshot_every if args.snapshot_dir else None,
        snapshot_dir=args.snapshot_dir,
        telemetry=TelemetryConfig(dir=args.telemetry_dir),
    ))
    frontend = ServeFrontend(runtime, ServeConfig(
        batch=args.batch,
        max_delay_s=args.max_delay_ms / 1e3,
        close_at_requests=args.window_requests,
        admission=AdmissionConfig(
            slo_p99_s=args.slo_ms / 1e3 if args.slo_ms else None,
        ),
        wal_dir=args.wal_dir,
        seed=args.seed,
    ), fallback=np.asarray(x_init[:, -1, :]))
    return runtime, frontend


async def run_clients(frontend: ServeFrontend, args) -> list:
    rng = np.random.default_rng(args.seed + 1)
    per_client = -(-args.requests // args.clients)
    n_features = frontend.builder.n_features

    async def client(c: int) -> list:
        acks = []
        for i in range(per_client):
            dev = int(rng.integers(args.devices))
            x = rng.normal(size=(1, n_features)).astype(np.float32)
            acks.append(await frontend.submit_with_retries(
                SampleRequest(device=dev, x=x, client=f"client-{c}")
            ))
        return acks

    nested = await asyncio.gather(*[client(c) for c in range(args.clients)])
    return [a for acks in nested for a in acks]


def serve(frontend: ServeFrontend, args) -> list:
    """Start the front-end, drive the synthetic clients to completion,
    drain and stop; returns every client's final ack."""

    async def _serve() -> list:
        await frontend.start()
        try:
            return await run_clients(frontend, args)
        finally:
            await frontend.stop()

    return asyncio.run(_serve())


def ack_counts(acks) -> dict[str, int]:
    by_status: dict[str, int] = {}
    for a in acks:
        by_status[a.status] = by_status.get(a.status, 0) + 1
    return by_status


def main(argv: list[str] | None = None) -> int:
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    args = parse_args(argv)
    runtime, frontend = build_frontend(args)
    if args.recover:
        restored, replayed = frontend.recover()
        print(f"recovered: tick {restored} + {replayed} replayed windows")

    by_status = ack_counts(serve(frontend, args))
    summary = runtime.finalize_telemetry()
    print(json.dumps({
        "acks": by_status,
        "ticks": runtime.tick_no,
        "merges": runtime.governor.state.merges,
        "ingress": summary["ingress"],
    }, indent=2, default=str))
    return 1 if by_status.get("failed", 0) else 0


if __name__ == "__main__":
    raise SystemExit(main())
