#!/usr/bin/env python3
"""Bring-up smoke of the fleet runtime on a TPU, at the paper's widths.

    python chip_smoke.py            # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4  # four chips: the sharded fleet only

(a) Served resident fleet, har widths (561 -> 128, identity): D=4096
    devices on a ring(hops=2), 2-sample windows, a merge every 2
    ticks, driven through ``repro.launch.serve_fleet`` (clients ->
    ServeFrontend -> FleetRuntime, fused ingest + topology-merge
    kernels). The served windows (kept by the write-ahead log) and each
    tick's merge decision are replayed through the plain reference:
    k=1 sequential OS-ELM updates plus the XLA ``fleet_merge_masked``
    at ``highest`` matmul precision.
(b) Paged fleet, driving widths (225 -> 16, sigmoid): D=2^17 devices in
    16,384-device cohorts of a host arena through CohortFleetRuntime,
    a few ticks and one two-tier star merge, against the same
    reference over the full fleet.
(4 chips) har widths, D=4096 laid out over the four chips, through
    ``FleetRuntime`` ticks: the per-shard kernel ingest, the star psum
    merge and the ring(hops=2) ppermute halo merge, against the plain
    reference on one chip over the same windows and merge masks.

Each phase prints one ``phase ...`` JSON line: the largest relative
errors against the reference, the evidence that the kernels ran
natively (``tpu_custom_call`` in the compiled programs), compile
seconds, ticks and acks. The last line is the device summary
``{"ok": true, "device": {...}}``; any failed phase exits non-zero.
There is no CPU fallback: without a TPU the script exits non-zero.
Fleets and data come from ``--seed``.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Largest relative error against the highest-precision reference that a
# phase may show (max |x - ref| / max |ref| for the final β and P; per
# device for the per-tick losses). Both paths run f32 end to end and
# differ only in rounding order: κ(P) ≤ 8e2 for the boot chunks and
# κ(ΣU+εI) ≈ 25 for the star merge, and a sequential f32 sum over a
# 16,384-device cohort against the reference's tree sum leaves 1.2e-5
# on β. One bf16 matmul pass anywhere on the path (unit roundoff
# 2^-9) moves β, P or the losses by 2.8e-4 to 2e-2.
REL_BOUND = 1e-4


# ------------------------------------------------------------- reference


def _reference_fns():
    """Jitted plain-reference steps (trace them under ``highest``):
    the pre-train loss and the k=1 sequential updates of each served
    device's window, and the XLA masked Eq. 8 merge."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from repro.core import OSELMState, ae_score, oselm_step_k1
    from repro.core.elm import SLFNParams
    from repro.fleet import fleet_merge_masked

    @partial(jax.jit, static_argnames=("activation",))
    def ingest(p, beta, alpha, bias, window, served, activation):
        params = SLFNParams(alpha=alpha, bias=bias)

        def one(p_i, b_i, xs):
            st = OSELMState(params=params, beta=b_i, p=p_i,
                            activation=activation)
            loss = jnp.mean(ae_score(st, xs))
            st, _ = jax.lax.scan(
                lambda s, x: (oselm_step_k1(s, x, x), None), st, xs
            )
            return st.p, st.beta, loss

        p2, b2, losses = jax.vmap(one)(p, beta, window)
        sel = jnp.asarray(served, bool)[:, None, None]
        return jnp.where(sel, p2, p), jnp.where(sel, b2, beta), losses

    @partial(jax.jit, static_argnames=("topology", "ridge", "activation"))
    def merge(p, beta, alpha, bias, mask, topology, ridge, activation):
        d = p.shape[0]
        st = OSELMState(
            params=SLFNParams(
                alpha=jnp.broadcast_to(alpha, (d,) + alpha.shape),
                bias=jnp.broadcast_to(bias, (d,) + bias.shape),
            ),
            beta=beta, p=p, activation=activation,
        )
        out = fleet_merge_masked(st, topology, mask, ridge=ridge)
        return out.p, out.beta

    return ingest, merge


def _rel(got, ref) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def _loss_rel(got, ref) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float((np.abs(got - ref) / np.maximum(np.abs(ref), 1e-12)).max())


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# --------------------------------------------------------------- phases


def phase_served(*, n_devices: int = 4096, n_requests: int = 384,
                 clients: int = 64, window: int = 64, seed: int = 0,
                 workdir: Path) -> dict:
    """(a): the served resident fleet at har widths vs the reference."""
    import jax
    import jax.numpy as jnp

    from repro.launch import serve_fleet
    from repro.serve.wal import WriteAheadLog

    args = serve_fleet.parse_args([
        "--config", "har", "--devices", str(n_devices),
        "--batch", "2", "--merge-every", "2",
        "--requests", str(n_requests), "--clients", str(clients),
        "--window-requests", str(window), "--max-delay-ms", "20",
        "--seed", str(seed), "--wal-dir", str(workdir / "wal"),
    ])
    runtime, frontend = serve_fleet.build_frontend(args)
    states0 = runtime.states
    d = runtime.n_devices

    t0 = time.perf_counter()
    runtime.warmup(args.batch)  # compiles the tick programs
    compile_s = time.perf_counter() - t0
    n_feat = int(states0.params.alpha.shape[1])
    ones = jnp.ones(d, bool)
    ingest_c = runtime._ingest_detect.lower(
        runtime.states, runtime.det, jnp.zeros((d, args.batch, n_feat)),
        jnp.asarray(False), ones, ones,
    ).compile()
    merge_c = runtime._merge_fresh.lower(
        runtime.states, jnp.zeros(d, jnp.float32)
    ).compile()
    mem = ingest_c.memory_analysis()

    reports, masks = [], {}
    tick = runtime.tick

    def recording_tick(batch, **kw):
        rep = tick(batch, **kw)
        reports.append(rep)
        if rep.decision.merge:
            masks[rep.tick] = runtime._merge_mask.copy()
        return rep

    runtime.tick = recording_tick
    t0 = time.perf_counter()
    acks = serve_fleet.serve(frontend, args)
    serve_s = time.perf_counter() - t0

    ref_ingest, ref_merge = _reference_fns()
    cfg = runtime.config
    alpha, bias = states0.params.alpha[0], states0.params.bias[0]
    act = states0.activation
    wal = WriteAheadLog(workdir / "wal")
    p, beta = states0.p, states0.beta
    loss_errs = []
    with jax.default_matmul_precision("highest"):
        for rep in reports:
            batch, served, _ = wal.load(rep.tick)
            p, beta, losses = ref_ingest(p, beta, alpha, bias, batch, served, act)
            loss_errs.append(_loss_rel(rep.losses, losses))
            if rep.decision.merge:
                p, beta = ref_merge(
                    p, beta, alpha, bias, jnp.asarray(masks[rep.tick]),
                    cfg.topology, cfg.ridge, act,
                )
    return {
        "phase": "a_served_har",
        "devices": d, "widths": [n_feat, int(states0.beta.shape[1])],
        "state_bytes": int(states0.p.nbytes + states0.beta.nbytes),
        "acks": serve_fleet.ack_counts(acks), "requests": len(acks),
        "ticks": len(reports),
        "merges": sum(bool(r.decision.merge) for r in reports),
        "compile_s": compile_s, "serve_s": serve_s,
        "ingest_custom_calls": _custom_calls(ingest_c),
        "merge_custom_calls": _custom_calls(merge_c),
        "ingest_temp_bytes": int(mem.temp_size_in_bytes) if mem else None,
        "beta_rel_err": _rel(runtime.states.beta, beta),
        "p_rel_err": _rel(runtime.states.p, p),
        "loss_rel_err": max(loss_errs),
        "loss_rel_err_by_tick": loss_errs,
    }


def phase_paged(*, n_devices: int = 2**17, cohort: int = 16384,
                ticks: int = 4, merge_every: int = 3, seed: int = 0) -> dict:
    """(b): the cohort-paged fleet at driving widths vs the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.oselm_edge import EDGE_CONFIGS
    from repro.fleet import init_arena, star
    from repro.runtime import CohortFleetRuntime, GovernorConfig, RuntimeConfig

    edge = EDGE_CONFIGS["driving"]
    f, h, act, ridge = edge.n_features, edge.n_hidden, edge.activation, edge.ridge
    key_basis, key_boot = jax.random.split(jax.random.PRNGKey(seed))

    def boot(lo, hi):
        return jax.random.normal(
            jax.random.fold_in(key_boot, lo), (hi - lo, 2 * h, f)
        )

    def window(t):
        return lambda lo, hi: np.random.default_rng([seed, t, lo]).standard_normal(
            (hi - lo, 2, f), dtype=np.float32
        )

    t0 = time.perf_counter()
    arena = init_arena(key_basis, n_devices, f, h, boot, cohort_size=cohort,
                       activation=act, ridge=ridge)
    init_s = time.perf_counter() - t0
    p0, beta0 = arena.p.copy(), arena.beta.copy()
    cfg = RuntimeConfig(topology=star(n_devices), ridge=ridge,
                        governor=GovernorConfig(merge_every=merge_every))
    rt = CohortFleetRuntime(arena, cfg, cohort_size=cohort)

    # compile the page programs once, for the timing and the HLO evidence
    pj, bj = jnp.asarray(p0[:cohort]), jnp.asarray(beta0[:cohort])
    wj = jnp.zeros((cohort, 2, f), jnp.float32)
    sj = jnp.ones(cohort, bool)
    t0 = time.perf_counter()
    ingest_c = rt._ingest.lower(pj, bj, wj, sj).compile()
    partial_fn = rt.merger._page_partial_fn(np.zeros(cohort, np.int32), 1)
    merge_c = partial_fn.lower(pj, bj, jnp.ones(cohort, jnp.float32)).compile()
    compile_s = time.perf_counter() - t0

    reports, masks, tick_s = [], {}, []
    for t in range(ticks):
        t0 = time.perf_counter()
        rep = rt.tick(window(t))
        tick_s.append(time.perf_counter() - t0)
        reports.append(rep)
        if rep.decision.merge:
            masks[t] = rt._merge_mask.copy()

    ref_ingest, ref_merge = _reference_fns()
    alpha, bias = jnp.asarray(arena.alpha), jnp.asarray(arena.bias)
    p, beta = p0, beta0
    loss_errs = []
    with jax.default_matmul_precision("highest"):
        for rep in reports:
            losses = np.empty(n_devices, np.float32)
            batch_fn = window(rep.tick)
            for lo in range(0, n_devices, cohort):
                hi = lo + cohort
                pc, bc, lc = ref_ingest(
                    p[lo:hi], beta[lo:hi], alpha, bias, batch_fn(lo, hi),
                    np.ones(cohort, bool), act,
                )
                p[lo:hi], beta[lo:hi] = np.asarray(pc), np.asarray(bc)
                losses[lo:hi] = np.asarray(lc)
            loss_errs.append(_loss_rel(rep.losses, losses))
            if rep.decision.merge:
                pm, bm = ref_merge(
                    jnp.asarray(p), jnp.asarray(beta), alpha, bias,
                    jnp.asarray(masks[rep.tick]), cfg.topology, ridge, act,
                )
                p, beta = np.array(pm), np.array(bm)
    return {
        "phase": "b_paged_driving",
        "devices": n_devices, "cohort": cohort, "widths": [f, h],
        "arena_bytes": int(arena.nbytes),
        "ticks": len(reports),
        "merges": sum(bool(r.decision.merge) for r in reports),
        "init_s": init_s, "compile_s": compile_s, "tick_s": tick_s,
        "ingest_custom_calls": _custom_calls(ingest_c),
        "merge_custom_calls": _custom_calls(merge_c),
        "beta_rel_err": _rel(arena.beta, beta),
        "p_rel_err": _rel(arena.p, p),
        "loss_rel_err": max(loss_errs),
        "loss_rel_err_by_tick": loss_errs,
    }


def phase_sharded(devices, *, n_devices: int = 4096, window: int = 8,
                  ticks: int = 3, seed: int = 0) -> dict:
    """Four chips: the har fleet laid out over them, through
    ``FleetRuntime`` ticks (sharded kernel ingest, then a star psum
    merge or a ring(hops=2) halo merge), against the plain reference on
    one chip over the same windows and merge masks.

    The windows go through the host and each runtime's arrays are
    dropped once its state is read back: the one-chip reference ring
    merge alone needs 6.65 GB of temporaries next to the 2.6 GB fleet."""
    import jax
    import numpy as np

    from repro.configs.oselm_edge import EDGE_CONFIGS
    from repro.fleet import init_fleet, ring, star
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import shard_fleet
    from repro.runtime import FleetRuntime, GovernorConfig, RuntimeConfig

    edge = EDGE_CONFIGS["har"]
    f, h, act, ridge = edge.n_features, edge.n_hidden, edge.activation, edge.ridge
    k_basis, k_boot, k_win = jax.random.split(jax.random.PRNGKey(seed), 3)
    fleet = jax.device_get(init_fleet(
        k_basis, n_devices, f, h, jax.random.normal(k_boot, (n_devices, 2 * h, f)),
        activation=act, ridge=ridge))
    windows = np.asarray(jax.random.normal(k_win, (ticks, n_devices, window, f)))

    mesh = make_mesh((len(devices),), ("fleet",), devices=devices)
    out = {"phase": "sharded_har", "devices": n_devices, "chips": len(devices),
           "widths": [f, h]}
    ref_ingest, ref_merge = _reference_fns()
    for name, topo in (("star", star(n_devices)), ("ring", ring(n_devices, hops=2))):
        runtime = FleetRuntime(shard_fleet(fleet, mesh, ("fleet",)), RuntimeConfig(
            topology=topo, ridge=ridge, governor=GovernorConfig(merge_every=2)))
        t0 = time.perf_counter()
        runtime.warmup(window)
        out[f"{name}_compile_s"] = time.perf_counter() - t0
        out["shards"] = sorted(
            (s.index[0].start or 0, str(s.device))
            for s in runtime.states.beta.addressable_shards)
        reports, masks = [], {}
        for x in windows:
            rep = runtime.tick(x)
            reports.append(rep)
            if rep.decision.merge:
                masks[rep.tick] = runtime._merge_mask.copy()
        zeros = runtime._put(np.zeros(n_devices, np.float32))
        out[f"{name}_merge_custom_calls"] = _custom_calls(runtime._merge_fresh.lower(
            runtime.states, zeros, *runtime._merge_operands).compile())
        if name == "star":
            ones = runtime._put(np.ones(n_devices, bool))
            out["ingest_custom_calls"] = _custom_calls(runtime._ingest_detect.lower(
                runtime.states, runtime.det, runtime._put(windows[0]),
                runtime._put(False, replicated=True), ones, ones).compile())
        got_p, got_beta = jax.device_get((runtime.states.p, runtime.states.beta))
        out[f"{name}_merges"] = len(masks)
        del runtime

        one = jax.devices()[0]
        p, beta = jax.device_put((fleet.p, fleet.beta), one)
        alpha, bias = jax.device_put((fleet.params.alpha[0], fleet.params.bias[0]), one)
        loss_errs = []
        with jax.default_matmul_precision("highest"):
            for rep, x in zip(reports, windows):
                p, beta, losses = ref_ingest(p, beta, alpha, bias, jax.device_put(x, one),
                                             np.ones(n_devices, bool), act)
                loss_errs.append(_loss_rel(rep.losses, losses))
                if rep.decision.merge:
                    p, beta = ref_merge(p, beta, alpha, bias, masks[rep.tick],
                                        topo, ridge, act)
        out[f"{name}_beta_rel_err"] = _rel(got_beta, beta)
        out[f"{name}_p_rel_err"] = _rel(got_p, p)
        out[f"{name}_loss_rel_err"] = max(loss_errs)
        del p, beta
    return out


# ------------------------------------------------------------------ checks


def check_phase(res: dict, *, native: bool = True) -> list[str]:
    """What failed in one phase result (empty = passed)."""
    bad = []
    for k, v in res.items():
        if k.endswith("rel_err") and not v <= REL_BOUND:
            bad.append(f"{k}={v:.3g} > {REL_BOUND:g}")
    if "acks" in res:
        if res["acks"] != {"ok": res["requests"]}:
            bad.append(f"acks not all ok: {res['acks']}")
    if "ticks" in res and res["ticks"] < 4:
        bad.append(f"ticks={res['ticks']} < 4")
    if "merges" in res and res["merges"] < 1:
        bad.append("no merge round")
    if native:
        for k, v in res.items():
            if k.endswith("custom_calls") and v < 1:
                bad.append(f"{k}=0: the kernel did not lower natively")
    return bad


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repo's src/repro is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import CacheCounter, enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {platform!r}); "
              "this smoke runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    devices = devices[: args.chips]

    failed = []
    if args.chips == 4:
        phases = [lambda: phase_sharded(devices, seed=args.seed)]
    else:
        workdir = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
        phases = [
            lambda: phase_served(seed=args.seed, workdir=workdir),
            lambda: phase_paged(seed=args.seed),
        ]
    for run in phases:
        res = run()
        bad = check_phase(res)
        res["ok"] = not bad
        print("phase " + json.dumps(res, default=str), flush=True)
        if bad:
            failed.append(f"{res['phase']}: " + "; ".join(bad))
    print("compile_cache " + json.dumps(
        {"dir": cache_dir, "hits": cache.hits, "writes": cache.misses}
    ), flush=True)
    if failed:
        for f in failed:
            print(f"chip_smoke FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
