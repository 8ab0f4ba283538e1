"""One benchmark run: the cell's fleet built from the seed, its first
ticks driven through the window's own call and kept for the check, the
measured window, then the plain reference replayed over the checked
ticks, and the metrics read by the readers under metrics/.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a data file or a reader of its own, found by the name
``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: widths, fleet size, runtime and topology;
- ``traffic/<traffic>.json``: the mix, read by ``Served`` (a closed
  loop through the serving front-end) or ``Feed`` (ticks driven directly
  from a host pool);
- ``metrics/<metric>.py`` (or ``metrics/<stem>.py`` for every
  ``<stem>.<suffix>``): ``read(ctx)`` returns the number or None;
- ``limits/<cell>.json``: the limit of each number the cell compares.
"""
from __future__ import annotations

import asyncio
import dataclasses
import importlib.util
import json
import math
import random
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


# ------------------------------------------------------------------ cells


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list


def _load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, spec: dict | None = None) -> Cell:
    """The cell ``name`` of BENCHMARK.json with its config, traffic and
    the metrics it reports."""
    spec = spec if spec is not None else _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
    ]
    return Cell(
        name=name,
        config=_load_json(BENCH / "configs" / f"{w['config']}.json"),
        traffic=_load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def reader(metric: str):
    """The ``read`` function of metrics/<metric>.py, else of
    metrics/<stem>.py for a name ``<stem>.<suffix>``."""
    for stem in (metric, metric.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {metric!r} under {BENCH / 'metrics'}")


def limits_for(cell: Cell) -> dict:
    """The limit of each number the cell compares (limits/<cell>.json)."""
    return _load_json(BENCH / "limits" / f"{cell.name}.json")


# ------------------------------------------------------------------- log


@dataclasses.dataclass
class TickRec:
    tick: int
    start: float                 # host clock around the runtime's tick
    end: float
    ingest_s: float | None       # TickReport.ingest_seconds (fenced)
    merge_s: float | None        # TickReport.merge_seconds (fenced)
    merge: bool
    mask: np.ndarray | None      # participation mask of a merge tick
    served_rows: int             # devices whose rows carried real samples
    losses: np.ndarray | None = None  # (D,) per-device losses (feed cells)
    offset: int | None = None    # pool offset of the tick's windows (feed)


@dataclasses.dataclass
class RunLog:
    ticks: list = dataclasses.field(default_factory=list)
    checked: int = 0                   # ticks[:checked] are replayed
    window: tuple = (0.0, 0.0)         # host-clock start and end
    window_ticks: tuple = (0, 0)       # [first, last) indices into ticks
    requests: dict | None = None       # served cells: column arrays
    diag: dict = dataclasses.field(default_factory=dict)

    def in_window(self):
        a, b = self.window_ticks
        return self.ticks[a:b]


@dataclasses.dataclass
class Context:
    """What a metric reader sees."""

    cell: Cell
    setup_s: float
    log: RunLog
    peaks: dict | None = None
    trace: object | None = None        # bench.trace.Reduced, traced runs only
    notes: list = dataclasses.field(default_factory=list)


def jax_key(seed: int):
    """A PRNG key from any whole number, wider than 32 bits included."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def np_rng(seed: int, stream: int) -> np.random.Generator:
    seed = int(seed)
    return np.random.default_rng([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, stream])


class CompileCounter:
    """Backend compiles and persistent-cache loads since it was made: the
    window must show none."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def _duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def float32_boot():
    """The Eq. 13 boot at the configuration's float32: the program's init
    forms H0'H0 and its Cholesky solves at JAX's default matmul precision,
    one bfloat16 pass on a TPU, which leaves the boot state off by about
    1e-3. The timed path is never run under this."""
    import jax

    return jax.default_matmul_precision("highest")


def _traced(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _rel(got, ref) -> float:
    """Largest absolute gap over the reference's largest magnitude."""
    scale = float(np.abs(ref).max())
    return float(np.abs(got - ref).max()) / max(scale, 1e-30)


def _loss_rel(got, ref) -> float:
    """Largest gap of one loss relative to its reference."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.size == 0:
        return 0.0
    return float((np.abs(got - ref) / np.maximum(np.abs(ref), 1e-12)).max())


# --------------------------------------------------------------- drivers


class _Driver:
    """Shared pieces: widths, keys and pools from the seed, the tick log,
    the reference's own boot and replay of the checked ticks."""

    def __init__(self, cell: Cell, seed: int, devices):
        import jax

        self.cell = cell
        self.devices = list(devices)      # the cell's chips
        self.cfg = cell.config
        self.tr = cell.traffic
        self.seed = int(seed)
        self.n_dev = int(self.cfg["n_devices"])
        self.n_feat = int(self.cfg["n_features"])
        self.n_hid = int(self.cfg["n_hidden"])
        self.act = self.cfg["activation"]
        self.ridge = float(self.cfg["ridge"])
        self.key_basis, self.key_boot = jax.random.split(jax_key(seed), 2)
        self.log = RunLog()
        self.snapshot = None

    def _topology(self):
        from repro.fleet import ring, star

        if self.cfg["topology"] == "ring":
            return ring(self.n_dev, hops=int(self.cfg["hops"]))
        if self.cfg["topology"] == "star":
            return star(self.n_dev)
        raise ValueError(f"unknown topology {self.cfg['topology']!r}")

    def _boot_x(self, lo: int, hi: int, key=None):
        """Boot chunks of devices [lo, hi), on the device, from the seed."""
        import jax

        return jax.random.normal(
            jax.random.fold_in(self.key_boot if key is None else key, lo),
            (hi - lo, int(self.cfg["boot_rows"]), self.n_feat),
        )

    def _boot_blocks(self):
        """The contiguous device ranges [lo, hi) the fleet is booted in:
        one per cohort of a paged fleet, else one per chip of the cell."""
        if self.cfg["runtime"] == "paged":
            c = int(self.cfg["cohort_size"])
            return [(lo, lo + c) for lo in range(0, self.n_dev, c)]
        chips = len(self.devices)
        if self.n_dev % chips:
            raise ValueError(f"config {self.cfg['name']!r}: n_devices {self.n_dev} "
                             f"does not divide over {chips} chips")
        per = self.n_dev // chips
        return [(lo, lo + per) for lo in range(0, self.n_dev, per)]

    def _block_devices(self):
        """The chip each boot block lives on: the blocks in order, spread
        evenly over the cell's chips."""
        n = len(self._boot_blocks())
        return [self.devices[i * len(self.devices) // n] for i in range(n)]

    def _resident_fleet(self):
        """The fleet state from the seed, block (lo, hi) of
        ``_boot_blocks`` booted on its chip in one jitted call from
        ``_boot_x(lo, hi)``, and laid out by device range over the cell's
        chips (``lay_out``); also each block's last boot rows on its chip
        (the front-end's fallback rows, block by block). The boot is
        set-up, run at float32 as the configuration states (see
        ``float32_boot``)."""
        import jax

        from repro.fleet import init_fleet

        def build(kb, kx, *, lo, hi):
            # keys are arguments, not constants: one program for every seed
            x0 = self._boot_x(lo, hi, kx)
            fleet = init_fleet(kb, hi - lo, self.n_feat, self.n_hid, x0,
                               activation=self.act, ridge=self.ridge,
                               forget=float(self.cfg["forget"]))
            return fleet, x0[:, -1, :]

        build = jax.jit(build, static_argnames=("lo", "hi"))
        blocks, rows = [], []
        for (lo, hi), dev in zip(self._boot_blocks(), self._block_devices()):
            with float32_boot(), jax.default_device(dev):
                fleet, last = build(self.key_basis, self.key_boot, lo=lo, hi=hi)
            blocks.append(fleet)
            rows.append(last)
        return lay_out(blocks, self.devices), rows

    def built(self):
        """Called once the runtime exists, before its first tick."""

    def _pool(self, shape, stream: int) -> np.ndarray:
        """Host data from the seed (where a deployment's data arrives)."""
        return np_rng(self.seed, stream).standard_normal(shape, dtype=np.float32)

    def record_tick(self, t0: float, rep, served_rows: int, **extra) -> TickRec:
        merge = bool(rep.decision.merge)
        rec = TickRec(
            tick=int(rep.tick), start=t0, end=time.perf_counter(),
            ingest_s=rep.ingest_seconds, merge_s=rep.merge_seconds,
            merge=merge,
            mask=~np.asarray(rep.drifted, bool) if merge else None,
            served_rows=served_rows, **extra,
        )
        self.log.ticks.append(rec)
        return rec

    def end_check_phase(self):
        """The checked ticks end here: keep the state they left."""
        self.log.checked = len(self.log.ticks)
        p, beta = self.state()
        self.snapshot = {"p": np.array(p), "beta": np.array(beta)}

    # the reference ------------------------------------------------------

    def replay(self, precision: str) -> dict:
        """The reference's own boot of the same fleet from the same seed,
        then every checked tick: its per-tick losses and final state."""
        import jax

        from bench import reference as ref

        alpha, bias = ref.basis(self.key_basis, self.n_feat, self.n_hid)
        devices = self._block_devices()
        blocks = []
        for (lo, hi), dev in zip(self._boot_blocks(), devices):
            with jax.default_device(dev):
                blocks.append(ref.boot(alpha, bias, self._boot_x(lo, hi),
                                       activation=self.act, ridge=self.ridge,
                                       precision=precision))
        fleet = ref.Fleet(blocks, devices, activation=self.act, ridge=self.ridge,
                          precision=precision)
        losses = []
        for rec in self.log.ticks[:self.log.checked]:
            window_fn, served = self.tick_inputs(rec)
            losses.append(fleet.tick(alpha, bias, window_fn, served))
            if rec.merge:
                fleet.merge(rec.mask, self.cfg["topology"], int(self.cfg["hops"]))
        p, beta = fleet.host_state()
        return {"losses": losses, "p": p, "beta": beta}

    def compare(self, got: dict, ref: dict) -> dict:
        out = {"beta_rel": _rel(got["beta"], ref["beta"]),
               "p_rel": _rel(got["p"], ref["p"])}
        out.update(self.compare_losses(got, ref))
        return out


class Served(_Driver):
    """Closed-loop traffic through ServeFrontend into a resident
    FleetRuntime. Requests carry one sample each; a device's j-th sample
    is row (j mod R, device) of a host pool made from the seed."""

    def setup(self):
        from repro.obs import TelemetryConfig
        from repro.runtime import FleetRuntime, GovernorConfig, RuntimeConfig
        from repro.serve import ServeConfig, ServeFrontend

        tr = self.tr
        fleet, rows = self._resident_fleet()
        self.runtime = FleetRuntime(fleet, RuntimeConfig(
            topology=self._topology(), ridge=self.ridge,
            governor=GovernorConfig(merge_every=int(tr["merge_every"])),
            telemetry=TelemetryConfig(),
        ))
        self.batch = int(tr["batch"])
        self.frontend = ServeFrontend(self.runtime, ServeConfig(
            batch=self.batch,
            max_delay_s=float(tr["max_delay_ms"]) / 1e3,
            close_at_requests=int(tr["close_at_requests"]),
            seed=self.seed,
        ), fallback=np.concatenate([np.asarray(r) for r in rows]))
        del fleet, rows
        self.pool = self._pool(
            (int(tr["pool_per_device"]), self.n_dev, self.n_feat), 1)
        self.next_sample = np.zeros(self.n_dev, np.int64)
        self.rng = random.Random(int(np_rng(self.seed, 2).integers(2**31)))
        self.cols = {k: [] for k in (
            "phase", "device", "sample", "order", "due", "submit", "ack",
            "status", "tick", "score")}
        self._order = 0
        self._phase = 0
        self._recs = {}
        tick = self.runtime.tick

        def timed_tick(batch, *, served=None, allow_merge=True):
            t0 = time.perf_counter()
            with _traced("bench.tick"):
                rep = tick(batch, served=served, allow_merge=allow_merge)
            n = self.n_dev if served is None else int(np.count_nonzero(served))
            rec = self.record_tick(t0, rep, n)
            self._recs[rec.tick] = rec
            return rep

        self.runtime.tick = timed_tick
        self.built()

    def state(self):
        st = self.runtime.states
        return np.asarray(st.p), np.asarray(st.beta)

    async def _submit(self, dev: int, due: float):
        from repro.serve import SampleRequest

        j = int(self.next_sample[dev])
        self.next_sample[dev] = j + 1
        r = self.pool.shape[0]
        req = SampleRequest(device=dev, x=self.pool[j % r, dev][None, :],
                            client=f"device-{dev}")
        retry = self.frontend.config.retry
        attempt = 0
        t_sub = time.perf_counter()
        while True:
            # admission happens synchronously inside submit, before its
            # first await: this counter is the admission order
            order = self._order
            self._order += 1
            ack = await self.frontend.submit(req)
            if ack.status != "busy" or attempt + 1 >= retry.max_attempts:
                break
            await asyncio.sleep(retry.delay(attempt, self.rng))
            attempt += 1
        c = self.cols
        c["phase"].append(self._phase)
        c["device"].append(dev)
        c["sample"].append(j)
        c["order"].append(order)
        c["due"].append(due)
        c["submit"].append(t_sub)
        c["ack"].append(time.perf_counter())
        c["status"].append(ack.status)
        c["tick"].append(-1 if ack.tick is None else int(ack.tick))
        c["score"].append(np.nan if ack.score is None else float(ack.score))
        if ack.status != "ok":
            # a device whose sample was not taken waits before its next
            # one: a shed or stale ack returns without yielding, and a
            # closed loop of them would starve the event loop
            await asyncio.sleep(retry.delay(attempt, self.rng))
        return ack.tick if ack.status == "ok" else None

    async def _closed(self, done):
        """Every device keeps its requests in flight; a client stops once
        ``done`` holds for the tick its last request was acked in (None
        where it was not taken)."""
        async def client(dev: int):
            while not done(await self._submit(dev, time.perf_counter())):
                pass

        k = int(self.tr["inflight_per_device"])
        await asyncio.gather(*[client(d) for d in range(self.n_dev) for _ in range(k)])

    async def _check_phase(self):
        """The first merge cycle and the tick after it, through the same
        front-end, clients and traffic as the window."""
        k = int(self.tr["merge_every"]) + 1
        await self._closed(lambda _tick: self.runtime.tick_no >= k)

    def _window_done(self, stop_at: float):
        """The window ends with the acks of the last tick of the first
        merge cycle that ends after --seconds: whole ticks of whole work,
        every client resubmitting up to that tick. A window cut at
        --seconds would end inside an ack burst, in which the clients
        past the cut stop resubmitting: the burst's acks all count while
        its work is cut short, so the rate jumps by a tick's 8,192
        samples with where the cut falls."""
        every = int(self.tr["merge_every"])
        last = []

        def done(tick):
            if tick is not None and not last and tick % every == every - 1 \
                    and self._recs[tick].end >= stop_at:
                last.append(tick)
            return bool(last) and (tick is None or tick >= last[0])
        return done

    def run(self, seconds: float, on_window_start, on_window_end) -> None:
        async def main():
            await self.frontend.start()        # compiles the tick programs
            await self._check_phase()
            self.end_check_phase()
            self._phase = 1
            self.log.diag["depth_at_open"] = int(self.frontend.builder.depth)
            w0 = on_window_start()
            try:
                await asyncio.wait_for(
                    self._closed(self._window_done(w0 + seconds)), seconds + 60.0)
            finally:
                w1 = on_window_end()
                self.log.diag["depth_at_close"] = int(self.frontend.builder.depth)
                await self.frontend.stop()
            self.log.window = (w0, w1)

        asyncio.run(main())
        c = self.cols
        self.log.requests = {k: np.asarray(v) for k, v in c.items()}
        w0, w1 = self.log.window
        ticks = self.log.ticks
        first = next((i for i, r in enumerate(ticks) if r.start >= w0), len(ticks))
        last = next((i for i, r in enumerate(ticks) if r.end > w1), len(ticks))
        self.log.window_ticks = (first, max(first, last))

    def attempted_failed(self) -> tuple[int, int]:
        req = self.log.requests
        w0, w1 = self.log.window
        due = (req["phase"] == 1) & (req["due"] >= w0) & (req["due"] < w1)
        return int(due.sum()), int((due & (req["status"] != "ok")).sum())

    def release(self):
        del self.frontend, self.runtime

    def audit(self) -> int:
        """Batching faults over every request, counted exactly: an ok ack
        naming a tick that never ran, a failed ack, a device given more
        than B samples in one tick, or a later admission served in an
        earlier tick. Also groups each tick's acked samples by device,
        in admission order, for the replay."""
        req = self.log.requests
        ok = req["status"] == "ok"
        ran = np.array([r.tick for r in self.log.ticks])
        faults = int(np.count_nonzero(ok & ~np.isin(req["tick"], ran)))
        faults += int(np.count_nonzero(req["status"] == "failed"))
        self.rows_by_tick = {}
        idx = np.flatnonzero(ok)
        idx = idx[np.lexsort((req["order"][idx], req["device"][idx]))]
        last_tick = {}
        for i in idx.tolist():
            dev, t = int(req["device"][i]), int(req["tick"][i])
            if t < last_tick.get(dev, -1):
                faults += 1
            last_tick[dev] = t
            self.rows_by_tick.setdefault(t, {}).setdefault(dev, []).append(
                int(req["sample"][i]))
        for rows in self.rows_by_tick.values():
            faults += sum(len(s) > self.batch for s in rows.values())
        return faults

    def tick_inputs(self, rec: TickRec):
        """The windows the batcher must have built for this tick, rebuilt
        from the acks: each device's acked samples in admission order,
        cycled to fill its row; devices with none are not served."""
        d, b, f = self.n_dev, self.batch, self.n_feat
        rows = self.rows_by_tick.get(rec.tick, {})
        win = np.zeros((d, b, f), np.float32)
        served = np.zeros(d, bool)
        r = self.pool.shape[0]
        for dev, samples in rows.items():
            x = self.pool[[s % r for s in samples], dev]
            win[dev] = np.tile(x, (-(-b // len(samples)), 1))[:b]
            served[dev] = True
        return (lambda lo, hi: win[lo:hi]), served

    def _checked_acks(self):
        req = self.log.requests
        checked = {r.tick for r in self.log.ticks[:self.log.checked]}
        pos = {r.tick: i for i, r in enumerate(self.log.ticks[:self.log.checked])}
        sel = np.flatnonzero((req["status"] == "ok") & np.isin(req["tick"], list(checked)))
        return sel, pos

    def program_outputs(self) -> dict:
        sel, _ = self._checked_acks()
        return {**self.snapshot, "scores": self.log.requests["score"][sel]}

    def control_outputs(self, ctl: dict) -> dict:
        return {"p": ctl["p"], "beta": ctl["beta"], "scores": self._ref_scores(ctl)}

    def _ref_scores(self, ref: dict) -> np.ndarray:
        req = self.log.requests
        sel, pos = self._checked_acks()
        return np.array([ref["losses"][pos[int(req["tick"][i])]][int(req["device"][i])]
                         for i in sel.tolist()], np.float64)

    def compare_losses(self, got: dict, ref: dict) -> dict:
        return {"score_rel": _loss_rel(got["scores"], self._ref_scores(ref))}


class Feed(_Driver):
    """Ticks driven directly: every device gets a window of the mix's
    length every tick, sliced from a host pool made from the seed, into a
    resident FleetRuntime or a host-paged CohortFleetRuntime."""

    def setup(self):
        from repro.runtime import (
            CohortFleetRuntime, FleetRuntime, GovernorConfig, RuntimeConfig,
        )

        tr, cfg = self.tr, self.cfg
        d, f, h = self.n_dev, self.n_feat, self.n_hid
        self.paged = cfg["runtime"] == "paged"
        self.window = int(tr["window"])
        self.merge_every = int(tr["merge_every"])
        rcfg = RuntimeConfig(
            topology=self._topology(), ridge=self.ridge,
            governor=GovernorConfig(merge_every=self.merge_every),
        )
        if self.paged:
            from repro.fleet import init_arena

            self.cohort = int(cfg["cohort_size"])
            with float32_boot():
                arena = init_arena(
                    self.key_basis, d, f, h, self._boot_x,
                    cohort_size=self.cohort, activation=self.act,
                    ridge=self.ridge, forget=float(cfg["forget"]),
                )
            self.runtime = CohortFleetRuntime(arena, rcfg, cohort_size=self.cohort)
        else:
            fleet, _ = self._resident_fleet()
            self.runtime = FleetRuntime(fleet, rcfg)
            del fleet
            self.runtime.warmup(self.window)   # compiles ingest and merge
        self.built()
        extra = max(1, int(d * float(tr["pool_extra_frac"])))
        self.pool = self._pool((d + extra, self.window, f), 1)
        self.offsets = np_rng(self.seed, 4).integers(0, extra + 1, 1 << 16)
        # the checked ticks: whole merge cycles through the window's own
        # call, which also compile every program a tick runs
        for _ in range(int(tr["check_cycles"]) * self.merge_every):
            self._tick()
        if self.paged:
            self._warm_rebase()
        self.end_check_phase()

    def _warm_rebase(self):
        """The paged runtime's post-merge rebase program first runs on the
        tick after a merge, inside the window: compile it now on the
        shapes and types the tick gives it (its output is dropped)."""
        import jax
        import jax.numpy as jnp

        rt = self.runtime
        common = getattr(rt, "_common", None)
        if common is not None:
            jax.block_until_ready(common(
                rt.det, jnp.asarray(np.zeros(self.n_dev, np.float32)),
                jnp.asarray(np.ones(self.n_dev, bool))))

    def state(self):
        rt = self.runtime
        if self.paged:
            return rt.arena.p, rt.arena.beta
        return np.asarray(rt.states.p), np.asarray(rt.states.beta)

    def _tick(self):
        t = self.runtime.tick_no
        o = int(self.offsets[t % len(self.offsets)])
        pool = self.pool
        t0 = time.perf_counter()
        with _traced("bench.tick"):
            if self.paged:
                def traffic(lo, hi):
                    with _traced("bench.traffic"):
                        return pool[o + lo:o + hi]

                rep = self.runtime.tick(traffic)
            else:
                rep = self.runtime.tick(pool[o:o + self.n_dev])
        self.record_tick(t0, rep, self.n_dev, losses=np.array(rep.losses),
                         offset=o)

    def run(self, seconds: float, on_window_start, on_window_end) -> None:
        """Whole merge cycles: from a cycle boundary to the first cycle
        boundary after ``seconds``."""
        first = len(self.log.ticks)
        w0 = on_window_start()
        while True:
            self._tick()
            if (time.perf_counter() - w0 >= seconds
                    and self.runtime.tick_no % self.merge_every == 0):
                break
        w1 = on_window_end()
        self.log.window = (w0, w1)
        self.log.window_ticks = (first, len(self.log.ticks))

    def attempted_failed(self) -> tuple[int, int]:
        a, b = self.log.window_ticks
        return b - a, 0

    def release(self):
        del self.runtime

    def audit(self) -> int:
        return 0

    def tick_inputs(self, rec: TickRec):
        o, pool = rec.offset, self.pool
        return (lambda lo, hi: pool[o + lo:o + hi]), np.ones(self.n_dev, bool)

    def program_outputs(self) -> dict:
        return {**self.snapshot,
                "losses": [r.losses for r in self.log.ticks[:self.log.checked]]}

    def control_outputs(self, ctl: dict) -> dict:
        return ctl

    def compare_losses(self, got: dict, ref: dict) -> dict:
        return {"loss_rel": max(
            (_loss_rel(g, r) for g, r in zip(got["losses"], ref["losses"])),
            default=0.0)}


DRIVERS = {"served_closed": Served, "feed": Feed}


def lay_out(blocks: list, devices: list):
    """One fleet from per-chip blocks of consecutive device ranges, block
    i on ``devices[i]``. Every leaf is stacked by device (the program
    keeps the shared basis once per device too) and is laid out by device
    range over a 1-D mesh of the chips; the basis must be the same in
    every block. One chip: its block as it is."""
    if len(blocks) == 1:
        return blocks[0]
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    basis = [np.asarray(leaf[0]) for leaf in jax.tree.leaves(blocks[0].params)]
    for i, block in enumerate(blocks[1:], 1):
        if not all(np.array_equal(np.asarray(leaf[0]), want)
                   for leaf, want in zip(jax.tree.leaves(block.params), basis)):
            raise ValueError(f"boot block {i} has another basis than block 0")
    by_range = NamedSharding(Mesh(np.array(devices), ("fleet",)), PartitionSpec("fleet"))

    def one(*leaves):
        shape = (sum(leaf.shape[0] for leaf in leaves),) + leaves[0].shape[1:]
        return jax.make_array_from_single_device_arrays(shape, by_range, list(leaves))

    return jax.tree.map(one, *blocks)


# ------------------------------------------------------------------- run


@dataclasses.dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    metrics: dict
    device: dict
    check: dict
    breakdown: dict | None
    notes: list


def scaled(cell: Cell, scale: dict | None) -> Cell:
    """The cell with config and traffic keys overridden (tiny rehearsals
    off the chip)."""
    if not scale:
        return cell
    return dataclasses.replace(
        cell,
        config={**cell.config, **{k: v for k, v in scale.items() if k in cell.config}},
        traffic={**cell.traffic, **{k: v for k, v in scale.items() if k in cell.traffic}},
    )


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict | None, devices,
             control: bool = False, driver=None) -> Outcome:
    """One run of ``cell`` on ``devices``, the cell's chips. ``control``
    puts the reference at bfloat16 operands in the program's place for
    the check, which must then read ``correct`` false; ``driver``
    replaces the driver class (fault rehearsals)."""
    import gc
    import shutil
    import tempfile

    import jax

    if len(devices) != cell.chips:
        raise ValueError(f"{cell.name} runs on {cell.chips} chips, given {len(devices)}")
    compiles = CompileCounter()
    drv = (driver or DRIVERS[cell.traffic["kind"]])(cell, seed, devices)
    drv.setup()
    notes = []
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    marks = {}

    def on_start():
        if tdir:
            jax.profiler.start_trace(tdir, profiler_options=_profile_options())
            marks["ann"] = _traced("bench.window")
            marks["ann"].__enter__()
        w0 = time.perf_counter()
        marks["setup_s"] = w0 - t_start
        marks["compiles"] = compiles.n
        return w0

    def on_end():
        w1 = time.perf_counter()
        marks["window_compiles"] = compiles.n - marks["compiles"]
        if tdir:
            marks["ann"].__exit__(None, None, None)
        return w1

    drv.run(seconds, on_start, on_end)
    if tdir:
        jax.profiler.stop_trace()
    peak = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]
    notes.append(f"memory_peak_bytes by chip: {peak}")
    dev_info = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": max(peak),
    }
    attempted, failed = drv.attempted_failed()
    ctx = Context(cell=cell, setup_s=marks["setup_s"], log=drv.log, peaks=peaks)
    breakdown = None
    if tdir:
        from bench import trace as trace_mod

        ctx.trace = trace_mod.reduce_dir(tdir, drv.log.window, [d.id for d in devices])
        shutil.rmtree(tdir, ignore_errors=True)
        if ctx.trace is not None and ctx.trace.device_events:
            dev_info["busy_s"] = ctx.trace.busy_s
            dev_info["window_s"] = ctx.trace.window_s
            breakdown = ctx.trace.breakdown()
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    notes += ctx.notes
    notes.append(f"compiles or cache loads inside the window: {marks['window_compiles']}")
    ticks = drv.log.in_window()
    if len(ticks) > 1:
        # where a run reads far off, these say whether every tick was
        # slower or a few ticks stalled
        dur = np.array([r.end - r.start for r in ticks]) * 1e3
        cyc = np.diff([r.start for r in ticks]) * 1e3
        notes.append(f"window ticks {len(ticks)}: tick ms p50 {np.median(dur):.1f} "
                     f"max {dur.max():.1f}; start-to-start ms p50 {np.median(cyc):.1f} "
                     f"max {cyc.max():.1f}")

    # the check: free the program's state, then replay the reference
    got = drv.program_outputs()
    drv.release()
    gc.collect()
    faults = drv.audit()
    t_ref = time.perf_counter()
    ref = drv.replay("highest")
    notes.append(f"reference replay {time.perf_counter() - t_ref:.2f} s over "
                 f"{drv.log.checked} checked ticks")
    if control:
        got = drv.control_outputs(drv.replay("bf16"))
        notes.append("control: the reference at bfloat16 operands in the program's place")
    nums = drv.compare(got, ref)
    limits = limits_for(cell)
    check = {k: {"value": v, "limit": limits[k]} for k, v in nums.items()}
    check["batching_faults"] = {"value": faults, "limit": 0}
    rounds = [r for r in drv.log.ticks[:drv.log.checked] if r.merge]
    check["checked_merges"] = {"value": len(rounds), "limit": 1, "at_least": True}
    # the reference takes each merge's mask from the program: a merge that
    # nobody joins changes nothing on either side, so most devices must join
    check["merge_participants"] = {
        "value": min((float(r.mask.mean()) for r in rounds), default=0.0),
        "limit": limits["merge_participants"], "at_least": True}
    correct = all(
        math.isfinite(c["value"]) and (
            c["value"] >= c["limit"] if c.get("at_least") else c["value"] <= c["limit"])
        for c in check.values()
    )
    for k, v in drv.log.diag.items():
        notes.append(f"{k} {v!r}")
    return Outcome(correct=correct, attempted=attempted, failed=failed,
                   metrics=metrics, device=dev_info, check=check,
                   breakdown=breakdown, notes=notes)


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # host spans come from TraceAnnotation
    opts.host_tracer_level = 1
    return opts
