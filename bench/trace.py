"""Reduction of a profiler trace of the measured window to device numbers.

Device work is the ``XLA Ops`` line of the plane ``/device:TPU:<n>`` of
each of the cell's chips, ``n`` its device id (one event per operation
that ran, kernels included); the planes of the host's other chips, and
other ``/device:`` planes such as ``/device:CUSTOM:Megascale Trace``, are
not the cell's and would dilute the average. Host spans are the
benchmark's own ``bench.*`` annotations. The ``bench.window`` span marks
the measured window on the trace's clock, which also maps the host
clock's tick times onto it. Everything is clipped to that window:

- busy: the union of the device's operation intervals; idle = the rest;
- operation time by a short name (``%fleet_ingest_kernel.1 = ...`` is
  ``fleet_ingest_kernel``; a custom call is named by its target);
- idle gaps attributed to the innermost benchmark span that covers them
  (``bench.traffic`` inside ``bench.tick``), else to time outside any.
"""
from __future__ import annotations

import bisect
import glob
import re

WINDOW = "bench.window"
OUTSIDE = "outside bench spans"
_NAME = re.compile(r"%?([A-Za-z_][\w\-.]*?)(?:\.\d+)? = ")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_CHIP = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")


def short_name(name: str) -> str:
    m = _NAME.match(name)
    base = m.group(1) if m else name.split(" ")[0].lstrip("%")
    if base == "custom-call":
        t = _TARGET.search(name)
        if t:
            base = t.group(1)
    return base


def merge_intervals(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _length(iv) -> float:
    return float(sum(e - s for s, e in iv))


def _subtract(iv, cut):
    """Intervals ``iv`` minus the merged intervals ``cut``."""
    out = []
    starts = [c[0] for c in cut]
    for s, e in iv:
        cur = s
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(cut) and cut[i][0] < e:
            cs, ce = cut[i]
            if ce > cur:
                if cs > cur:
                    out.append((cur, min(cs, e)))
                cur = max(cur, ce)
            i += 1
        if cur < e:
            out.append((cur, e))
    return out


class Reduced:
    """A trace reduced to device operations and benchmark spans.

    ``devices``: per chip of the cell, first chip first, a list of
    (short name, start_ns, end_ns);
    ``spans``: benchmark span name -> list of (start_ns, end_ns);
    ``host_window``: the window's (start, end) on the host clock, seconds.
    """

    def __init__(self, devices, spans, host_window):
        self.spans = {k: merge_intervals(v) for k, v in spans.items()}
        win = self.spans.get(WINDOW)
        if not win:
            raise ValueError("trace has no bench.window span")
        self.lo, self.hi = win[0][0], win[-1][1]
        self.host_window = host_window
        self.devices = [
            [(n, max(s, self.lo), min(e, self.hi)) for n, s, e in evs
             if e > self.lo and s < self.hi]
            for evs in devices
        ]
        self.busy = [merge_intervals([(s, e) for _, s, e in evs]) for evs in self.devices]

    @property
    def device_events(self) -> int:
        return sum(len(evs) for evs in self.devices)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.busy:
            return 0.0
        return sum(_length(b) for b in self.busy) / len(self.busy) / 1e9

    def to_ns(self, t: float) -> float:
        """A host-clock time (seconds) on the trace's clock."""
        return self.lo + (t - self.host_window[0]) * 1e9

    def busy_between(self, t0: float, t1: float) -> float:
        """Device-busy seconds inside a host-clock interval, averaged."""
        a, b = self.to_ns(t0), self.to_ns(t1)
        if not self.busy:
            return 0.0
        return sum(_length(_clip(bz, a, b)) for bz in self.busy) / len(self.busy) / 1e9

    def op_seconds(self, pattern: str) -> float:
        """Summed device time of the operations whose short name matches."""
        rx = re.compile(pattern)
        return sum(e - s for evs in self.devices for n, s, e in evs if rx.search(n)) / 1e9

    def op_totals(self) -> list:
        tot = {}
        for evs in self.devices:
            for n, s, e in evs:
                tot[n] = tot.get(n, 0.0) + (e - s) / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])

    def idle_by_span(self) -> list:
        """Idle device seconds by the innermost benchmark span covering
        them (on the cell's first chip), largest first."""
        if not self.busy:
            return []
        idle = _subtract([(self.lo, self.hi)], self.busy[0])
        names = sorted((k for k in self.spans if k != WINDOW),
                       key=lambda k: _length(self.spans[k]))
        out = []
        for name in names:
            cover = merge_intervals(_clip(self.spans[name], self.lo, self.hi))
            inside = [(s, e) for s, e in _subtract(idle, _subtract(idle, cover))]
            sec = _length(inside) / 1e9
            if sec > 0:
                out.append((name, sec))
            idle = _subtract(idle, cover)
        rest = _length(idle) / 1e9
        if rest > 0:
            out.append((OUTSIDE, rest))
        return sorted(out, key=lambda kv: -kv[1])

    def breakdown(self) -> dict:
        return {
            "device_ops": [[n, s] for n, s in self.op_totals()[:10]],
            "idle_gaps": [[n, s] for n, s in self.idle_by_span()[:10]],
        }


def reduce_profile(profile, host_window, chip_ids) -> Reduced:
    """``profile``: a jax.profiler.ProfileData (or anything with planes,
    lines and events of that shape); ``chip_ids``: the device ids of the
    cell's chips, first chip first. A chip with no plane ran nothing."""
    chips, spans = {int(i): [] for i in chip_ids}, {}
    for plane in profile.planes:
        chip = _CHIP.match(plane.name)
        if chip:
            evs = chips.get(int(chip.group(1)))
            if evs is None:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs += [(short_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return Reduced(list(chips.values()), spans, host_window)


def reduce_dir(tdir: str, host_window, chip_ids) -> Reduced | None:
    """The reduced trace written under ``tdir``; None where there is none."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    if not files:
        return None
    return reduce_profile(ProfileData.from_file(files[0]), host_window, chip_ids)
