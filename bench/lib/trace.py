"""Reduction of a JAX profiler trace to what the per-layer metrics read.

A trace (``<dir>/plugins/profile/<time>/*.xplane.pb``) holds planes: one
per device (``/device:TPU:<i>``) and the host (``/host:CPU``). A device
plane's ``XLA Ops`` line has one event per operation run, its
``XLA Modules`` line one per jitted program run. The harness wraps the
traced window in a host span named ``WINDOW_SPAN``; every number here is
taken inside that span, so set-up and the correctness check never count.

Everything below the loader works on plain ``Event`` lists, so the same
arithmetic is tested on a trace recorded on the CPU.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

#: host span around the traced window (``jax.profiler.TraceAnnotation``)
WINDOW_SPAN = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict  # line name -> [Event]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> list[Plane]:
    """All planes of one ``.xplane.pb`` file, with their events."""
    from jax.profiler import ProfileData
    planes = []
    for p in ProfileData.from_file(path).planes:
        lines = {}
        for line in p.lines:
            lines.setdefault(line.name, []).extend(
                Event(e.name, float(e.start_ns), float(e.end_ns))
                for e in line.events)
        planes.append(Plane(p.name, lines))
    return planes


def device_planes(planes: list[Plane]) -> list[Plane]:
    """The TPU device planes, ordered by device id."""
    dev = [(int(m.group(1)), p) for p in planes
           if (m := _DEVICE_PLANE.match(p.name))]
    return [p for _, p in sorted(dev, key=lambda t: t[0])]


def host_events(planes: list[Plane]) -> list[Event]:
    return [e for p in planes if p.name.startswith("/host:")
            for evs in p.lines.values() for e in evs]


def window(host: list[Event], span: str = WINDOW_SPAN) -> tuple[float, float]:
    """(start_ns, end_ns) of the one host span named ``span``."""
    spans = [e for e in host if e.name == span]
    if len(spans) != 1:
        raise ValueError(f"expected one {span!r} span, found {len(spans)}")
    return spans[0].start_ns, spans[0].end_ns


def clip(events: list[Event], lo: float, hi: float) -> list[Event]:
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def merged(events: list[Event]) -> list[tuple[float, float]]:
    """Union of the events' intervals, as sorted disjoint (start, end)."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if out and e.start_ns <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end_ns)
        else:
            out.append([e.start_ns, e.end_ns])
    return [(s, t) for s, t in out]


def busy_ns(events: list[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals inside [lo, hi]."""
    return sum(t - s for s, t in merged(clip(events, lo, hi)))


def idle_gaps(events: list[Event], lo: float,
              hi: float) -> list[tuple[float, float]]:
    """Stretches of [lo, hi] in which no event runs, longest first."""
    gaps, cur = [], lo
    for s, t in merged(clip(events, lo, hi)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        gaps.append((cur, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def sum_by_name(events: list[Event]) -> dict[str, float]:
    """Total duration (ns) of the events of each name."""
    out: dict[str, float] = {}
    for e in events:
        out[e.name] = out.get(e.name, 0.0) + e.dur_ns
    return out


def short_name(op: str) -> str:
    """An HLO op's instruction name: ``%fusion.3 = f32[..] fusion(..)``
    -> ``fusion.3``."""
    return op.split(" = ", 1)[0].lstrip("%")


def innermost(host: list[Event], t_ns: float, skip=(WINDOW_SPAN,)) -> str:
    """Name of the shortest host event that covers ``t_ns``: what the
    host was doing then."""
    cover = [e for e in host if e.start_ns <= t_ns <= e.end_ns
             and e.name not in skip and e.dur_ns > 0]
    if not cover:
        return "host: nothing traced"
    return min(cover, key=lambda e: e.dur_ns).name


@dataclasses.dataclass
class TraceView:
    """One traced window: what a per-layer metric reader gets.

    ``ops`` and ``modules`` hold, per device used, the events of its
    ``XLA Ops`` and ``XLA Modules`` lines inside the window. ``units`` is
    how many rounds or steps the window ran; ``counts`` the driver's work
    counts (sizes, not times); ``peaks`` the chip's published peaks.
    """
    lo: float
    hi: float
    ops: list
    modules: list
    host: list
    units: int
    counts: dict
    peaks: dict

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def chips(self) -> int:
        return len(self.ops)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(busy_ns(ev, self.lo, self.hi) for ev in self.ops) \
            * 1e-9 / self.chips

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def module_s(self, prefix: str) -> tuple[float, int]:
        """(seconds, runs) of the programs whose module name starts with
        ``prefix``, summed over the chips and averaged per chip."""
        evs = [e for dev in self.modules for e in dev
               if e.name.startswith(prefix)]
        return (sum(e.dur_ns for e in evs) * 1e-9 / self.chips,
                len(evs) // self.chips)

    def op_s(self, pattern: str) -> float:
        """Seconds of the operations whose name matches ``pattern``, per
        chip."""
        rx = re.compile(pattern)
        return sum(e.dur_ns for dev in self.ops for e in dev
                   if rx.search(e.name)) * 1e-9 / self.chips

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (seconds per chip)
        and the longest idle gaps of chip 0, named by what the host did."""
        totals: dict[str, float] = {}
        for dev in self.ops:
            for name, ns in sum_by_name(dev).items():
                name = short_name(name)
                totals[name] = totals.get(name, 0.0) + ns
        ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        gaps = idle_gaps(self.ops[0], self.lo, self.hi)[:top]
        return {
            "device_ops": [[n, ns * 1e-9 / self.chips] for n, ns in ops],
            "idle_gaps": [[innermost(self.host, (s + t) / 2), (t - s) * 1e-9]
                          for s, t in gaps],
        }


def view(planes: list[Plane], n_devices: int, units: int, counts: dict,
         peaks: dict) -> TraceView:
    """The traced window of the first ``n_devices`` TPU planes."""
    host = host_events(planes)
    lo, hi = window(host)
    devs = device_planes(planes)[:n_devices]
    if len(devs) < n_devices:
        raise ValueError(f"trace has {len(devs)} TPU planes, "
                         f"{n_devices} were used")
    ops = [clip(p.lines.get(OPS_LINE, []), lo, hi) for p in devs]
    mods = [clip(p.lines.get(MODULES_LINE, []), lo, hi) for p in devs]
    if not any(ops):
        raise ValueError(f"no {OPS_LINE!r} events inside the window; "
                         f"device lines: {sorted(devs[0].lines)}")
    return TraceView(lo, hi, ops, mods, host, units, counts, peaks)
