"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* The window is the harness's ``window`` host span.
* The device's clock is put on the host's: the k-th program the host
  asked to run (``PJRT_LoadedExecutable_Execute``) is the k-th program
  the device ran (``XLA Modules``), which cannot start before it was
  asked for, so the device's events move by the least gap between the
  two (where the counts differ, they stay as recorded).
* Device busy time is the union of the intervals of the operations on a
  device's ``XLA Ops`` line, clipped to the window, averaged over the
  devices the cell uses; idle is the rest of the window.
* A kernel's or a program's time is the sum of its events' durations.
* The top operations are summed by name.
* Each idle gap of the first device is named by the harness span (other
  than ``window``) that covers most of it on the host, or ``none``.
* The program's own spans (the names in ``repro.tracing.SPANS``) are kept
  apart from the harness's, each with its arguments: the keyword stats
  its ``TraceAnnotation`` recorded on the host event itself, which
  ``ProfileData`` shows (unlike an operation's ``tf_op``, a stat of the
  event's metadata, which ``chipbench.scopes`` reads from the file).
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

from chipbench.common import SPAN_NAMES

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
EXECUTE = "PJRT_LoadedExecutable_Execute"


def find_xplane(trace_dir: Path) -> Path:
    """The newest ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(op: str) -> str:
    """The instruction name of an ``XLA Ops`` event, whose name is the
    whole HLO instruction text."""
    return op.split(" = ", 1)[0].lstrip("%")


def union_s(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals within [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Summary:
    window: tuple[float, float]          # seconds on the trace's clock
    ops: list                            # per device: [(name, start, end)]
    modules: list                        # per device: [(name, start, end)]
    spans: list                          # host: [(name, start, end)]
    clock_shift_s: float = 0.0           # added to the device's times
    # the program's host spans: [(name, start, end, {argument: value})]
    program_spans: list = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        lo, hi = self.window
        per = [union_s([(s, e) for _, s, e in dev], lo, hi)
               for dev in self.ops]
        return sum(per) / len(per)

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s

    def _time(self, events, match) -> tuple[float, int]:
        lo, hi = self.window
        total, n = 0.0, 0
        for dev in events:
            for name, s, e in dev:
                if match(name) and s < hi and e > lo:
                    total += e - s
                    n += 1
        return total / len(events), n

    def op_time(self, match) -> tuple[float, int]:
        """Device seconds (averaged over devices) and count of the
        operations whose name ``match`` accepts, within the window."""
        return self._time(self.ops, match)

    def module_time(self, match) -> tuple[float, int]:
        """As :meth:`op_time`, for whole programs (XLA modules)."""
        return self._time(self.modules, match)

    def program_spans_named(self, name: str) -> list:
        """``(start, end, arguments)`` of the program's spans named
        ``name`` that start inside the window."""
        lo, hi = self.window
        return [(s, e, args) for n, s, e, args in self.program_spans
                if n == name and lo <= s < hi]

    def top_ops(self, n: int = 10) -> list:
        """Device seconds by operation, most first; an operation is named
        by its HLO instruction (``%name = ...`` gives ``name``)."""
        lo, hi = self.window
        by_name: dict = {}
        for dev in self.ops:
            for name, s, e in dev:
                if s < hi and e > lo:
                    name = short_name(name)
                    by_name[name] = by_name.get(name, 0.0) + (e - s)
        return sorted(([k, v / len(self.ops)] for k, v in by_name.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10, least_s: float = 1e-6) -> list:
        """The ``n`` longest idle gaps of the first device (of at least
        ``least_s``), each named by the host span that covers most of it,
        or ``none`` where no span covers most of it."""
        lo, hi = self.window
        found = [g for g in gaps([(s, e) for _, s, e in self.ops[0]], lo, hi)
                 if g[1] - g[0] >= least_s]
        found.sort(key=lambda g: g[0] - g[1])
        host = [sp for sp in self.spans if sp[0] != "window"]
        out = []
        for g0, g1 in found[:n]:
            cover = {"none": (g1 - g0) - union_s(
                [(s, e) for _, s, e in host], g0, g1)}
            for name, s, e in host:
                o = min(e, g1) - max(s, g0)
                if o > 0:
                    cover[name] = cover.get(name, 0.0) + o
            out.append([max(cover, key=cover.get), g1 - g0])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _events(line) -> list:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def reduce(path: Path, n_devices: int = 1) -> Summary:
    from jax.profiler import ProfileData

    from repro.tracing import SPANS
    pd = ProfileData.from_file(str(path))
    devices = sorted((p for p in pd.planes
                      if p.name.startswith(DEVICE_PREFIX)),
                     key=lambda p: p.name)[:n_devices]
    if not devices:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}* plane")
    ops, modules = [], []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        ops.append(_events(lines[OPS_LINE]) if OPS_LINE in lines else [])
        modules.append(_events(lines[MODULES_LINE])
                       if MODULES_LINE in lines else [])
    host = [ev for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in _events(line)]
    spans = [ev for ev in host if ev[0] in SPAN_NAMES]
    program = [(e.name, e.start_ns * 1e-9,
                (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
               for plane in pd.planes if plane.name.startswith("/host:")
               for line in plane.lines for e in line.events
               if e.name in SPANS]
    asked = sorted(s for name, s, _ in host if name == EXECUTE)
    ran = sorted(s for _, s, _ in modules[0])
    shift = 0.0
    if asked and len(asked) == len(ran):
        shift = -min(r - a for r, a in zip(ran, asked))
        ops, modules = ([[(n, s + shift, e + shift) for n, s, e in dev]
                         for dev in events] for events in (ops, modules))
    windows = [(s, e) for name, s, e in spans if name == "window"]
    if windows:
        window = windows[0]
    else:
        every = [t for dev in ops for _, s, e in dev for t in (s, e)]
        window = (min(every), max(every))
    return Summary(window=window, ops=ops, modules=modules, spans=spans,
                   clock_shift_s=shift, program_spans=program)
