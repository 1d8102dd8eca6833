"""The window's profiler trace as intervals, for the per-layer readers that
need more than ``trace_reduce``'s summary: each chip's device events and
its jitted programs ("XLA Modules") by name, and the host's annotations by
name.

``load(ctx)`` reads the window's ``.xplane.pb`` from ``<run.work>/xprof``
once (``run.py`` removes that directory after the readers have run) and
keeps the result in the reader context under ``KEY``; every reader of the
run shares it.  It returns None where the run holds no trace.  The window
is the one ``trace_reduce`` takes: the loop's ``window_ns``, else the span
of the benchmark's ``bench.*`` annotations.
"""

from __future__ import annotations

import glob
import os

import trace_reduce as tr

KEY = "window_trace"


def overlap(a, b) -> float:
    """Length of the intersection of two disjoint sorted interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


class WindowTrace:
    """``window``: ``(lo_ns, hi_ns)``; ``chips``: per chip ``{"busy":
    [(s, e)], "modules": [(name, s, e)]}``; ``host``: ``[(name, s, e)]``.
    Every time in nanoseconds, every result in seconds."""

    def __init__(self, window, chips, host):
        self.lo, self.hi = window
        self.chips = chips
        self.host = host

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self) -> float:
        """The union of device events in the window, summed over chips."""
        return sum(tr.covered(tr.clip(c["busy"], self.lo, self.hi))
                   for c in self.chips) / 1e9

    def module_s(self, prefix: str) -> float:
        """Device seconds in the window of the programs whose name starts
        with ``prefix``, summed over chips."""
        return sum(tr.covered(tr.clip(
            [(s, e) for n, s, e in c["modules"] if n.startswith(prefix)],
            self.lo, self.hi)) for c in self.chips) / 1e9

    def idle_within_s(self, name: str) -> "float | None":
        """Seconds, summed over chips, in which a chip is idle while at
        least one host span ``name`` is open; None where the trace holds
        no such span."""
        spans = [(s, e) for n, s, e in self.host if n == name]
        if not spans:
            return None
        open_ = tr.clip(tr.union(spans), self.lo, self.hi)
        return sum(overlap(tr.gaps(c["busy"], self.lo, self.hi), open_)
                   for c in self.chips) / 1e9


def load(ctx: dict) -> "WindowTrace | None":
    if KEY not in ctx:
        ctx[KEY] = _read(ctx)
    return ctx[KEY]


def _read(ctx: dict) -> "WindowTrace | None":
    from jax.profiler import ProfileData

    run = ctx["run"]
    pb = glob.glob(os.path.join(run.work, "xprof", "**", "*.xplane.pb"),
                   recursive=True)
    if not pb:
        return None
    devices, host = [], []
    for plane in ProfileData.from_file(pb[0]).planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            devices.append(plane)
        elif plane.name == tr.HOST_PLANE:
            for _, ev in tr._events(plane):
                host.append((ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns))
    devices.sort(key=lambda p: int(p.name[len(tr.DEVICE_PREFIX):]))
    devices = devices[:len(run.devices)]
    if not devices:
        return None
    chips = []
    for plane in devices:
        busy, modules = [], []
        for line, ev in tr._events(plane):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            busy.append((s, e))
            if line == "XLA Modules":
                # "jit_tpq_gather(1234)" -> "jit_tpq_gather"
                modules.append((ev.name.split("(")[0], s, e))
        chips.append({"busy": busy, "modules": modules})
    window = ctx["window"].get("window_ns")
    if window is None:
        marks = [(s, e) for n, s, e in host
                 if n.startswith(tr.ANNOTATION_PREFIX)]
        if not marks:
            return None
        window = (min(s for s, _ in marks), max(e for _, e in marks))
    return WindowTrace(window, chips, host)
