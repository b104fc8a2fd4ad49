"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the metrics read.

The device planes are ``/device:TPU:<n>``.  On each, the ``XLA Ops`` line
holds one event per operation that ran (fusions, copies, Pallas kernels
as custom calls) and the ``XLA Modules`` line one event per executable
run (``jit_<function>(<id>)``).  The host plane's ``python`` line holds
the benchmark's own ``TraceAnnotation`` spans; ``bench.window`` brackets
the traced window.  All times are nanoseconds on one clock.

* busy: the union of the op intervals on a device, clipped to the window.
* idle gaps: the complement of that union in the window, each labelled by
  the innermost benchmark span that covers its midpoint ("none" if no
  span does).
* time by name: the summed durations of ops or modules in the window.

Op events are named by their HLO instruction (``copy.65``,
``paged_decode_attention.6`` for a Pallas kernel's custom call); control-
flow ops (``while``, ``conditional``, ``call``) are dropped, since they
span the ops inside them.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import re

DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# control-flow ops span the ops they run; counting them would count twice
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
WINDOW = "bench.window"


@dataclasses.dataclass
class Trace:
    window: tuple          # (start_ns, end_ns) of the traced window
    ops: dict              # device -> [(name, start_ns, end_ns)]
    modules: dict          # device -> [(name, start_ns, end_ns)]
    spans: list            # [(name, start_ns, end_ns)] benchmark host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(_length(union(ev, self.window))
                   for ev in self.ops.values()) * 1e-9 / len(self.ops)

    def op_time_s(self, pattern=None, *, modules=False) -> float:
        """Seconds of ops (or modules) whose name matches ``pattern``,
        summed over devices and clipped to the window."""
        rx = re.compile(pattern) if pattern else None
        src = self.modules if modules else self.ops
        return sum(_clip(s, e, self.window) for ev in src.values()
                   for n, s, e in ev if rx is None or rx.search(n)) * 1e-9

    def top_ops(self, n=10) -> list:
        tot = collections.Counter()
        for ev in self.ops.values():
            for name, s, e in ev:
                tot[_base(name)] += _clip(s, e, self.window)
        return [[k, v * 1e-9] for k, v in tot.most_common(n)]

    def idle_gaps(self) -> list:
        """[(label, seconds)] of every idle gap on the first device."""
        if not self.ops:
            return []
        dev = sorted(self.ops)[0]
        busy = union(self.ops[dev], self.window)
        edges = [self.window[0]] + [x for iv in busy for x in iv] \
            + [self.window[1]]
        gaps = []
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((self.label((a + b) / 2), (b - a) * 1e-9))
        return gaps

    def label(self, t) -> str:
        best = None
        for name, s, e in self.spans:
            if name != WINDOW and s <= t < e and (
                    best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return "none" if best is None else best[2]

    def top_gaps(self, n=10) -> list:
        """The ``n`` longest idle gaps, as [label, seconds]."""
        return [[lbl, s] for lbl, s in
                sorted(self.idle_gaps(), key=lambda g: -g[1])[:n]]


def op_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its HLO instruction text,
    ``%copy.65 = bf16[...] copy(...)``: keep ``copy.65``."""
    m = re.match(r"%?([\w.-]+) =", text)
    return m.group(1) if m else text


def _base(name: str) -> str:
    """``copy.65`` -> ``copy``: one entry per kind of op."""
    return re.sub(r"\.\d+$", "", name)


def _clip(s, e, window) -> int:
    return max(0, min(e, window[1]) - max(s, window[0]))


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def union(events, window) -> list:
    """Merged [(start, end)] of ``events`` clipped to ``window``."""
    ivs = sorted((max(s, window[0]), min(e, window[1]))
                 for _, s, e in events if e > window[0] and s < window[1])
    out = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        elif e > s:
            out.append((s, e))
    return out


def find(directory: str) -> str:
    paths = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one trace under {directory}, "
                                f"found {paths}")
    return paths[0]


def load(path: str, spans=()) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    keep = set(spans) | {WINDOW}
    for plane in data.planes:
        if DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (n, int(e.start_ns), int(e.end_ns))
                        for e in line.events
                        if not CONTAINER.match(n := op_name(e.name))]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [(e.name, int(e.start_ns),
                                            int(e.end_ns))
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events if e.name in keep)
    win = [(s, e) for n, s, e in host if n == WINDOW]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(win)}")
    return Trace(window=win[0], ops=ops, modules=modules,
                 spans=[h for h in host if h[0] != WINDOW])
